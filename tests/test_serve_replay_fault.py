"""Stand-in for ``tests/benchmark/test_benchmark_serve_replay.py::
test_a_fault_under_the_timed_path_comes_out_not_correct[token_altered]``,
which plants its fault in ``engine._pick_token`` and is ``xfail`` (strict)
from ``tests/conftest.py::PLANTED_IN_THE_HOSTS_PICK`` since
``TransformerLM``'s programs pick their greedy tokens on the device (PR 46):
the same tiny cell through the same ``drive``, the token altered where
every path emits it. Goes, with the marker, when a ``benchmark`` PR moves
the original's fault to ``_emit_token``."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmark"))

from test_benchmark_serve_replay import drive, replay_cell  # noqa: E402


def test_a_token_altered_where_it_is_emitted_comes_out_not_correct(
        light_compile):
    """One session served another token than the program chose, in every
    step: every session still gains its tokens, and the reference reads
    the served tokens as far from its first choice."""
    def plant(session):
        eng = session.eng
        emit, vocab = eng._emit_token, eng.config.model.vocab_size

        def altered(slot, token):
            emit(slot, (token + 1) % vocab
                 if slot.request.rid == "s2" else token)
        eng._emit_token = altered

    obs = drive(replay_cell(), before_window=plant)
    assert obs.problems
    assert obs.failed == 0           # every session gained its tokens
    assert any("chosen_gap_rel" in p for p in obs.problems)
    gap = obs.notes["compared"]["chosen_gap_rel"]
    assert gap["value"] > gap["limit"] == 0.2
