"""graftlint tier-1 gate: every rule fires on its seeded fixture, every
clean fixture passes, and the repo itself is clean against the checked-in
baseline.

Three layers:

1. **Fixture corpus** (``tests/fixtures/lint/``) — seeded violations per
   rule id; proves each rule detects its failure class and that the
   guarded twins don't trip it (false-positive control).
2. **Baseline machinery** — the TOML-subset parser, suppression matching
   on snippets (line-churn-proof), and unused-entry reporting.
3. **Repo gate** — passes 1+3 run in-process over the repo (pure AST,
   fast); pass 2 runs via the ``tools/graftlint.py`` subprocess because
   the AOT path mutates process env (forced compiled Pallas kernels) —
   importing it here would poison this pytest process. Off-TPU toolchains
   skip the AOT half gracefully (the driver reports it, we accept it).
"""

import json
import os
import sys

import pytest

from tests.helpers import run_child
from tpu_sandbox.analysis import (
    BaselineError,
    apply_baseline,
    parse_baseline,
    render_baseline,
    run_collective_pass,
    run_control_pass,
)
from tpu_sandbox.analysis.collective_pass import lint_source as lint_coll
from tpu_sandbox.analysis.control_pass import lint_source as lint_ctrl
from tpu_sandbox.analysis.findings import RULES, make_finding
from tpu_sandbox.analysis.hlo_pass import (
    lint_donation,
    lint_hlo_text,
    lint_int8_padding,
    lint_jaxpr,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "lint")
BASELINE = os.path.join(ROOT, "tpu_sandbox", "analysis", "baseline.toml")


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name), "r", encoding="utf-8") as f:
        return f.read()


# ---------------------------------------------------------------------------
# Pass 1 fixtures
# ---------------------------------------------------------------------------


def test_bad_collective_fixture_fires_every_rule():
    findings = lint_coll(_fixture("bad_collective.py"), "bad_collective.py")
    rules = {f.rule for f in findings}
    assert {"GL-C101", "GL-C102", "GL-C103"} <= rules
    # every seeded function is caught
    msgs = "\n".join(f.message for f in findings)
    assert "pmean" in msgs          # rank_branch_collective
    assert "psum" in msgs           # rank_early_exit
    assert "_helper_syncs" in msgs  # rank_branch_calls_helper (via summary)
    assert "all_gather" in msgs     # rank_cond_lambda
    assert "ppermute" in msgs       # rank_while_collective
    # self-call resolution through the class method table: ShardSyncB's
    # rank-gated self._sync() fires even though _ShardSyncA owns a
    # collective-free method of the same name (the old bare-name table
    # let A answer for B)
    c103 = [f for f in findings if f.rule == "GL-C103"]
    assert len(c103) == 2
    assert any("'_sync'" in f.message for f in c103)
    # the name-shadowed ShardSyncB.gated is linted as its own function
    # (it used to be skipped entirely once A.gated took the bare slot)
    assert sum(1 for f in findings if f.rule == "GL-C101") >= 4
    # findings carry real locations + hints
    assert all(f.line > 0 and f.hint for f in findings)


def test_clean_collective_fixture_passes():
    findings = lint_coll(
        _fixture("clean_collective.py"), "clean_collective.py")
    assert findings == [], [f.format() for f in findings]


# ---------------------------------------------------------------------------
# Cross-module resolution (xmodule.CrossIndex)
# ---------------------------------------------------------------------------


def _xmodule_paths(*names):
    return [os.path.join(FIXTURES, n) for n in names]


def test_cross_module_fixture_fires_through_imports():
    """Collective-bearing calls hidden one (or two) imports away resolve
    when the file set is linted together: from-import, module-attribute,
    post-rank-exit depth-2 chain, and a jit of an imported sync fn."""
    paths = _xmodule_paths("xmodule_helper.py", "bad_xmodule.py")
    findings = run_collective_pass(FIXTURES, paths=paths) \
        + run_control_pass(FIXTURES, paths=paths)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {"GL-C102", "GL-C103", "GL-R305"}, \
        [f.format() for f in findings]
    # both import spellings of the rank-gated sync fire
    assert len(by_rule["GL-C103"]) == 2
    assert all("sync_all" in f.message for f in by_rule["GL-C103"])
    # bearing crossed the import edge AND a local hop inside the helper
    assert "sync_step" in by_rule["GL-C102"][0].message
    assert "stepper" in by_rule["GL-R305"][0].snippet
    # the helper module itself carries no findings
    assert all(f.file.endswith("bad_xmodule.py") for f in findings)


def test_cross_module_clean_twin_passes():
    paths = _xmodule_paths("xmodule_helper.py", "clean_xmodule.py")
    findings = run_collective_pass(FIXTURES, paths=paths) \
        + run_control_pass(FIXTURES, paths=paths)
    assert findings == [], [f.format() for f in findings]


def test_cross_module_dotted_receivers_fire():
    """``pkg.mod.fn()`` and ``alias.submodule.fn()`` receivers resolve by
    longest import-alias prefix — the PR-19 remainder. Both rank-gated
    dotted spellings fire, and the depth-2 chain crosses the dotted
    edge after a rank exit."""
    paths = _xmodule_paths(os.path.join("xpkg", "helpers.py"),
                          "bad_xdotted.py")
    findings = run_collective_pass(FIXTURES, paths=paths)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {"GL-C102", "GL-C103"}, \
        [f.format() for f in findings]
    assert len(by_rule["GL-C103"]) == 2
    assert all("sync_all" in f.message for f in by_rule["GL-C103"])
    assert "sync_step" in by_rule["GL-C102"][0].message
    assert all(f.file.endswith("bad_xdotted.py") for f in findings)


def test_cross_module_dotted_clean_twin_passes():
    """Same dotted receivers, unconditional (or collective-free): the
    resolution must prove absence as well as presence."""
    paths = _xmodule_paths(os.path.join("xpkg", "helpers.py"),
                          "clean_xdotted.py")
    findings = run_collective_pass(FIXTURES, paths=paths) \
        + run_control_pass(FIXTURES, paths=paths)
    assert findings == [], [f.format() for f in findings]


def test_cross_module_bad_file_reads_clean_alone():
    """Single-file lint cannot see through imports — the asymmetry that
    makes the whole-set run the only honest gate. If this starts firing,
    the fixture's imports got inlined and the cross-module test above
    stopped proving anything."""
    findings = lint_coll(_fixture("bad_xmodule.py"), "bad_xmodule.py")
    assert findings == [], [f.format() for f in findings]


# ---------------------------------------------------------------------------
# Pass 3 fixtures
# ---------------------------------------------------------------------------


def test_bad_control_fixture_fires_every_rule():
    findings = lint_ctrl(_fixture("bad_control.py"), "bad_control.py")
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {"GL-R301", "GL-R302", "GL-R303", "GL-R304",
                            "GL-R305", "GL-R306"}
    # both claim spellings: constant key AND unscoped key helper
    assert len(by_rule["GL-R301"]) == 2
    # the unbounded queue anchors on the append site
    assert "waiting" in by_rule["GL-R306"][0].message
    # leader-reachability: the blocking get() is inside _resolve, reached
    # from _leader_tick
    assert "_resolve" in by_rule["GL-R304"][0].message
    # ...and through the inheritance edge: _BaseResolver._lookup is only
    # leader-reachable via BadLeaderSub's _leader_sync
    assert len(by_rule["GL-R304"]) == 2
    assert "BadLeaderSub._lookup" in by_rule["GL-R304"][1].message
    # the launch storm anchors on the dispatch site inside the loop
    assert "_sync_grads" in by_rule["GL-R305"][0].snippet


def test_clean_control_fixture_passes():
    findings = lint_ctrl(_fixture("clean_control.py"), "clean_control.py")
    assert findings == [], [f.format() for f in findings]


def test_bad_obs_fixture_fires_gl_o401():
    findings = lint_ctrl(_fixture("bad_obs.py"), "bad_obs.py")
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    # the obs fixture trips ONLY the span-leak rule — three spellings
    assert set(by_rule) == {"GL-O401"}
    assert len(by_rule["GL-O401"]) == 3
    msgs = "\n".join(f.message for f in by_rule["GL-O401"])
    assert "discarded" in msgs          # handle_discarded
    assert "'sp'" in msgs               # assigned-but-unguarded spellings
    assert all(f.line > 0 and f.hint for f in findings)


def test_clean_obs_fixture_passes():
    findings = lint_ctrl(_fixture("clean_obs.py"), "clean_obs.py")
    assert findings == [], [f.format() for f in findings]


def test_bad_metrics_fixture_fires_gl_o402():
    findings = lint_ctrl(_fixture("bad_metrics.py"), "bad_metrics.py")
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    # trips ONLY the metric-name rule — four spellings: f-string,
    # concatenation, flat (undotted) literal, and a span's dynamic hist=
    assert set(by_rule) == {"GL-O402"}
    assert len(by_rule["GL-O402"]) == 4
    msgs = "\n".join(f.message for f in by_rule["GL-O402"])
    assert "span() hist=" in msgs
    assert "counter()" in msgs
    assert "gauge()" in msgs
    assert "histogram()" in msgs
    assert all(f.line > 0 and f.hint for f in findings)


def test_clean_metrics_fixture_passes():
    findings = lint_ctrl(_fixture("clean_metrics.py"), "clean_metrics.py")
    assert findings == [], [f.format() for f in findings]


def test_bad_spans_fixture_fires_gl_o403():
    findings = lint_ctrl(_fixture("bad_spans.py"), "bad_spans.py")
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    # trips ONLY the span-name rule — three spellings: f-string without a
    # family prefix, %-formatting, bare variable
    assert set(by_rule) == {"GL-O403"}
    assert len(by_rule["GL-O403"]) == 3
    msgs = "\n".join(f.message for f in by_rule["GL-O403"])
    assert "span()" in msgs
    assert "complete()" in msgs
    assert "instant()" in msgs
    assert all(f.line > 0 and f.hint for f in findings)


def test_clean_spans_fixture_passes():
    # static literals, colon families, the sanctioned f"family:{value}"
    # shape, keyword name=, and non-recorder receivers all stay silent
    findings = lint_ctrl(_fixture("clean_spans.py"), "clean_spans.py")
    assert findings == [], [f.format() for f in findings]


# ---------------------------------------------------------------------------
# Pass 2 fixtures (pure layers; the compile layer runs in the subprocess
# gate below)
# ---------------------------------------------------------------------------


def test_donation_rule_h201():
    bad, entry = lint_donation(
        "dp", donate_requested=True, alias_bytes=0, output_bytes=650_000)
    assert [f.rule for f in bad] == ["GL-H201"]
    assert entry["donation"] == "missing"
    clean, entry = lint_donation(
        "dp", donate_requested=True,
        alias_bytes=649_000, output_bytes=650_000)
    assert clean == [] and entry["donation"] == "verified"


def test_upcast_rule_h202_jaxpr():
    import jax
    import jax.numpy as jnp

    def bad(x):
        return x.astype(jnp.float32) * 2.0  # large bf16->f32 upcast

    def clean(x):
        # NOTE: jnp.sum would NOT be clean — it upcasts the bf16
        # accumulator to f32 (the rule caught that in an earlier draft of
        # this very test)
        return x * 2.0  # stays bf16

    big = jnp.zeros((128, 64), jnp.bfloat16)
    fired = lint_jaxpr(jax.make_jaxpr(bad)(big), "fix")
    assert [f.rule for f in fired] == ["GL-H202"]
    assert lint_jaxpr(jax.make_jaxpr(clean)(big), "fix") == []
    # below the element threshold: noise, not a finding
    small = jnp.zeros((8,), jnp.bfloat16)
    assert lint_jaxpr(jax.make_jaxpr(bad)(small), "fix") == []


def test_host_transfer_rule_h203():
    import jax
    import jax.numpy as jnp

    def bad(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    x = jnp.zeros((4,), jnp.float32)
    fired = lint_jaxpr(jax.make_jaxpr(bad)(x), "fix")
    assert "GL-H203" in {f.rule for f in fired}
    assert lint_jaxpr(jax.make_jaxpr(lambda v: v * 2)(x), "fix") == []
    # HLO-text spelling of the same class
    hlo_bad = ('  %send = f32[8] custom-call(f32[8] %p0), '
               'custom_call_target="SendToHost"\n')
    assert [f.rule for f in lint_hlo_text(hlo_bad, "fix")] == ["GL-H203"]
    assert lint_hlo_text("  %a = f32[8] add(f32[8] %p0, f32[8] %p0)\n",
                         "fix") == []


def test_int8_padding_rule_h205():
    # 30 tiny leaves: block/axis alignment zeros dwarf the payload
    fired, wire = lint_int8_padding([16] * 30, 8, label="fix")
    assert [f.rule for f in fired] == ["GL-H205"]
    assert wire["overhead_fraction"] > 0.25
    # one large aligned leaf: scales overhead only, well under threshold
    clean, wire = lint_int8_padding([262_144], 8, label="fix")
    assert clean == [] and wire["overhead_fraction"] < 0.05


# ---------------------------------------------------------------------------
# baseline machinery
# ---------------------------------------------------------------------------


def test_baseline_roundtrip_and_unused_reporting():
    f1 = make_finding("GL-R303", "a.py", 10, "thread", snippet="t = Thread()")
    f2 = make_finding("GL-R301", "b.py", 20, "claim", snippet="kv.add(k, 1)")
    text = render_baseline([f1])
    sups = parse_baseline(text)
    assert len(sups) == 1 and sups[0].rule == "GL-R303"
    kept, suppressed, unused = apply_baseline([f1, f2], sups)
    assert kept == [f2] and suppressed == [f1] and unused == []
    # snippet-substring matching survives line churn
    f1_moved = make_finding("GL-R303", "a.py", 99, "thread",
                            snippet="t = Thread()")
    kept, suppressed, _ = apply_baseline([f1_moved], sups)
    assert kept == [] and suppressed == [f1_moved]
    # unused entries are surfaced for deletion
    _, _, unused = apply_baseline([f2], sups)
    assert unused == sups


def test_baseline_parser_rejects_malformed():
    with pytest.raises(BaselineError):
        parse_baseline('rule = "GL-R303"')  # key outside a table
    with pytest.raises(BaselineError):
        parse_baseline('[[suppress]]\nrule = unquoted')
    with pytest.raises(BaselineError):
        parse_baseline('[[suppress]]\nfile = "a.py"')  # missing rule
    assert parse_baseline("# comment only\n") == []


def test_rule_catalog_is_complete():
    prefixes = {r[:5] for r in RULES}
    assert prefixes == {"GL-C1", "GL-H2", "GL-R3", "GL-O4"}
    assert all(title and hint for title, hint in RULES.values())


# ---------------------------------------------------------------------------
# repo gate
# ---------------------------------------------------------------------------


def test_repo_ast_passes_clean_against_baseline():
    """Passes 1+3 over the repo must be clean modulo the checked-in
    baseline — THE ratchet. A new finding means: fix it or triage it into
    analysis/baseline.toml with a reason."""
    from tpu_sandbox.analysis import load_baseline

    findings = run_collective_pass(ROOT) + run_control_pass(ROOT)
    kept, _, unused = apply_baseline(findings, load_baseline(BASELINE))
    assert kept == [], (
        "new graftlint findings (fix or triage into baseline.toml):\n"
        + "\n".join(f.format() for f in kept)
    )
    assert unused == [], (
        "stale baseline entries (delete them):\n"
        + "\n".join(f"{s.rule} {s.file} {s.match!r}" for s in unused)
    )


def _run_graftlint(*extra):
    """graftlint in a subprocess: the AOT tools mutate process env
    (forced compiled Pallas kernels), so pass 2's compile layer must
    never run inside this long-lived pytest process."""
    proc = run_child(
        [sys.executable, os.path.join(ROOT, "tools", "graftlint.py"),
         "--json", *extra], timeout=150, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (
        f"graftlint exited {proc.returncode}:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("steps,traced", [
    ("dp,zero,pjit,pipeline,dp-int8,sp",
     ("dp", "zero", "pjit", "pipeline", "dp-int8", "sp")),
    ("decode,prefill,fsdp,tp,ep",
     ("decode", "prefill", "prefill-b16", "fsdp", "tp", "ep")),
    ("mpmd", ("mpmd-s0-fwd", "mpmd-s0-bwd", "mpmd-s1-loss_grad"))],
    ids=["engines", "serve_and_pjit_layouts", "mpmd"])
def test_graftlint_cli_traces_all_steps(steps, traced):
    """Tier-1 half of the CLI gate: all three passes, jaxpr-tracing the
    real DP/ZeRO/pjit/pipeline steps — plus the engine-flag variant
    (int8 grad compress), SeqParallel, and the serve
    decode + bucketed-prefill steps — on CPU, a third of the steps a child
    (together they are `--steps`' default). The AOT compiles are skipped
    here (`--no-aot`) to keep tier-1 inside its time budget — the full
    chipless AOT receipt runs in the slow twin below."""
    first = steps.startswith("dp,")  # the AST passes run once, with these
    report = _run_graftlint(*(["--all"] if first else ["--pass", "hlo"]),
                            "--no-aot", "--steps", steps)
    assert report["findings"] == 0
    assert not first or report["unused_suppressions"] == 0
    hlo = report["hlo"]
    for step in traced:
        assert hlo[step]["status"] == "traced", hlo


@pytest.mark.slow
def test_graftlint_cli_full_run_including_aot():
    """Pass 2 end-to-end: AOT-compiles the DP/ZeRO steps against the
    chipless v5e topology and verifies donation and int8 wire padding. Skips gracefully where the toolchain can't build
    topologies."""
    report = _run_graftlint("--all")
    assert report["findings"] == 0
    aot = report["hlo"]["aot"]
    if aot.get("status") == "skipped":
        pytest.skip(f"AOT toolchain unavailable: {aot.get('reason')}")
    # the acceptance receipt: donation status for the DP and ZeRO steps
    assert aot["dp"]["donation"] == "verified", aot
    assert aot["zero"]["donation"] == "verified", aot
