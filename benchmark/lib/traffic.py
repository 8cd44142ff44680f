"""One general traffic generator: a mix is a data file of parameters
(``benchmark/traffic/<mix>.json``) and a seed; the same seed gives the
same bytes.

Four kinds of mix, by the file's ``kind``:

``image_batches``  labelled 28x28 images for an image trainer (the program
                   resizes on the device); ``n_images``, ``batch_per_chip``.
``token_batches``  a token stream for an LM trainer; ``batch``, ``seq_len``.
``requests``       an open-loop arrival schedule for a server: ``rate_per_s``,
                   ``arrivals`` (``poisson``, or ``gamma`` with a ``cv``),
                   ``prompt_len`` / ``output_len`` (``lognormal`` with
                   ``median``, ``sigma``, ``min``, ``max``; or ``uniform``
                   with ``min``, ``max``), ``shared_prefix`` (``count``
                   prefixes of ``len`` tokens, drawn uniformly; 0 = every
                   prompt unique), ``warmup_s`` of the same mix before the
                   window.
``decode_replay``  a fixed set of sessions for a server, no arrivals:
                   ``sessions`` prompts of ``prompt_len`` (as above) and
                   ``max_new_tokens`` each, every prompt unique;
                   ``warmup_steps`` unmeasured steps after the prefills.

Every attribute draws from its own stream (``default_rng([seed, k])``), so
changing one distribution leaves the others' draws where they were.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

_STREAMS = {"labels": 0, "noise": 1, "tokens": 2, "arrivals": 3,
            "prompt_len": 4, "output_len": 5, "prompt_tokens": 6,
            "prefix_tokens": 7, "prefix_choice": 8, "session_order": 9,
            "session_tokens": 10}


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), _STREAMS[stream]])


def image_batches(spec: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(uint8 [n,28,28], uint8 [n]): ten fixed class prototypes (Gaussian
    blobs at class-specific positions) plus per-image noise — the shape of
    ``tpu_sandbox.data.mnist.synthetic_mnist`` (copied: there is no network
    for the real MNIST), with labels and noise drawn from ``seed``."""
    n = int(spec["n_images"])
    yy, xx = np.mgrid[0:28, 0:28].astype(np.float32)
    protos = np.stack([
        220.0 * np.exp(-(((yy - (6 + 4 * (c // 4) + 3 * ((c * 7) % 3))) ** 2
                          + (xx - (5 + 6 * (c % 4))) ** 2) / (2 * 3.0 ** 2)))
        for c in range(10)])
    labels = _rng(seed, "labels").integers(0, 10, size=n).astype(np.uint8)
    noise = _rng(seed, "noise").normal(0.0, 15.0, size=(n, 28, 28)).astype(
        np.float32)
    images = np.clip(protos[labels] + noise, 0, 255).astype(np.uint8)
    return images, labels


def token_batches(spec: dict, seed: int, vocab: int):
    """Endless ``(tokens, targets)`` int32 ``[batch, seq_len]`` batches: the
    stream of ``lm_train.make_batches`` (copied) — uniform random tokens,
    targets = (token + k) mod vocab with k = 1 + position mod 3, learnable
    from position embeddings."""
    batch, seq_len = int(spec["batch"]), int(spec["seq_len"])
    rng = _rng(seed, "tokens")
    shift = (np.arange(seq_len, dtype=np.int32) % 3) + 1
    while True:
        tokens = rng.integers(0, vocab, size=(batch, seq_len), dtype=np.int32)
        yield tokens, ((tokens + shift[None, :]) % vocab).astype(np.int32)


@dataclass(frozen=True)
class Arrival:
    due_s: float        # seconds from the start of the window; < 0 = warm-up
    rid: str
    prompt: tuple[int, ...]
    max_new_tokens: int


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "lognormal":
        draw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    elif spec["dist"] == "uniform":
        draw = rng.uniform(spec["min"], spec["max"] + 1, size=n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(draw), spec["min"], spec["max"]).astype(np.int64)


def requests(spec: dict, seed: int, seconds: float, vocab: int) -> list[Arrival]:
    """The arrival schedule from ``-warmup_s`` to ``seconds``, in due order."""
    rate = float(spec["rate_per_s"])
    warmup = float(spec.get("warmup_s", 0.0))
    span = warmup + float(seconds)
    arrivals = spec.get("arrivals", {"process": "poisson"})
    rng = _rng(seed, "arrivals")
    # draw well past the span, then cut: the count is random, the draws are not
    n_draw = int(rate * span * 1.5 + 10 * np.sqrt(rate * span) + 32)
    if arrivals["process"] == "poisson":
        gaps = rng.exponential(1.0 / rate, size=n_draw)
    elif arrivals["process"] == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        gaps = rng.gamma(shape, 1.0 / (rate * shape), size=n_draw)
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    due = np.cumsum(gaps)
    due = due[due < span] - warmup
    n = len(due)
    plen = _lengths(spec["prompt_len"], n, _rng(seed, "prompt_len"))
    olen = _lengths(spec["output_len"], n, _rng(seed, "output_len"))
    shared = spec.get("shared_prefix", {"count": 0, "len": 0})
    prefixes = _rng(seed, "prefix_tokens").integers(
        1, vocab, size=(int(shared["count"]), int(shared["len"])))
    choice = (_rng(seed, "prefix_choice").integers(
        0, shared["count"], size=n) if shared["count"] else None)
    tok_rng = _rng(seed, "prompt_tokens")
    out = []
    for i in range(n):
        body = tok_rng.integers(1, vocab, size=int(plen[i]))
        if choice is not None:
            body = np.concatenate([prefixes[choice[i]], body])
        out.append(Arrival(float(due[i]), f"r{i}", tuple(int(t) for t in body),
                           int(olen[i])))
    return out


@dataclass(frozen=True)
class ReplaySession:
    rid: str
    prompt: tuple[int, ...]
    max_new_tokens: int


def _length_quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` evenly spaced quantiles ((i + 1/2) / n) of a length
    distribution, clipped as ``_lengths`` clips its draws."""
    p = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in p])
        values = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        values = spec["min"] + p * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(values), spec["min"], spec["max"]).astype(np.int64)


def decode_replay(spec: dict, seed: int, vocab: int) -> list[ReplaySession]:
    """``sessions`` sessions to be prefilled in set-up and decoded in the
    window. Every seed gets the **same set** of prompt lengths (the
    distribution's evenly spaced quantiles: the work of a step must not
    swing with the seed) in an order drawn from the seed, and token ids of
    its own, unique to each session."""
    n = int(spec["sessions"])
    lengths = _rng(seed, "session_order").permutation(
        _length_quantiles(spec["prompt_len"], n))
    rng = _rng(seed, "session_tokens")
    return [ReplaySession(f"s{i}", tuple(int(t) for t in rng.integers(
        1, vocab, size=int(lengths[i]))), int(spec["max_new_tokens"]))
        for i in range(n)]


def digest(obj) -> str:
    """sha256 over the generated traffic, for the byte-identity test."""
    h = hashlib.sha256()

    def feed(x):
        if isinstance(x, np.ndarray):
            h.update(str(x.dtype).encode() + str(x.shape).encode())
            h.update(np.ascontiguousarray(x).tobytes())
        elif isinstance(x, (list, tuple)):
            for y in x:
                feed(y)
        elif isinstance(x, Arrival):
            feed((x.due_s, x.rid, np.asarray(x.prompt, np.int64),
                  x.max_new_tokens))
        else:
            h.update(repr(x).encode())

    feed(obj)
    return h.hexdigest()
