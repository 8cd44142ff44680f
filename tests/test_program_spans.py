"""The program's one span primitive (``obs.record.Recorder.span``) and where
it is applied: every span lands on the profiler's timeline, in the always-on
registry and (when enabled) in the JSONL; a span named for device work ends
after that work; the compiled steps carry ``loss`` / ``grad_sync`` /
``optimizer`` scopes. All on the CPU: counts and orderings, never a time
that stands for a device's."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.helpers import run_child
from tpu_sandbox.obs import get_recorder, get_registry, reset_recorder
from tpu_sandbox.obs.record import Recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def registry():
    reg = get_registry()
    reg.reset()
    yield reg
    reg.reset()


@pytest.fixture
def dark(monkeypatch):
    """The process recorder, disabled (no ``TPU_SANDBOX_TRACE_DIR``)."""
    monkeypatch.delenv("TPU_SANDBOX_TRACE_DIR", raising=False)
    reset_recorder()
    yield get_recorder()
    reset_recorder()


def _mesh2():
    from tpu_sandbox.runtime.mesh import make_mesh

    return make_mesh({"data": 2}, devices=jax.devices()[:2])


def _hist(reg, name):
    return reg.snapshot()["histograms"].get(name)


def _state():
    from tpu_sandbox.train import TrainState

    return TrainState(step=jnp.zeros((), jnp.int32), params=jnp.zeros(()),
                      batch_stats={}, opt_state={})


@jax.jit
def _work(state, images, labels=None):
    """A train step that is one reduction: the loop is what is under test,
    not a model."""
    loss = jnp.sum(images)
    return state.replace(step=state.step + 1, params=state.params + loss), loss


def _batches(steps):
    return [(np.ones((2, 4), np.float32), np.zeros((2,), np.int32))
            for _ in range(steps)]


def _tiny_fit(steps, step_fn=_work, **trainer_kw):
    from tpu_sandbox.train import Trainer

    trainer = Trainer(step_fn, verbose=False, **trainer_kw)
    return trainer.fit(_state(), _batches(steps), 1)


# -- the primitive ------------------------------------------------------------


def test_span_feeds_the_registry_with_the_recorder_disabled(registry, dark):
    assert not dark.enabled
    with dark.span("train:dispatch", hist="train.dispatch_s", loop=True):
        time.sleep(0.002)
    h = _hist(registry, "train.dispatch_s")
    assert h["count"] == 1 and h["sum"] >= 0.002
    # a span with no hist= observes nothing, and complete() takes hist= too
    with dark.span("admit"):
        pass
    dark.complete("train:step", time.monotonic() - 0.5, hist="train.step_s",
                  loop=True)
    snap = registry.snapshot()["histograms"]
    assert set(snap) == {"train.dispatch_s", "train.step_s"}
    assert snap["train.step_s"]["sum"] >= 0.5


def test_span_writes_jsonl_only_when_enabled_and_buffers_until_flush(
        registry, tmp_path):
    path = str(tmp_path / "spans.jsonl")
    rec = Recorder(path, proc="unit")
    size0 = os.path.getsize(path)  # the preamble is flushed at once
    for _ in range(100):  # 25 train steps' worth: no write in the hot path
        with rec.span("train:dispatch", hist="train.dispatch_s", loop=True):
            pass
    assert os.path.getsize(path) == size0
    rec.close()
    spans = [json.loads(line) for line in open(path)][1:]
    assert len(spans) == 100
    assert {s["name"] for s in spans} == {"train:dispatch"}
    # a loop span starts no trace: the collector's request chains skip it
    assert all(s["trace"] is None and s["span"] is None for s in spans)
    assert _hist(registry, "train.dispatch_s")["count"] == 100


def test_a_root_span_still_starts_a_trace_and_children_chain(tmp_path):
    rec = Recorder(str(tmp_path / "t.jsonl"), proc="unit", flush_every=1)
    with rec.span("submit") as root:
        with rec.span("admit", parent=root.ctx) as child:
            pass
    rec.close()
    assert root.ctx.trace_id == child.ctx.trace_id
    assert child.parent.span_id == root.ctx.span_id


def test_a_process_without_jax_can_open_and_close_a_span():
    # the package's __init__ imports jax (runtime.bootstrap); a gateway /
    # scheduler / KV process that loads obs/ alone must not pay that import
    code = """
import os, sys, types
pkg = types.ModuleType("tpu_sandbox")
pkg.__path__ = [os.path.join(os.getcwd(), "tpu_sandbox")]
sys.modules["tpu_sandbox"] = pkg
from tpu_sandbox.obs.record import get_recorder
from tpu_sandbox.obs.metrics import get_registry
with get_recorder().span("gateway:route", hist="gateway.route_s"):
    pass
assert get_registry().histogram("gateway.route_s").count == 1
assert "jax" not in sys.modules, "obs/record imported jax"
print("ok")
"""
    env = {k: v for k, v in os.environ.items()
           if k != "TPU_SANDBOX_TRACE_DIR"}
    proc = run_child([sys.executable, "-c", code], cwd=ROOT, env=env,
                     timeout=60)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


def test_the_only_trace_annotation_of_the_program_is_the_recorders():
    hits = []
    for d, _, files in os.walk(os.path.join(ROOT, "tpu_sandbox")):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(d, f), encoding="utf-8").read()
                if "TraceAnnotation(" in text or "import TraceAnnotation" in text:
                    hits.append(os.path.relpath(os.path.join(d, f), ROOT))
    assert hits == ["tpu_sandbox/obs/record.py"]


# -- the training loop ----------------------------------------------------------


def test_fit_fills_the_registry_with_the_recorder_disabled(registry, dark):
    steps = 7
    _tiny_fit(steps, log_every=3)
    snap = registry.snapshot()
    assert snap["counters"]["train.steps"] == steps
    hist = snap["histograms"]
    # the first iteration of a fit has no predecessor
    assert hist["train.step_s"]["count"] == steps - 1
    assert hist["train.dispatch_s"]["count"] == steps
    # one draw a batch, and the one that found the loader empty
    assert hist["train.next_batch_s"]["count"] == steps + 1
    # two log lines (steps 3 and 6) and fit's final block_until_ready
    assert hist["train.sync_s"]["count"] == 2 + 1


def test_fit_under_the_profiler_puts_its_spans_on_the_host_plane(
        registry, dark, tmp_path):
    from jax.profiler import ProfileData

    from tpu_sandbox.utils.profiling import trace

    with trace(str(tmp_path)):
        _tiny_fit(5, log_every=2)
    found = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(found) == 1
    host = [p for p in ProfileData.from_file(found[0]).planes
            if p.name == "/host:CPU"]
    assert len(host) == 1
    names = [e.name for line in host[0].lines for e in line.events]
    assert names.count("train:dispatch") == 5
    assert names.count("train:next_batch") == 6
    assert names.count("train:sync") == 2 + 1
    # train:step is an interval between two calls: no annotation of its own
    assert "train:step" not in names


def test_train_step_spans_the_device_work_and_dispatch_only_the_enqueue(
        registry, dark):
    """``train:step`` of a step that waits 20 ms for its result reads
    >= 20 ms; ``train:dispatch`` of a step that only enqueues does not: the
    span named for the step no longer measures the enqueue."""
    def waiting_step(state, images, labels):
        out = jax.block_until_ready(_work(state, images))
        time.sleep(0.020)  # stands for the device finishing 20 ms later
        return out

    _tiny_fit(4, step_fn=waiting_step)
    h = _hist(registry, "train.step_s")
    assert h["count"] == 3 and h["min"] >= 0.020

    registry.reset()

    # asynchronous dispatch: the call returns at once, and what paces the
    # loop (here a loader that takes 20 ms a batch) lies outside it
    class SlowLoader(list):
        def __iter__(self):
            for b in list.__iter__(self):
                time.sleep(0.020)
                yield b

    from tpu_sandbox.train import Trainer

    Trainer(_work, verbose=False).fit(_state(), SlowLoader(_batches(4)), 1)
    hist = registry.snapshot()["histograms"]
    assert hist["train.dispatch_s"]["p50"] < 0.010
    assert hist["train.step_s"]["min"] >= 0.020
    assert hist["train.next_batch_s"]["p50"] >= 0.020


@pytest.mark.usefixtures("light_compile")
def test_lm_train_loop_carries_the_same_spans(registry, dark):
    import lm_train

    # no --force-cpu: it would resize this process's CPU client for every
    # later test; the conftest's virtual CPU devices are the backend here
    args = lm_train.build_parser().parse_args(
        ["--steps", "4", "--log-every", "2", "--n-layers", "1", "--d-model",
         "16", "--n-heads", "2", "--d-ff", "32", "--seq-len", "16",
         "--batch", "2", "--lr", "1e-2"])
    lm_train.train(args)
    snap = registry.snapshot()
    assert snap["counters"]["train.steps"] == 4
    hist = snap["histograms"]
    assert hist["train.step_s"]["count"] == 3
    assert hist["train.dispatch_s"]["count"] == 4
    assert hist["train.sync_s"]["count"] == 2
    assert hist["place.batch_s"]["count"] == 4
    assert hist["place.state_s"]["count"] == 1
    assert hist["setup.model_init_s"]["count"] == 1
    assert hist["setup.opt_init_s"]["count"] == 1


# -- placement, set-up, the compile cache ----------------------------------------


def test_placement_and_setup_spans_of_data_parallel(registry, dark):
    from tpu_sandbox.models.convnet import ConvNet
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.train import TrainState

    model, tx = ConvNet(), optax.sgd(1e-3)
    state = TrainState.create(model, jax.random.key(0),
                              jnp.zeros((1, 28, 28, 1)), tx)
    hist = registry.snapshot()["histograms"]
    assert hist["setup.model_init_s"]["count"] == 1
    assert hist["setup.opt_init_s"]["count"] == 1
    assert hist["setup.model_init_s"]["sum"] > hist["setup.opt_init_s"]["sum"]
    dp = DataParallel(model, tx, _mesh2())
    dp.shard_state(state)
    images = np.zeros((4, 28, 28, 1), np.float32)
    labels = np.zeros((4,), np.int32)
    dp.shard_batch(images, labels)
    dp.shard_batch(images, labels)
    snap = registry.snapshot()
    assert snap["histograms"]["place.state_s"]["count"] == 1
    assert snap["histograms"]["place.batch_s"]["count"] == 2


def test_configure_compile_cache_counts_hits_and_misses(
        registry, launch, tmp_path, monkeypatch):
    bootstrap = launch
    registry.reset()
    assert "compile.cache_misses" not in registry.snapshot()["counters"]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    bootstrap.configure_compile_cache()
    bootstrap.configure_compile_cache()  # registers its listener once
    # both exist from the call on: a warm run reads 0, not nothing
    assert registry.snapshot()["counters"] == {
        "compile.cache_hits": 0, "compile.cache_misses": 0,
        "compile.in_loop": 0}
    # what jax reports through jax.monitoring lands in the counters (the
    # names are jax's own: _src/compiler.py, _src/compilation_cache.py)
    from jax._src import monitoring

    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert registry.snapshot()["counters"] == {
        "compile.cache_hits": 2, "compile.cache_misses": 1,
        "compile.in_loop": 0}


# -- scopes inside the compiled steps ----------------------------------------------


def _op_names(lowered):
    import re

    text = lowered.as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def _has_scope(names, scope):
    """``scope`` as one level of an op_name: ``jit(step)/optimizer/add``,
    wrapped by a transform as ``jit(step)/transpose(jvp(loss))/mul``, or
    relative inside a ``shard_map`` body as ``grad_sync/psum``."""
    import re

    level = re.compile(rf"(?:^|[/(]){scope}(?:[/)]|$)")
    return any(level.search(n) for n in names)


def _convnet_state():
    from tpu_sandbox.models.convnet import ConvNet
    from tpu_sandbox.train import TrainState

    model, tx = ConvNet(), optax.sgd(1e-3)
    state = TrainState.create(model, jax.random.key(0),
                              jnp.zeros((1, 28, 28, 1)), tx)
    return model, tx, state


@pytest.mark.parametrize("engine", [
    "one_chip", "data_parallel", "data_parallel_zero",
    "data_parallel_compressed", "pjit_lm"])
def test_compiled_steps_carry_loss_grad_sync_and_optimizer_scopes(
        engine, registry, dark):
    images = jnp.zeros((4, 28, 28, 1))
    labels = jnp.zeros((4,), jnp.int32)
    if engine == "one_chip":
        from tpu_sandbox.train import make_train_step

        model, tx, state = _convnet_state()
        lowered = make_train_step(model, tx).lower(state, images, labels)
        want = {"loss", "optimizer"}
    elif engine.startswith("data_parallel"):
        from tpu_sandbox.parallel import DataParallel

        # each of the engine's three sync forms stands under ``grad_sync``
        model, tx, state = _convnet_state()
        dp = DataParallel(
            model, tx, _mesh2(), zero=engine.endswith("_zero"),
            grad_compress="bf16" if engine.endswith("_compressed") else "none")
        lowered = dp.lower_step(dp.shard_state(state),
                                *dp.shard_batch(images, labels))
        want = {"loss", "grad_sync", "optimizer"}
    else:
        from tpu_sandbox.models.transformer import (TransformerConfig,
                                                    TransformerLM)
        from tpu_sandbox.parallel import PjitEngine
        from tpu_sandbox.train import TrainState

        cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=8)
        model, tx = TransformerLM(cfg), optax.adam(1e-3)
        tokens = jnp.zeros((2, 8), jnp.int32)
        state = TrainState.create(model, jax.random.key(0), tokens[:1], tx)
        eng = PjitEngine(model, tx, _mesh2(), task="lm")
        lowered = eng.lower_step(eng.shard_state(state),
                                 *eng.shard_batch(tokens, tokens))
        # XLA inserts the gradient all-reduce itself: there is no gradient
        # mean in the program to scope
        want = {"loss", "optimizer"}
    names = _op_names(lowered)
    for scope in want:
        assert _has_scope(names, scope), (engine, scope)
    if "grad_sync" not in want:
        assert not _has_scope(names, "grad_sync")
    # the loss scope goes around the loss, not the forward pass: the model's
    # own modules stay at the top of their op_names, where the benchmark
    # looks for its kernel scopes
    assert not any("loss)/conv1" in n or "loss)/block0" in n
                   or "loss/conv1" in n or "loss/block0" in n for n in names)


def test_serve_programs_have_stable_names_and_scopes():
    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.decode import (buffer_shapes, make_decode_fn,
                                          make_prefill_fn)

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=2,
                            d_ff=32, max_len=32)
    cache = CacheConfig(num_blocks=8, block_size=4, max_blocks_per_seq=4)
    from tpu_sandbox.models.transformer import TransformerLM

    params = jax.eval_shape(lambda: TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    kd, vd = buffer_shapes(cfg, cache, 2, jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    prefill = make_prefill_fn(cfg).lower(
        params, kd, vd, i32(1, 8), i32(8), i32())
    decode = make_decode_fn(cfg, cache).lower(
        params, kd, vd, i32(2, 1), i32(2), i32(2, 4))
    assert "jit_serve_prefill" in prefill.as_text()
    assert "jit_serve_decode" in decode.as_text()
    assert _has_scope(_op_names(prefill), "write_kv")
    names = _op_names(decode)
    for scope in ("write_kv", "gather_ctx"):
        assert _has_scope(names, scope)
    # flax names the modules; the two scopes sit inside each block's attn
    assert any("block1/attn/gather_ctx" in n for n in names)
    assert any("block1/mlp" in n for n in names)


# -- the serving engine ----------------------------------------------------------


class _CountingSteps:
    """The engine's compiled programs, with every call and every fetch of a
    result counted: the logits and the rows' greedy picks (what the engine
    fetches for a greedy request) come back as objects that note when
    ``np.asarray`` reads them."""

    class _Logits:
        def __init__(self, owner, array):
            self.owner, self.array = owner, array

        def __array__(self, dtype=None, copy=None):
            self.owner.events.append("fetch")
            time.sleep(0.005)  # the wait for the device
            return np.asarray(self.array, dtype)

    def __init__(self, real):
        self.real = real
        self.events: list[str] = []
        self.prefill = {b: self._wrap("prefill", f)
                        for b, f in real.prefill.items()}
        self.decode = self._wrap("decode", real.decode)
        self.pick_bucket = real.pick_bucket

    def next_tokens(self, picks):
        """The call dispatched ahead takes its tokens from the picks on the
        device: no fetch."""
        return self.real.next_tokens(picks.array)

    def _wrap(self, kind, fn):
        def call(*args):
            self.events.append(kind)
            logits, picks, k, v = fn(*args)
            return (self._Logits(self, logits), self._Logits(self, picks),
                    k, v)
        return call


def _tiny_engine(tmp_path=None):
    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=32, max_len=32)
    serve = ServeConfig(model=cfg, cache=CacheConfig(
        num_blocks=16, block_size=4, max_blocks_per_seq=8),
        max_batch=2, buckets=(8,))
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousEngine(params, serve)
    eng.step_fns = _CountingSteps(eng.step_fns)
    return eng


def test_engine_spans_end_after_the_logits_are_fetched(registry, tmp_path,
                                                       monkeypatch):
    from tpu_sandbox.serve.engine import Request

    monkeypatch.setenv("TPU_SANDBOX_TRACE_DIR", str(tmp_path))
    reset_recorder()
    try:
        eng = _tiny_engine()
        for n in range(3):
            eng.submit(Request(rid=f"r{n}", prompt=[1 + n, 2, 3],
                               max_new_tokens=4))
        eng.run_until_idle()
        get_recorder().flush()
    finally:
        reset_recorder()
    events = eng.step_fns.events
    decode_calls = events.count("decode")
    assert decode_calls >= 3 and events.count("prefill") == 3
    # every program call is followed by the fetch of its logits
    assert events.count("fetch") == len(events) // 2
    snap = registry.snapshot()
    hist = snap["histograms"]
    assert hist["engine.decode_call_s"]["count"] == decode_calls
    assert hist["engine.decode_call_s"]["min"] >= 0.005  # holds the fetch
    assert hist["engine.prefill_s"]["count"] == 3
    assert hist["engine.prefill_s"]["min"] >= 0.005
    assert hist["engine.step_s"]["count"] == eng.steps
    assert hist["engine.sample_s"]["count"] == decode_calls
    # a decode call is two phases, which the step log keeps: the dispatch
    # ends before the fetch, and the wait holds it (``_CountingSteps``
    # sleeps inside ``__array__``)
    records = list(eng.step_log.steps)
    assert sum(r.calls for r in records) == decode_calls
    assert min(r.wait_s for r in records) >= 0.005
    assert sum(r.dispatch_s + r.wait_s for r in records) \
        <= hist["engine.decode_call_s"]["sum"]
    assert max(r.dispatch_s for r in records) \
        < hist["engine.decode_call_s"]["max"]
    assert hist["engine.admit_s"]["count"] == hist["engine.step_s"]["count"]
    assert "engine.occupancy" not in hist
    assert [r.rows for r in records].count(0) == 0
    assert sum(r.rows for r in eng.step_log.steps) == 3 * 3  # a prefill's
    assert snap["counters"]["engine.tokens"] == 3 * 4   # first token counts
    # the JSONL: a request's prefill span holds the fetch too, inside admit
    (log,) = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    spans = [json.loads(line) for line in open(tmp_path / log)]
    prefills = [s for s in spans if s.get("name") == "prefill"]
    admits = {s["span"]: s for s in spans if s.get("name") == "admit"}
    assert len(prefills) == 3
    for p in prefills:
        assert p["dur"] >= 0.005
        a = admits[p["parent"]]
        assert a["ts"] <= p["ts"] and \
            p["ts"] + p["dur"] <= a["ts"] + a["dur"] + 1e-6
    steps = [s for s in spans if s.get("name") == "engine:step"]
    assert len(steps) == eng.steps and all(s["trace"] is None for s in steps)


def test_buffered_spans_are_written_at_a_clean_exit(tmp_path):
    # fewer spans than flush_every, no explicit flush: the process's exit
    # writes them (the LM cell's place:batch spans were lost without it)
    code = """
from tpu_sandbox.obs import get_recorder
for _ in range(5):
    with get_recorder().span("place:batch", hist="place.batch_s", loop=True):
        pass
"""
    env = dict(os.environ, TPU_SANDBOX_TRACE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    proc = run_child([sys.executable, "-c", code], cwd=ROOT, env=env,
                     timeout=120)
    assert proc.returncode == 0, proc.stderr
    (log,) = os.listdir(tmp_path)
    names = [json.loads(line).get("name") for line in open(tmp_path / log)]
    assert names.count("place:batch") == 5


# -- the launch seen from inside (PR 35) ------------------------------------------


def _records(path):
    return [json.loads(line) for line in open(path)][1:]


def test_a_loop_span_records_the_span_it_was_opened_inside(registry, tmp_path):
    rec = Recorder(str(tmp_path / "t.jsonl"), proc="unit")
    with rec.span("setup:build", loop=True):
        assert rec.innermost() == "setup:build"
        with rec.span("setup:model_init", hist="setup.model_init_s",
                      loop=True):
            with rec.span("trace:kernel", loop=True):
                assert rec.innermost() == "trace:kernel"
                # what the compile listener asks: kernel sites passed over
                assert rec.innermost(skip="trace:") == "setup:model_init"
            rec.complete("compile:trace", time.monotonic() - 0.01, loop=True)
        # a request span names its parent itself and is no one's child here
        with rec.span("submit") as root:
            with rec.span("admit", parent=root.ctx):
                pass
    assert rec.innermost() is None
    rec.close()
    by_name = {r["name"]: r for r in _records(tmp_path / "t.jsonl")}
    assert by_name["setup:build"]["parent"] is None
    assert by_name["setup:model_init"]["parent"] == "setup:build"
    assert by_name["trace:kernel"]["parent"] == "setup:model_init"
    assert by_name["compile:trace"]["parent"] == "setup:model_init"
    assert by_name["submit"]["parent"] is None
    assert by_name["admit"]["parent"] == by_name["submit"]["span"]
    # self time = duration less what the children cover
    build, init = by_name["setup:build"], by_name["setup:model_init"]
    assert build["ts"] <= init["ts"] and init["dur"] <= build["dur"]


@pytest.mark.parametrize("how", ["exception", "out_of_order", "other_thread"])
def test_the_stack_of_open_spans_is_empty_after(how, dark):
    rec = dark
    if how == "exception":
        with pytest.raises(RuntimeError):
            with rec.span("setup:build", loop=True):
                with rec.span("setup:data", loop=True):
                    raise RuntimeError("the loader failed")
    elif how == "out_of_order":
        outer = rec.begin_span("engine:step", loop=True)
        try:
            inner = rec.begin_span("engine:admit", loop=True)
            try:
                outer.close()  # the outer one first: it leaves by identity
                assert rec.innermost() == "engine:admit"
            finally:
                inner.close()
        finally:
            outer.close()      # a second close is a no-op
    else:
        import threading

        sp = rec.begin_span("engine:step", loop=True)
        try:
            t = threading.Thread(target=sp.close)
            t.start()
            t.join(10)
        finally:
            sp.close()
    assert rec.innermost() is None and rec._open_spans() == []


def _hist_sums(reg, name):
    """``under`` label -> (count, sum) of the histogram ``name``."""
    out = {}
    for key, h in reg.snapshot()["histograms"].items():
        if key.startswith(name + "{under="):
            out[key[len(name) + 7:-1]] = (h["count"], h["sum"])
    return out


def test_a_jit_inside_a_jit_counts_its_trace_seconds_once(
        registry, dark, launch):
    """jax reports the inner function's trace before, and within, the
    outer's. The histograms observe each phase less what lies inside it, so
    their sum is the outermost interval; the table keeps whole seconds by
    program."""
    @jax.jit
    def launch_inner(x):
        time.sleep(0.05)          # at trace time
        return x * 2

    @jax.jit
    def launch_outer(x):
        time.sleep(0.02)
        return launch_inner(x) + launch_inner(x + 1)

    launch_outer.lower(np.ones(3, np.float32))
    gauges = registry.snapshot()["gauges"]
    outer = gauges[
        "compile.program_s{cache=none,phase=trace,program=jit(launch_outer)}"]
    inner = gauges[
        "compile.program_s{cache=none,phase=trace,program=jit(launch_inner)}"]
    assert inner >= 0.05 and outer >= 0.07 and outer >= inner + 0.02
    traces = _hist_sums(registry, "compile.trace_s")
    assert set(traces) == {"none"}
    count, total = traces["none"]
    assert count >= 3                       # outer, inner twice, the jnp calls
    assert total == pytest.approx(outer, abs=0.005)   # not outer + inner
    lowered = _hist_sums(registry, "compile.lower_s")
    assert lowered["none"][0] == 1          # one module: the outer program's
    assert "compile.backend_s{under=none}" not in \
        registry.snapshot()["histograms"]   # lowered, never compiled


def test_phases_carry_the_span_they_ran_under(registry, launch, tmp_path,
                                              monkeypatch):
    from tpu_sandbox.train import TrainState

    monkeypatch.setenv("TPU_SANDBOX_TRACE_DIR", str(tmp_path))
    reset_recorder()
    try:
        class Tiny:
            def init(self, rng, x):
                return {"params": {"w": jax.random.normal(rng, (4, 3)) + x}}

        TrainState.create(Tiny(), jax.random.key(0), jnp.zeros(()),
                          optax.adam(1e-2))     # two moments: zeros_like
        jax.jit(lambda v: v * 3 + 1)(np.float32(2))   # under no span
        get_recorder().flush()
    finally:
        reset_recorder()
    backend = _hist_sums(registry, "compile.backend_s")
    assert backend["setup:model_init"][0] >= 2     # eager: a program an op
    assert backend["none"][0] >= 1     # the key, the sample, the lambda
    assert "setup:opt_init" in _hist_sums(registry, "compile.trace_s")
    (log,) = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    records = _records(tmp_path / log)
    phases = [r for r in records if r["name"].startswith("compile:")]
    assert {r["name"] for r in phases} == {
        "compile:trace", "compile:lower", "compile:backend"}
    under = {r["args"]["under"] for r in phases}
    assert under == {"setup:model_init", "setup:opt_init", "none"}
    for r in phases:   # a loop record's parent is the span it ran under
        assert (r["parent"] or "none") == r["args"]["under"]
        assert r["args"]["program"].startswith("jit(")
        assert "step" not in r["args"]             # no loop runs
    lam = [r for r in phases if r["args"]["program"] == "jit(<lambda>)"]
    assert {r["name"] for r in lam} == {
        "compile:trace", "compile:lower", "compile:backend"}
    assert all(r["args"]["cache"] == "none" for r in lam)


def test_the_program_table_keeps_the_largest_and_a_backend_phase_its_cache(
        registry, launch):
    from jax._src import monitoring

    table = launch.PROGRAM_TABLE

    def phase(name, seconds, event="backend_compile_duration"):
        monitoring.record_event_duration_secs(
            "/jax/core/compile/" + event, seconds, fun_name=name)

    phase("train_step", 10.0, "jaxpr_trace_duration")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    phase("jit(train_step)", 0.15)               # a load: the event fell in it
    for i in range(table + 8):                   # a flood of small programs
        phase(f"jit(op{i})", 0.001 * (i + 1))
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    phase("jit(reference)", 5.0)
    gauges = registry.snapshot()["gauges"]
    assert gauges["compile.program_s{cache=none,phase=trace,"
                  "program=jit(train_step)}"] == 10.0
    assert gauges["compile.program_s{cache=hit,phase=backend,"
                  "program=jit(train_step)}"] == 0.15
    assert gauges["compile.program_s{cache=miss,phase=backend,"
                  "program=jit(reference)}"] == 5.0
    programs = {key[key.index("program=") + 8:-1] for key in gauges}
    assert len(programs) == table                # bounded, by program
    assert "jit(op0)" not in programs            # the smallest left
    assert f"jit(op{table + 7})" in programs


def test_a_compile_inside_the_loop_is_counted_where_it_happens(
        registry, launch, tmp_path, monkeypatch):
    from tpu_sandbox.train import Trainer

    monkeypatch.setenv("TPU_SANDBOX_TRACE_DIR", str(tmp_path))
    reset_recorder()
    try:
        @jax.jit
        def ragged_step(state, images, labels=None):
            loss = jnp.sum(images)
            return state.replace(step=state.step + 1), loss

        batches = _batches(3) + [
            (np.ones((1, 4), np.float32), np.zeros((1,), np.int32))]
        state = _state()
        # what a fit dispatches round its loop (it reads state.step, and
        # waits at its end), compiled before the loop under test
        Trainer(ragged_step, verbose=False).fit(state, [], 0)
        jax.block_until_ready(ragged_step(state, *batches[0]))
        before = registry.snapshot()["counters"]["compile.in_loop"]
        Trainer(ragged_step, verbose=False).fit(state, batches, 1)
        assert get_recorder().loop_step is None      # the loop has ended
        counted = registry.snapshot()["counters"]["compile.in_loop"] - before
        # after the loop: compiles, and none is counted
        jax.jit(lambda v: v - 7)(np.float32(1))
        get_recorder().flush()
    finally:
        reset_recorder()
    assert counted == 1       # the last, ragged batch: one new shape
    assert registry.snapshot()["counters"]["compile.in_loop"] == before + 1
    (log,) = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    records = _records(tmp_path / log)
    (instant,) = [r for r in records if r.get("name") == "compile:in_loop"]
    assert instant["ph"] == "i"
    assert instant["args"] == {"program": "jit(ragged_step)", "step": 3}
    stepped = [r for r in records if r["name"].startswith("compile:")
               and r.get("args", {}).get("step") is not None]
    assert {r["args"]["program"] for r in stepped if r["ph"] == "X"} >= {
        "jit(ragged_step)"}
    assert all(r["args"]["step"] == 3 for r in stepped)
    assert all(r["args"]["under"] == "train:dispatch" for r in stepped
               if r["ph"] == "X")


def _kernel_hist(reg):
    """kernel -> sites of ``trace.kernel_s``, over every ``under``."""
    out = {}
    for key, h in reg.snapshot()["histograms"].items():
        if key.startswith("trace.kernel_s{kernel="):
            kernel = key[len("trace.kernel_s{kernel="):].split(",")[0]
            out[kernel] = out.get(kernel, 0) + h["count"]
    return out


def test_kernel_site_fires_once_a_site_and_once_a_shape_when_jitted(
        registry, dark):
    """Two call sites of one shape: the grouped product's plain kernel
    traces at both, mHC's jitted call (``_traced_once``) at the first only,
    and its choice is still counted at both."""
    from tpu_sandbox.ops import pallas_mhc as mhc
    from tpu_sandbox.ops.pallas_grouped_matmul import grouped_matmul

    x = jnp.ones((256, 128), jnp.float32)
    w = jnp.ones((2, 128, 128), jnp.float32)
    group = jnp.zeros((2,), jnp.int32)

    def two_products(x, w):
        return grouped_matmul(grouped_matmul(x, w, group, 128), w, group, 128)

    jax.eval_shape(two_products, x, w)
    assert _kernel_hist(registry) == {"gmm": 2}

    registry.reset()
    streams = jnp.ones((4, 2, 16, 128), jnp.bfloat16)
    y = jnp.ones((2, 16, 128), jnp.bfloat16)
    h_res = jnp.ones((4, 4, 2, 16), jnp.float32)
    h_post = jnp.ones((4, 2, 16), jnp.float32)
    # a width no other test of this process gives the jitted call
    mhc._post_fwd.clear_cache()

    def two_mixes(streams, y):
        once = mhc.post(streams, y, h_res, h_post)
        return mhc.post(once, y, h_res, h_post)

    jax.eval_shape(two_mixes, streams, y)
    assert _kernel_hist(registry) == {"mhc_post_fwd": 1}
    choices = {k: v for k, v in registry.snapshot()["counters"].items()
               if k.startswith("mhc.kernel_choice")}
    assert list(choices.values()) == [2]
    assert "kernel=post_fwd" in next(iter(choices))


def test_the_choice_counters_read_as_before_through_the_helper(
        registry, dark):
    """``attn.tile_choice``, ``ssd.chunk_choice`` and ``moe.share_table``
    keep their names and labels, one count a traced site; a site with a
    choice opens the same ``trace:kernel`` span as one without
    (``dp.grad_sync`` marks no kernel: ``tests/test_grad_sync.py``;
    ``mhc.kernel_choice`` above)."""
    import functools

    from tpu_sandbox.ops.pallas_attention import flash_attention
    from tpu_sandbox.ops.ssd import ssd_scan

    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    jax.eval_shape(jax.grad(lambda q: flash_attention(q, q, q).sum()), q)
    counters = registry.snapshot()["counters"]
    tiles = {k: v for k, v in counters.items()
             if k.startswith("attn.tile_choice")}
    kinds = {k.split("kernel=")[1].split(",")[0] for k in tiles}
    assert kinds == {"fwd", "dkv", "dq"}
    sites = _kernel_hist(registry)
    assert {k: sites[k] for k in ("flash_fwd", "flash_dkv", "flash_dq")} == {
        "flash_fwd": sum(v for k, v in tiles.items() if "kernel=fwd" in k),
        "flash_dkv": 1, "flash_dq": 1}
    label_keys = {kv.split("=")[0] for k in tiles
                  for kv in k[k.index("{") + 1:-1].split(",")}
    assert label_keys == {"kernel", "block_q", "block_k", "s", "d", "dv",
                          "steps", "steps_with_work", "layout",
                          "heads_per_block"}

    x = jnp.ones((1, 16, 4, 8), jnp.float32)
    dt = jnp.ones((1, 16, 4), jnp.float32)
    a = -jnp.ones((4,), jnp.float32)
    b = jnp.ones((1, 16, 2, 16), jnp.float32)
    jax.eval_shape(functools.partial(ssd_scan, chunk=8), x, dt, a, b, b)
    assert registry.snapshot()["counters"][
        "ssd.chunk_choice{chunk=8,groups=2,head_dim=8,heads=4,impl=jnp,"
        "state=16,tokens=16}"] == 1
    assert _kernel_hist(registry)["ssd_scan"] == 1
