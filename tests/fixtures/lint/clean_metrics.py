"""Clean twins for GL-O402 — sanctioned metric-name shapes.

Static ``snake.dotted`` literals; bounded dimensions ride ``labels=``
instead of being baked into the name.
"""

from tpu_sandbox.obs import get_recorder, get_registry


def static_names(tenant):
    reg = get_registry()
    reg.counter("sched.admissions", labels={"kind": "admitted"}).inc()
    reg.gauge("sched.tenant.queued", labels={"tenant": tenant}).set(3)
    reg.histogram("engine.ttft").observe(0.12)


def keyword_name():
    get_registry().counter(name="gateway.shed.door").inc()


def non_registry_receiver(index):
    # instrument-shaped calls on non-registry objects are out of scope
    index.counter(f"dynamic.{index}").inc()


def span_histogram(t0):
    rec = get_recorder()
    with rec.span("train:dispatch", hist="train.dispatch_s", loop=True):
        pass
    rec.complete("train:step", t0, hist="train.step_s", loop=True)
    with rec.span("admit", hist=None):
        pass
