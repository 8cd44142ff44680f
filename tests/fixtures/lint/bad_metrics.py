"""Seeded GL-O402 violations — dynamic / malformed metric names.

Parsed by the lint tests, never imported. Each function mints series
cardinality at runtime: the exact incident class the rule exists for
(one dashboard per tenant id, one alert rule that matches nothing).
"""

from tpu_sandbox.obs import get_recorder, get_registry


def fstring_name(tenant):
    # one counter series per tenant id — unbounded cardinality
    get_registry().counter(f"sched.tenant.{tenant}.queued").inc()


def concatenated_name(stage):
    reg = get_registry()
    reg.gauge("pipeline." + stage).set(1.0)


def undotted_name():
    registry = get_registry()
    # a static literal, but flat: no component prefix for rules to key on
    registry.histogram("latency").observe(0.5)


def span_hist_from_a_variable(layer):
    # the recorder relays hist= to registry.histogram(): same cardinality
    with get_recorder().span("train:dispatch", hist=f"{layer}.dispatch_s"):
        pass
