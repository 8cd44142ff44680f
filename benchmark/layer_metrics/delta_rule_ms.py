"""Device milliseconds per step in the delta rule itself (the scope
``delta_rule`` that ``ops/delta_rule.py`` opens, whatever implements it):
forward, recomputation and backward."""

from benchmark.lib.readers import scope_ms


def read(obs):
    if obs.facts.get("delta_rule_flops_per_step") is None:
        return None     # no such rule in this program
    return scope_ms(obs, r"/gdn/delta_rule")
