"""Device milliseconds per step under the Gated DeltaNet mixers
(``block{i}/gdn``): the six projections, the short convolutions, the gates,
the delta rule, the gated head norm and the mixer's own norm, forward,
recomputation and backward."""

from benchmark.lib.readers import scope_ms


def read(obs):
    if obs.facts.get("delta_rule_flops_per_step") is None:
        return None     # no such mixer in this program
    return scope_ms(obs, r"/gdn/")
