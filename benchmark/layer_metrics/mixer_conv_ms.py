"""Device milliseconds per step under the recurrent mixers' short causal
convolution (the scope ``conv`` that ``Mamba2Mixer`` and ``GatedDeltaNet``
open round it, whatever implements it): the depthwise convolution of K
taps with ``silu`` and, in the Gated DeltaNet, the keys' and queries' L2
norm with the heads' relayouts, forward, recomputation and backward."""

import re

from benchmark.lib.readers import scope_ms

SCOPE = r"/(gdn|mamba)/conv"


def read(obs):
    if not any(re.search(SCOPE, scope) for names in obs.scopes.values()
               for scope in names.values()):
        return None     # no such mixer in this program
    return scope_ms(obs, SCOPE)
