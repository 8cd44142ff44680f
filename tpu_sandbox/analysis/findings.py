"""Shared finding model + rule catalog for the graftlint passes.

Every pass emits :class:`Finding` rows — file:line, a stable rule id, a
one-line message, and a fix hint — so the CLI, the tier-1 gate, and the
baseline suppressor all speak one format. Rule ids are grouped by pass:

- ``GL-C1xx``  Pass 1: collective consistency (AST, SPMD-divergence class)
- ``GL-H2xx``  Pass 2: jaxpr / chipless AOT HLO step lint
- ``GL-R3xx``  Pass 3: control-plane lint (AST over runtime/ + serve/)
- ``GL-O4xx``  Pass 3 observability rules (span/recorder discipline)
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint hit. ``snippet`` is the stripped source line (or a short
    machine summary for compile-level findings) — the baseline matches on
    it so suppressions survive line-number churn."""

    rule: str
    file: str        # repo-relative path, or "<step:NAME>" for compile lint
    line: int        # 1-based; 0 for compile-level findings
    message: str
    hint: str = ""
    snippet: str = ""

    def format(self) -> str:
        loc = f"{self.file}:{self.line}" if self.line else self.file
        out = f"{loc}: {self.rule}: {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


#: rule id -> (title, default fix hint)
RULES: dict[str, tuple[str, str]] = {
    # -- Pass 1: collective consistency --------------------------------------
    "GL-C101": (
        "collective under a rank-conditioned branch",
        "hoist the collective out of the rank-conditional (all ranks must "
        "reach every collective in the same order) or guard the whole "
        "function, not the call",
    ),
    "GL-C102": (
        "collective after a rank-conditioned early exit",
        "a rank that returns/raises early never reaches the collective the "
        "others are blocked in; make the exit unconditional or move it "
        "after the last collective",
    ),
    "GL-C103": (
        "collective-bearing call under a rank-conditioned branch",
        "the callee's collective sequence diverges across ranks through "
        "this call site; hoist the call or strip the callee's collectives",
    ),
    # -- Pass 2: step-function jaxpr / HLO lint ------------------------------
    "GL-H201": (
        "missing input donation on TrainState buffers",
        "pass donate=True (donate_argnums=(0,)) so XLA aliases the old "
        "state's buffers into the new state instead of holding both live",
    ),
    "GL-H202": (
        "bf16->fp32 upcast inside the step",
        "a large convert_element_type to f32 doubles that buffer's HBM "
        "footprint; keep the tensor in bf16 or upcast per-block",
    ),
    "GL-H203": (
        "host transfer inside the step",
        "callbacks/infeed/outfeed serialize the step on host round-trips; "
        "move the host work outside the jit or behind io_callback batching",
    ),
    "GL-H205": (
        "int8 block padding waste above threshold",
        "block/axis alignment padding dominates the int8 wire payload; "
        "lower CompressedAllReduce.block",
    ),
    # -- Pass 3: control-plane lint ------------------------------------------
    "GL-R301": (
        "KV add() claim without generation/term scoping",
        "an unscoped add()-wins claim stays claimed across generations "
        "(double-charge / never-again-charge); scope the key with the "
        "generation, term, or another per-round discriminator",
    ),
    "GL-R302": (
        "heartbeat stamp compared against the local clock",
        "cross-host clock skew makes wall-stamp arithmetic read as death "
        "(or mask one); track when the observer last saw the stamp CHANGE "
        "and bound that local age instead (see runtime/watchdog.Watchdog)",
    ),
    "GL-R303": (
        "thread started without daemon=True",
        "non-daemon threads trip the conftest leak check and outlive "
        "crashed owners; pass daemon=True (or set .daemon before start())",
    ),
    "GL-R304": (
        "blocking KV read inside a leader-action critical section",
        "a blocking get() can park the leader past its lease TTL (a peer "
        "takes over while this one still thinks it leads); use try_get() "
        "and re-observe next tick",
    ),
    "GL-R305": (
        "Python loop dispatching a multi-device jitted fn per iteration",
        "each dispatch of a collective-bearing jit is a cross-device "
        "rendezvous; a Python-speed storm of them interleaves across "
        "ranks and deadlocks XLA:CPU gangs — batch the loop into the "
        "program (lax.scan / fori_loop) or hoist the dispatch out",
    ),
    "GL-R306": (
        "unbounded in-memory request queue",
        "a producer-facing queue appended to with no capacity comparison "
        "and no shed path turns overload into unbounded memory growth and "
        "unbounded tail latency; bound the queue and shed with an explicit "
        "verdict (see serve/engine.ContinuousEngine.submit)",
    ),
    # -- Pass 3: observability discipline ------------------------------------
    "GL-O401": (
        "span begun without a guaranteed close",
        "a leaked open span never emits its record and the request "
        "silently vanishes from the merged timeline; use `with "
        "rec.span(...)`, or assign `sp = rec.begin_span(...)` and follow "
        "it IMMEDIATELY with try/finally sp.close()",
    ),
    "GL-O402": (
        "metric name is not a static snake.dotted literal",
        "a dynamic metric name (f-string, concatenation, variable) mints "
        "one series per distinct value — unbounded cardinality that "
        "bloats every registry snapshot, OP_METRICS scrape, and tsdb "
        "flush, and breaks alert rules keyed on the name; use a static "
        "'component.metric' literal and carry the bounded dimension in "
        "labels= (see obs/metrics.py)",
    ),
    "GL-O403": (
        "span name is minted at runtime",
        "a span/instant name built with %, .format(), concatenation, or "
        "a bare variable has unbounded cardinality — the critical-path "
        "analyzer, waterfalls, and trace-diff gating all aggregate by "
        "span name and fragment across it; use a static literal, or the "
        "sanctioned f'family:{value}' shape (static family prefix ending "
        "in ':') which downstream aggregation keys on, with the value "
        "drawn from a bounded set",
    ),
}


def make_finding(rule: str, file: str, line: int, message: str,
                 snippet: str = "", hint: str | None = None) -> Finding:
    if rule not in RULES:
        raise ValueError(f"unknown rule id {rule!r}")
    return Finding(
        rule=rule, file=file, line=line, message=message,
        hint=RULES[rule][1] if hint is None else hint,
        snippet=snippet,
    )
