"""Pass 3: control-plane lint over ``runtime/``, ``serve/``,
``gateway/``, ``obs/`` and ``deploy/`` (AST).

Nine rules distilled from this repo's own elastic-runtime and serving
incident history:

- **GL-R301** — ``kv.add(key, 1) == 1`` claims whose key carries no
  generation/term/round discriminator. An unscoped claim-once key stays
  claimed forever: budgets double-charge on the first race and then
  never charge again. Key helpers (module functions / methods that
  return f-strings, e.g. ``k_charge_claim(gen)``) are resolved so a
  scoped helper call counts as scoped.
- **GL-R302** — arithmetic mixing ``time.time()`` with a value read from
  the KV store (a remote wall-clock stamp). Cross-host skew makes that
  difference meaningless; the watchdog idiom is to track when the local
  observer last saw the stamp *change* and bound that local age.
- **GL-R303** — ``threading.Thread(...)`` without ``daemon=True`` (and
  no ``x.daemon = True`` before ``x.start()`` in the same function).
  Non-daemon threads outlive crashed owners and trip the conftest
  ``_no_resource_leaks`` check.
- **GL-R304** — blocking ``kv.get(...)`` reachable from a leader-action
  method (``_leader*`` roots; the ``self.``-call graph spans same-module
  base classes, so a helper one inheritance edge away is still seen). A
  blocking read can park the leader past its lease TTL; leader ticks
  must use ``try_get`` and re-observe next tick.
- **GL-R305** — a Python ``for``/``while`` loop dispatching a
  *multi-device* jitted computation (one whose body runs a collective,
  or a ``shard_map``) per iteration. Every dispatch is a fresh
  cross-device rendezvous; on XLA:CPU a storm of them interleaves
  across ranks until two ranks wait in different rendezvous and the
  job deadlocks (the ROADMAP launch-storm carry-over). Batch the loop
  into the program (``lax.scan``/``fori_loop``) or hoist the dispatch
  out of the loop.
- **GL-R306** — ``.append()`` onto a queue-ish attribute (``queue``,
  ``waiting``, ``pending``, ``backlog``, ``inbox``, ``mailbox``) in a
  function with no capacity comparison on that queue and no shed/drop
  path. An unbounded producer-facing queue converts overload into
  unbounded memory growth and unbounded tail latency; the fix is a
  bounded queue that sheds with an explicit verdict (the
  ``serve/engine.ContinuousEngine.submit`` idiom). ``appendleft`` is
  deliberately exempt: requeueing already-admitted work (preemption)
  adds nothing the queue has not already accepted.
- **GL-O401** — a span begun with ``begin_span()`` whose ``close()`` is
  not guaranteed on every path. The sanctioned forms are ``with
  rec.span(...)`` or ``sp = rec.begin_span(...)`` followed
  *immediately* by a ``try`` whose ``finally`` calls ``sp.close()``.
  Anything looser (a bare call whose handle is discarded, work between
  the begin and the ``try``, a close only on the happy path) can leak
  the span: a leaked open span never emits its record, so the request
  silently vanishes from the merged timeline — the observability
  equivalent of a lost verdict.
- **GL-O402** — a ``counter()``/``gauge()``/``histogram()`` call on a
  metrics registry whose name argument is not a static ``snake.dotted``
  string literal. A dynamic name (f-string, concatenation, variable)
  mints one series per distinct value: unbounded cardinality in every
  snapshot, scrape, and tsdb flush, and nothing stable for alert rules
  to key on. Bounded dimensions belong in ``labels=``. The ``hist=`` of
  a recorder's ``span()``/``begin_span()``/``complete()`` names a
  registry histogram and is held to the same shape at the call site.
- **GL-O403** — a ``span()``/``begin_span()``/``complete()``/
  ``instant()`` call on a recorder whose name argument is minted at
  runtime (``%``, ``.format()``, concatenation, a bare variable, or an
  f-string with no static family prefix). Span names are the
  aggregation key for the critical-path analyzer, waterfalls, and
  trace-diff gating — unbounded names fragment every one of them. The
  one sanctioned dynamic shape is ``f"family:{value}"`` with a static
  family prefix ending in ``:`` (``door:{reason}``, ``shed:{reason}``,
  ``fault:{action}``): downstream aggregation keys on the family, and
  the tail must come from a bounded set. Request-sized dimensions
  (rid, step) belong in ``args=``.
"""

from __future__ import annotations

import ast
import os
import re

from tpu_sandbox.analysis.findings import Finding, make_finding

#: identifiers that count as a per-round discriminator inside a claim key
SCOPE_TOKENS = frozenset({
    "gen", "generation", "term", "index", "idx", "step", "epoch",
    "attempt", "round", "fault", "token", "nonce", "seq", "rid",
})

#: attribute names that mark a receiver as "the KV client"
KV_RECEIVERS = frozenset({"kv", "client", "store", "_kv", "_client", "_store"})

#: attribute names that mark an in-memory collection as a request queue
QUEUE_NAMES = frozenset({
    "queue", "waiting", "pending", "backlog", "inbox", "mailbox",
})

#: call-name substrings that mark a function as overload-aware — it has
#: somewhere to put work it refuses (shed verdicts, drop/evict paths)
SHED_MARKERS = ("shed", "drop", "reject", "evict")

#: instrument factories on a metrics registry (GL-O402)
METRIC_FACTORIES = frozenset({"counter", "gauge", "histogram"})

#: the sanctioned metric-name shape: lowercase snake segments joined by
#: dots, at least two segments ("component.metric")
METRIC_NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")

#: span/event emitters on a recorder (GL-O403). ``metric`` is excluded:
#: the tsdb flusher relays registry names already policed by GL-O402
SPAN_EMITTERS = frozenset({"span", "begin_span", "complete", "instant"})

#: a static span name: lowercase snake/dotted segments, optionally
#: colon-joined into a family ("claim", "door:no_replicas", "swap:pause")
SPAN_NAME_RE = re.compile(
    r"^[a-z0-9_]+(\.[a-z0-9_]+)*(:[a-z0-9_]+(\.[a-z0-9_]+)*)*$")

#: the static family prefix an f-string span name must open with to be
#: sanctioned: f"door:{reason}" aggregates as "door"
SPAN_FAMILY_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)*:$")


#: nested scopes a statement walk must not descend into — each is
#: linted as its own function/class
_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)


def _stmt_lists(fn: ast.AST):
    """Yield every statement sequence under ``fn`` (bodies, else/finally
    arms, except handlers, match cases) without descending into nested
    function/class scopes."""
    stack: list[ast.AST] = [fn]
    while stack:
        cur = stack.pop()
        for field in ("body", "orelse", "finalbody"):
            stmts = getattr(cur, field, None)
            if isinstance(stmts, list):
                yield stmts
                stack.extend(
                    s for s in stmts if not isinstance(s, _SCOPE_NODES))
        stack.extend(getattr(cur, "handlers", ()))
        stack.extend(getattr(cur, "cases", ()))


def _is_queueish(name: str | None) -> bool:
    if name is None:
        return False
    low = name.lstrip("_").lower()
    return low in QUEUE_NAMES or any(
        low.endswith("_" + q) for q in QUEUE_NAMES)


def _final_attr(node: ast.AST) -> str | None:
    """``self.kv`` -> 'kv', ``agent.client`` -> 'client', ``kv`` -> 'kv'."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_kv_receiver(node: ast.AST) -> bool:
    name = _final_attr(node)
    return name is not None and name in KV_RECEIVERS


def _fstring_idents(node: ast.JoinedStr) -> set[str]:
    idents: set[str] = set()
    for part in node.values:
        if isinstance(part, ast.FormattedValue):
            for sub in ast.walk(part.value):
                if isinstance(sub, ast.Name):
                    idents.add(sub.id)
                elif isinstance(sub, ast.Attribute):
                    idents.add(sub.attr)
    return idents


def _has_scope(idents: set[str]) -> bool:
    return any(
        tok in SCOPE_TOKENS or any(tok.startswith(s) or tok.endswith(s)
                                   for s in ("gen", "term", "idx"))
        for tok in {i.lower() for i in idents}
    )


class _KeyHelperIndex:
    """Module functions / methods whose body ``return``s a string key.

    Maps bare helper name -> (set of identifiers interpolated into the
    returned f-string, unioned with the helper's own parameter names when
    they feed the f-string). A helper returning a constant string maps to
    an empty set — calling it for a claim is as unscoped as the literal.
    """

    def __init__(self, tree: ast.Module):
        self.scopes: dict[str, set[str] | None] = {}
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            returned = self._returned_key_idents(node)
            if returned is not None:
                self.scopes[node.name] = returned

    @staticmethod
    def _returned_key_idents(fn: ast.AST) -> set[str] | None:
        """None if the function doesn't look like a key helper; else the
        identifier set interpolated into its returned string."""
        idents: set[str] | None = None
        for node in ast.walk(fn):
            if isinstance(node, ast.Return) and node.value is not None:
                if isinstance(node.value, ast.JoinedStr):
                    found = _fstring_idents(node.value)
                elif isinstance(node.value, ast.Constant) \
                        and isinstance(node.value.value, str):
                    found = set()
                else:
                    continue
                idents = found if idents is None else (idents | found)
        return idents


class _FnLinter:
    def __init__(self, path: str, lines: list[str], helpers: _KeyHelperIndex,
                 findings: list[Finding]):
        self.path = path
        self.lines = lines
        self.helpers = helpers
        self.findings = findings

    def _snippet(self, node: ast.AST) -> str:
        ln = getattr(node, "lineno", 0)
        return self.lines[ln - 1].strip() if 0 < ln <= len(self.lines) else ""

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(make_finding(
            rule, self.path, getattr(node, "lineno", 0), message,
            snippet=self._snippet(node),
        ))

    # -- GL-R301 -------------------------------------------------------------

    def _key_scope(self, key: ast.AST) -> bool | None:
        """True = scoped, False = provably unscoped, None = unknown."""
        if isinstance(key, ast.JoinedStr):
            return _has_scope(_fstring_idents(key))
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            return False
        if isinstance(key, ast.Call):
            name = _final_attr(key.func)
            if name in self.helpers.scopes:
                helper_idents = self.helpers.scopes[name]
                # identifiers interpolated by the helper + what the call
                # site passes in (k_claim(gen) scopes even if the helper
                # names its parameter differently)
                site_idents: set[str] = set()
                for arg in key.args:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Name):
                            site_idents.add(sub.id)
                        elif isinstance(sub, ast.Attribute):
                            site_idents.add(sub.attr)
                return _has_scope(helper_idents | site_idents)
            return None
        if isinstance(key, ast.BinOp):  # "prefix/" + str(gen) style
            idents = {
                sub.id for sub in ast.walk(key) if isinstance(sub, ast.Name)
            } | {
                sub.attr for sub in ast.walk(key)
                if isinstance(sub, ast.Attribute)
            }
            return _has_scope(idents)
        return None  # bare Name / subscript: key built elsewhere — skip

    def _check_claim(self, node: ast.Compare) -> None:
        """``X.add(key, ..) == 1`` / ``!= 1`` with an unscoped key."""
        sides = [node.left] + list(node.comparators)
        call = next(
            (s for s in sides
             if isinstance(s, ast.Call)
             and isinstance(s.func, ast.Attribute)
             and s.func.attr == "add"
             and _is_kv_receiver(s.func.value)),
            None,
        )
        if call is None or not call.args:
            return
        one = any(
            isinstance(s, ast.Constant) and s.value == 1
            for s in sides if s is not call
        )
        if not one or not isinstance(node.ops[0], (ast.Eq, ast.NotEq)):
            return
        if self._key_scope(call.args[0]) is False:
            self._emit(
                "GL-R301", node,
                "add()-wins claim key carries no generation/term scope — "
                "it stays claimed across rounds",
            )

    # -- GL-R302 -------------------------------------------------------------

    @staticmethod
    def _is_time_call(node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("time", "monotonic")
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time"
        )

    def _taint_kv_reads(self, fn: ast.AST) -> set[str]:
        """Names assigned (transitively through float()/decode()/…) from a
        kv-ish ``.get``/``.try_get`` in this function."""
        tainted: set[str] = set()

        def expr_tainted(expr: ast.AST) -> bool:
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Name) and sub.id in tainted:
                    return True
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr in ("get", "try_get") \
                        and _is_kv_receiver(sub.func.value):
                    return True
            return False

        changed = True
        while changed:
            changed = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and expr_tainted(node.value):
                    # only plain-name (or tuple-of-name) targets taint:
                    # `obj[k] = (stamp, now)` must not taint `obj` or `k`
                    for tgt in node.targets:
                        names = [tgt] if isinstance(tgt, ast.Name) else (
                            tgt.elts if isinstance(tgt, (ast.Tuple, ast.List))
                            else []
                        )
                        for sub in names:
                            if isinstance(sub, ast.Name) \
                                    and sub.id not in tainted:
                                tainted.add(sub.id)
                                changed = True
        return tainted

    def _check_stamp_math(self, fn: ast.AST) -> None:
        tainted = self._taint_kv_reads(fn)

        def side_is_now(expr: ast.AST) -> bool:
            if self._is_time_call(expr):
                return True
            return isinstance(expr, ast.Name) and expr.id in ("now", "t_now")

        def side_is_stamp(expr: ast.AST) -> bool:
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Name) and sub.id in tainted:
                    return True
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr in ("get", "try_get") \
                        and _is_kv_receiver(sub.func.value):
                    return True
            return False

        for node in ast.walk(fn):
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
                pairs = ((node.left, node.right), (node.right, node.left))
                if any(side_is_now(a) and side_is_stamp(b)
                       for a, b in pairs):
                    self._emit(
                        "GL-R302", node,
                        "local clock minus a KV-read stamp: cross-host "
                        "skew corrupts this age",
                    )

    # -- GL-R303 -------------------------------------------------------------

    def _check_threads(self, fn: ast.AST) -> None:
        daemon_set: set[str] = set()   # names with `.daemon = True` later
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.targets[0], ast.Attribute) \
                    and node.targets[0].attr == "daemon" \
                    and isinstance(node.value, ast.Constant) \
                    and node.value.value is True:
                tgt = node.targets[0].value
                name = _final_attr(tgt)
                if name:
                    daemon_set.add(name)
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and _final_attr(node.func) == "Thread"):
                continue
            daemon_kw = next(
                (kw for kw in node.keywords if kw.arg == "daemon"), None,
            )
            if daemon_kw is not None:
                if not (isinstance(daemon_kw.value, ast.Constant)
                        and daemon_kw.value.value is True):
                    self._emit(
                        "GL-R303", node,
                        "Thread created with daemon != True",
                    )
                continue
            # no daemon kwarg: accept `x = Thread(...)` + `x.daemon = True`
            assigned = self._assigned_name(fn, node)
            if assigned is not None and assigned in daemon_set:
                continue
            self._emit(
                "GL-R303", node,
                "Thread created without daemon=True (leaks past the "
                "conftest check, outlives crashed owners)",
            )

    @staticmethod
    def _assigned_name(fn: ast.AST, call: ast.Call) -> str | None:
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and node.value is call:
                name = _final_attr(node.targets[0])
                if name:
                    return name
        return None

    # -- GL-R306 -------------------------------------------------------------

    def _check_unbounded_queues(self, fn: ast.AST) -> None:
        """``.append()`` onto a queue-ish attribute in a function with no
        capacity comparison on that queue and no shed/drop call.

        ``appendleft`` (requeue of already-admitted work) is exempt, and
        a ``len(<queue>)`` that appears inside any comparison counts as
        the capacity check even when it guards a different branch — this
        is a lint heuristic, not a proof."""
        appends: list[tuple[ast.Call, str]] = []
        len_compared: set[str] = set()
        sheds = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Compare):
                for side in [node.left] + list(node.comparators):
                    for sub in ast.walk(side):
                        if isinstance(sub, ast.Call) \
                                and _final_attr(sub.func) == "len" \
                                and sub.args:
                            qn = _final_attr(sub.args[0])
                            if qn is not None:
                                len_compared.add(qn)
                continue
            if not isinstance(node, ast.Call):
                continue
            name = _final_attr(node.func)
            if name is None:
                continue
            if name == "append" and isinstance(node.func, ast.Attribute):
                qname = _final_attr(node.func.value)
                if _is_queueish(qname):
                    appends.append((node, qname))
            elif any(m in name.lower() for m in SHED_MARKERS):
                sheds = True
        if sheds:
            return
        for node, qname in appends:
            if qname in len_compared:
                continue
            self._emit(
                "GL-R306", node,
                f"append to '{qname}' with no capacity check and no shed "
                f"path — overload grows this queue without bound",
            )

    # -- GL-O401 -------------------------------------------------------------

    @staticmethod
    def _is_begin_span(expr: ast.AST) -> bool:
        return isinstance(expr, ast.Call) \
            and _final_attr(expr.func) == "begin_span"

    @staticmethod
    def _finally_closes(tryst: ast.Try, name: str) -> bool:
        for stmt in tryst.finalbody:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr == "close" \
                        and _final_attr(sub.func.value) == name:
                    return True
        return False

    def _check_span_leaks(self, fn: ast.AST) -> None:
        """``begin_span()`` must be the sanctioned shape: the handle
        assigned, then IMMEDIATELY a ``try`` whose ``finally`` closes
        it. A discarded handle, or any statement between the begin and
        the ``try``, is a path on which the span never emits — it
        silently vanishes from the merged timeline. (``with
        rec.span(...)`` compiles to this shape inside the recorder and
        is the preferred spelling.)"""
        for stmts in _stmt_lists(fn):
            for i, stmt in enumerate(stmts):
                if isinstance(stmt, ast.Expr) \
                        and self._is_begin_span(stmt.value):
                    self._emit(
                        "GL-O401", stmt,
                        "begin_span() handle discarded — nothing can "
                        "ever close this span",
                    )
                    continue
                if not (isinstance(stmt, ast.Assign)
                        and self._is_begin_span(stmt.value)):
                    continue
                name = _final_attr(stmt.targets[0])
                nxt = stmts[i + 1] if i + 1 < len(stmts) else None
                if name is not None and isinstance(nxt, ast.Try) \
                        and self._finally_closes(nxt, name):
                    continue
                self._emit(
                    "GL-O401", stmt,
                    f"span '{name}' begun without an immediate "
                    f"try/finally close — an exception before close() "
                    f"leaks it from the timeline",
                )

    # -- GL-O402 -------------------------------------------------------------

    @staticmethod
    def _is_registry_receiver(node: ast.AST) -> bool:
        """``get_registry().x``, ``reg.x``, ``self.registry.x`` — anything
        that reads as "the metrics registry". Instrument calls on other
        objects are out of scope."""
        if isinstance(node, ast.Call):
            return _final_attr(node.func) == "get_registry"
        name = _final_attr(node)
        if name is None:
            return False
        low = name.lstrip("_").lower()
        return low == "reg" or "registry" in low

    def _check_metric_names(self, fn: ast.AST) -> None:
        """Instrument names must be static ``snake.dotted`` literals; a
        name built at runtime mints a series per distinct value."""
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in METRIC_FACTORIES
                    and self._is_registry_receiver(node.func.value)):
                continue
            name_arg = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords if kw.arg == "name"),
                None)
            if name_arg is None:
                continue
            if isinstance(name_arg, ast.Constant) \
                    and isinstance(name_arg.value, str) \
                    and METRIC_NAME_RE.match(name_arg.value):
                continue
            self._emit(
                "GL-O402", node,
                f"{node.func.attr}() name is not a static snake.dotted "
                f"literal — a dynamic name mints one series per distinct "
                f"value (put bounded dimensions in labels=)",
            )
        # a span's ``hist=`` is a histogram name the recorder relays to
        # the registry: the literal lives at the span's call site
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in SPAN_EMITTERS
                    and self._is_recorder_receiver(node.func.value)):
                continue
            hist = next((kw.value for kw in node.keywords
                         if kw.arg == "hist"), None)
            if hist is None or (isinstance(hist, ast.Constant) and (
                    hist.value is None or (
                        isinstance(hist.value, str)
                        and METRIC_NAME_RE.match(hist.value)))):
                continue
            self._emit(
                "GL-O402", node,
                f"{node.func.attr}() hist= is not a static snake.dotted "
                f"literal — the recorder observes it as a registry "
                f"histogram, one series per distinct value",
            )

    # -- GL-O403 -------------------------------------------------------------

    @staticmethod
    def _is_recorder_receiver(node: ast.AST) -> bool:
        """``get_recorder().x``, ``rec.x``, ``self._recorder.x`` —
        anything that reads as "the recorder". Same-named methods on
        other objects (a checkpoint's ``complete``, say) are out of
        scope."""
        if isinstance(node, ast.Call):
            return _final_attr(node.func) == "get_recorder"
        name = _final_attr(node)
        if name is None:
            return False
        low = name.lstrip("_").lower()
        return low == "rec" or "recorder" in low

    @staticmethod
    def _span_name_ok(name_arg: ast.AST) -> bool:
        if isinstance(name_arg, ast.Constant):
            return isinstance(name_arg.value, str) \
                and bool(SPAN_NAME_RE.match(name_arg.value))
        if isinstance(name_arg, ast.JoinedStr) and name_arg.values:
            head = name_arg.values[0]
            return isinstance(head, ast.Constant) \
                and isinstance(head.value, str) \
                and bool(SPAN_FAMILY_RE.match(head.value))
        return False

    def _check_span_names(self, fn: ast.AST) -> None:
        """Span names must be static literals (or family-prefixed
        f-strings); everything downstream aggregates by span name."""
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in SPAN_EMITTERS
                    and self._is_recorder_receiver(node.func.value)):
                continue
            name_arg = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords if kw.arg == "name"),
                None)
            if name_arg is None or self._span_name_ok(name_arg):
                continue
            self._emit(
                "GL-O403", node,
                f"{node.func.attr}() span name is minted at runtime — "
                f"trace aggregation keys on span names; use a static "
                f"literal or f\"family:{{value}}\" with a static family "
                f"prefix, and put request-sized dimensions in args=",
            )

    # -- GL-R304 (per-class, run separately) ---------------------------------

    def run_common(self, fn: ast.AST) -> None:
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                continue
            if isinstance(node, ast.Compare):
                self._check_claim(node)
        self._check_stamp_math(fn)
        self._check_threads(fn)
        self._check_unbounded_queues(fn)
        self._check_span_leaks(fn)
        self._check_metric_names(fn)
        self._check_span_names(fn)


def _base_label(expr: ast.AST) -> str | None:
    """Trailing name of a base-class expression (``Base``, ``mod.Base``)."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


def _class_method_table(
    cls: ast.ClassDef, class_map: dict[str, ast.ClassDef],
    _seen: set[str] | None = None,
) -> dict[str, ast.AST]:
    """The class's effective method table: own methods plus same-module
    base methods (own overrides win; bases merge left-to-right, nearest
    definition first — the static shadow of the MRO). A ``_leader*`` tick
    that calls ``self._lookup()`` defined on a mixin is exactly as
    blocking as one defined inline, so GL-R304 must see through the
    inheritance edge."""
    seen = set() if _seen is None else _seen
    if cls.name in seen:  # cycle guard: malformed code must not recurse
        return {}
    seen.add(cls.name)
    table: dict[str, ast.AST] = {
        n.name: n for n in cls.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for base in cls.bases:
        bname = _base_label(base)
        if bname in class_map:
            for name, fn in _class_method_table(
                    class_map[bname], class_map, seen).items():
                table.setdefault(name, fn)
    return table


def _leader_reachable(
    cls: ast.ClassDef, class_map: dict[str, ast.ClassDef],
) -> tuple[set[str], dict[str, ast.AST]]:
    """(method names reachable from ``_leader*`` roots via ``self._x()``,
    the class's merged method table)."""
    methods = _class_method_table(cls, class_map)
    calls: dict[str, set[str]] = {}
    for name, fn in methods.items():
        out: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "self" \
                    and node.func.attr in methods:
                out.add(node.func.attr)
        calls[name] = out
    reachable = {n for n in methods if n.startswith("_leader")}
    frontier = list(reachable)
    while frontier:
        cur = frontier.pop()
        for callee in calls.get(cur, ()):
            if callee not in reachable:
                reachable.add(callee)
                frontier.append(callee)
    return reachable, methods


def _check_leader_blocking_reads(
    cls: ast.ClassDef, class_map: dict[str, ast.ClassDef],
    path: str, lines: list[str], findings: list[Finding],
    reported: set[int],
) -> None:
    """``reported`` dedupes by method node identity across classes: a
    base method reached from two subclasses is one finding, attributed to
    the first reaching class."""
    reachable, methods = _leader_reachable(cls, class_map)
    if not reachable:
        return
    ordered = sorted(
        ((n, methods[n]) for n in reachable),
        key=lambda item: getattr(item[1], "lineno", 0),
    )
    for method_name, node in ordered:
        if id(node) in reported:
            continue
        hit = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr == "get" \
                    and _is_kv_receiver(sub.func.value):
                hit = True
                ln = getattr(sub, "lineno", 0)
                snippet = lines[ln - 1].strip() \
                    if 0 < ln <= len(lines) else ""
                findings.append(make_finding(
                    "GL-R304", path, ln,
                    f"blocking kv.get() inside leader-reachable "
                    f"'{cls.name}.{method_name}' can outlast the lease TTL",
                    snippet=snippet,
                ))
        if hit:
            reported.add(id(node))


# -- GL-R305 (module-level) --------------------------------------------------

#: cross-device rendezvous primitives — a jit whose trace hits one of
#: these runs on every device of the mesh, so each dispatch is a
#: collective rendezvous (shard_map-wrapped fns are multi-device by
#: construction)
_COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "psum_scatter",
    "all_gather", "ppermute", "pshuffle", "all_to_all",
})


def _calls_collective(fn: ast.AST,
                      external_coll: frozenset = frozenset()) -> bool:
    """``external_coll``: names imported from other modules whose bodies
    (transitively) issue collectives — xmodule.CrossIndex resolves them,
    so a jitted wrapper around an imported sync helper still counts."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = _final_attr(node.func)
            if name in _COLLECTIVES or name == "shard_map" \
                    or (isinstance(node.func, ast.Name)
                        and name in external_coll):
                return True
    return False


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` (bare or decorator), incl. the
    ``partial(jax.jit, ...)`` decorator form."""
    if isinstance(node, ast.Call):
        fname = _final_attr(node.func)
        if fname == "partial" and node.args:
            return _is_jit_expr(node.args[0])
        return fname == "jit"
    return _final_attr(node) == "jit"


def _wrapped_is_multi_device(arg: ast.AST, coll_fns: set[str]) -> bool:
    """Does ``jax.jit(<arg>)`` trace a collective? ``<arg>`` is a known
    collective-calling function name, a lambda with a collective, or a
    ``shard_map(...)`` expression."""
    if isinstance(arg, ast.Name):
        return arg.id in coll_fns
    if isinstance(arg, ast.Lambda):
        return _calls_collective(arg, frozenset(coll_fns))
    if isinstance(arg, ast.Call):
        if _final_attr(arg.func) == "shard_map":
            return True
        if _final_attr(arg.func) == "partial" and arg.args:
            return _wrapped_is_multi_device(arg.args[0], coll_fns)
    return False


def _multi_device_jits(
    tree: ast.Module, external_coll: frozenset = frozenset(),
) -> tuple[set[str], set[str], set[ast.AST]]:
    """(names bound to multi-device jitted callables, names of functions
    that call collectives, jit-decorated defs).

    The last set matters for scoping: a loop *inside* a jitted function
    is traced into one program (one dispatch), so it is exempt.
    ``external_coll`` (from-imported collective-bearing functions, per
    xmodule.CrossIndex) count as collective-calling directly — a
    ``jax.jit(imported_sync)`` is exactly as multi-device as a local one.
    """
    coll_fns = {
        node.name for node in ast.walk(tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and _calls_collective(node, external_coll)
    } | set(external_coll)
    jitted: set[str] = set()
    traced_defs: set[ast.AST] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_expr(d) for d in node.decorator_list):
                traced_defs.add(node)
                if node.name in coll_fns:
                    jitted.add(node.name)
        elif isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and _is_jit_expr(node.value.func) \
                and node.value.args \
                and _wrapped_is_multi_device(node.value.args[0], coll_fns):
            name = _final_attr(node.targets[0])
            if name:
                jitted.add(name)
    return jitted, coll_fns, traced_defs


def _loops_outside_traced(tree: ast.Module, traced_defs: set[ast.AST]):
    """Yield every For/While whose dispatches happen at Python speed —
    i.e. not inside a jit-decorated function body."""
    def visit(node):
        for child in ast.iter_child_nodes(node):
            if child in traced_defs:
                continue
            if isinstance(child, (ast.For, ast.AsyncFor, ast.While)):
                yield child
            yield from visit(child)
    yield from visit(tree)


def _check_launch_storms(
    tree: ast.Module, path: str, lines: list[str],
    findings: list[Finding], external_coll: frozenset = frozenset(),
) -> None:
    jitted, coll_fns, traced_defs = _multi_device_jits(tree, external_coll)
    if not jitted and not coll_fns:
        return
    for loop in _loops_outside_traced(tree, traced_defs):
        bodies = list(loop.body) + list(loop.orelse)
        if isinstance(loop, ast.While):
            bodies.append(loop.test)
        for part in bodies:
            for node in ast.walk(part):
                if not isinstance(node, ast.Call):
                    continue
                name = _final_attr(node.func)
                dispatches = name in jitted
                if not dispatches and isinstance(node.func, ast.Call):
                    # inline form: jax.jit(f)(x) inside the loop — a
                    # storm AND a retrace per iteration
                    call = node.func
                    dispatches = bool(
                        _is_jit_expr(call.func) and call.args
                        and _wrapped_is_multi_device(call.args[0],
                                                     coll_fns)
                    )
                if dispatches:
                    ln = getattr(node, "lineno", 0)
                    snippet = lines[ln - 1].strip() \
                        if 0 < ln <= len(lines) else ""
                    findings.append(make_finding(
                        "GL-R305", path, ln,
                        "Python loop dispatches a multi-device jitted "
                        "computation per iteration — each dispatch is a "
                        "collective rendezvous; the resulting launch "
                        "storm deadlocks XLA:CPU gangs",
                        snippet=snippet,
                    ))


def lint_source(source: str, path: str, *,
                external_coll: frozenset = frozenset()) -> list[Finding]:
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [make_finding(
            "GL-R303", path, e.lineno or 0,
            f"unparseable module skipped ({e.msg})",
            hint="fix the syntax error so the pass can see this file",
        )]
    lines = source.splitlines()
    helpers = _KeyHelperIndex(tree)
    findings: list[Finding] = []
    linter = _FnLinter(path, lines, helpers, findings)
    class_map = {
        node.name: node for node in ast.walk(tree)
        if isinstance(node, ast.ClassDef)
    }
    reported: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            linter.run_common(node)
        elif isinstance(node, ast.ClassDef):
            _check_leader_blocking_reads(node, class_map, path, lines,
                                         findings, reported)
    _check_launch_storms(tree, path, lines, findings, external_coll)
    return findings


def run_control_pass(
    root: str, *, paths: list[str] | None = None,
) -> list[Finding]:
    """Lint ``runtime/`` + ``serve/`` + ``gateway/`` + ``obs/`` (or
    explicit ``paths``); labels are root-relative. The whole tree under
    ``root`` is indexed first (xmodule.CrossIndex) so GL-R305 sees
    collective-bearing functions imported from modules outside the
    linted set — e.g. a jitted wrapper in ``runtime/`` around a sync
    helper defined in ``parallel/``."""
    from tpu_sandbox.analysis import xmodule
    from tpu_sandbox.analysis.collective_pass import iter_py_files

    if paths is None:
        paths = []
        for pkg in ("runtime", "serve", "gateway", "obs", "deploy"):
            pkg_dir = os.path.join(root, "tpu_sandbox", pkg)
            if os.path.isdir(pkg_dir):
                for fn in sorted(os.listdir(pkg_dir)):
                    if fn.endswith(".py"):
                        paths.append(os.path.join(pkg_dir, fn))
    # index every module the linted files could import from: the whole
    # tree (minus fixture corpora) plus the explicit paths themselves
    index_paths = set(iter_py_files(root, {"tests", "related"}))
    index_paths.update(paths)
    sources: dict[str, str] = {}
    for p in index_paths:
        try:
            with open(p, "r", encoding="utf-8") as f:
                sources[p] = f.read()
        except OSError:
            continue
    cross = xmodule.CrossIndex(root, sources)
    findings: list[Finding] = []
    for p in paths:
        rel = os.path.relpath(p, root)
        src = sources.get(p)
        if src is None:
            continue
        findings.extend(lint_source(
            src, rel,
            external_coll=frozenset(cross.imported_coll_fns(p))))
    return findings
