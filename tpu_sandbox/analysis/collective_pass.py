"""Pass 1: static collective-consistency lint (the SPMD-divergence class).

A collective reached under a rank-, coords-, or process_index-conditioned
branch (or after a rank-conditioned early return) diverges the collective
sequence across ranks: the guarded ranks issue it, the others don't, and
the job hangs in the fabric with no error. Every multi-host framework has
this failure class; this pass catches it at parse time.

Mechanics (pure ``ast``, no imports of the scanned code):

- Collective call sites are recognized by *name*: ``lax.pmean``/``psum``/
  ``psum_scatter``/``all_gather``/``ppermute``/``all_to_all``, the
  :class:`~tpu_sandbox.parallel.collectives.CollectiveGroup` method
  surface, and the compressed sync entry points
  (``pmean_tree``, ``int8_block_pmean``).
- Rank-likeness of a condition is a token scan of the test expression:
  identifiers/attributes such as ``rank``, ``process_index``, ``coords``,
  or calls to ``lax.axis_index`` / ``jax.process_index``.
- Each function gets a summary — "does it (transitively, through direct
  same-module calls) always issue a collective?" — propagated to a fixed
  point, so a call to a collective-bearing helper under a rank branch is
  flagged (GL-C103) exactly like a literal collective (GL-C101).
  ``lax.cond`` branches with a rank-like predicate are checked the same
  way (both branch callables must have the SAME collective footprint).
"""

from __future__ import annotations

import ast
import os

from tpu_sandbox.analysis.findings import Finding, make_finding

#: Call names that ARE collectives (jax.lax spellings + this repo's
#: CollectiveGroup methods + the compressed sync entry points).
COLLECTIVE_NAMES = frozenset({
    "pmean", "psum", "psum_scatter", "pmax", "pmin",
    "all_gather", "ppermute", "all_to_all", "pshuffle",
    "all_reduce", "reduce_scatter", "broadcast", "shift",
    "compressed_all_reduce",
    "pmean_tree", "int8_block_pmean",
})

#: Identifier / attribute tokens that mark a condition as rank-derived.
RANK_TOKENS = frozenset({
    "rank", "local_rank", "ranks", "process_index", "process_id",
    "proc_id", "coords", "coord", "axis_index", "device_index",
    "is_leader", "agent_id",
})

_EXCLUDE_DIRS = {
    "__pycache__", ".git", ".pytest_cache", "build", "dist",
    ".eggs", "node_modules",
}


def _call_name(func: ast.AST) -> str | None:
    """Trailing name of a call target: ``lax.pmean`` -> 'pmean',
    ``group.all_reduce`` -> 'all_reduce', ``pmean_tree`` -> itself."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_rank_like(test: ast.AST) -> bool:
    for node in ast.walk(test):
        if isinstance(node, ast.Name) and node.id in RANK_TOKENS:
            return True
        if isinstance(node, ast.Attribute) and node.attr in RANK_TOKENS:
            return True
    return False


def _cond_desc(test: ast.AST) -> str:
    try:
        s = ast.unparse(test)
    except Exception:  # pragma: no cover - unparse is total on py>=3.9
        s = "<condition>"
    return s if len(s) <= 60 else s[:57] + "..."


def _via_self(func: ast.AST) -> bool:
    """Is this call target ``self.<something>``? Those resolve through the
    enclosing class's method table, never by bare-name coincidence."""
    return (isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name) and func.value.id == "self")


def _recv_name(func: ast.AST) -> str | None:
    """Dotted receiver of an attribute call: ``helpers.sync()`` ->
    'helpers', ``pkg.mod.fn()`` -> 'pkg.mod'. None for bare names,
    anything rooted at ``self``, and non-name roots (call results,
    subscripts) — only a plain name chain can be an imported-module
    path, which xmodule resolves by longest alias prefix."""
    if not isinstance(func, ast.Attribute):
        return None
    parts: list[str] = []
    node = func.value
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name) or node.id == "self":
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _base_name(expr: ast.AST) -> str | None:
    """Trailing name of a base-class expression (``Mixin``, ``mod.Mixin``)."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


class _FunctionIndex:
    """Per-module function table + transitive "bears a collective" summary.

    Keys are bare names for module-level functions and ``Class.method``
    for methods. ``self.foo()`` call sites resolve through the enclosing
    class's method table — own methods first, then same-module bases
    (BFS) — so two classes with a same-named method never shadow each
    other (the bug this replaces: the first ``_sync`` in the file used to
    win the bare-name slot and answer for every class). Plain-name calls
    resolve to the module-level function when one exists, else any-match
    across same-named methods (the conservative choice for ``obj.foo()``
    where ``obj``'s class is unknown). Nested defs index under their own
    name (closures calling helpers defined alongside them still resolve).
    """

    def __init__(self, tree: ast.Module):
        self.functions: dict[str, ast.AST] = {}
        #: every function exactly once: (key, enclosing class | None, node)
        self.entries: list[tuple[str, str | None, ast.AST]] = []
        self._bare: dict[str, list[str]] = {}
        self._bases: dict[str, list[str]] = {}
        #: cross-module hook, wired by xmodule.CrossIndex: callable
        #: (recv, name) -> bool answering "does this call reach a
        #: collective-bearing function in ANOTHER scanned module?"
        self.external = None
        self._collect(tree, None)
        self.bearing = self._summarize()

    def _collect(self, node: ast.AST, cls: str | None) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                key = f"{cls}.{child.name}" if cls else child.name
                if key in self.functions:  # redefinition / nested twin
                    n = 2
                    while f"{key}#{n}" in self.functions:
                        n += 1
                    key = f"{key}#{n}"
                self.functions[key] = child
                self.entries.append((key, cls, child))
                self._bare.setdefault(child.name, []).append(key)
                self._collect(child, cls)
            elif isinstance(child, ast.ClassDef):
                self._bases[child.name] = [
                    b for b in map(_base_name, child.bases) if b
                ]
                self._collect(child, child.name)
            else:
                self._collect(child, cls)

    def resolve(self, name: str, cls: str | None,
                via_self: bool) -> list[str]:
        """Candidate table keys a call to ``name`` may reach from a
        function whose enclosing class is ``cls``."""
        if via_self:
            seen: set[str] = set()
            queue = [cls] if cls else []
            while queue:
                c = queue.pop(0)
                if c in seen:
                    continue
                seen.add(c)
                key = f"{c}.{name}"
                if key in self.functions:
                    return [key]  # nearest definition wins, like the MRO
                queue.extend(self._bases.get(c, []))
            return []  # not in this module's hierarchy: unknowable
        if name in self.functions:
            return [name]
        return list(self._bare.get(name, []))

    def _direct_facts(self, fn: ast.AST) -> tuple[bool, set]:
        """(has a literal collective, (via_self, recv, name) of calls it
        makes) — counting only this function's own body, not nested
        defs. ``recv`` is the dotted name-chain attribute receiver (the
        only shape that can be an imported-module path), else None."""
        has = False
        calls: set[tuple[bool, str | None, str]] = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                continue  # nested defs summarize separately
            if isinstance(node, ast.Call):
                name = _call_name(node.func)
                if name in COLLECTIVE_NAMES:
                    has = True
                elif name:
                    calls.add((_via_self(node.func),
                               _recv_name(node.func), name))
        return has, calls

    def _summarize(self) -> dict[str, bool]:
        #: kept on the instance: xmodule.CrossIndex re-walks these same
        #: edges for the global (cross-module) fixed point
        self.facts: dict[str, tuple] = {}
        for key, cls, fn in self.entries:
            has, calls = self._direct_facts(fn)
            self.facts[key] = (cls, has, calls)
        bearing = {key: has for key, (_, has, _) in self.facts.items()}
        changed = True
        while changed:  # fixed point over the (acyclic-enough) call graph
            changed = False
            for key, (cls, _, calls) in self.facts.items():
                if bearing[key]:
                    continue
                for via_self, _recv, name in calls:
                    if any(bearing.get(t, False)
                           for t in self.resolve(name, cls, via_self)):
                        bearing[key] = True
                        changed = True
                        break
        return bearing

    def bears_collective(self, name: str | None, *, cls: str | None = None,
                         via_self: bool = False,
                         recv: str | None = None) -> bool:
        if not name:
            return False
        candidates = self.resolve(name, cls, via_self)
        if candidates:
            return any(self.bearing.get(k, False) for k in candidates)
        # nothing local answers for this name: in a cross-module run the
        # call may target an imported function (never for self.-calls —
        # those stay inside the class hierarchy by construction)
        if self.external is not None and not via_self:
            return self.external(recv, name)
        return False


class _FunctionLinter(ast.NodeVisitor):
    """Walks ONE function body tracking rank-conditioned context and
    rank-conditioned early exits; nested defs are linted independently."""

    def __init__(self, path: str, lines: list[str], index: _FunctionIndex,
                 findings: list[Finding], cls: str | None = None):
        self.path = path
        self.lines = lines
        self.index = index
        self.findings = findings
        self.cls = cls  # enclosing class: scopes self.-call resolution
        self._rank_depth = 0          # inside how many rank-like branches
        self._divergent_exit: tuple[int, str] | None = None  # (line, cond)
        self._rank_names: set[str] = set()  # names assigned from axis_index

    def lint_function(self, fn: ast.AST) -> None:
        """Entry point: prescan for rank-derived names, then lint."""
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                derived = any(
                    isinstance(sub, ast.Call)
                    and _call_name(sub.func) in (
                        "axis_index", "process_index", "axis_index_groups",
                    )
                    for sub in ast.walk(node.value)
                )
                if derived:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            self._rank_names.add(tgt.id)
        self.lint_body(fn.body)

    def _is_rank(self, test: ast.AST) -> bool:
        if _is_rank_like(test):
            return True
        return any(
            isinstance(sub, ast.Name) and sub.id in self._rank_names
            for sub in ast.walk(test)
        )

    # -- helpers -------------------------------------------------------------

    def _snippet(self, node: ast.AST) -> str:
        ln = getattr(node, "lineno", 0)
        return self.lines[ln - 1].strip() if 0 < ln <= len(self.lines) else ""

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(make_finding(
            rule, self.path, getattr(node, "lineno", 0), message,
            snippet=self._snippet(node),
        ))

    def _check_call(self, node: ast.Call) -> None:
        name = _call_name(node.func)
        if name in COLLECTIVE_NAMES:
            if self._rank_depth:
                self._emit(
                    "GL-C101", node,
                    f"collective '{name}' is reached only under a "
                    "rank-conditioned branch",
                )
            elif self._divergent_exit is not None:
                ln, cond = self._divergent_exit
                self._emit(
                    "GL-C102", node,
                    f"collective '{name}' sits after the rank-conditioned "
                    f"early exit at line {ln} (if {cond}: ...)",
                )
        elif self.index.bears_collective(name, cls=self.cls,
                                         via_self=_via_self(node.func),
                                         recv=_recv_name(node.func)):
            if self._rank_depth:
                self._emit(
                    "GL-C103", node,
                    f"call to '{name}' (whose body issues collectives) is "
                    "reached only under a rank-conditioned branch",
                )
            elif self._divergent_exit is not None:
                ln, cond = self._divergent_exit
                self._emit(
                    "GL-C102", node,
                    f"call to collective-bearing '{name}' sits after the "
                    f"rank-conditioned early exit at line {ln} "
                    f"(if {cond}: ...)",
                )
        if name == "cond" and len(node.args) >= 2 \
                and self._is_rank(node.args[0]):
            # lax.cond with a rank-dependent predicate: a collective inside
            # either branch executes on a data-dependent subset of ranks
            for branch in node.args[1:3]:
                self._branch_collectives(branch, node)

    def _branch_collectives(self, branch: ast.AST, site: ast.Call) -> None:
        if isinstance(branch, ast.Lambda):
            for sub in ast.walk(branch.body):
                if isinstance(sub, ast.Call):
                    name = _call_name(sub.func)
                    if name in COLLECTIVE_NAMES or \
                            self.index.bears_collective(
                                name, cls=self.cls,
                                via_self=_via_self(sub.func),
                                recv=_recv_name(sub.func)):
                        self._emit(
                            "GL-C101", site,
                            f"lax.cond on a rank-derived predicate runs "
                            f"collective-bearing '{name}' in one branch only",
                        )
                        return
        elif isinstance(branch, (ast.Name, ast.Attribute)):
            name = branch.id if isinstance(branch, ast.Name) else branch.attr
            ref_self = (isinstance(branch, ast.Attribute)
                        and isinstance(branch.value, ast.Name)
                        and branch.value.id == "self")
            ref_recv = _recv_name(branch) \
                if isinstance(branch, ast.Attribute) else None
            if self.index.bears_collective(name, cls=self.cls,
                                           via_self=ref_self,
                                           recv=ref_recv):
                self._emit(
                    "GL-C103", site,
                    f"lax.cond on a rank-derived predicate calls "
                    f"collective-bearing '{name}' in one branch only",
                )

    @staticmethod
    def _exits(body: list[ast.stmt]) -> bool:
        """Does this branch body end the surrounding control flow?"""
        return any(
            isinstance(s, (ast.Return, ast.Raise, ast.Break, ast.Continue))
            for s in body
        )

    # -- statement walk ------------------------------------------------------

    def lint_body(self, body: list[ast.stmt]) -> None:
        for stmt in body:
            self._lint_stmt(stmt)

    def _lint_stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return  # nested defs are linted as their own functions
        if isinstance(stmt, (ast.If, ast.While)):
            rank_like = self._is_rank(stmt.test)
            self._scan_exprs(stmt.test)
            if rank_like:
                self._rank_depth += 1
            self.lint_body(stmt.body)
            if isinstance(stmt, ast.If):
                # the else-branch of `if rank...` is just as conditioned
                self.lint_body(stmt.orelse)
            if rank_like:
                self._rank_depth -= 1
                if isinstance(stmt, ast.If) and self._divergent_exit is None \
                        and (self._exits(stmt.body)
                             or self._exits(stmt.orelse)):
                    self._divergent_exit = (
                        stmt.lineno, _cond_desc(stmt.test)
                    )
            elif isinstance(stmt, ast.While):
                pass
            if isinstance(stmt, ast.While):
                self.lint_body(stmt.orelse)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._scan_exprs(stmt.iter)
            self.lint_body(stmt.body)
            self.lint_body(stmt.orelse)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._scan_exprs(item.context_expr)
            self.lint_body(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            self.lint_body(stmt.body)
            for h in stmt.handlers:
                self.lint_body(h.body)
            self.lint_body(stmt.orelse)
            self.lint_body(stmt.finalbody)
            return
        # plain statement: scan every expression inside it
        self._scan_exprs(stmt)

    def _scan_exprs(self, node: ast.AST) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(sub, ast.Call):
                self._check_call(sub)
            elif isinstance(sub, ast.IfExp) and self._is_rank(sub.test):
                for branch in (sub.body, sub.orelse):
                    for c in ast.walk(branch):
                        if isinstance(c, ast.Call):
                            name = _call_name(c.func)
                            if name in COLLECTIVE_NAMES or \
                                    self.index.bears_collective(
                                        name, cls=self.cls,
                                        via_self=_via_self(c.func),
                                        recv=_recv_name(c.func)):
                                self._emit(
                                    "GL-C101", sub,
                                    f"collective-bearing '{name}' inside a "
                                    "rank-conditioned ternary",
                                )


def lint_source(source: str, path: str, *,
                index: _FunctionIndex | None = None) -> list[Finding]:
    """Lint one module's source text; ``path`` labels the findings.
    ``index`` lets a whole-tree run pass the module's cross-module-wired
    _FunctionIndex (xmodule.CrossIndex) instead of a fresh local one."""
    if index is None:
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            return [make_finding(
                "GL-C101", path, e.lineno or 0,
                f"unparseable module skipped ({e.msg})",
                hint="fix the syntax error so the pass can see this file",
            )]
        index = _FunctionIndex(tree)
    lines = source.splitlines()
    findings: list[Finding] = []
    for _key, cls, fn in index.entries:
        linter = _FunctionLinter(path, lines, index, findings, cls)
        linter.lint_function(fn)
    return findings


def iter_py_files(root: str, exclude_dirs: set[str] | None = None):
    exclude = _EXCLUDE_DIRS | (exclude_dirs or set())
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d not in exclude)
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def run_collective_pass(
    root: str,
    *,
    paths: list[str] | None = None,
    exclude_dirs: set[str] | None = None,
) -> list[Finding]:
    """Lint every Python file under ``root`` (or just ``paths``); findings
    carry root-relative file labels. ``tests`` is excluded by default —
    fixture corpora deliberately violate the rules. The whole file set is
    indexed together (xmodule.CrossIndex) before any file is linted, so
    collective-bearing calls hidden behind an import resolve."""
    from tpu_sandbox.analysis import xmodule

    if paths is None:
        exclude = (exclude_dirs or set()) | {"tests", "related"}
        paths = list(iter_py_files(root, exclude))
    sources: dict[str, str] = {}
    for p in paths:
        try:
            with open(p, "r", encoding="utf-8") as f:
                sources[p] = f.read()
        except OSError:
            continue
    cross = xmodule.CrossIndex(root, sources)
    findings: list[Finding] = []
    for p, src in sources.items():
        rel = os.path.relpath(p, root)
        findings.extend(lint_source(src, rel, index=cross.index_for(p)))
    return findings
