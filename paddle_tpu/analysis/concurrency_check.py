"""Concurrency discipline checker (CX10xx): the threaded runtime's gate.

PRs 5–15 filled the runtime with threads — DataLoader/DeviceLoader
prefetch workers, the serving scheduler/decode executors, the telemetry
``ThreadingHTTPServer``, snapshot writers, breaker boards — and chaos
testing (FT9xx) can only *probabilistically* tickle the bug class that
kills such systems: data races, lock-order deadlocks, blocking calls
under held locks. This module is the lockdep/TSan shape applied where it
is cheap — Python source + the instrumented lock registry
(``observability/locks.py``) — wired as the ``concurrency`` family of
``python -m tools.lint``:

CX1000  unguarded shared mutation   a module/instance attribute mutated
                                    both from a thread entry point
                                    (``threading.Thread(target=...)``, a
                                    ``Thread`` subclass ``run``, an
                                    executor ``submit``, a ``do_*`` HTTP
                                    handler) and from another entry
                                    context, with at least one mutation
                                    site not lexically inside a ``with
                                    <lock>`` region (error)
CX1001  static lock-order cycle     the lexical lock-nesting graph
                                    (``with a: ... with b:``) collected
                                    over the whole scanned tree contains
                                    a cycle — two call paths take the
                                    same locks in opposite orders
                                    (error)
CX1002  blocking under a lock       ``.result()``, ``queue.get/put``
                                    without a timeout, ``block_until_
                                    ready``, ``device_put``, ``open()``
                                    or socket I/O lexically inside a
                                    held-lock region: the lock's hold
                                    time is now someone else's I/O
                                    (error)
CX1003  unregistered lock           bare ``threading.Lock()`` /
                                    ``RLock()`` / ``Condition()``
                                    construction outside
                                    ``observability/locks.py`` — the
                                    witness cannot watch a lock the
                                    registry never saw (error)
CX1004  lock-order inversion        *runtime*: the lit witness recorded
                                    a cycle-closing acquisition edge
                                    (error)
CX1005  lock hold over budget       *runtime*: a lit-mode hold exceeded
                                    ``FLAGS_concurrency_max_hold_ms``
                                    (error)

Shared ``# noqa: CX10xx`` grammar with the trace/fault linters. The
static rules are deliberately under-approximate (per-module, per-class
reachability with an in-class transitive call closure) — findings are
meant to be fixed or suppressed with a reasoned noqa, not argued with.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import Finding

_ANALYZER = "concurrency"

# an expression whose trailing name looks like a lock/condition guard
_LOCKISH_RE = re.compile(r"(?:^|_)(lock|locks|cond|cv|mutex|wlock)$",
                         re.IGNORECASE)
# receivers that look like queues (for the .get/.put blocking rule);
# dict/attr .get(...) receivers never match this
_QUEUEISH_RE = re.compile(r"(?:^|_)(q|queue|in_q|out_q|work_q|done_q)$",
                          re.IGNORECASE)
# container method calls that mutate the receiver in place
_MUTATORS = frozenset({
    "append", "extend", "insert", "pop", "popleft", "popitem", "remove",
    "clear", "update", "setdefault", "add", "discard", "appendleft",
    "sort", "reverse"})
# attribute value types that are themselves thread-safe rendezvous
# objects: method calls on them are not shared-state mutations
_SAFE_TYPES = frozenset({
    "Queue", "LifoQueue", "PriorityQueue", "SimpleQueue", "Event",
    "Semaphore", "BoundedSemaphore", "Barrier", "local"})
_LOCK_CTORS = frozenset({"Lock", "RLock", "Condition"})
_REGISTRY_MODULE = "observability/locks.py"


def _expr_text(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - unparse covers our python floor
        return ""


def _tail_name(node: ast.AST) -> str:
    """The trailing identifier of a Name/Attribute chain (``self._lock``
    -> ``_lock``; ``a.b.cond`` -> ``cond``)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _is_lockish(node: ast.AST) -> bool:
    return bool(_LOCKISH_RE.search(_tail_name(node)))


def _callee(node: ast.Call) -> str:
    return _tail_name(node.func)


def _has_timeout(node: ast.Call) -> bool:
    if any(kw.arg == "timeout" for kw in node.keywords):
        return True
    # queue.get(block, timeout) / .put(item, block, timeout) positionals
    return len(node.args) >= 2


class _WithRegion:
    __slots__ = ("key", "node")

    def __init__(self, key: str, node: ast.AST):
        self.key = key
        self.node = node


class _CxVisitor(ast.NodeVisitor):
    """Single pass collecting CX1001 edges, CX1002 blocking-under-lock
    sites and CX1003 bare lock constructions. Lock-region tracking is
    lexical: a ``with <lockish>:`` body is a held region."""

    def __init__(self, filename: str):
        self.filename = filename
        self.findings: List[Finding] = []
        # (outer_key, inner_key, file:line) lock-nesting edges for the
        # cross-file CX1001 graph
        self.edges: List[Tuple[str, str, str]] = []
        self._held: List[_WithRegion] = []
        self._class_stack: List[str] = []

    # ------------------------------------------------------------- helpers
    def _flag(self, code: str, node: ast.AST, message: str,
              severity: str = "error") -> None:
        self.findings.append(Finding(
            _ANALYZER, code, severity, message,
            f"{self.filename}:{getattr(node, 'lineno', 0)}"))

    def _lock_key(self, node: ast.AST) -> str:
        """Normalize a lock expression to its lockdep 'class': named_lock
        calls key on their name literal; ``self.X`` keys on the enclosing
        class so two classes' ``self._lock`` never alias."""
        if isinstance(node, ast.Call):
            name = _callee(node)
            if name in ("named_lock", "named_condition") and node.args and \
                    isinstance(node.args[0], ast.Constant):
                return f"named:{node.args[0].value}"
        text = _expr_text(node)
        if self._class_stack and text.startswith("self."):
            return f"{self._class_stack[-1]}.{text[5:]}"
        return text

    # --------------------------------------------------------------- class
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()

    # ---------------------------------------------------------------- with
    def visit_With(self, node: ast.With) -> None:
        lock_items = [item.context_expr for item in node.items
                      if _is_lockish(item.context_expr)
                      or (isinstance(item.context_expr, ast.Call)
                          and _callee(item.context_expr)
                          in ("named_lock", "named_condition"))]
        pushed = 0
        for expr in lock_items:
            key = self._lock_key(expr)
            if self._held and self._held[-1].key != key:
                self.edges.append((self._held[-1].key, key,
                                   f"{self.filename}:{node.lineno}"))
            self._held.append(_WithRegion(key, node))
            pushed += 1
        for item in node.items:
            self.visit(item.context_expr)
        for stmt in node.body:
            self.visit(stmt)
        for _ in range(pushed):
            self._held.pop()

    # nested defs inside a with-block run LATER, not under the lock
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        held, self._held = self._held, []
        self.generic_visit(node)
        self._held = held

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        held, self._held = self._held, []
        self.generic_visit(node)
        self._held = held

    # ---------------------------------------------------------------- call
    def visit_Call(self, node: ast.Call) -> None:
        self._check_bare_lock(node)
        if self._held:
            self._check_blocking(node)
        self.generic_visit(node)

    def _check_bare_lock(self, node: ast.Call) -> None:
        fn = node.func
        bare = None
        if isinstance(fn, ast.Attribute) and fn.attr in _LOCK_CTORS and \
                isinstance(fn.value, ast.Name) and fn.value.id == "threading":
            bare = f"threading.{fn.attr}"
        elif isinstance(fn, ast.Name) and fn.id in _LOCK_CTORS:
            bare = fn.id
        if bare is None:
            return
        if self.filename.replace("\\", "/").endswith(_REGISTRY_MODULE):
            return  # the registry itself wraps the primitives
        self._flag(
            "CX1003", node,
            f"bare {bare}() constructed outside observability.locks — use "
            "named_lock()/named_condition() so the runtime witness and the "
            "lock registry can see it (bootstrap modules imported before "
            "the registry carry a reasoned noqa instead)")

    def _check_blocking(self, node: ast.Call) -> None:
        held = self._held[-1].key
        name = _callee(node)
        fn = node.func
        if name == "result" and isinstance(fn, ast.Attribute) and \
                not _has_timeout(node):
            self._flag("CX1002", node,
                       f"future .result() with no timeout inside the held "
                       f"lock region {held!r}: the lock's hold time is now "
                       "bounded by another executor's backlog")
        elif name in ("get", "put") and isinstance(fn, ast.Attribute) and \
                _QUEUEISH_RE.search(_tail_name(fn.value)) and \
                not _has_timeout(node):
            self._flag("CX1002", node,
                       f"queue .{name}() with no timeout inside the held "
                       f"lock region {held!r}: a full/empty queue parks "
                       "this thread while it owns the lock")
        elif name in ("block_until_ready", "device_put"):
            self._flag("CX1002", node,
                       f"{name}() inside the held lock region {held!r}: a "
                       "device transfer/sync under a lock serializes every "
                       "other thread behind device latency")
        elif name == "open" and isinstance(fn, ast.Name):
            self._flag("CX1002", node,
                       f"file open() inside the held lock region {held!r}: "
                       "disk I/O under a lock stalls every waiter on the "
                       "filesystem")
        elif name in ("recv", "accept", "sendall", "connect") and \
                isinstance(fn, ast.Attribute):
            self._flag("CX1002", node,
                       f"socket .{name}() inside the held lock region "
                       f"{held!r}: network I/O under a lock stalls every "
                       "waiter on the peer")


# --------------------------------------------------------------- CX1000
class _MethodInfo:
    __slots__ = ("node", "calls", "mutations")

    def __init__(self, node: ast.FunctionDef):
        self.node = node
        self.calls: set = set()        # self.<m>() callees
        # (attr, ast node, guarded, kind)
        self.mutations: List[tuple] = []


def _thread_entry_names(tree: ast.Module) -> Tuple[set, set]:
    """(function names, ``self.<attr>`` method names) referenced as thread
    entry points anywhere in the module: ``Thread(target=...)``,
    ``executor.submit(fn, ...)``."""
    fn_names: set = set()
    method_names: set = set()

    def note(expr: Optional[ast.AST]) -> None:
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and expr.value.id == "self":
            method_names.add(expr.attr)
        elif isinstance(expr, ast.Name):
            fn_names.add(expr.id)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _callee(node)
        if callee == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    note(kw.value)
        elif callee in ("submit", "map") and isinstance(node.func,
                                                        ast.Attribute):
            if node.args:
                note(node.args[0])
    return fn_names, method_names


def _guarded(stack: List[ast.AST]) -> bool:
    """Is the innermost enclosing context a ``with <lockish>`` region?"""
    for node in stack:
        if isinstance(node, ast.With) and any(
                _is_lockish(item.context_expr) for item in node.items):
            return True
    return False


def _collect_mutations(fn: ast.FunctionDef) -> List[tuple]:
    """(attr, node, guarded, kind) for every ``self.<attr>`` mutation in
    ``fn`` — assignments, augmented assignments, subscript stores and
    in-place container method calls."""
    out: List[tuple] = []

    def self_attr(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and node.value.id == "self":
            return node.attr
        return None

    def walk(node: ast.AST, stack: List[ast.AST]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)) and node is not fn:
            return  # nested defs execute in their own context
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                attr = self_attr(t)
                if attr is None and isinstance(t, ast.Subscript):
                    attr = self_attr(t.value)
                if attr is not None:
                    out.append((attr, node, _guarded(stack), "assign"))
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _MUTATORS:
            attr = self_attr(node.func.value)
            if attr is not None:
                out.append((attr, node, _guarded(stack), "call"))
        for child in ast.iter_child_nodes(node):
            walk(child, stack + [node])

    walk(fn, [])
    return out


def _check_class_shared_state(cls: ast.ClassDef, filename: str,
                              entry_methods: set) -> List[Finding]:
    findings: List[Finding] = []
    bases = {_tail_name(b) for b in cls.bases}
    methods: Dict[str, _MethodInfo] = {}
    safe_attrs: set = set()
    for item in cls.body:
        if not isinstance(item, ast.FunctionDef):
            continue
        info = methods[item.name] = _MethodInfo(item)
        # every `self.X` reference is a closure edge, not just calls:
        # `self._guarded(self._decode_step)` passes a method as a
        # callable and the entry thread still runs it (the closure's
        # `not in methods` guard drops plain data attributes)
        for node in ast.walk(item):
            if isinstance(node, ast.Attribute) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == "self":
                info.calls.add(node.attr)
        info.mutations = _collect_mutations(item)
        if item.name == "__init__":
            for attr, node, _g, kind in info.mutations:
                if kind == "assign" and isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Call) and \
                        _callee(node.value) in _SAFE_TYPES:
                    safe_attrs.add(attr)
                if kind == "assign" and isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Call) and (
                            _is_lockish(node.value.func)
                            or _callee(node.value)
                            in ("named_lock", "named_condition")):
                    safe_attrs.add(attr)

    entries = {m for m in methods if m in entry_methods}
    if any("Thread" in b for b in bases) and "run" in methods:
        entries.add("run")
    if any("Handler" in b for b in bases):
        entries.update(m for m in methods if m.startswith("do_"))
    if not entries:
        return findings

    # transitive in-class closure: methods reachable from each entry
    reach: Dict[str, set] = {}
    for entry in entries:
        seen, frontier = set(), [entry]
        while frontier:
            m = frontier.pop()
            if m in seen or m not in methods:
                continue
            seen.add(m)
            frontier.extend(methods[m].calls)
        reach[entry] = seen

    # attr -> {context label -> [(node, guarded)]}; context = the entry
    # point the mutating method is reachable from, else "main"
    attr_sites: Dict[str, Dict[str, list]] = {}
    for mname, info in methods.items():
        if mname in ("__init__", "__del__"):
            continue  # before threads exist / after they matter
        contexts = sorted(e for e, seen in reach.items() if mname in seen) \
            or ["main"]
        for attr, node, guarded, _kind in info.mutations:
            if attr in safe_attrs or _LOCKISH_RE.search(attr):
                continue
            cell = attr_sites.setdefault(attr, {})
            for ctx in contexts:
                cell.setdefault(ctx, []).append((node, guarded))

    for attr, cell in sorted(attr_sites.items()):
        if len(cell) < 2 or not any(c != "main" for c in cell):
            continue
        unguarded = [(ctx, node) for ctx, sites in cell.items()
                     for node, guarded in sites if not guarded]
        seen_lines: set = set()
        for ctx, node in unguarded:
            if node.lineno in seen_lines:
                continue
            seen_lines.add(node.lineno)
            findings.append(Finding(
                _ANALYZER, "CX1000", "error",
                f"{cls.name}.{attr} is mutated from {len(cell)} thread "
                f"entry contexts ({', '.join(sorted(cell))}) but this "
                f"mutation (in context {ctx!r}) is not inside a `with "
                "<lock>` region — a data race once both contexts run",
                f"{filename}:{node.lineno}"))
    return findings


def _check_module_globals(tree: ast.Module, filename: str,
                          entry_fns: set) -> List[Finding]:
    """CX1000 for module-level state: globals mutated both from a thread
    entry function (transitive in-module closure) and from other code."""
    findings: List[Finding] = []
    module_names = {t.id for node in tree.body
                    if isinstance(node, (ast.Assign, ast.AnnAssign))
                    for t in (node.targets if isinstance(node, ast.Assign)
                              else [node.target])
                    if isinstance(t, ast.Name)}
    if not module_names or not entry_fns:
        return findings
    fns = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}
    calls: Dict[str, set] = {
        name: {_callee(c) for c in ast.walk(fn)
               if isinstance(c, ast.Call)}
        for name, fn in fns.items()}
    reach: Dict[str, set] = {}
    for entry in entry_fns & set(fns):
        seen, frontier = set(), [entry]
        while frontier:
            m = frontier.pop()
            if m in seen or m not in fns:
                continue
            seen.add(m)
            frontier.extend(calls[m])
        reach[entry] = seen

    def mutations(fn: ast.FunctionDef) -> List[tuple]:
        declared_global = {n for node in ast.walk(fn)
                           if isinstance(node, ast.Global)
                           for n in node.names}
        out = []

        def walk(node, stack):
            if isinstance(node, (ast.FunctionDef, ast.Lambda)) and \
                    node is not fn:
                return
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Name) and t.id in declared_global \
                            and t.id in module_names:
                        out.append((t.id, node, _guarded(stack)))
                    elif isinstance(t, ast.Subscript) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id in module_names:
                        out.append((t.value.id, node, _guarded(stack)))
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _MUTATORS and \
                    isinstance(node.func.value, ast.Name) and \
                    node.func.value.id in module_names:
                out.append((node.func.value.id, node, _guarded(stack)))
            for child in ast.iter_child_nodes(node):
                walk(child, stack + [node])

        walk(fn, [])
        return out

    sites: Dict[str, Dict[str, list]] = {}
    for fname, fn in fns.items():
        contexts = sorted(e for e, seen in reach.items() if fname in seen) \
            or ["main"]
        for gname, node, guarded in mutations(fn):
            if _LOCKISH_RE.search(gname):
                continue
            cell = sites.setdefault(gname, {})
            for ctx in contexts:
                cell.setdefault(ctx, []).append((node, guarded))
    for gname, cell in sorted(sites.items()):
        if len(cell) < 2 or not any(c != "main" for c in cell):
            continue
        seen_lines: set = set()
        for ctx, cell_sites in cell.items():
            for node, guarded in cell_sites:
                if guarded or node.lineno in seen_lines:
                    continue
                seen_lines.add(node.lineno)
                findings.append(Finding(
                    _ANALYZER, "CX1000", "error",
                    f"module global {gname!r} is mutated from "
                    f"{len(cell)} thread entry contexts "
                    f"({', '.join(sorted(cell))}) but this mutation (in "
                    f"context {ctx!r}) is not inside a `with <lock>` "
                    "region — a data race once both contexts run",
                    f"{filename}:{node.lineno}"))
    return findings


# -------------------------------------------------------------- per file
def check_source(source: str, filename: str = "<string>",
                 _edges_out: Optional[list] = None) -> List[Finding]:
    """CX1000/CX1002/CX1003 over one file; lock-nesting edges are
    appended to ``_edges_out`` for the caller's cross-file CX1001 graph
    (standalone calls get their own single-file cycle check)."""
    from .noqa import apply_noqa

    try:
        tree = ast.parse(source, filename=filename)
    except SyntaxError as e:
        return [Finding(_ANALYZER, "CX999", "error",
                        f"could not parse {filename}: {e}", filename)]
    visitor = _CxVisitor(filename)
    visitor.visit(tree)
    findings = visitor.findings

    entry_fns, entry_methods = _thread_entry_names(tree)
    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            findings += _check_class_shared_state(node, filename,
                                                  entry_methods)
    findings += _check_module_globals(tree, filename, entry_fns)

    if _edges_out is not None:
        _edges_out.extend(visitor.edges)
    else:
        findings += _cycle_findings(visitor.edges)
    return apply_noqa(findings, source)


def _cycle_findings(edges: Sequence[Tuple[str, str, str]]) -> List[Finding]:
    """CX1001 over the collected lock-nesting edges: report each edge
    that participates in a cycle (reachable back to its own source)."""
    graph: Dict[str, set] = {}
    for outer, inner, _loc in edges:
        graph.setdefault(outer, set()).add(inner)

    def reaches(src: str, dst: str) -> bool:
        seen, frontier = set(), [src]
        while frontier:
            node = frontier.pop()
            if node == dst:
                return True
            if node in seen:
                continue
            seen.add(node)
            frontier.extend(graph.get(node, ()))
        return False

    findings: List[Finding] = []
    reported: set = set()
    for outer, inner, loc in edges:
        if (outer, inner) in reported:
            continue
        if reaches(inner, outer):
            reported.add((outer, inner))
            findings.append(Finding(
                _ANALYZER, "CX1001", "error",
                f"static lock-order cycle: {outer!r} is taken before "
                f"{inner!r} here, but another path takes them in the "
                "opposite order — two threads on the two paths deadlock",
                loc))
    return findings


# ------------------------------------------------------------- runtime
def audit_witness() -> List[Finding]:
    """CX1004/CX1005 over the live process witness: every violation the
    lit witness has recorded becomes an error finding."""
    from ..observability import locks

    findings: List[Finding] = []
    for v in locks.witness_violations():
        if v["code"] == "CX1004":
            findings.append(Finding(
                _ANALYZER, "CX1004", "error",
                "runtime lock-order inversion: acquired "
                f"{v['edge'][1]!r} while holding {v['edge'][0]!r}, but "
                "the recorded order graph already reaches "
                f"{v['edge'][0]!r} from {v['edge'][1]!r} "
                f"(thread {v.get('thread', '?')}, held stack "
                f"{v.get('held_stack')})", "witness"))
        else:
            findings.append(Finding(
                _ANALYZER, "CX1005", "error",
                f"lock {v['name']!r} held for {v['held_ms']}ms — over the "
                f"FLAGS_concurrency_max_hold_ms budget of "
                f"{v['limit_ms']}ms (thread {v.get('thread', '?')})",
                "witness"))
    return findings


def check_paths(paths: Sequence[str]) -> List[Finding]:
    """CX1000/CX1002/CX1003 per file + the cross-file CX1001 nesting
    graph. Purely static — the runtime half (CX1004/CX1005) comes from
    :func:`audit_witness` / :func:`record_demo_concurrency` so the lint
    runner never double-reports a witness violation."""
    from . import iter_py_files

    findings: List[Finding] = []
    edges: List[Tuple[str, str, str]] = []
    for f in iter_py_files(paths):
        with open(f, encoding="utf-8") as fh:
            findings.extend(check_source(fh.read(), f, _edges_out=edges))
    findings += _cycle_findings(edges)
    return findings


# ----------------------------------------------------------------- demo
def record_demo_concurrency(tmpdir: Optional[str] = None) -> List[Finding]:
    """The representative concurrent session, driven under the lit
    witness: a warmed ServingEngine takes live traffic (scheduler +
    completion threads over the queue condition, admission, stats and
    KV-free locks) while a DeviceLoader stages batches through its
    prefetch thread. Returns the CX1004/CX1005 findings the run
    produced (none, on a healthy tree) — and errors loudly if the demo
    recorded NO acquisitions, which would mean the runtime locks left
    the registry (a silently dead witness must not pass the gate)."""
    import shutil
    import tempfile

    import numpy as np

    from ..io.device_prefetch import DeviceLoader
    from ..observability import locks

    own_tmp = tmpdir is None
    if own_tmp:
        tmpdir = tempfile.mkdtemp(prefix="paddle_lint_cx_")
    before = locks.witness_stats()["acquires"]
    baseline_violations = len(locks.witness_violations())
    was = locks.set_witness(True)
    try:
        from .jaxpr_audit import record_demo_engine

        engine = record_demo_engine(tmpdir)
        del engine
        batches = [(np.zeros((2, 4), np.float32),) for _ in range(4)]
        for _ in DeviceLoader(batches, depth=2):
            pass
    finally:
        locks.set_witness(was)
        if own_tmp:
            shutil.rmtree(tmpdir, ignore_errors=True)
    findings = [f for f in audit_witness()][baseline_violations:]
    after = locks.witness_stats()["acquires"]
    if after <= before:
        findings.append(Finding(
            _ANALYZER, "CX1004", "error",
            "the lit witness recorded ZERO lock acquisitions across a "
            "full serving + prefetch demo — the runtime locks are no "
            "longer named_lock()s (registry migration regressed), so "
            "inversion detection is silently dead", "witness"))
    return findings
