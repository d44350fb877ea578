"""Signature-keyed kernel cache: the eager dispatch fast path.

Rebuild of the reference's generated ``xxx_ad_func`` fast path (the eager
auto-code-generated layer caches kernel selection and backward-node shape
per op signature): here the cached object is a **jitted executable** — the
op's forward, and for differentiable calls the forward+VJP pair — keyed by

    (op name, kernel identity, per-arg (shape, dtype, is-diff) spec,
     frozen static args, frozen attrs)

so steady-state eager steps replay compiled programs instead of re-running
``jax.vjp`` tracing per op (~1ms/op eager trace vs ~10µs/op cached replay
on CPU). The VJP side rides on jax's contract that ``jax.vjp`` under
``jax.jit`` returns its pullback as a ``jax.tree_util.Partial`` pytree:
the compiled forward emits the residuals as ordinary outputs, and a shared
jitted applier (:data:`_VJP_APPLIER`) replays the backward without ever
tracing on the hot path. :class:`CachedVJP` is what ``GradNode`` holds in
place of a live ``vjp_fn`` closure (core/autograd.py).

Kernels must be pure (the trace-safety linter enforces this for the
framework's own ops): staging executes the python body once under trace, so
a host side effect in a custom kernel fires during the staging attempt and
— if staging fails and the call falls back — again on the eager re-run.
Only global-RNG corruption is actively detected and repaired
(:func:`_staging_call`); other host side effects in kernels are undefined
under caching, as under any jit.

Kernel identity: op fns arrive as per-call-site lambdas that close over
their attrs (``lambda v: jnp.sum(v, axis=ax)``), so the key derives from
``fn.__code__`` (stable per call site) plus the **frozen closure cell
values** (the attrs). Anything that cannot be frozen to a hashable token —
arrays or Tensors in cells, unhashable attrs — bypasses the fast path for
that call; the dispatcher also self-disables whenever it cannot be
semantically transparent (active discovery / static_capture / op_observer
hooks, AMP cast insertion, tracer inputs). Every bypass is counted per op
with its reason (:func:`stats`), feeding the JX32x kernel-cache audit in
``analysis/jaxpr_audit.py``.

Flags: ``FLAGS_eager_kernel_cache`` (master switch),
``FLAGS_eager_kernel_cache_max_entries`` (LRU capacity).
"""
from __future__ import annotations

import time as _time
from collections import OrderedDict
from typing import Any, Optional, Sequence

import numpy as np

import jax

from ..base.flags import get_flag
from ..observability.tracing import tracer as _tracer

__all__ = ["CachedVJP", "clear", "cost_stats", "execute", "lookup",
           "poison", "record_bypass", "stats"]


class _Unhashable(Exception):
    """Internal signal: a key component cannot be frozen. ``reason`` is the
    bypass counter it lands in — ``array_capture`` for arrays/Tensors in
    the signature (the deliberate pattern: per-call PRNG keys, captured
    payloads), ``unhashable`` for everything else (the JX320 storm
    numerator)."""

    def __init__(self, reason="unhashable"):
        super().__init__(reason)
        self.reason = reason


# ---------------------------------------------------------------------------
# key derivation
# ---------------------------------------------------------------------------

_FREEZE_DEPTH = 4


def _freeze(v, depth=0):
    """Hashable token for a static key component, or raise :class:`_Unhashable`.

    Containers are frozen structurally (list/dict attrs like ``perm`` or
    ``axis`` lists are common); numeric scalars carry their type (``2``,
    ``2.0`` and ``True`` are ==/hash-equal but stage different programs);
    arrays and Tensors are refused — baking a mutable payload into a cache
    key would serve stale programs."""
    if v is None or v is Ellipsis or isinstance(v, (str, bytes, np.dtype)):
        return v
    if isinstance(v, (bool, int, float, complex, np.generic)):
        return (type(v), v)
    from .tensor import Tensor

    if isinstance(v, (Tensor, np.ndarray, jax.Array)) or hasattr(v, "aval"):
        raise _Unhashable("array_capture")
    if depth >= _FREEZE_DEPTH:
        raise _Unhashable
    if isinstance(v, slice):  # unhashable on py3.10
        return ("__slice__", _freeze(v.start, depth + 1),
                _freeze(v.stop, depth + 1), _freeze(v.step, depth + 1))
    if isinstance(v, (list, tuple)):
        return ("__seq__", tuple(_freeze(x, depth + 1) for x in v))
    if isinstance(v, (set, frozenset)):
        return ("__set__", frozenset(_freeze(x, depth + 1) for x in v))
    if isinstance(v, dict):
        return ("__map__", tuple(sorted(
            (k, _freeze(x, depth + 1)) for k, x in v.items())))
    if callable(v):
        return _fn_key(v, depth + 1)
    try:
        hash(v)
    except TypeError:
        raise _Unhashable from None
    return v


# code object -> content token. CPython code equality includes
# co_firstlineno, so the same kernel text at two call sites (or a factory
# re-exec'd at different lines) hashes apart and churns the cache with
# duplicate executables. The token hashes code CONTENT — bytecode, consts
# (recursing into nested code, so closures holding fresh inner lambdas
# collapse too), names — and drops filename/lineno. Memoized per code
# object: the content walk runs once per call site, the hot path pays one
# dict hit.
_CODE_TOKENS: dict = {}


def _const_token(c):
    """Type-aware token for one co_consts entry: ``1``, ``1.0`` and
    ``True`` are ==/hash-equal in Python but stage different programs, so
    a plain tuple compare would collide ``x * 1`` with ``x * 1.0`` (code
    objects themselves compare constants type-aware — keep that)."""
    if hasattr(c, "co_code"):
        return _code_token(c)
    if isinstance(c, (bool, int, float, complex)):
        return (type(c), c)
    if isinstance(c, tuple):
        return ("__tuple__", tuple(_const_token(x) for x in c))
    if isinstance(c, frozenset):
        return ("__fset__", frozenset(_const_token(x) for x in c))
    return c  # str/bytes/None/Ellipsis: type-unambiguous


def _code_token(code):
    tok = _CODE_TOKENS.get(code)
    if tok is None:
        consts = tuple(_const_token(c) for c in code.co_consts)
        tok = ("__code__", code.co_code, consts, code.co_names,
               code.co_argcount, code.co_posonlyargcount,
               code.co_kwonlyargcount, code.co_flags,
               code.co_freevars, code.co_cellvars)
        _CODE_TOKENS[code] = tok
    return tok


def _fn_key(fn, depth=0):
    """Identity of the kernel computation: code CONTENT token + frozen
    closure cell values (+ defaults). Call sites with identical code —
    even at different lines/files, even when their cells hold fresh inner
    lambdas — collapse to one key: cells hash by VALUE (``_freeze`` of the
    contents, recursing through :func:`_code_token` for function values),
    never by cell identity."""
    import functools

    if isinstance(fn, functools.partial):
        return ("__partial__", _fn_key(fn.func, depth + 1),
                tuple(_freeze(a, depth + 1) for a in fn.args),
                _freeze(fn.keywords, depth + 1))
    if getattr(fn, "__self__", None) is not None:
        # bound method: __code__/__closure__ proxy the underlying function
        # and would drop the instance (and its mutable state) from the key
        raise _Unhashable
    code = getattr(fn, "__code__", None)
    if code is None:
        return fn  # builtin / C function: stable by identity
    cells = getattr(fn, "__closure__", None) or ()
    return (_code_token(code),
            _freeze(getattr(fn, "__defaults__", None), depth),
            _freeze(getattr(fn, "__kwdefaults__", None), depth),
            tuple(_freeze(c.cell_contents, depth) for c in cells))


def _sig_str(spec_parts) -> str:
    """Compact human signature for trace events: ``float32[4,8],int64[4]``
    with static args elided. Cold-path only (compile events)."""
    parts = []
    for part in spec_parts:
        if part is None or part[0] == "__static__":
            continue
        shape, dtype = part[0], part[1]
        name = getattr(dtype, "name", str(dtype))
        parts.append(f"{name}[{','.join(str(d) for d in shape)}]")
    return ",".join(parts)


_STATIC, _ARRAY, _TRACER = 0, 1, 2
_KIND_BY_TYPE: dict = {}  # exact type -> kind (jax's abc isinstance is slow)


def _arg_kind(v) -> int:
    t = type(v)
    k = _KIND_BY_TYPE.get(t)
    if k is None:
        if isinstance(v, jax.core.Tracer):
            k = _TRACER
        elif isinstance(v, (jax.Array, np.ndarray)):
            k = _ARRAY
        else:
            k = _STATIC
        _KIND_BY_TYPE[t] = k
    return k


# ---------------------------------------------------------------------------
# cache state + stats
# ---------------------------------------------------------------------------

_cache: "OrderedDict[Any, _Entry]" = OrderedDict()
# ordered set of keys whose entry failed to trace (bypass without re-paying
# the failed trace). Bounded: an evicted key that fails again just re-pays
# one staging attempt, whereas an unbounded set leaks key tuples forever.
_poisoned: "OrderedDict[Any, None]" = OrderedDict()
_stats: dict = {}        # op name -> counter dict
_kernel_cacheable = None  # lazily bound registry.kernel_cacheable (import cycle)


def _poison_cap() -> int:
    cap = int(get_flag("eager_kernel_cache_max_entries"))
    return 4 * cap if cap > 0 else 4096


def _op_stats(op: str) -> dict:
    s = _stats.get(op)
    if s is None:
        s = _stats[op] = {"hits": 0, "misses": 0, "bypasses": 0,
                          "evictions": 0, "bypass_reasons": {}}
    return s


def record_bypass(op: str, reason: str) -> None:
    """Count one fast-path bypass for ``op``. Reasons in use: ``amp``,
    ``discovery``, ``static_capture``, ``observer`` (dispatcher-level
    transparency gates), ``tracer``, ``unhashable``, ``array_capture``
    (deliberate array/Tensor/PRNG-key in the signature — dropout et al.),
    ``denied``, ``trace_failed`` (cache-level). The JX320 storm audit
    counts only ``unhashable`` — ``array_capture`` is by design."""
    s = _op_stats(op)
    s["bypasses"] += 1
    s["bypass_reasons"][reason] = s["bypass_reasons"].get(reason, 0) + 1
    if _tracer.enabled:
        _tracer.instant("kernel_cache.bypass", track="dispatch",
                        op=op, reason=reason)


_bypass = record_bypass


def stats() -> dict:
    """Cache statistics snapshot: per-op ``hits/misses/bypasses/evictions``
    (+ ``bypass_reasons``) under ``"ops"``, aggregate ``"totals"``, and the
    current ``"size"``/``"capacity"``. Consumed by the JX32x kernel-cache
    audit."""
    ops = {op: {**s, "bypass_reasons": dict(s["bypass_reasons"])}
           for op, s in _stats.items()}
    totals = {k: sum(s[k] for s in _stats.values())
              for k in ("hits", "misses", "bypasses", "evictions")}
    return {"ops": ops, "totals": totals, "size": len(_cache),
            "capacity": int(get_flag("eager_kernel_cache_max_entries"))}


def cost_stats(max_entries: Optional[int] = None) -> dict:
    """Per-entry static cost of every cached executable: retrace each
    entry's staged function from the (shape, dtype) specs its cache key
    already records and run the analysis cost model over the jaxpr
    (``analysis/cost_model.py`` — tracing only, no XLA compilation, no
    counters touched). On-demand companion to :func:`stats`, which stays
    a pure counter read; ``max_entries`` bounds the walk to the N most
    recently used entries."""
    import jax

    from ..analysis.cost_model import cost_jaxpr

    items = list(_cache.items())
    if max_entries is not None and max_entries > 0:
        items = items[-max_entries:]  # OrderedDict: tail = most recent
    entries = []
    totals = {"flops": 0.0, "bytes_read": 0.0, "bytes_written": 0.0,
              "peak_bytes": 0}
    for key, entry in items:
        sds = [jax.ShapeDtypeStruct(tuple(part[0]), part[1])
               for part in key[2] if part[0] != "__static__"]
        row = {"op": entry.op, "has_vjp": entry.has_vjp}
        try:
            closed = jax.make_jaxpr(entry.fwd)(*sds)
            rep = cost_jaxpr(closed, location=f"kernel_cache:{entry.op}")
        except Exception as e:
            row["error"] = str(e).splitlines()[0]
            entries.append(row)
            continue
        row.update(flops=rep.flops, bytes_read=rep.bytes_read,
                   bytes_written=rep.bytes_written, peak_bytes=rep.peak_bytes,
                   arithmetic_intensity=round(rep.arithmetic_intensity, 4))
        totals["flops"] += rep.flops
        totals["bytes_read"] += rep.bytes_read
        totals["bytes_written"] += rep.bytes_written
        totals["peak_bytes"] = max(totals["peak_bytes"], rep.peak_bytes)
        entries.append(row)
    return {"entries": entries, "totals": totals, "n_entries": len(entries)}


def clear(reset_stats: bool = True) -> None:
    """Drop every cached executable (and, by default, the counters)."""
    _cache.clear()
    _poisoned.clear()
    if reset_stats:
        _stats.clear()


def poison(key, op: str) -> None:
    """Bypass ``key`` from now on: its entry failed to trace or execute
    (data-dependent shapes, host ops or RNG draws inside the kernel). The
    slow path serves every later call without re-paying the failed trace."""
    _cache.pop(key, None)
    _poisoned[key] = None
    while len(_poisoned) > _poison_cap():
        _poisoned.popitem(last=False)
    _bypass(op, "trace_failed")


# ---------------------------------------------------------------------------
# entries
# ---------------------------------------------------------------------------

class _Entry:
    __slots__ = ("key", "op", "fwd", "bwd", "traced_idx", "has_vjp", "staged")

    def __init__(self, key, op, fwd, bwd, traced_idx, has_vjp):
        self.key = key
        self.op = op
        self.fwd = fwd            # jitted: (*arrays) -> out | (out, vjp Partial)
        # per-ENTRY jitted pullback applier: each staging trace mints a
        # pullback with a fresh static identity, so a process-shared applier
        # would retain one compiled backward per staging forever — here the
        # executable's lifetime is the entry's (plus any live GradNode's)
        self.bwd = bwd
        self.traced_idx = traced_idx
        self.has_vjp = has_vjp
        self.staged = False       # first call traces; later calls replay


def _build(key, op, fn, values, attrs, diff_idx, traced_idx) -> _Entry:
    """Stage the op into one jitted executable. Static (non-array) args are
    baked from this call's values — the key proves equality for every
    future hit. For differentiable calls the staged function returns
    ``jax.vjp``'s ``(out, pullback)`` pair; the pullback crosses the jit
    boundary as a ``Partial`` pytree carrying the residual arrays."""
    tset = set(traced_idx)
    static_vals = tuple(None if i in tset else values[i]
                        for i in range(len(values)))
    diff = tuple(diff_idx)
    traced = tuple(traced_idx)
    has_vjp = bool(diff)

    def staged(*arrs):
        full = list(static_vals)
        for j, i in enumerate(traced):
            full[i] = arrs[j]
        if not has_vjp:
            return fn(*full, **attrs)
        dvals = tuple(full[i] for i in diff)

        def partial_fn(*dv):
            f2 = list(full)
            for i, v in zip(diff, dv):
                f2[i] = v
            return fn(*f2, **attrs)

        return jax.vjp(partial_fn, *dvals)

    bwd = (jax.jit(lambda pullback, cotangent: pullback(cotangent))
           if has_vjp else None)
    return _Entry(key, op, jax.jit(staged), bwd, traced, has_vjp)


def lookup(op: str, fn, values: Sequence[Any], attrs: dict,
           diff_idx: Sequence[int]) -> Optional[_Entry]:
    """The cached executable for this call signature, building it on a
    miss. ``None`` means bypass (reason recorded in :func:`stats`): the
    call must take the slow path. Never raises on key trouble — unhashable
    attrs/cells and tracer inputs degrade to a counted bypass."""
    global _kernel_cacheable
    if _kernel_cacheable is None:
        from ..ops.registry import kernel_cacheable as _kernel_cacheable
    if not _kernel_cacheable(op):
        _bypass(op, "denied")
        return None
    try:
        n = len(values)
        spec_parts = [None] * n  # pre-sized: no list growth on the hot path
        traced_idx = []
        if diff_idx:
            diff = set(diff_idx)
            for i in range(n):
                v = values[i]
                kind = _arg_kind(v)
                if kind == _TRACER:
                    _bypass(op, "tracer")
                    return None
                if kind == _ARRAY:
                    traced_idx.append(i)
                    spec_parts[i] = (v.shape, v.dtype, i in diff)
                else:
                    spec_parts[i] = ("__static__", _freeze(v))
        else:
            # no-grad fast path: on single-primitive ops the key build IS
            # the dispatch cost — skip the diff-set allocation and the
            # per-arg membership test entirely
            for i in range(n):
                v = values[i]
                kind = _arg_kind(v)
                if kind == _TRACER:
                    _bypass(op, "tracer")
                    return None
                if kind == _ARRAY:
                    traced_idx.append(i)
                    spec_parts[i] = (v.shape, v.dtype, False)
                else:
                    spec_parts[i] = ("__static__", _freeze(v))
        key = (op, _fn_key(fn), tuple(spec_parts),
               _freeze(attrs) if attrs else None)
        hash(key)
    except _Unhashable as e:
        _bypass(op, e.reason)
        return None
    except TypeError:
        _bypass(op, "unhashable")
        return None

    if key in _poisoned:
        _bypass(op, "trace_failed")
        return None

    entry = _cache.get(key)
    s = _op_stats(op)
    if entry is not None:
        s["hits"] += 1
        _cache.move_to_end(key)
        if _tracer.enabled:
            _tracer.instant("kernel_cache.hit", track="dispatch", op=op)
        return entry

    s["misses"] += 1
    t0 = _time.perf_counter() if _tracer.enabled else 0.0
    try:
        entry = _build(key, op, fn, values, attrs, tuple(diff_idx),
                       tuple(traced_idx))
    except Exception:
        poison(key, op)
        return None
    if _tracer.enabled:
        # the dispatch compile event: which op, what signature, why it
        # missed (a fresh signature — bypasses record their own reason),
        # and what the build cost on the wall clock
        _tracer.emit("kernel_cache.compile", t0, _time.perf_counter() - t0,
                     track="dispatch", op=op, signature=_sig_str(spec_parts),
                     reason="new_signature", has_vjp=bool(diff_idx))
    _cache[key] = entry
    cap = int(get_flag("eager_kernel_cache_max_entries"))
    while len(_cache) > cap > 0:
        _, evicted = _cache.popitem(last=False)
        _op_stats(evicted.op)["evictions"] += 1
    return entry


def execute(entry: _Entry, values: Sequence[Any]):
    """Run the cached executable on this call's array args. Returns the
    raw forward output, or ``(out, CachedVJP)`` for differentiable
    entries. Raises on the first call if the kernel cannot be staged
    (the dispatcher poisons the key and falls back)."""
    arrs = tuple(values[i] for i in entry.traced_idx)
    if not entry.staged:
        return _staging_call(entry, arrs)
    if not entry.has_vjp:
        return entry.fwd(*arrs)
    out, pullback = entry.fwd(*arrs)
    return out, CachedVJP(pullback, entry.bwd)


def _staging_call(entry: _Entry, arrs):
    """First execution of a fresh entry — the call that traces the kernel.
    A kernel that draws from the global RNG inside its body would both
    freeze its randomness into the executable and write a jit tracer into
    the generator cell, corrupting every later random op process-wide
    (framework random ops split the key host-side, outside the kernel —
    this guards the custom-op surface). Detect it, repair the generator,
    and refuse the entry so the dispatcher poisons the key."""
    from ..base.global_state import default_generator as gen

    cell = gen._cell
    before = None if cell is None else cell._value
    clean_before = before is None or not isinstance(before, jax.core.Tracer)
    try:
        if not entry.has_vjp:
            result = entry.fwd(*arrs)
        else:
            out, pullback = entry.fwd(*arrs)
            result = (out, CachedVJP(pullback, entry.bwd))
    except Exception:
        if clean_before:
            _repair_rng(gen, cell, before)
        raise
    if clean_before and _repair_rng(gen, cell, before):
        raise RuntimeError(
            f"kernel for op '{entry.op}' drew from the global RNG under the "
            "staging trace — split the key outside the kernel body")
    entry.staged = True
    return result


def _repair_rng(gen, cell_before, value_before) -> bool:
    """Restore the global generator if the staging trace leaked a tracer
    into it. Returns True when corruption was found (and undone)."""
    cell = gen._cell
    if cell is None or not isinstance(cell._value, jax.core.Tracer):
        return False
    if cell is cell_before and value_before is not None:
        cell._value = value_before
        return True
    gen._cell = None  # created (or swapped) under the trace: rebuild lazily
    return True


# ---------------------------------------------------------------------------
# lazy backward
# ---------------------------------------------------------------------------

def _has_float0(cotangent) -> bool:
    leaves = cotangent if isinstance(cotangent, (tuple, list)) else (cotangent,)
    return any(isinstance(leaf, np.ndarray) and leaf.dtype == jax.dtypes.float0
               for leaf in leaves)


class CachedVJP:
    """The lazy backward handle a fast-path ``GradNode`` holds instead of a
    live ``jax.vjp`` closure: a residual-carrying ``jax.tree_util.Partial``
    emitted by the cached forward executable, plus its entry's jitted
    applier. The Partial's treedef (fixed at the entry's one staging trace)
    is the applier's jit cache key, so steady-state backward replays a
    compiled program — and the executable dies with the entry/GradNode
    instead of accumulating in a process-wide cache. ``float0`` cotangents
    (integer primal outputs) fall back to direct application: float0 is not
    a jit-transferable type."""

    __slots__ = ("pullback", "applier")

    def __init__(self, pullback, applier):
        self.pullback = pullback
        self.applier = applier

    def __call__(self, cotangent):
        if self.applier is None or _has_float0(cotangent):
            return self.pullback(cotangent)
        return self.applier(self.pullback, cotangent)
