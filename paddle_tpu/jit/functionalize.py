"""The compile path: eager code -> one XLA program.

TPU-native replacement for the reference's whole to_static stack (SOT bytecode
capture paddle/fluid/pybind/sot/eval_frame.c + dy2static AST transforms +
PIR program + StandaloneExecutor, see SURVEY.md §3.3). The rebuild exploits
that this framework's eager layer is jax-traceable end to end:

1. **Discovery run** — execute the python function once eagerly while
   intercepting every Tensor the dispatcher reads and every payload write
   (core/hooks.py). That yields the *state cells*: parameters, buffers
   (BatchNorm running stats), optimizer accumulators, the global RNG key —
   exactly the variables the reference's program would hold. Writes are
   rolled back afterwards, so discovery is side-effect-free.
2. **Functionalization** — build ``pure(cell_values, args) -> (out,
   new_cell_values)`` by installing traced values into the cells and re-running
   the same python; jax.jit compiles it with the cell inputs donated (in-place
   buffer reuse on TPU, the analog of the reference's inplace pass).
3. **Execution** — subsequent calls run the compiled program and write the new
   cell values back into the live objects.

Python control flow on tensor *values* is handled with SOT-style branch
guards (reference python/paddle/jit/sot/ graph breaks, VERDICT r3 #6):
``if some_tensor_cond:`` records the concrete outcome during discovery and
compiles a specialization per branch signature; the compiled program also
RETURNS the predicate values, so each call verifies its speculation and, on
a flip, re-runs the specialization for the actual branch (cells are not
donated for guarded programs, so the originals stay intact). Only an
unseen branch signature — or a conversion the guard can't see, like
``float(loss)`` — costs an eager step (recorded in ``fallback_reason`` /
``stats``).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..base.log import get_logger
from ..core import hooks
from ..core.tensor import Tensor, unwrap

# process-wide program-build count across every CompiledFunction — the
# whole-step analog of kernel_cache's miss counter, re-homed into
# observability.snapshot() under "jit.compile" (adapters.py). Build-time
# only: the hot __call__ replay path never touches it.
_build_totals = {"programs": 0}


def build_totals() -> int:
    """Total compiled-program builds this process (all CompiledFunctions)."""
    return _build_totals["programs"]


def _record_build(name: str, t0: float) -> None:
    """Count one program build and, when tracing, span it on the dispatch
    track (signature-level detail lives in the kernel-cache events; here
    the unit is one whole-step XLA program)."""
    import time

    _build_totals["programs"] += 1
    from ..observability.tracing import tracer

    if tracer.enabled:
        tracer.emit("jit.build", t0, time.perf_counter() - t0,
                    track="dispatch", program=name)


class _BranchRecorder:
    """Eager-run mode of the branch hook: log every tensor-bool outcome."""

    def __init__(self):
        self.outcomes: List[bool] = []

    def on_bool(self, t: Tensor) -> bool:
        val = bool(np.asarray(t._value).item()) if not isinstance(
            t._value, jax.core.Tracer) else None
        if val is None:
            raise jax.errors.TracerBoolConversionError(t._value)
        self.outcomes.append(val)
        return val


class _BranchReplayer:
    """Trace-time mode: return the recorded outcome so tracing follows the
    recorded path, and collect the predicate tracer as a guard output."""

    def __init__(self, outcomes):
        self.outcomes = list(outcomes)
        self.idx = 0
        self.preds: List[Any] = []

    def on_bool(self, t: Tensor) -> bool:
        if self.idx >= len(self.outcomes):
            raise _BranchMismatch(
                "branch structure changed during replay (more tensor-bool "
                "conversions than the recorded path)")
        self.preds.append(jnp.asarray(t._value).reshape(()).astype(jnp.bool_))
        val = self.outcomes[self.idx]
        self.idx += 1
        return val


class _BranchMismatch(RuntimeError):
    pass


class DiscoveryContext:
    def __init__(self):
        self.cells: Dict[int, Tensor] = {}
        self.old_values: Dict[int, Any] = {}
        self.arg_ids = set()
        self.internal_ids = set()  # tensors created during discovery (intermediates)

    def record_create(self, t: Tensor):
        self.internal_ids.add(id(t))

    def record_reads(self, tensor_args):
        for a in tensor_args:
            if (
                isinstance(a, Tensor)
                and id(a) not in self.arg_ids
                and id(a) not in self.internal_ids
                and id(a) not in self.cells
            ):
                self.cells[id(a)] = a

    def record_write(self, t: Tensor):
        if id(t) in self.arg_ids:
            return
        if id(t) not in self.old_values:
            self.old_values[id(t)] = t._value
        if id(t) not in self.cells:
            self.cells[id(t)] = t

    def prune_tracer_cells(self):
        """Drop cells whose value is a dead tracer. Tensors created inside an
        inner trace during the eager discovery run (e.g. the pipeline
        schedule's per-tick RNG cells) get registered by their writes but die
        with that trace — keeping them would pin a leaked tracer into the
        compiled entry's state. Real state (params, optimizer moments created
        lazily on the first step) holds concrete arrays and stays."""
        import jax.core as jcore

        dead = [tid for tid, c in self.cells.items()
                if isinstance(c._value, jcore.Tracer)]
        for tid in dead:
            self.cells.pop(tid, None)
            self.old_values.pop(tid, None)

    def rollback(self):
        for tid, old in self.old_values.items():
            self.cells[tid]._value = old  # raw restore, no re-interception


def _tree_key(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sig = tuple(
        (tuple(l.shape), str(l.dtype)) if hasattr(l, "shape") else (type(l).__name__, l if isinstance(l, (int, float, bool, str, type(None))) else None)
        for l in leaves
    )
    return treedef, sig



def _abstract_call(args, kwargs):
    """(args, kwargs) with every array leaf replaced by its
    ShapeDtypeStruct: memory_analysis only needs shapes/dtypes to re-lower,
    and storing live arrays would pin a whole input batch in memory between
    steps."""
    return jax.tree_util.tree_map(
        lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                   if hasattr(x, "shape") and hasattr(x, "dtype") else x),
        (args, kwargs))

def _clear_trace_residue(tensors):
    """Drop autograd residue that closes over tracers after a trace."""
    for t in tensors:
        t._grad_node = None
        if t._grad is not None and isinstance(t._grad._value, jax.core.Tracer):
            t._grad = None


class CompiledFunction:
    """One to_static-compiled callable with a per-signature program cache."""

    def __init__(self, fn: Callable, static_key_fn: Optional[Callable] = None, donate_cells=True, name=None):
        self.fn = fn
        self.static_key_fn = static_key_fn
        self.donate_cells = donate_cells
        self.name = name or getattr(fn, "__name__", "fn")
        self._cache: Dict[Any, dict] = {}
        self.fallback_reason: Optional[str] = None
        self.last_entry: Optional[dict] = None
        # compiled-vs-eager accounting (VERDICT r3 #6): how often do steps
        # actually run compiled, and how often do branch guards miss?
        self.stats = {"compiled_steps": 0, "eager_steps": 0, "guard_misses": 0}
        # per-cache-key program-build counts, maintained at BUILD time only —
        # the hot __call__ path never touches this (audit is on-demand)
        self._compile_counts: Dict[Any, int] = {}

    def _cache_key(self, args, kwargs):
        # treedefs are hashable and compare structurally — keying on the
        # object skips a per-call str() of the whole tree structure
        treedef, sig = _tree_key((args, kwargs))
        extra = self.static_key_fn() if self.static_key_fn else None
        return (treedef, sig, extra)

    def __call__(self, *args, **kwargs):
        key = self._cache_key(args, kwargs)
        entry = self._cache.get(key)
        if entry is None:
            entry = self._build(key, args, kwargs)
        # memoized per key: memory_analysis only needs the last call's
        # abstract (shape, dtype) tree, which cannot change while the key
        # doesn't — steady-state steps skip the tree_map
        if key != getattr(self, "_last_key", None):
            self._last_call = _abstract_call(args, kwargs)
            self._last_key = key
        self.last_entry = entry
        if entry.get("eager"):
            self.stats["eager_steps"] += 1
            return self.fn(*args, **kwargs)
        if entry.get("guarded"):
            return self._run_guarded(key, entry, args, kwargs)
        return self._run(entry, args, kwargs)

    # ------------------------------------------------------------------ build
    def _discover(self, args, kwargs):
        """Eager side-effect-free run: collects state cells AND the concrete
        outcome of every tensor-bool branch taken for these inputs."""
        ctx = DiscoveryContext()
        arg_leaves = [
            l
            for l in jax.tree_util.tree_leaves(
                (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)
            )
            if isinstance(l, Tensor)
        ]
        ctx.arg_ids = {id(l) for l in arg_leaves}
        recorder = _BranchRecorder()
        prev = hooks.discovery
        prev_branch = hooks.branch_trace
        hooks.discovery = ctx
        hooks.branch_trace = recorder
        try:
            self.fn(*args, **kwargs)
        finally:
            hooks.discovery = prev
            hooks.branch_trace = prev_branch
            ctx.rollback()
        return ctx, tuple(recorder.outcomes)

    def _build(self, key, args, kwargs):
        import time

        t0 = time.perf_counter()
        try:
            ctx, outcomes = self._discover(args, kwargs)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            # The eager discovery run holds every intermediate live at the
            # full batch shape. The cell SET does not depend on the batch
            # size, so retry discovery on a batch-1 probe slice; the jit
            # below still traces/compiles at the real shape, where XLA
            # schedules within HBM.
            get_logger().warning(
                "discovery OOM for %s at full shape; retrying with batch-1 probe",
                self.name,
            )
            import gc

            gc.collect()
            probe_args, probe_kwargs = jax.tree_util.tree_map(
                lambda l: (
                    Tensor(l._value[:1], stop_gradient=l.stop_gradient)
                    if isinstance(l, Tensor) and l.ndim >= 1 and l.shape[0] > 1
                    else l
                ),
                (args, kwargs),
                is_leaf=lambda x: isinstance(x, Tensor),
            )
            ctx, outcomes = self._discover(probe_args, probe_kwargs)

        if outcomes:
            family = {"guarded": True, "entries": {}, "last": outcomes,
                      "eager": False, "key": key,
                      "abstract_call": _abstract_call(args, kwargs)}
            self._cache[key] = family
            self._specialize(family, outcomes, ctx)
            return family

        entry = self._make_entry(ctx, guards=None)
        entry["abstract_call"] = _abstract_call(args, kwargs)
        self._cache[key] = entry
        self._compile_counts[key] = self._compile_counts.get(key, 0) + 1
        _record_build(self.name, t0)
        self._maybe_runtime_audit(entry)
        return entry

    def _maybe_runtime_audit(self, entry):
        """FLAGS_jaxpr_audit_runtime: audit + cost each program at BUILD
        time (cache misses only — steady-state replay never pays this),
        logging through base.log so arbitrary user CompiledFunctions get
        the analysis tier without on-demand calls. Only the just-built
        entry is retraced (plus the cheap cache-shape heuristics) — a
        ladder of N builds pays N retrace audits, not N²."""
        from ..base.flags import get_flag

        try:
            if not get_flag("jaxpr_audit_runtime"):
                return
        except Exception:
            return
        log = get_logger()
        try:
            from ..analysis.cost_model import cost_jaxpr
            from ..analysis.jaxpr_audit import (audit_compiled_function,
                                                retrace_entry)

            for f in audit_compiled_function(self, only_entry=entry):
                log.warning("jaxpr_audit[%s]: %s", self.name, f)
            closed, _n_user, _n_cells = retrace_entry(entry)
            rep = cost_jaxpr(closed, location=self.name)
            log.info(
                "cost[%s]: flops=%.3e bytes=%.3e peak=%.1f MiB "
                "intensity=%.3f",
                self.name, rep.flops, rep.bytes_read + rep.bytes_written,
                rep.peak_bytes / 2**20, rep.arithmetic_intensity)
        except Exception as e:  # a debug aid must never sink the build
            log.warning("jaxpr_audit_runtime failed for %s: %s", self.name, e)

    def _make_entry(self, ctx, guards):
        ctx.prune_tracer_cells()
        cells: List[Tensor] = list(ctx.cells.values())
        fn = self.fn

        def pure(cell_vals, a, kw):
            saved = [c._value for c in cells]
            for c, v in zip(cells, cell_vals):
                c._value = v
            replayer = _BranchReplayer(guards) if guards is not None else None
            prev_branch = hooks.branch_trace
            if replayer is not None:
                hooks.branch_trace = replayer
            try:
                out = fn(*a, **kw)
                new_vals = [c._value for c in cells]
            finally:
                hooks.branch_trace = prev_branch
                for c, v in zip(cells, saved):
                    c._value = v
                _clear_trace_residue(cells)
            # Tensors are pytree nodes: jit flattens/reconstructs the output
            # structure itself (fresh Tensor wrappers around result arrays)
            if replayer is not None:
                return out, new_vals, replayer.preds
            return out, new_vals

        # guarded programs never donate: a guard miss must re-run the actual
        # specialization on the ORIGINAL cell values
        donate = (0,) if (self.donate_cells and guards is None) else ()
        jitted = jax.jit(pure, donate_argnums=donate)
        return {"cells": cells, "jitted": jitted, "pure": pure, "eager": False,
                "compiled_once": False, "guards": guards}

    def _specialize(self, family, outcomes, ctx=None, args=None, kwargs=None):
        import time

        t0 = time.perf_counter()
        if ctx is None:
            ctx, outcomes = self._discover(args, kwargs)  # path actually taken
        if outcomes not in family["entries"]:
            entry = self._make_entry(ctx, guards=outcomes)
            entry["abstract_call"] = (
                _abstract_call(args, kwargs) if args is not None or kwargs
                else family.get("abstract_call"))
            family["entries"][outcomes] = entry
            key = family.get("key")
            self._compile_counts[key] = self._compile_counts.get(key, 0) + 1
            _record_build(self.name, t0)
            self._maybe_runtime_audit(entry)  # guard-miss builds too
        family["last"] = outcomes
        return outcomes

    # ------------------------------------------------------------------ run
    def _run_guarded(self, key, family, args, kwargs):
        """Speculative execution against the last-seen branch signature:
        the compiled program returns its predicate values; a mismatch
        re-runs the right specialization (cells not donated → originals
        intact). Unseen signatures build a new specialization from a fresh
        side-effect-free discovery — no committed eager steps."""
        guard = family["last"]
        entry = family["entries"][guard]
        try:
            out, ok = self._exec_entry(entry, args, kwargs)
        except _BranchMismatch as e:
            family["eager"] = True
            self.fallback_reason = str(e)
            get_logger().warning("to_static fallback to eager for %s: %s",
                                 self.name, self.fallback_reason)
            self.stats["eager_steps"] += 1
            return self.fn(*args, **kwargs)
        if ok:
            self.stats["compiled_steps"] += 1
            return out
        self.stats["guard_misses"] += 1
        actual = self._specialize(family, None, args=args, kwargs=kwargs)
        entry = family["entries"][actual]
        out, ok = self._exec_entry(entry, args, kwargs)
        if not ok:
            # predicates depend on state mutated between runs in a way the
            # guard can't pin — degrade honestly
            family["eager"] = True
            self.fallback_reason = "branch guard unstable across re-run"
            self.stats["eager_steps"] += 1
            return self.fn(*args, **kwargs)
        self.stats["compiled_steps"] += 1
        return out

    def _exec_entry(self, entry, args, kwargs):
        """Run one guarded specialization; commit writes only when the
        observed predicates match the speculated signature."""
        cells = entry["cells"]
        cell_vals = [c._value for c in cells]
        out_vals, new_vals, preds = entry["jitted"](cell_vals, args, kwargs)
        observed = tuple(bool(np.asarray(p)) for p in preds)
        if observed != entry["guards"]:
            return None, False
        entry["compiled_once"] = True
        for c, v in zip(cells, new_vals):
            c._value = v
            c._version += 1
        return out_vals, True

    def memory_analysis(self):
        """Compiled-memory report of the last-run program (XLA
        memory_analysis) — the ground truth the planner's HBM estimates
        calibrate against (VERDICT r3 #9). None when the last call ran
        eagerly or nothing has run yet."""
        entry = self.last_entry
        if not entry or entry.get("eager"):
            return None
        if entry.get("guarded"):
            # unwrap to the active specialization; compiled_once lives there,
            # not on the family dict
            entry = entry["entries"][entry["last"]]
        if not entry.get("compiled_once"):
            return None
        last = getattr(self, "_last_call", None)
        if last is None:
            return None
        args, kwargs = last
        cells = entry["cells"]
        cell_vals = [c._value for c in cells]
        return entry["jitted"].lower(cell_vals, args, kwargs).compile(
        ).memory_analysis()

    def _run(self, entry, args, kwargs):
        cells = entry["cells"]
        cell_vals = [c._value for c in cells]
        if self.donate_cells:
            # donated buffers must be unique and must not alias non-donated
            # args (jax caches small constants, so fresh zeros can share one
            # buffer); copy aliased values
            arg_ids = {
                id(l._value)
                for l in jax.tree_util.tree_leaves(
                    (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)
                )
                if isinstance(l, Tensor)
            }
            seen = set(arg_ids)
            for i, v in enumerate(cell_vals):
                if id(v) in seen:
                    cell_vals[i] = jnp.array(v)
                else:
                    seen.add(id(v))
        try:
            out_vals, new_vals = entry["jitted"](cell_vals, args, kwargs)
        except (
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerBoolConversionError,
        ) as e:  # data-dependent value use the guards can't see -> eager
            entry["eager"] = True
            self.fallback_reason = str(e).split("\n")[0]
            get_logger().warning("to_static fallback to eager for %s: %s", self.name, self.fallback_reason)
            self.stats["eager_steps"] += 1
            return self.fn(*args, **kwargs)
        entry["compiled_once"] = True
        self.stats["compiled_steps"] += 1
        for c, v in zip(cells, new_vals):
            c._value = v
            c._version += 1
        return out_vals

    # ------------------------------------------------------------------ audit
    def audit_report(self) -> dict:
        """Per-cache-key program-build counts + run accounting. Pure reads
        of counters maintained at build time — never triggers discovery,
        tracing, or compilation (ISSUE 2 acceptance)."""
        keys = []
        for key, entry in self._cache.items():
            row = {
                "key": repr(key),
                "builds": self._compile_counts.get(key, 0),
                "eager": bool(entry.get("eager")),
                "guarded": bool(entry.get("guarded")),
            }
            if entry.get("guarded"):
                row["specializations"] = len(entry["entries"])
            keys.append(row)
        return {
            "name": self.name,
            "n_cache_keys": len(self._cache),
            "total_builds": sum(self._compile_counts.values()),
            "keys": keys,
            "stats": dict(self.stats),
            "fallback_reason": self.fallback_reason,
        }

    def audit(self, max_cache_keys=None):
        """Static audit of every cached program's jaxpr plus the
        recompilation heuristics; returns ``analysis.Finding`` objects
        (JX3xx). Retraces via ``jax.make_jaxpr`` — no XLA compilation."""
        from ..analysis.jaxpr_audit import audit_compiled_function

        return audit_compiled_function(self, max_cache_keys=max_cache_keys)

    def cost(self):
        """Static cost model of every cached program (FLOPs / bytes /
        collective volume / liveness peak residency): a
        ``analysis.cost_model.CostReport`` for the costliest entry, with
        the per-entry breakdown under ``.per_entry``. Same retrace
        machinery as ``audit()`` — tracing only, never compiles, never
        touches the hot ``__call__`` path."""
        from ..analysis.cost_model import cost_compiled_function

        return cost_compiled_function(self)


def functionalize(fn=None, *, static_key_fn=None, donate_cells=True, name=None):
    if fn is None:
        return functools.partial(functionalize, static_key_fn=static_key_fn, donate_cells=donate_cells, name=name)
    return CompiledFunction(fn, static_key_fn=static_key_fn, donate_cells=donate_cells, name=name)
