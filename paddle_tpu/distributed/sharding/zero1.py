"""ZeRO-1 cross-replica sharded optimizer states and weight update.

Per PAPERS "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (arxiv 2004.13336): in data-parallel training
every replica holds the full optimizer state and repeats the identical
weight update — the states are the largest redundant allocation in the
step. The zero1 strategy shards them across the dp (or dedicated
``sharding``) mesh axis:

1. **reduce-scatter(grads)** — each flattened gradient is padded to
   ``axis_size · block`` granularity and constrained onto the axis, so
   GSPMD lowers the dp partial-sum directly to a reduce-scatter (or
   all-reduce + slice on backends without one — same numerics);
2. **per-shard update** — every replica owns one contiguous
   ``1/axis_size`` slice of the flattened param/moment space; the
   optimizer's own ``_apply_one`` rule runs on flat *shard-space*
   proxies, so every optimizer (SGD/Adam/AdamW/Lamb/...) shards without
   a rewritten update rule, and the moments/master cells persist as
   genuinely sharded arrays (~``1/axis_size`` bytes per device);
3. **all-gather(updated weights)** — the updated shard gathers back to
   the replicated parameter; optionally as int8 blocks + fp32 scales
   (the same blockwise-scale wire math as ``collective_opt.qpsum``'s
   gather half), in which case a persistent fp32 **master shard** keeps
   exact updates (int8 weights would otherwise swallow sub-quantum
   steps in the rounding dead zone).

Engagement (all three key the TrainStep compile cache, so flips
retrace instead of replaying the other tier's program):

- ``group_sharded_parallel(level="os"|"os_g")`` attaches the strategy;
- ``FLAGS_sharding_stage="zero1"`` engages it process-wide;
- ``TrainStep(sharding="zero1")`` / ``sharding="replicated"`` overrides
  both per step program.

The quantized gather tier rides the comm engagement policy
(``FLAGS_comm_quantize_dp_grads`` / ``amp.auto_cast(comm_dtype="int8")``).

Pure accounting (:func:`plan_shards`, :func:`zero1_wire_report`,
:func:`opt_state_report`) is shared by the planner's step-cost pricing
and the QZ804/QZ805 lint gates.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

__all__ = [
    "ShardRow", "plan_shards", "step_spec", "ensure_strategy", "attached",
    "Zero1Strategy", "zero1_wire_report", "opt_state_report",
    "save_sharded_optimizer_state", "load_sharded_optimizer_state",
]


def _flag(name, default):
    try:
        from ...base.flags import get_flag

        return get_flag(name)
    except Exception:
        return default


def _block() -> int:
    return max(int(_flag("comm_quantize_block", 256)), 8)


# ------------------------------------------------------------------ planning
@dataclasses.dataclass
class ShardRow:
    """Shard-space layout of one tensor: flattened, padded to
    ``axis_size · shard_elems`` so each replica owns one contiguous,
    block-aligned slice. ``sharded`` is False when sharding would not
    shrink the per-replica bytes (tiny tensors: one padded block per
    shard would exceed the whole tensor) — those stay on the replicated
    update path."""

    name: str
    numel: int
    itemsize: int = 4
    axis_size: int = 1
    block: int = 256
    sharded: bool = False
    shard_elems: int = 0       # per-replica elements (cb · block)
    padded: int = 0            # axis_size · shard_elems

    @property
    def pad_per_shard(self) -> float:
        """Average padding elements carried per replica shard."""
        return (self.padded - self.numel) / max(self.axis_size, 1)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["pad_per_shard"] = self.pad_per_shard
        return d


def plan_row(name: str, numel: int, itemsize: int, axis_size: int,
             block: Optional[int] = None) -> ShardRow:
    block = block or _block()
    n = max(int(axis_size), 1)
    cb = max(int(math.ceil(numel / float(n * block))), 1)
    shard = cb * block
    # shard only when the per-replica slice is strictly smaller than the
    # whole tensor — otherwise block padding would *grow* per-replica
    # state (QZ805's invariant)
    if n <= 1 or shard >= numel:
        return ShardRow(name, int(numel), int(itemsize), n, block)
    return ShardRow(name, int(numel), int(itemsize), n, block,
                    sharded=True, shard_elems=shard, padded=n * shard)


def plan_shards(specs, axis_size: int,
                block: Optional[int] = None) -> List[ShardRow]:
    """Shard-space plan over ``(name, numel, itemsize)`` specs — pure
    arithmetic, shared by the strategy, the planner pricing, the QZ805
    audit and the bench."""
    return [plan_row(name, numel, itemsize, axis_size, block)
            for name, numel, itemsize in specs]


def zero1_wire_report(specs, axis_size: int, quantize: bool = False,
                      block: Optional[int] = None) -> dict:
    """Per-device wire bytes of one zero1 step over ``(name, numel,
    itemsize)`` specs: the reduce-scatter half (always fp32) plus the
    all-gather half (fp32, or int8 blocks + one fp32 scale per block
    when ``quantize``), against the replicated baseline's all-reduce
    ring (``2(n-1)/n · bytes``). Tensors the plan leaves replicated
    keep their all-reduce cost on both sides."""
    block = block or _block()
    n = max(int(axis_size), 1)
    ring = (n - 1) / n if n > 1 else 0.0
    rs = ag = baseline = 0.0
    n_sharded = 0
    for row in plan_shards(specs, n, block):
        dense = row.numel * row.itemsize
        baseline += 2.0 * ring * dense
        if not row.sharded:
            rs += 2.0 * ring * dense  # stays a plain all-reduce
            continue
        n_sharded += 1
        padded_bytes = row.padded * row.itemsize
        rs += ring * padded_bytes
        if quantize:
            ag += ring * (row.padded * 1 + (row.padded // row.block) * 4)
        else:
            ag += ring * padded_bytes
    return {
        "reduce_scatter_bytes": rs,
        "all_gather_bytes": ag,
        "wire_bytes": rs + ag,
        "allreduce_bytes": baseline,
        "n_sharded": n_sharded,
        "axis_size": n,
        "block": block,
        "quantized_gather": bool(quantize),
    }


# --------------------------------------------------------------- engagement
def step_spec(optimizer, explicit: object = "__unset__"):
    """``(mesh, axis, axis_size)`` when the zero1 sharded update should
    engage for this optimizer's next step, else ``None``. Resolution
    order: explicit per-step override (``TrainStep(sharding=...)`` via
    ``optimizer._sharding_override``) > ``FLAGS_sharding_stage`` >
    a strategy attached by ``group_sharded_parallel``. A mesh must
    already be installed (never built as a side effect of a step) and
    the axis must be real (size > 1)."""
    if explicit == "__unset__":
        explicit = getattr(optimizer, "_sharding_override", None)
    if explicit == "replicated":
        return None
    requested = explicit == "zero1"
    if not requested:
        requested = _flag("sharding_stage", "") == "zero1"
    if not requested:
        st = getattr(optimizer, "_zero1_strategy", None)
        requested = st is not None and st.requested
    if not requested:
        return None
    from .. import env as env_mod

    inst = env_mod.instance()
    mesh = inst.mesh
    if mesh is None:
        return None
    axis = "sharding" if inst.axis_degrees.get("sharding", 1) > 1 else "dp"
    n = int(dict(mesh.shape).get(axis, 1))
    if n <= 1:
        return None
    return mesh, axis, n


def attached(optimizer) -> Optional["Zero1Strategy"]:
    return getattr(optimizer, "_zero1_strategy", None)


def ensure_strategy(optimizer, requested: bool = False) -> "Zero1Strategy":
    """The optimizer's strategy, attached on first use. ``requested``
    marks a deliberate ``group_sharded_parallel`` opt-in (sticky
    engagement); lazily attached strategies engage only while the flag
    or an explicit override asks."""
    st = getattr(optimizer, "_zero1_strategy", None)
    if st is None:
        st = Zero1Strategy(optimizer, requested=requested)
        optimizer._zero1_strategy = st
    elif requested:
        st.requested = True
    return st


# ---------------------------------------------------------------- telemetry
def _tick(name: str, value: float = 1.0, **labels):
    try:
        from ...observability import registry

        registry.counter("comm." + name).inc(value, **labels)
    except Exception:
        pass


# ----------------------------------------------------------------- strategy
class _ShardView:
    """Set lazily to the no-discovery-hook Parameter subclass (avoids a
    module-import cycle with core.tensor)."""


def _shard_view_cls():
    from ...core.tensor import Parameter

    global _ShardView
    if isinstance(_ShardView, type) and issubclass(_ShardView, Parameter):
        return _ShardView

    class ShardView(Parameter):
        """Flat shard-space view of one parameter. Its value is DERIVED
        from the live parameter every step (or aliases the master
        shard), so writes bypass the jit discovery hook — the view must
        not be captured as a state cell of the compiled step."""

        __slots__ = ()

        def _replace_value(self, new_value):
            self._value = new_value

    _ShardView = ShardView
    return ShardView


class Zero1Strategy:
    """Per-optimizer zero1 state: shard plans, shard-space proxies, the
    optional fp32 master shards, and the in-trace update. One strategy
    serves both the eager path (``optimizer.step()``) and the compiled
    ``TrainStep`` program (the same python runs under discovery and
    trace — exactly like the rest of the framework)."""

    def __init__(self, optimizer, requested: bool = False):
        self.optimizer = optimizer
        self.requested = bool(requested)
        self._rows: Dict[int, ShardRow] = {}
        self._proxies: Dict[int, object] = {}
        self._grad_views: Dict[int, object] = {}
        self._masters: Dict[int, object] = {}
        self._acc_wrapped = False

    # ------------------------------------------------------------- layout
    def row(self, p, axis_size: int) -> ShardRow:
        key = id(p)
        row = self._rows.get(key)
        if row is None or row.axis_size != axis_size:
            import numpy as np

            numel = int(np.prod(p._value.shape)) if p._value.shape else 1
            # moments/master update in fp32 regardless of param dtype
            row = plan_row(p.name, numel, 4, axis_size)
            self._rows[key] = row
        return row

    def proxy_for(self, p, row: Optional[ShardRow] = None):
        """The persistent flat shard-space Parameter proxy for ``p`` —
        accumulators are keyed on its id, so it must live as long as
        the strategy."""
        view = self._proxies.get(id(p))
        if view is None:
            import jax.numpy as jnp

            cls = _shard_view_cls()
            view = cls(jnp.zeros((), jnp.float32), name=p.name)
            view.optimize_attr = p.optimize_attr
            view.regularizer = getattr(p, "regularizer", None)
            view.stop_gradient = True
            self._proxies[id(p)] = view
        return view

    def _grad_view(self, p):
        g = self._grad_views.get(id(p))
        if g is None:
            from ...core.tensor import Tensor

            g = Tensor(0.0, stop_gradient=True, name=f"{p.name}_zero1_grad")
            self._grad_views[id(p)] = g
        return g

    def _wrap_accumulators(self, placement):
        """Fresh accumulators created against a shard-space proxy are
        placed sharded from birth (eager path + discovery run), so the
        per-replica bytes drop from the first step — donated through
        the compiled program, they then stay sharded."""
        self._placement = placement
        if self._acc_wrapped:
            return
        self._acc_wrapped = True
        opt = self.optimizer
        orig = opt._get_accumulator
        proxies = self._proxies

        def sharded_get_accumulator(name, param, fill=0.0, dtype=None):
            import jax

            store = opt._accumulators[name]
            fresh = id(param) not in store
            acc = orig(name, param, fill, dtype)
            if (fresh and any(v is param for v in proxies.values())
                    and not isinstance(acc._value, jax.core.Tracer)):
                acc._value = jax.device_put(acc._value, self._placement)
            return acc

        opt._get_accumulator = sharded_get_accumulator

    def prime_proxy(self, p, spec):
        """The cell owner accumulator *priming* should target for ``p``
        (``Optimizer._prime_accumulators`` before the first step — the
        GradScaler snapshot path): the shard-space proxy, pre-shaped to
        its flat padded layout and placed sharded, so primed cells are
        born with the shapes and placement the sharded update will use.
        Unsharded rows prime against the param itself."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, axis, n = spec
        row = self.row(p, n)
        if not row.sharded:
            return p
        placement = NamedSharding(mesh, P(axis))
        self._wrap_accumulators(placement)
        proxy = self.proxy_for(p, row)
        if tuple(proxy._value.shape) != (row.padded,):
            proxy._value = jax.device_put(
                jnp.zeros((row.padded,), jnp.float32), placement)
        return proxy

    def master_for(self, p, row: ShardRow, placement):
        """The persistent fp32 master shard backing the int8 gather
        tier: exact updates accumulate here; the gathered int8 weights
        are only the forward-pass representation."""
        m = self._masters.get(id(p))
        if m is None:
            import jax
            import jax.numpy as jnp

            from ...core.tensor import Tensor

            flat = jnp.pad(jnp.ravel(p._value).astype(jnp.float32),
                           (0, row.padded - row.numel))
            flat = jax.lax.with_sharding_constraint(flat, placement)
            m = Tensor(flat, stop_gradient=True,
                       name=f"{p.name}_zero1_master")
            self._masters[id(p)] = m
        return m

    # ------------------------------------------------------------- update
    def apply_one(self, opt, p, g, lr, weight_decay, spec):
        """One parameter's sharded update: reduce-scatter the grad,
        run ``opt._apply_one`` in flat shard space, all-gather the
        updated weights (optionally int8-quantized). Falls back to the
        replicated rule for tensors the plan leaves unsharded."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, axis, n = spec
        row = self.row(p, n)
        if not row.sharded:
            opt._apply_one(p, g, lr, weight_decay)
            return
        from .. import collective_opt as copt

        gather_dtype = copt.engaged_comm_dtype() or "fp32"
        shard_sp = NamedSharding(mesh, P(axis))
        rep_sp = NamedSharding(mesh, P())
        pad = row.padded - row.numel

        # 1. reduce-scatter: the dp-partial grad, flattened + padded,
        # constrained onto the axis — GSPMD emits the reduce-scatter
        gv = jnp.pad(jnp.ravel(g._value).astype(jnp.float32), (0, pad))
        g_view = self._grad_view(p)
        g_view._value = jax.lax.with_sharding_constraint(gv, shard_sp)

        proxy = self.proxy_for(p, row)
        master = None
        if gather_dtype == "int8":
            master = self.master_for(p, row, shard_sp)
            proxy._value = master._value
        else:
            pv = jnp.pad(jnp.ravel(p._value).astype(jnp.float32), (0, pad))
            # replicated param -> owned slice: comm-free under GSPMD
            proxy._value = jax.lax.with_sharding_constraint(pv, shard_sp)

        # 2. the optimizer's own update rule, in flat shard space
        self._wrap_accumulators(shard_sp)
        opt._apply_one(proxy, g_view, lr, weight_decay)
        new_shard = jax.lax.with_sharding_constraint(proxy._value, shard_sp)
        for store in opt._accumulators.values():
            cell = store.get(id(proxy))
            if cell is not None and not isinstance(cell._value, (int, float)):
                cell._value = jax.lax.with_sharding_constraint(
                    cell._value, shard_sp)

        # 3. all-gather the updated weights back to replicated — the
        # int8 tier is qpsum's gather half verbatim: quantize the shard
        # blockwise, gather int8 blocks + fp32 scales, dequantize
        if master is not None:
            master._replace_value(new_shard)
            q, scales = copt.quantize_blockwise(new_shard, row.block)
            q = jax.lax.with_sharding_constraint(q, rep_sp)
            scales = jax.lax.with_sharding_constraint(scales, rep_sp)
            full = copt.dequantize_blockwise(q, scales)
            copt.note_wire_dtype(axis, "int8")
        else:
            full = jax.lax.with_sharding_constraint(new_shard, rep_sp)
        out = full[:row.numel].reshape(p._value.shape)
        p._replace_value(out.astype(p._value.dtype))
        # NaN/Inf + range sentinel on the gathered update (one bool read
        # when dark; inside the compiled TrainStep the value is a tracer
        # and the lit witness skips it — eager optimizer paths observe)
        from ...observability import numerics

        numerics.watch("zero1.update", p._value)

        _tick("zero1_params")
        ring = (n - 1) / n
        _tick("zero1_bytes_rs", ring * row.padded * 4)
        if master is not None:
            _tick("zero1_bytes_ag",
                  ring * (row.padded + row.padded // row.block * 4))
        else:
            _tick("zero1_bytes_ag", ring * row.padded * 4)

    # ----------------------------------------------------------- state map
    def cell_for(self, store: dict, p):
        """The accumulator cell for ``p`` inside one store: the
        shard-space proxy's cell when the sharded update owns one (it
        wins over a stale full-shape cell a pre-step priming pass may
        have left keyed on the param), else the param's own."""
        view = self._proxies.get(id(p))
        if view is not None:
            cell = store.get(id(view))
            if cell is not None:
                return cell
        return store.get(id(p))

    def extra_state_cells(self) -> list:
        return list(self._masters.values())

    def restore_masters(self, opt, state: dict) -> None:
        """Restore ``{p.name}_zero1_master`` entries from a plain
        state_dict (the counterpart of ``state_dict`` emitting them):
        into the existing master cell when one lives, else created
        fresh against the installed mesh. Without a mesh the entries
        are skipped with a warning — the next int8-gather step would
        rebuild masters from the dequantized weights, losing the
        accumulated sub-quantum residual."""
        import numpy as np

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        for p in opt._parameter_list:
            src = state.get(f"{p.name}_zero1_master")
            if src is None:
                continue
            arr = src.numpy() if hasattr(src, "numpy") else np.asarray(src)
            m = self._masters.get(id(p))
            if m is not None:
                m.set_value(arr)
                continue
            spec = step_spec(opt, explicit="zero1")
            if spec is None:
                from ...base.log import get_logger

                get_logger().warning(
                    "set_state_dict: dropping zero1 master shard for %r — "
                    "no installed mesh with a real dp/sharding axis to "
                    "re-scatter onto (dist.init_parallel_env first to keep "
                    "exact int8-gather updates)", p.name)
                continue
            mesh, axis, n = spec
            row = self.row(p, n)
            placement = NamedSharding(mesh, P(axis))
            m = self.master_for(p, row, placement)
            m._value = jax.device_put(arr.reshape(-1), placement)

    def shard_entries(self, optimizer) -> list:
        """Every sharded optimizer-state cell as ``(param_name,
        state_name, cell, row)`` — the unit the sharded checkpoint
        saves/loads."""
        out = []
        for p in optimizer._parameter_list:
            view = self._proxies.get(id(p))
            row = self._rows.get(id(p))
            if view is None or row is None or not row.sharded:
                continue
            for name, store in optimizer._accumulators.items():
                cell = store.get(id(view))
                if cell is not None:
                    out.append((p.name, name, cell, row))
            m = self._masters.get(id(p))
            if m is not None:
                out.append((p.name, "zero1_master", m, row))
        return out


# --------------------------------------------------------------- accounting
def _per_replica_bytes(value) -> int:
    """Max bytes any one replica holds for ``value`` (its shard for
    sharded arrays, everything for replicated/uncommitted ones). The
    shard fraction comes from the cost model's ``value_divisor`` — one
    implementation serves both the residency accounting here and the
    sharding-aware liveness walk."""
    from ...analysis.cost_model import value_divisor

    return int(round(int(getattr(value, "nbytes", 0))
                     / value_divisor(value)))


def opt_state_report(optimizer) -> dict:
    """Measured optimizer-state residency: for every accumulator / aux /
    master cell, the bytes one replica actually holds (via the array's
    committed sharding) vs the bytes the replicated layout would hold.
    ``ratio`` is the headline the bench trends
    (``zero1.opt_state_bytes_ratio``)."""
    st = attached(optimizer)
    rows = []

    def add(key, cell, logical_bytes=None):
        v = cell._value
        per = _per_replica_bytes(v)
        logical = int(logical_bytes if logical_bytes is not None
                      else getattr(v, "nbytes", 0))
        rows.append({"key": key, "logical_bytes": logical,
                     "per_replica_bytes": per,
                     "sharded": per < int(getattr(v, "nbytes", 0))})

    seen = set()
    for name, store in optimizer._accumulators.items():
        for p in optimizer._parameter_list:
            cell, row = None, None
            if st is not None:
                view = st._proxies.get(id(p))
                if view is not None:
                    cell = store.get(id(view))
                    row = st._rows.get(id(p))
            if cell is None:
                cell, row = store.get(id(p)), None
            if cell is None or id(cell) in seen:
                continue
            seen.add(id(cell))
            # replicated-layout baseline: one fp32 moment per param
            # element (the proxy cell's padded length overstates it)
            logical = (row.numel * 4) if row is not None else None
            add(f"{p.name}_{name}", cell, logical)
    if st is not None:
        for m in st._masters.values():
            if id(m) not in seen:
                seen.add(id(m))
                # masters have no replicated counterpart: pure overhead
                # of the int8 gather tier
                add(m.name, m, 0)
    replicated = sum(r["logical_bytes"] for r in rows)
    per_replica = sum(r["per_replica_bytes"] for r in rows)
    return {
        "rows": rows,
        "replicated_bytes": int(replicated),
        "per_replica_bytes": int(per_replica),
        "ratio": (replicated / per_replica) if per_replica else 1.0,
        "n_cells": len(rows),
    }


# ------------------------------------------------------------- checkpointing
_SHARD_FORMAT = "zero1-shard-v1"


def _host_key_map(optimizer) -> dict:
    """state_dict key -> position-stable key for the host-side save
    (``{p.name}_{accum}`` embeds the instance's auto-generated tensor
    names; ``__param{i}__:{accum}`` survives a fresh twin)."""
    out = {}
    for i, p in enumerate(optimizer._parameter_list):
        for name in optimizer._accum_names:
            out[f"{p.name}_{name}"] = f"__param{i}__:{name}"
    return out


def _shard_pieces(value):
    """This process's addressable ``(offset, numpy)`` pieces of one flat
    sharded array, deduplicated (replication over other mesh axes aside,
    each offset appears once)."""
    import numpy as np

    pieces = {}
    for s in value.addressable_shards:
        idx = s.index[0] if s.index else slice(None)
        off = int(idx.start or 0) if isinstance(idx, slice) else 0
        if off not in pieces:
            pieces[off] = np.asarray(s.data)
    return sorted(pieces.items())


def save_sharded_optimizer_state(optimizer, path_prefix: str) -> dict:
    """Write the zero1 optimizer state as ``{path}.pdopt`` (host-side
    state: step counter, aux cells, LR scheduler, unsharded
    accumulators) plus ``{path}.pdopt.shard{rank}of{world}`` holding
    ONLY this process's addressable shard pieces — no full-tensor
    gather, O(shard) host memory. Returns the shard manifest."""
    from ...framework.io import save
    from .. import env as env_mod

    st = attached(optimizer)
    entries = st.shard_entries(optimizer) if st is not None else []
    sharded_cells = {id(c) for _, _, c, _ in entries}

    # host-side remainder keyed by param POSITION (auto-generated tensor
    # names differ between model instances; positions don't)
    key_map = _host_key_map(optimizer)
    host_state = {}
    for key, val in optimizer.state_dict().items():
        if not (hasattr(val, "_value") and id(val) in sharded_cells):
            host_state[key_map.get(key, key)] = val
    save(host_state, path_prefix + ".pdopt")

    rank = env_mod.get_rank()
    world = max(env_mod.get_world_size(), 1)
    manifest = {"format": _SHARD_FORMAT, "rank": int(rank),
                "world": int(world), "entries": []}
    # entries key on the param's POSITION in _parameter_list: auto-
    # generated tensor names differ between model instances, positions
    # don't (the name is kept for diagnostics)
    index_of = {p.name: i
                for i, p in enumerate(optimizer._parameter_list)}
    for pname, sname, cell, row in entries:
        manifest["entries"].append({
            "param": pname, "param_index": index_of.get(pname, -1),
            "state": sname,
            "numel": row.numel, "padded": row.padded,
            "shard_elems": row.shard_elems, "axis_size": row.axis_size,
            "dtype": str(cell._value.dtype),
            "pieces": _shard_pieces(cell._value),
        })
    save(manifest, f"{path_prefix}.pdopt.shard{rank}of{world}")
    return manifest


def _reslice_piece(by_off: dict, start: int, length: int, entry: dict,
                   pname: str, sname: str):
    """One target shard slice ``[start, start+length)`` of the flat
    padded space, assembled from saved pieces of a DIFFERENT layout.
    Copies only the overlapping ranges (O(shard) residency — the full
    tensor never materializes); target elements past the old padded span
    are new-layout shard padding and stay zero. Real data
    (``[0, numel)``) must be fully covered by saved pieces — a gap there
    is an incomplete shard-file set and fails loudly."""
    import numpy as np

    sample = next(iter(by_off.values()))
    out = np.zeros(length, dtype=sample.dtype)
    end = start + length
    covered = np.zeros(length, dtype=bool)
    for off, arr in by_off.items():
        lo = max(start, int(off))
        hi = min(end, int(off) + arr.shape[0])
        if lo >= hi:
            continue
        out[lo - start: hi - start] = arr[lo - off: hi - off]
        covered[lo - start: hi - start] = True
    real_end = min(end, int(entry["numel"]))
    if real_end > start and not covered[: real_end - start].all():
        raise ValueError(
            f"sharded state {pname}/{sname}: saved pieces "
            f"(axis_size={entry['axis_size']}) do not cover "
            f"[{start}, {real_end}) of the flat value — shard file set "
            "incomplete; cannot re-slice onto the new topology")
    return out


def load_sharded_optimizer_state(optimizer, path_prefix: str) -> int:
    """Round-trip of :func:`save_sharded_optimizer_state`: host state
    restores through ``set_state_dict``; each shard file re-scatters its
    pieces straight to the owning devices (``device_put`` per piece +
    ``make_array_from_single_device_arrays`` — the full tensor never
    materializes on host). A checkpoint saved under a DIFFERENT dp/
    sharding degree (dp=8 pieces onto dp=4 and vice versa) re-slices the
    pieces onto the new shard grid at load (:func:`_reslice_piece`)
    instead of rejecting the layout. Returns the number of sharded cells
    restored."""
    import glob
    import os

    import numpy as np

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ...core.tensor import Tensor
    from ...framework.io import load
    from .. import env as env_mod

    host_state = load(path_prefix + ".pdopt")
    inverse = {v: k for k, v in _host_key_map(optimizer).items()}
    optimizer.set_state_dict(
        {inverse.get(k, k): v for k, v in host_state.items()})
    shard_files = sorted(glob.glob(path_prefix + ".pdopt.shard*of*"))
    if not shard_files:
        return 0
    spec = step_spec(optimizer, explicit="zero1")
    if spec is None:
        raise RuntimeError(
            "load_sharded_optimizer_state needs an installed mesh with a "
            "real dp/sharding axis to re-scatter onto "
            "(dist.init_parallel_env first)")
    mesh, axis, n = spec
    st = ensure_strategy(optimizer)
    sharding = NamedSharding(mesh, P(axis))
    params = list(optimizer._parameter_list)

    # merge pieces across every shard file this process can read (single
    # host: all of them; multi-host: at least its own rank's)
    merged: Dict[tuple, dict] = {}
    for f in shard_files:
        manifest = load(f, return_numpy=True)
        if manifest.get("format") != _SHARD_FORMAT:
            raise ValueError(f"{os.path.basename(f)}: not a "
                             f"{_SHARD_FORMAT} shard file")
        for e in manifest["entries"]:
            key = (e.get("param_index", -1), e["state"])
            row = merged.setdefault(key, dict(e, pieces=[]))
            row["pieces"].extend(e["pieces"])

    restored = 0
    for (pidx, sname), e in merged.items():
        p = params[pidx] if 0 <= pidx < len(params) else None
        if p is None:
            continue
        pname = p.name
        row = st.row(p, n)
        resliced = e["padded"] != row.padded or e["axis_size"] != n
        if resliced:
            # CHANGED topology (e.g. a dp=8 checkpoint onto dp=4): the
            # logical flat value is identical, only the shard grid moved —
            # re-slice the saved pieces onto the new offsets instead of
            # rejecting the layout. O(shard) per target slice: each new
            # piece copies only the old-piece ranges overlapping it
            # (regions past the old padded span are shard padding, zeros
            # by construction).
            from ...base.log import get_logger

            get_logger().info(
                "load_sharded_optimizer_state: re-slicing %s/%s from "
                "axis_size=%d (padded=%d) onto axis_size=%d (padded=%d)",
                pname, sname, e["axis_size"], e["padded"], n, row.padded)
        by_off = {off: np.asarray(arr) for off, arr in e["pieces"]}
        idx_map = sharding.addressable_devices_indices_map((row.padded,))
        arrays = []
        for dev, idx in idx_map.items():
            off = int(idx[0].start or 0)
            if resliced:
                piece = _reslice_piece(by_off, off, row.shard_elems, e,
                                       pname, sname)
            else:
                piece = by_off.get(off)
                if piece is None:
                    raise ValueError(
                        f"sharded state {pname}/{sname}: no saved piece "
                        f"for offset {off} — shard file set incomplete")
            arrays.append(jax.device_put(piece, dev))
        value = jax.make_array_from_single_device_arrays(
            (row.padded,), sharding, arrays)
        view = st.proxy_for(p, row)
        if sname == "zero1_master":
            m = st.master_for(p, row, sharding)
            m._value = value
        else:
            optimizer._accumulators[sname][id(view)] = Tensor(
                value, stop_gradient=True, name=f"{pname}_{sname}")
        restored += 1
    return restored
