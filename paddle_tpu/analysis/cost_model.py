"""Static jaxpr cost model (CM5xx): FLOPs / bytes / comm / peak residency.

The analysis tier up to PR 2 verifies that a compiled program is
*well-formed* (jaxpr_audit's JX3xx); this pass asks what it *costs*. A
single static walker over ClosedJaxprs — reusing ``jaxpr_audit``'s
retrace machinery (``jax.make_jaxpr`` over the entry's recorded ``pure``
wrapper; trace only, never compiles) — computes per-equation and
aggregate:

- **FLOPs** — 2·M·N·K for ``dot_general``, 2·out·cin·k for convolutions,
  one per output element for elementwise ops, one per input element for
  reductions; ``scan`` bodies multiply by trip count, ``cond`` branches
  take the max. The matmul share is tracked separately
  (``matmul_flops``) for the arithmetic-intensity check.
- **Bytes** — operand bytes read / result bytes written per equation
  (aval numel × itemsize), the denominators of arithmetic intensity.
- **Collective volume per mesh axis** — bytes moved by
  psum/all_gather/ppermute/... attributed to each named axis, at the
  axis-size-aware ring cost: 2(n−1)/n for the all-reduce family,
  (n−1)/n for the single-pass family, with n resolved from the
  enclosing shard_map ``mesh`` / pmap ``axis_size`` (or an explicit
  ``cost_jaxpr(axis_sizes=...)`` seed); an unresolvable axis keeps the
  historical 2×/1× static upper bound.
- **Peak residency** — a liveness walk: every SSA value is live from its
  defining equation to its last use, program arguments from entry to
  their last use (donation semantics), constants and outputs to the end.
  The running live-set maximum estimates the HBM high-water mark the way
  XLA's ``memory_analysis`` reports ``argument + temp`` — the planner's
  calibration target (scalar broadcasts/iota are treated as fused, not
  materialized, matching XLA's fusion behavior). The walk is
  **sharding-aware**: ``sharding_constraint`` equations record the
  per-device residency divisor their partition spec implies
  (conservatively propagated through elementwise chains — an output's
  divisor is the *minimum* across its non-scalar operands), and program
  arguments carry the divisors of the live cells they were retraced
  from (``cost_jaxpr(arg_divisors=...)``). This is what lets the
  liveness estimate show the ~1/dp optimizer-state drop of the zero1
  sharded weight update: the moment/master cells really are
  dp-sharded arrays, and the walk prices them at shard size the way
  XLA's ``memory_analysis`` does.

Everything lands in one :class:`CostReport`, exposed as
``CompiledFunction/BucketedFunction/TrainStep.cost()`` (per-entry
breakdown under ``.per_entry``) and per cached executable via
``core.kernel_cache.cost_stats()``. Two consumers:

1. the ``cost`` family of ``python -m tools.lint`` (:func:`check_cost`):

   CM500  cost retrace failed    a cache entry no longer retraces
   CM501  oversized intermediate one equation's result exceeds
                                 ``FLAGS_cost_max_intermediate_bytes``
   CM502  intensity cliff        a matmul-free program moving real bytes
                                 below ``FLAGS_cost_min_arith_intensity``
                                 flops/byte — memory-bound on TPU
   CM503  comm-bound program     estimated collective seconds on one mesh
                                 axis (volume / declared bandwidth model)
                                 exceed estimated compute seconds
   CM504  peak over HBM budget   liveness peak per device (under the
                                 active Plan's degrees) exceeds
                                 ``FLAGS_cost_hbm_budget_bytes``
   CM505  guard-predicate cost   a speculative branch family verifying
                                 more guard predicates per call than
                                 ``FLAGS_cost_max_guard_preds`` — each
                                 predicate is a device→host fetch every
                                 step (the overhead the max-branch
                                 accounting used to ignore)

2. the parallelism planner (``distributed/auto_parallel/planner.py``):
   jaxpr-backed ``estimate_per_device_bytes``/``estimate_step_cost``
   that prefer measured-from-jaxpr numbers over the closed-form
   transformer accounting, and ``compare_with_measured`` reporting all
   three (closed-form / cost-model / XLA memory_analysis).

The per-layer formulas ``hapi/dynamic_flops.py`` applies through its
forward-hook API live here too (:func:`linear_flops` et al., MAC
convention for parity with the reference's ``paddle.flops``) — one
accounting, two front ends.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from . import Finding

_ANALYZER = "cost"

# collectives, by ring-algorithm family. With the mesh axis size n
# resolved (shard_map's `mesh` param, pmap's `axis_size`, or an explicit
# cost_jaxpr(axis_sizes=...) override) the volume multiplier is the exact
# ring cost: all-reduce moves 2(n-1)/n of the buffer per device
# (reduce-scatter pass + all-gather pass), the single-pass family moves
# (n-1)/n, point-to-point permutes move the whole buffer once. When the
# axis size is unresolvable (a bare axis name with no enclosing mesh —
# sizes are a runtime property there) the historical static constants
# (2x all-reduce / 1x rest) remain the documented upper bound.
_ALLREDUCE_PRIMS = {"psum", "psum2", "pmean", "pmax", "pmin"}
_ONEPASS_PRIMS = {"all_gather", "all_gather_invariant", "all_to_all",
                  "psum_scatter", "reduce_scatter"}
_P2P_PRIMS = {"ppermute", "pshuffle"}
_COLLECTIVE_PRIMS = _ALLREDUCE_PRIMS | _ONEPASS_PRIMS | _P2P_PRIMS


def _ring_factor(name: str, axis_size) -> float:
    """Volume multiplier for one collective on one axis of ``axis_size``
    devices (None = unknown size → the static fallback constants)."""
    if name in _ALLREDUCE_PRIMS:
        if axis_size is None:
            return 2.0
        n = max(int(axis_size), 1)
        return 2.0 * (n - 1) / n
    if name in _ONEPASS_PRIMS:
        if axis_size is None:
            return 1.0
        n = max(int(axis_size), 1)
        return (n - 1) / n
    return 1.0  # point-to-point: the whole buffer crosses one link

# result-moving primitives XLA reliably fuses into their consumer when the
# operand is a scalar/empty: counting their full output as resident would
# systematically overshoot memory_analysis
_FUSED_EXPANSIONS = {"broadcast_in_dim", "iota"}

# primitives whose cost is pure data movement (flops = 0; bytes counted)
_MOVEMENT_PRIMS = {
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad", "rev",
    "gather", "scatter", "copy", "convert_element_type", "bitcast",
    "bitcast_convert_type", "iota", "stop_gradient", "device_put",
    "sharding_constraint", "split", "expand_dims",
}

_REDUCTIONS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "reduce_precision",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
}


# ---------------------------------------------------------------------------
# shared layer-level formulas (hapi/dynamic_flops.py delegates here).
# MAC convention (1 multiply-accumulate = 1 FLOP) for parity with the
# reference's paddle.flops; the jaxpr walker below uses the standard
# 2·MAC convention, matching ``benchmark/flops.py``'s step-FLOPs formulas.
# ---------------------------------------------------------------------------

def linear_flops(out_numel: int, in_features: int, has_bias: bool) -> int:
    """Dense layer: one MAC per (output element, input feature)."""
    return out_numel * in_features + (out_numel if has_bias else 0)


def conv_flops(out_numel: int, cin_per_group: int, kernel_numel: int,
               has_bias: bool) -> int:
    """Convolution: one MAC per (output element, in-channel, kernel tap)."""
    return out_numel * cin_per_group * kernel_numel + (
        out_numel if has_bias else 0)


def norm_flops(in_numel: int) -> int:
    """Normalization layers: ~2 passes (stats + affine)."""
    return 2 * in_numel


def activation_flops(out_numel: int) -> int:
    return out_numel


def pool_flops(out_numel: int, kernel_numel: int) -> int:
    return out_numel * kernel_numel


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CostReport:
    """Aggregate static cost of one program (or one CompiledFunction's
    costliest cached program, with ``per_entry`` holding every entry)."""

    flops: float = 0.0
    matmul_flops: float = 0.0          # dot/conv share of `flops`
    bytes_read: float = 0.0
    bytes_written: float = 0.0
    comm_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    peak_bytes: int = 0                # liveness high-water mark
    arg_bytes: int = 0                 # program inputs (cells + batch)
    out_bytes: int = 0
    largest_intermediate_bytes: int = 0
    largest_intermediate_prim: str = ""
    # speculative branch families (jit/functionalize guarded entries):
    # every call returns `guard_preds` predicate values that the caller
    # fetches device→host to verify its speculation — a per-call sync the
    # max-branch accounting used to ignore. Set by cost_compiled_function.
    guard_preds: int = 0
    guard_sync_bytes: int = 0
    n_eqns: int = 0
    by_primitive: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    location: str = ""
    # set by cost_compiled_function:
    per_entry: Optional[Dict[str, "CostReport"]] = None
    retrace_errors: List[str] = dataclasses.field(default_factory=list)
    analysis_seconds: float = 0.0

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per byte moved — the roofline x-coordinate."""
        return self.flops / max(self.bytes_read + self.bytes_written, 1.0)

    def to_dict(self) -> dict:
        d = {
            "flops": self.flops, "matmul_flops": self.matmul_flops,
            "bytes_read": self.bytes_read, "bytes_written": self.bytes_written,
            "comm_bytes": dict(self.comm_bytes),
            "peak_bytes": self.peak_bytes, "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes, "n_eqns": self.n_eqns,
            "arithmetic_intensity": round(self.arithmetic_intensity, 4),
            "largest_intermediate_bytes": self.largest_intermediate_bytes,
            "largest_intermediate_prim": self.largest_intermediate_prim,
            "location": self.location,
            "analysis_seconds": round(self.analysis_seconds, 4),
        }
        if self.guard_preds:
            d["guard_preds"] = self.guard_preds
            d["guard_sync_bytes"] = self.guard_sync_bytes
        if self.retrace_errors:
            d["retrace_errors"] = list(self.retrace_errors)
        if self.per_entry is not None:
            d["per_entry"] = {k: {"flops": r.flops, "peak_bytes": r.peak_bytes}
                              for k, r in self.per_entry.items()}
        return d


# ---------------------------------------------------------------------------
# aval arithmetic
# ---------------------------------------------------------------------------

def _aval_numel(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()) or ():
        if not isinstance(d, int):
            return 0  # dynamic dim: JX305's problem, not ours
        n *= d
    return n


def _aval_bytes(aval) -> int:
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0  # token/abstract value
    return _aval_numel(aval) * int(getattr(dtype, "itemsize", 4))


def _var_bytes(var) -> int:
    return _aval_bytes(getattr(var, "aval", None))


def _sub_jaxprs(eqn):
    """Every ClosedJaxpr/Jaxpr reachable through one eqn's params."""
    from jax.extend import core as jex_core

    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for item in vs:
            if isinstance(item, jex_core.ClosedJaxpr):
                out.append(item.jaxpr)
            elif isinstance(item, jex_core.Jaxpr):
                out.append(item)
    return out


# ---------------------------------------------------------------------------
# per-equation FLOPs
# ---------------------------------------------------------------------------

def _dot_general_flops(eqn) -> float:
    (lhs_c, rhs_c), (lhs_b, _rhs_b) = eqn.params["dimension_numbers"]
    lhs = getattr(eqn.invars[0], "aval", None)
    rhs = getattr(eqn.invars[1], "aval", None)
    if lhs is None or rhs is None:
        return 0.0
    lshape, rshape = lhs.shape, rhs.shape
    k = 1
    for i in lhs_c:
        k *= lshape[i]
    batch = 1
    for i in lhs_b:
        batch *= lshape[i]
    m = max(_aval_numel(lhs) // max(k * batch, 1), 1)
    n = max(_aval_numel(rhs) // max(k * batch, 1), 1)
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    dn = eqn.params.get("dimension_numbers")
    rhs = getattr(eqn.invars[1], "aval", None)
    out = getattr(eqn.outvars[0], "aval", None)
    if rhs is None or out is None:
        return 0.0
    rhs_spec = getattr(dn, "rhs_spec", None)
    if rhs_spec is None:
        return 2.0 * _aval_numel(out) * _aval_numel(rhs)
    cin = rhs.shape[rhs_spec[1]]
    kernel = 1
    for i in rhs_spec[2:]:
        kernel *= rhs.shape[i]
    return 2.0 * _aval_numel(out) * cin * kernel


def _eqn_flops(eqn) -> tuple:
    """(flops, matmul_flops) for one equation, sub-jaxprs excluded."""
    name = eqn.primitive.name
    if name == "dot_general":
        f = _dot_general_flops(eqn)
        return f, f
    if name.startswith("conv_general"):
        f = _conv_flops(eqn)
        return f, f
    if name in _MOVEMENT_PRIMS:
        return 0.0, 0.0
    if name in _REDUCTIONS:
        return float(sum(_aval_numel(getattr(v, "aval", None) or ())
                         for v in eqn.invars
                         if getattr(v, "aval", None) is not None)), 0.0
    # default: one flop per output element (elementwise / select / compare)
    return float(sum(_aval_numel(getattr(v, "aval", None))
                     for v in eqn.outvars
                     if getattr(v, "aval", None) is not None)), 0.0


def accumulation_width_delta(eqn) -> Dict[str, float]:
    """Price one dot/conv equation's accumulation-width choice: what
    widening a narrow-float contraction to float32
    (``preferred_element_type=float32`` + cast back) costs over keeping
    the narrow accumulator. Static aval arithmetic — never compiles.

    FLOPs do not change: the MXU accumulates partial products at full
    width either way, so the price is pure memory traffic — the f32
    result materializes at 4 bytes/element where the narrow one took
    ``itemsize``. Returned dict:

    - ``extra_bytes``  ``out_numel * (4 - narrow_itemsize)`` — the added
      result-write traffic of the widened accumulator
    - ``out_bytes``    the narrow result's bytes as traced (the base)
    - ``flops``        the contraction's FLOPs (unchanged; context for
      ranking one dot against the program)

    This is the NM1103 pricing hook: ``numerics_check`` compares
    ``extra_bytes`` against the whole program's read+write bytes and
    downgrades the flat error to a priced warning only when the widened
    result would dominate the program's traffic.
    """
    out = getattr(eqn.outvars[0], "aval", None) if eqn.outvars else None
    numel = _aval_numel(out)
    itemsize = int(getattr(getattr(out, "dtype", None), "itemsize", 4))
    flops, _ = _eqn_flops(eqn)
    return {
        "extra_bytes": float(numel * max(4 - itemsize, 0)),
        "out_bytes": float(numel * itemsize),
        "flops": float(flops),
    }


def _eqn_comm(eqn, axis_sizes: Optional[Dict[str, int]] = None
              ) -> Dict[str, float]:
    """Collective volume per mesh axis for one equation: moved bytes ×
    the axis-size-aware ring factor (``axis_sizes`` is the environment
    threaded down from enclosing shard_map/pmap equations; an unknown
    axis falls back to the static constants). The moved-bytes base is
    the LARGER of operand/result bytes: all_gather's wire traffic scales
    with the gathered result (n× its operand), psum_scatter's with its
    operand (n× its result) — taking only operand bytes undercounted the
    gather family by the axis size, which broke the quantized-collective
    (int8 payload + fp32 scales) accounting the planner ranks plans on."""
    name = eqn.primitive.name
    if name not in _COLLECTIVE_PRIMS:
        return {}
    axes = eqn.params.get("axis_name", eqn.params.get("axes"))
    if axes is None:
        return {}
    if not isinstance(axes, (list, tuple)):
        axes = (axes,)
    bytes_in = sum(_var_bytes(v) for v in eqn.invars)
    bytes_out = sum(_var_bytes(v) for v in eqn.outvars)
    moved = max(bytes_in, bytes_out)
    sizes = axis_sizes or {}
    return {str(ax): _ring_factor(name, sizes.get(str(ax))) * moved
            for ax in axes}


def _eqn_axis_sizes(eqn) -> Dict[str, int]:
    """Axis sizes an equation's body executes under: shard_map carries
    its ``mesh`` (name → size mapping), pmap carries ``axis_name`` +
    ``axis_size``. Merged over the enclosing environment when recursing
    into sub-jaxprs."""
    sizes: Dict[str, int] = {}
    mesh = eqn.params.get("mesh")
    shape = getattr(mesh, "shape", None)
    if shape is not None:
        try:
            sizes.update({str(k): int(v) for k, v in dict(shape).items()})
        except (TypeError, ValueError):
            pass
    axis_name = eqn.params.get("axis_name")
    axis_size = eqn.params.get("global_axis_size",
                               eqn.params.get("axis_size"))
    if axis_name is not None and isinstance(axis_size, int):
        names = axis_name if isinstance(axis_name, (list, tuple)) \
            else (axis_name,)
        for n in names:
            sizes[str(n)] = axis_size
    return sizes


def _constraint_divisor(eqn) -> Optional[float]:
    """Per-device residency divisor a ``sharding_constraint`` equation
    implies: the product of the mesh-axis sizes its partition spec names
    (1.0 for a replicated constraint). None when the sharding param
    carries no inspectable NamedSharding."""
    sh = eqn.params.get("sharding")
    spec = getattr(sh, "spec", None)
    mesh = getattr(sh, "mesh", None)
    shape = getattr(mesh, "shape", None)
    if spec is None or shape is None:
        return None
    try:
        sizes = {str(k): int(v) for k, v in dict(shape).items()}
    except (TypeError, ValueError):
        return None
    d = 1.0
    for entry in spec:
        axes = entry if isinstance(entry, (list, tuple)) else (
            (entry,) if entry is not None else ())
        for ax in axes:
            d *= float(sizes.get(str(ax), 1))
    return max(d, 1.0)


def value_divisor(value) -> float:
    """Per-device residency divisor of one LIVE jax array: total numel
    over the committed sharding's shard numel (1.0 for replicated /
    uncommitted / non-array values). Feeds ``cost_jaxpr(arg_divisors=)``
    for program arguments retraced from live state cells."""
    sh = getattr(value, "sharding", None)
    shape = getattr(value, "shape", None)
    if sh is None or shape is None:
        return 1.0
    try:
        shard_shape = sh.shard_shape(tuple(shape))
    except Exception:
        return 1.0
    total = per = 1
    for d in shape:
        total *= int(d)
    for d in shard_shape:
        per *= int(d)
    if per <= 0 or total <= 0:
        return 1.0
    return max(float(total) / float(per), 1.0)


def _is_fused_expansion(eqn) -> bool:
    """True for broadcast-of-scalar / iota results: XLA fuses these into
    their consumers, so charging their full output to the live set would
    overshoot measured peaks by the batch size."""
    if eqn.primitive.name not in _FUSED_EXPANSIONS:
        return False
    for v in eqn.invars:
        if _aval_numel(getattr(v, "aval", None)) > 1:
            return False
    return True


# ---------------------------------------------------------------------------
# the walker
# ---------------------------------------------------------------------------

def _scan_length(eqn) -> int:
    length = eqn.params.get("length")
    return int(length) if isinstance(length, int) and length > 0 else 1


_CMP_PRIMS = ("lt", "le", "gt", "ge")
_FLIP_CMP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}


def _while_trip_count(eqn) -> int:
    """Static trip count of a ``while`` equation for the counter pattern
    (``cond: counter <op> bound``, ``body: counter += step``, all three of
    init/bound/step literals) — the shape every pipelined loop lowered
    from ``lax.while_loop`` with static bounds takes. Anything else falls
    back to ``FLAGS_cost_while_default_trips`` (default 1: the historical
    single-iteration lower bound — trip counts are data)."""
    import math

    from jax.extend import core as jex_core

    from ..base.flags import get_flag

    try:
        fallback = max(int(get_flag("cost_while_default_trips")), 1)
    except Exception:
        fallback = 1
    try:
        cond = eqn.params["cond_jaxpr"].jaxpr
        body = eqn.params["body_jaxpr"].jaxpr
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
    except (KeyError, AttributeError, TypeError):
        return fallback
    Literal = jex_core.Literal
    carry_outer = list(eqn.invars)[cn + bn:]
    cond_const_outer = list(eqn.invars)[:cn]
    cond_const_vars = list(cond.invars)[:cn]
    carry_cond_vars = list(cond.invars)[cn:]

    # the predicate equation producing the cond output
    pred_var = cond.outvars[0]
    pred = next((e for e in cond.eqns if pred_var in e.outvars), None)
    if pred is None or pred.primitive.name not in _CMP_PRIMS or \
            len(pred.invars) != 2:
        return fallback

    def concrete(v):
        """Literal value of ``v`` inside the cond scope, through one hop
        of cond-consts to the outer invars."""
        if isinstance(v, Literal):
            return v.val
        for cv, ov in zip(cond_const_vars, cond_const_outer):
            if v is cv and isinstance(ov, Literal):
                return ov.val
        return None

    lhs, rhs = pred.invars
    op = pred.primitive.name
    idx = next((i for i, cv in enumerate(carry_cond_vars)
                if lhs is cv or rhs is cv), None)
    if idx is None:
        return fallback
    counter_is_lhs = lhs is carry_cond_vars[idx]
    bound = concrete(rhs if counter_is_lhs else lhs)
    init_v = carry_outer[idx] if idx < len(carry_outer) else None
    init = init_v.val if isinstance(init_v, Literal) else None

    # the body's increment of that carry position
    carry_body_vars = list(body.invars)[bn:]
    if idx >= len(carry_body_vars) or idx >= len(body.outvars):
        return fallback
    out_v = body.outvars[idx]
    step = None
    for e in body.eqns:
        if out_v in e.outvars and len(e.invars) == 2 \
                and e.primitive.name in ("add", "add_any", "sub"):
            x, y = e.invars
            if x is carry_body_vars[idx] and isinstance(y, Literal):
                step = -y.val if e.primitive.name == "sub" else y.val
            elif y is carry_body_vars[idx] and isinstance(x, Literal) \
                    and e.primitive.name != "sub":
                step = x.val
            break
    if bound is None or init is None or step is None:
        return fallback
    try:
        bound, init, step = float(bound), float(init), float(step)
    except (TypeError, ValueError):
        return fallback
    if not counter_is_lhs:  # normalize to `counter <op> bound`
        op = _FLIP_CMP[op]
    if op in ("gt", "ge"):  # count-down loop -> mirrored count-up
        init, bound, step = -init, -bound, -step
        op = "lt" if op == "gt" else "le"
    if step <= 0:
        return fallback
    span = bound - init + (1.0 if op == "le" else 0.0)
    # a successful derivation is authoritative, including 0 (a loop whose
    # guard statically never passes costs nothing)
    return max(int(math.ceil(span / step)), 0)


def _walk_jaxpr(jaxpr, axis_sizes: Optional[Dict[str, int]] = None,
                arg_divisors: Optional[List[float]] = None) -> CostReport:
    """Cost one (open) Jaxpr: totals + liveness peak. Recurses into
    pjit/scan/while/cond bodies; scan multiplies by trip count, cond takes
    the max across branches, while multiplies by the statically derived
    counter trip count when the loop has one (else the
    FLAGS_cost_while_default_trips lower bound). ``axis_sizes`` is the
    mesh-axis environment for collective ring factors, extended by every
    shard_map/pmap equation recursed through. ``arg_divisors`` carries a
    per-device residency divisor per invar (sharded program arguments —
    zero1 optimizer-state cells enter at shard size); the walk extends
    it through ``sharding_constraint`` equations and elementwise chains
    (minimum across non-scalar operands — conservative when sharded and
    replicated values mix)."""
    from jax.extend import core as jex_core

    rep = CostReport(n_eqns=len(jaxpr.eqns))

    # ---- last-use table for the liveness walk ---------------------------
    last_use: Dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jex_core.Var):
                last_use[v] = i
    n = len(jaxpr.eqns)
    for v in jaxpr.outvars:
        if isinstance(v, jex_core.Var):
            last_use[v] = n  # live to the end

    # per-var residency divisors (see docstring)
    divs: Dict = {}
    if arg_divisors:
        for v, d in zip(jaxpr.invars, arg_divisors):
            if isinstance(v, jex_core.Var) and d and d > 1.0:
                divs[v] = float(d)

    def _resident(v) -> float:
        return _var_bytes(v) / divs.get(v, 1.0)

    # program arguments + constants resident at entry (XLA argument size
    # — per device: sharded arguments count their shard)
    rep.arg_bytes = int(sum(_resident(v) for v in jaxpr.invars))
    rep.out_bytes = sum(_var_bytes(v) for v in jaxpr.outvars)
    entry_vars = list(jaxpr.invars) + list(jaxpr.constvars)
    live = {}
    for v in entry_vars:
        live[v] = _resident(v)
    live_bytes = sum(live.values())
    peak = live_bytes
    # arguments never read free right after entry (they still hit the peak
    # once — XLA holds every argument at program start)
    for v in entry_vars:
        if v not in last_use:
            live_bytes -= live.pop(v)

    for i, eqn in enumerate(jaxpr.eqns):
        pname = eqn.primitive.name
        in_b = sum(_var_bytes(v) for v in eqn.invars)
        out_b = sum(_var_bytes(v) for v in eqn.outvars)

        # container equations (pjit / scan / while / cond / remat /
        # custom_vjp wrappers) carry NO cost of their own: everything —
        # flops, bytes, comm — comes from the recursed body, otherwise
        # every jit boundary double-counts its operand bytes and charges
        # phantom per-output-element flops
        subs = _sub_jaxprs(eqn)
        sub_peak_extra = 0
        if subs:
            flops = mm = 0.0
            inner_sizes = _eqn_axis_sizes(eqn)
            sub_env = ({**(axis_sizes or {}), **inner_sizes}
                       if inner_sizes else axis_sizes)
            sub_reports = [_walk_jaxpr(s, sub_env) for s in subs]
            if pname == "scan":
                mult = _scan_length(eqn)
            elif pname == "while":
                mult = _while_trip_count(eqn)
            else:
                mult = 1
            if pname == "cond":
                best = max(sub_reports, key=lambda r: r.flops)
                agg = [best]
            else:
                agg = sub_reports
            for sr in agg:
                flops += mult * sr.flops
                mm += mult * sr.matmul_flops
                rep.bytes_read += mult * sr.bytes_read
                rep.bytes_written += mult * sr.bytes_written
                for ax, vol in sr.comm_bytes.items():
                    rep.comm_bytes[ax] = rep.comm_bytes.get(ax, 0.0) + mult * vol
                for sub_prim, sub_row in sr.by_primitive.items():
                    row = rep.by_primitive.setdefault(
                        sub_prim, {"count": 0, "flops": 0.0, "bytes": 0.0})
                    row["count"] += mult * sub_row["count"]
                    row["flops"] += mult * sub_row["flops"]
                    row["bytes"] += mult * sub_row["bytes"]
                if sr.largest_intermediate_bytes > rep.largest_intermediate_bytes:
                    rep.largest_intermediate_bytes = sr.largest_intermediate_bytes
                    rep.largest_intermediate_prim = sr.largest_intermediate_prim
            # the body's internal peak, minus its arguments (the outer
            # operands already sit in the live set)
            sub_peak_extra = max(
                (sr.peak_bytes - sr.arg_bytes for sr in sub_reports),
                default=0)
            sub_peak_extra = max(sub_peak_extra, 0)
        else:
            flops, mm = _eqn_flops(eqn)
            rep.bytes_read += in_b
            rep.bytes_written += out_b
            for ax, vol in _eqn_comm(eqn, axis_sizes).items():
                rep.comm_bytes[ax] = rep.comm_bytes.get(ax, 0.0) + vol
            row = rep.by_primitive.setdefault(
                pname, {"count": 0, "flops": 0.0, "bytes": 0.0})
            row["count"] += 1
            row["flops"] += flops
            row["bytes"] += in_b + out_b

        rep.flops += flops
        rep.matmul_flops += mm

        # ---- residency-divisor propagation -----------------------------
        if pname == "sharding_constraint":
            out_div = _constraint_divisor(eqn)
        elif subs:
            out_div = None  # container results: no propagation
        else:
            in_divs = [divs.get(v, 1.0) for v in eqn.invars
                       if isinstance(v, jex_core.Var)
                       and _aval_numel(getattr(v, "aval", None)) > 1]
            out_div = min(in_divs) if in_divs else None
        if out_div is not None and out_div > 1.0:
            for v in eqn.outvars:
                if isinstance(v, jex_core.Var) and \
                        _aval_numel(getattr(v, "aval", None)) > 1:
                    divs[v] = out_div

        # ---- liveness update -------------------------------------------
        materialized = 0 if _is_fused_expansion(eqn) else out_b
        if materialized > rep.largest_intermediate_bytes:
            rep.largest_intermediate_bytes = materialized
            rep.largest_intermediate_prim = pname
        for v in eqn.outvars:
            if isinstance(v, jex_core.Var) and v in last_use and v not in live:
                b = 0 if _is_fused_expansion(eqn) else _resident(v)
                live[v] = b
                live_bytes += b
        peak = max(peak, live_bytes + sub_peak_extra)
        freed = set()
        for v in eqn.invars:
            if (isinstance(v, jex_core.Var) and v not in freed
                    and last_use.get(v) == i):
                freed.add(v)
                live_bytes -= live.pop(v, 0)

    rep.peak_bytes = int(peak)
    return rep


def cost_jaxpr(closed_jaxpr, *, location: str = "",
               axis_sizes: Optional[Dict[str, int]] = None,
               arg_divisors: Optional[List[float]] = None) -> CostReport:
    """Cost one ClosedJaxpr. Static — never compiles, never executes.
    ``axis_sizes`` seeds the mesh-axis environment for collective ring
    factors (e.g. ``{"dp": 8}`` from a planner Plan) — axes declared by
    shard_map/pmap equations inside the program resolve themselves.
    ``arg_divisors`` (one per invar, in flatten order) prices sharded
    program arguments at per-device shard size in the liveness walk —
    ``cost_compiled_function`` derives them from the live state cells'
    committed shardings."""
    rep = _walk_jaxpr(closed_jaxpr.jaxpr, dict(axis_sizes or {}) or None,
                      arg_divisors=arg_divisors)
    rep.location = location
    return rep


# ---------------------------------------------------------------------------
# CompiledFunction / kernel-cache front ends
# ---------------------------------------------------------------------------

def cost_compiled_function(cf) -> CostReport:
    """Cost every cache entry of one ``CompiledFunction`` (same retrace
    machinery as ``audit_compiled_function`` — tracing only). Returns the
    costliest entry's report with ``per_entry`` holding each entry and
    ``retrace_errors`` any entries that no longer trace (CM500 feed)."""
    import time

    from .jaxpr_audit import retrace_entry

    t0 = time.perf_counter()
    name = getattr(cf, "name", "fn")
    per_entry: Dict[str, CostReport] = {}
    errors: List[str] = []

    def one(entry, loc):
        try:
            closed, _n_user, _n_cells = retrace_entry(entry)
        except Exception as e:
            errors.append(f"{loc}: {str(e).splitlines()[0]}")
            return
        # program arguments = [cell values..., user args...]: cells are
        # live arrays whose committed shardings tell us the per-device
        # residency (zero1 moments enter at 1/dp), user args replicated
        divisors = [value_divisor(c._value) for c in entry.get("cells", ())]
        divisors += [1.0] * max(len(closed.jaxpr.invars) - len(divisors), 0)
        rep = cost_jaxpr(closed, location=loc, arg_divisors=divisors)
        guards = entry.get("guards")
        if guards:
            # the guard-predicate overhead of a speculative branch family
            # (jit/functionalize): the program's outvars are laid out
            # [user outs..., new cells..., predicates...] — the trailing
            # len(guards) values are fetched to the host EVERY call to
            # verify the speculation (CM505's feed)
            pred_vars = list(closed.jaxpr.outvars)[-len(guards):]
            rep.guard_preds = len(guards)
            rep.guard_sync_bytes = sum(_var_bytes(v) for v in pred_vars)
        per_entry[loc] = rep

    for idx, (_key, entry) in enumerate(list(cf._cache.items())):
        loc = f"{name}[{idx}]"
        if entry.get("guarded"):
            if entry.get("eager"):
                continue
            for outcomes, sub in entry["entries"].items():
                one(sub, f"{loc}:guards={outcomes}")
        elif not entry.get("eager"):
            one(entry, loc)

    if per_entry:
        rep = max(per_entry.values(), key=lambda r: r.peak_bytes)
    else:
        rep = CostReport(location=name)
    rep.per_entry = per_entry
    rep.retrace_errors = errors
    rep.analysis_seconds = time.perf_counter() - t0
    return rep


def cost_bucketed_function(bf) -> CostReport:
    """Cost a ``BucketedFunction``'s wrapped cache (one entry per engaged
    bucket rung)."""
    return cost_compiled_function(bf._compiled)


# ---------------------------------------------------------------------------
# CM5xx checks (the `cost` lint family)
# ---------------------------------------------------------------------------

def _flag(name, override, fallback):
    if override is not None:
        return override
    try:
        from ..base.flags import get_flag

        return get_flag(name)
    except Exception:
        return fallback


def check_cost(report: CostReport, *, plan=None,
               max_intermediate_bytes=None, hbm_budget_bytes=None,
               min_arith_intensity=None, intensity_min_bytes=None,
               bandwidth_gbps=None, device_tflops=None,
               max_guard_preds=None) -> List[Finding]:
    """CM5xx findings over one :class:`CostReport` (and its per-entry
    breakdown). ``plan`` is an optional ``auto_parallel.planner.Plan``:
    when given, the CM504 peak check divides the traced single-program
    peak across the plan's model-sharding degrees before comparing to the
    HBM budget."""
    max_inter = int(_flag("cost_max_intermediate_bytes",
                          max_intermediate_bytes, 2 << 30))
    hbm = int(_flag("cost_hbm_budget_bytes", hbm_budget_bytes, 16 << 30))
    min_ai = float(_flag("cost_min_arith_intensity", min_arith_intensity, 0.25))
    ai_floor = int(_flag("cost_intensity_min_bytes", intensity_min_bytes,
                         32 << 20))
    bw = float(_flag("cost_mesh_bandwidth_gbps", bandwidth_gbps, 100.0))
    tflops = float(_flag("cost_device_tflops", device_tflops, 197.0))
    guard_cap = int(_flag("cost_max_guard_preds", max_guard_preds, 8))

    findings: List[Finding] = []

    for msg in report.retrace_errors:
        findings.append(Finding(
            _ANALYZER, "CM500", "error",
            f"cost retrace failed: {msg}", report.location))

    entries = (list(report.per_entry.items()) if report.per_entry
               else [(report.location, report)])
    for loc, rep in entries:
        if rep.largest_intermediate_bytes > max_inter:
            findings.append(Finding(
                _ANALYZER, "CM501", "warning",
                f"'{rep.largest_intermediate_prim}' materializes a "
                f"{rep.largest_intermediate_bytes / 2**20:.0f} MiB "
                f"intermediate (> {max_inter / 2**20:.0f} MiB budget, "
                "FLAGS_cost_max_intermediate_bytes) — a single buffer this "
                "size dominates the program's residency; reshape/chunk it",
                loc))

        moved = rep.bytes_read + rep.bytes_written
        if (rep.matmul_flops == 0 and moved >= ai_floor
                and rep.arithmetic_intensity < min_ai):
            findings.append(Finding(
                _ANALYZER, "CM502", "warning",
                f"matmul-free program moving {moved / 2**20:.0f} MiB at "
                f"{rep.arithmetic_intensity:.3f} flops/byte (< {min_ai}) — "
                "memory-bound on TPU; the MXU idles while HBM streams "
                "(fuse elementwise chains or batch this into a matmul path)",
                loc))

        if rep.comm_bytes and rep.flops > 0:
            compute_s = rep.flops / (tflops * 1e12)
            for ax, vol in sorted(rep.comm_bytes.items()):
                comm_s = vol / (bw * 1e9)
                if comm_s > compute_s:
                    findings.append(Finding(
                        _ANALYZER, "CM503", "warning",
                        f"collective volume on axis '{ax}' "
                        f"({vol / 2**20:.0f} MiB ≈ {comm_s * 1e3:.2f} ms at "
                        f"{bw:.0f} GB/s) exceeds estimated compute "
                        f"({compute_s * 1e3:.2f} ms at {tflops:.0f} TFLOP/s) "
                        "— the step is communication-bound under the "
                        "declared bandwidth model", loc))

        if rep.guard_preds > guard_cap > 0:
            findings.append(Finding(
                _ANALYZER, "CM505", "warning",
                f"speculative branch family verifies {rep.guard_preds} "
                f"guard predicates per call ({rep.guard_sync_bytes} bytes "
                f"fetched device→host each step, > {guard_cap} predicate "
                "budget, FLAGS_cost_max_guard_preds) — every tensor-bool "
                "branch is a per-call host sync AND a potential "
                "specialization fork; hoist the conditions or fold them "
                "into lax.cond/where", loc))

        shards = 1
        if plan is not None:
            shards = max(int(getattr(plan, "mp", 1))
                         * int(getattr(plan, "pp", 1))
                         * int(getattr(plan, "sep", 1)), 1)
        per_device = rep.peak_bytes / shards
        if per_device > hbm:
            findings.append(Finding(
                _ANALYZER, "CM504", "error",
                f"estimated peak residency {per_device / 2**30:.2f} GiB "
                f"per device (liveness peak {rep.peak_bytes / 2**30:.2f} GiB "
                f"over {shards} model shard(s)) exceeds the "
                f"{hbm / 2**30:.0f} GiB HBM budget "
                "(FLAGS_cost_hbm_budget_bytes) — this program OOMs at "
                "dispatch; raise the sharding degrees or cut the batch",
                loc))

    return findings
