"""Program-drift analyzer (PD12xx): canonical jaxpr lockfile + cost gate.

Every other lint family audits the programs the runtime builds *today*
against rules. This family audits them against *yesterday*: a committed
``programs.lock.json`` at the repo root records a canonical fingerprint
of each representative program the framework stakes its performance
story on — the TrainStep sharding tiers (replicated / quantized-gspmd /
zero1), the serving batch ladder, the paged-decode (batch x table) rung
grid, the quantized-allreduce oracle and the portable reshard route —
and the lint compares a fresh retrace of each against the lock. A PR
that silently adds a host callback to the train step, drops KV-buffer
donation, narrows the fp32 accumulator or doubles the step's FLOPs now
fails ``python -m tools.lint --select PD`` with the offending program
and metric named, instead of surfacing as a cluster-wide regression
three weeks later.

The fingerprint is *canonical*, never a jaxpr pretty-print (variable
names and equation order churn across jax versions): sorted primitive
histogram, donation map, per-dtype operand byte totals, collective
count per mesh axis, and the static cost-model scalars
(:mod:`analysis.cost_model`: FLOPs, bytes read/written, comm bytes,
peak residency, guard predicates). Tracing only — nothing here ever
compiles or executes except the three TrainStep tiers, which compile
once at lint time exactly like the ``jaxpr`` family's demo step
(``audit_builds_delta == 0``: the hot path never pays).

PD1200  program set drift       a locked program no longer exists live
                                (extinct builder), a live program is
                                missing from the lock (stale lock), or
                                the lockfile itself is missing (error;
                                a program skipped for insufficient
                                devices is a warning — CI's 8-device
                                harness covers it)
PD1201  primitive drift         a primitive appears in the live program
                                that the lock never recorded (host
                                callback, stray cast, new collective) —
                                error; a locked primitive vanishing is
                                an error for collectives (a sharding
                                tier disengaged) and a warning
                                otherwise (legitimate fusion)
PD1202  cost drift              a cost scalar grew past its per-metric
                                tolerance flag (``FLAGS_drift_max_
                                flops_ratio`` / ``_bytes_ratio`` /
                                ``_comm_ratio`` / ``_peak_ratio``), a
                                guard predicate was added, or comm
                                bytes appeared from zero (error)
PD1203  donation lost           a buffer the locked program donates is
                                no longer donated live — XLA loses the
                                in-place reuse and the step's residency
                                doubles (error)
PD1204  dtype narrowing         a wide float's traced byte volume fell
                                while narrower-float bytes grew — an
                                accumulator or reduction silently lost
                                precision (error)
PD1205  rung-grid shrinkage     a locked serving/decode rung is no
                                longer built — traffic on that shape
                                would retrace at serve time (error)
PD999   parse/retrace crash     the lockfile does not parse, or a
                                builder raised (``tools.lint`` maps
                                analyzer crashes here too)

``python -m tools.lint --update-lock`` regenerates the lockfile
deterministically: sorted keys, rounded floats, no timestamps — two
consecutive runs are byte-identical, so the committed file only changes
when a program actually changes.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
from typing import Dict, List, Optional

from . import Finding

_ANALYZER = "drift"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LOCK_BASENAME = "programs.lock.json"
LOCK_VERSION = 1

# float widths for the PD1204 narrowing rule: traffic migrating from a
# wider row to a narrower one is precision loss, whatever the pair
_FLOAT_WIDTH = {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2,
                "float8_e4m3fn": 1, "float8_e5m2": 1}

# cost scalar -> the tolerance flag its growth is gated by
_RATIO_FLAGS = {
    "flops": "drift_max_flops_ratio",
    "bytes_read": "drift_max_bytes_ratio",
    "bytes_written": "drift_max_bytes_ratio",
    "comm_bytes": "drift_max_comm_ratio",
    "peak_bytes": "drift_max_peak_ratio",
}


def default_lock_path() -> str:
    return os.path.join(_REPO_ROOT, LOCK_BASENAME)


def lock_digest(path: Optional[str] = None) -> Optional[str]:
    """sha256 of the lockfile bytes (None when absent) — the digest
    ``tools.lint --update-lock`` prints."""
    path = path or default_lock_path()
    try:
        with open(path, "rb") as fh:
            return hashlib.sha256(fh.read()).hexdigest()
    except OSError:
        return None


# ---------------------------------------------------------------------------
# fingerprint
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    """Jaxprs nested in one equation's params (pjit/scan/while bodies,
    cond branch lists) — duck-typed, robust to jax version churn."""
    for v in eqn.params.values():
        cands = v if isinstance(v, (list, tuple)) else (v,)
        for c in cands:
            if hasattr(c, "eqns"):
                yield c
            elif hasattr(c, "jaxpr") and hasattr(c.jaxpr, "eqns"):
                yield c.jaxpr


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _aval_bytes(aval) -> int:
    try:
        numel = 1
        for d in aval.shape:
            numel *= int(d)
        return int(numel * aval.dtype.itemsize)
    except Exception:
        return 0  # symbolic dims: shape identity is covered by the rung key


def fingerprint_jaxpr(closed, *, donation=(), axis_sizes=None) -> dict:
    """The canonical, json-stable fingerprint of one ClosedJaxpr. Pure
    structure + static cost — nothing here depends on variable naming,
    equation order or parameter values, so it is byte-reproducible
    across processes and platforms."""
    from .cost_model import _COLLECTIVE_PRIMS, cost_jaxpr

    prims: Dict[str, int] = {}
    dtype_bytes: Dict[str, int] = {}
    collectives: Dict[str, int] = {}
    for eqn in _walk(closed.jaxpr):
        name = eqn.primitive.name
        prims[name] = prims.get(name, 0) + 1
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            b = _aval_bytes(aval)
            if b:
                key = str(aval.dtype)
                dtype_bytes[key] = dtype_bytes.get(key, 0) + b
        if name in _COLLECTIVE_PRIMS:
            axes = eqn.params.get("axis_name", eqn.params.get("axes"))
            if axes is None:
                axes = ()
            elif isinstance(axes, (str, int)):
                axes = (axes,)
            for ax in axes:
                collectives[str(ax)] = collectives.get(str(ax), 0) + 1
    rep = cost_jaxpr(closed, axis_sizes=axis_sizes)
    return {
        "primitives": {k: prims[k] for k in sorted(prims)},
        "dtype_bytes": {k: dtype_bytes[k] for k in sorted(dtype_bytes)},
        "collectives": {k: collectives[k] for k in sorted(collectives)},
        "donation": sorted(str(d) for d in donation),
        "cost": {
            "flops": round(float(rep.flops), 3),
            "bytes_read": round(float(rep.bytes_read), 3),
            "bytes_written": round(float(rep.bytes_written), 3),
            "comm_bytes": round(float(sum(rep.comm_bytes.values())), 3),
            "peak_bytes": int(rep.peak_bytes),
            "guard_preds": int(rep.guard_preds),
        },
    }


# ---------------------------------------------------------------------------
# representative-program builders
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _hermetic():
    """The builders mutate global state to reach each sharding tier —
    the RNG stream (deterministic init), the quantized-sync and zero1
    flags, and the installed mesh. Save and restore ALL of it: a lint
    run is an in-process health check and must not reconfigure the
    caller's session (same discipline as ``record_demo_step``)."""
    from ..base import global_state
    from ..base.flags import get_flags, set_flags
    from ..distributed import env as env_mod

    gen = global_state.default_generator
    prev_seed = gen._seed
    prev_cell = gen._cell
    prev_key = None if prev_cell is None else prev_cell._value
    prev_flags = get_flags(["comm_quantize_dp_grads", "sharding_stage",
                            "comm_quantize_block"])
    env = env_mod.instance()
    prev_env = (env.initialized, env.mesh, dict(env.axis_degrees),
                env.device_kind)
    try:
        yield env
    finally:
        set_flags(prev_flags)
        env.initialized, env.mesh, env.axis_degrees, env.device_kind = prev_env
        gen._seed = prev_seed
        if prev_cell is None:
            gen._cell = None
        else:
            gen._cell = prev_cell
            prev_cell._replace_value(prev_key)


def _clear_mesh(env) -> None:
    env.mesh = None
    env.axis_degrees = {}


def _single_entry(cf):
    """The one cache entry a freshly built demo TrainStep must hold."""
    entries = []
    for e in cf._cache.values():
        if e.get("guarded"):
            entries.extend(e["entries"].values())
        else:
            entries.append(e)
    if len(entries) != 1:
        raise RuntimeError(
            f"drift demo step compiled {len(entries)} cache entries "
            "(expected exactly 1) — the builder is no longer canonical")
    return entries[0]


def _train_fingerprints(env, programs, skipped) -> None:
    """The three TrainStep sharding tiers over one Linear(64, 32) demo
    model — 64x32 fp32 weight = 8 KiB, above FLAGS_comm_quantize_min_
    bytes, so the quantized dp sync engages on the weight grad."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from ..base.flags import set_flags
    from ..jit.api import TrainStep
    from .jaxpr_audit import retrace_entry

    n_dev = len(jax.devices())

    def build(sharding=None):
        paddle.seed(0)
        model = nn.Linear(64, 32)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        crit = nn.MSELoss()
        step = TrainStep(model=model, optimizer=opt,
                         loss_fn=lambda x, y: crit(model(x), y),
                         sharding=sharding)
        x = paddle.Tensor(np.ones((4, 64), np.float32), stop_gradient=True)
        y = paddle.Tensor(np.zeros((4, 32), np.float32), stop_gradient=True)
        step(x, y)
        cf = step._compiled
        closed, _n_user, _n_cells = retrace_entry(_single_entry(cf))
        donation = ("cells",) if getattr(cf, "donate_cells", False) else ()
        axis_sizes = dict(env.axis_degrees) if env.mesh is not None else None
        return fingerprint_jaxpr(closed, donation=donation,
                                 axis_sizes=axis_sizes)

    set_flags({"comm_quantize_dp_grads": False})
    _clear_mesh(env)
    programs["train_step/replicated"] = build()

    for name, min_dev in (("train_step/gspmd_int8", 8),
                          ("train_step/zero1", 8)):
        if n_dev < min_dev:
            skipped[name] = min_dev
            continue
        if name.endswith("gspmd_int8"):
            set_flags({"comm_quantize_dp_grads": True})
            env.build_mesh({"dp": 8})
            programs[name] = build()
            set_flags({"comm_quantize_dp_grads": False})
        else:
            env.build_mesh({"dp": 8})
            programs[name] = build(sharding="zero1")
    _clear_mesh(env)


def _serving_fingerprints(programs, rung_grids) -> None:
    """The batch-serving ladder: the exported demo MLP's program per
    rung, retraced abstractly through the exported module (zero
    compiles — ``_BatchProgram`` jits lazily)."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from ..inference import _BatchProgram
    from ..jit.serialization import load as jit_load

    ladder = [1, 2, 4]
    tmpdir = tempfile.mkdtemp(prefix="paddle_drift_serving_")
    try:
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        net.eval()
        prefix = os.path.join(tmpdir, "drift_served")
        paddle.jit.save(net, prefix,
                        input_spec=[paddle.static.InputSpec([None, 8],
                                                            "float32")])
        layer = jit_load(prefix)
        prog = _BatchProgram(layer, layer._meta.get("dynamic_axes") or [],
                             ladder)
        params_sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype),
            prog._params)
        donation = tuple(f"arg{i}" for i in prog._donate)
        for b in ladder:
            closed = jax.make_jaxpr(
                lambda p, x: prog._exported.call(p, x))(
                    params_sds,
                    jax.ShapeDtypeStruct((b, 8), np.dtype("float32")))
            programs[f"serving/batch:b{b}"] = fingerprint_jaxpr(
                closed, donation=donation)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    rung_grids["serving/batch"] = [f"b{b}" for b in ladder]


def _program_set_fingerprints(progs, family, programs, rung_grids) -> None:
    """Every rung of one decode program set, retraced abstractly
    (``make_jaxpr`` over the program bodies with the rungs' own zero-arg
    templates — zero compiles) under ``<family>:<rung>``: the programs over
    the pool, and the token carry in front of the decode programs
    (``("carry", p, b)``: no parameters, no pool, nothing donated)."""
    import jax
    import numpy as np

    def sds(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype)

    donation = tuple(f"arg{i}" for i in progs._donate)
    held = tuple(sds(a) for a in progs.pool.arrays())
    grid = []
    for key in progs.rungs:
        if key[0] == "carry":
            closed = jax.make_jaxpr(progs._carry_fn)(
                *(sds(a) for a in progs._carry_zero_args(key)))
        else:
            closed = jax.make_jaxpr(getattr(progs, f"_{key[0]}_fn"))(
                jax.tree_util.tree_map(sds, progs._call_params(key)), *held,
                *(sds(a) for a in progs._zero_args(key)))
        rung = ":".join(str(p) for p in key)
        grid.append(rung)
        programs[f"{family}:{rung}"] = fingerprint_jaxpr(
            closed, donation=() if key[0] == "carry" else donation)
    rung_grids[family] = sorted(grid)


def _decode_fingerprints(programs, rung_grids) -> None:
    """The paged-decode rung grid: every ``("decode", b, t)`` /
    ``("prefill", b, s)`` / ``("draft", b, t)`` / ``("verify", b, t)``
    specialization of a 1-layer tiny GPT over a KVPagePool, retraced
    abstractly (``make_jaxpr`` over the program bodies with the rungs'
    own zero-arg templates — zero compiles). Speculation rungs use
    ``speculate_k=2`` with a full-depth (1-layer) draft — the same
    degenerate-draft shape the demo decode engine audits."""
    import paddle_tpu as paddle
    from ..models.gpt import GPTForCausalLM, gpt_tiny
    from ..serving.decode import PagedDecodePrograms
    from ..serving.kv_cache import KVPagePool

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(
        num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
        max_position_embeddings=32))
    model.eval()
    pool = KVPagePool(num_layers=1, num_pages=8, page_size=8,
                      num_heads=2, head_dim=16)
    progs = PagedDecodePrograms(model, pool, seq_ladder=[8, 16],
                                prefill_batch_rungs=[1, 2],
                                decode_rungs=[1, 2], max_seq=16,
                                speculate_k=2, draft_layers=1)
    _program_set_fingerprints(progs, "decode/paged", programs, rung_grids)


def _retention_fingerprints(programs, rung_grids) -> None:
    """The state-lane residency's representatives: one prefill chunk and
    one decode rung of a 1-layer tiny Brumby over a StateLanePool (the jnp
    decode path: the Pallas kernel is a TPU's), retraced abstractly."""
    import paddle_tpu as paddle
    from ..models.brumby import BrumbyForCausalLM, brumby_tiny
    from ..serving.decode import RetentionPrograms
    from ..serving.kv_cache import StateLanePool

    paddle.seed(0)
    model = BrumbyForCausalLM(brumby_tiny(
        num_hidden_layers=1, hidden_size=32, intermediate_size=48,
        num_attention_heads=2, num_key_value_heads=1, vocab_size=64,
        max_position_embeddings=32))
    model.eval()
    pool = StateLanePool(num_layers=1, max_slots=2, num_kv_heads=1,
                         head_dim=16, max_seq=32)
    progs = RetentionPrograms(model, pool, seq_ladder=[8],
                              prefill_batch_rungs=[1], decode_rungs=[2])
    _program_set_fingerprints(progs, "decode/state", programs, rung_grids)


def _latent_fingerprints(programs, rung_grids) -> None:
    """The latent-page residency's representatives: one prefill chunk and
    one decode rung of a tiny A.X-K1 (one dense layer, one sparse, a quarter
    of the experts held) over a one-array KVPagePool (the jnp decode path:
    the Pallas kernel is a TPU's), retraced abstractly."""
    import paddle_tpu as paddle
    from ..models.axk1 import AXK1ForCausalLM, axk1_tiny
    from ..serving.decode import LatentPrograms
    from ..serving.kv_cache import KVPagePool

    paddle.seed(0)
    model = AXK1ForCausalLM(axk1_tiny(
        num_hidden_layers=2, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, vocab_size=64, max_position_embeddings=32),
        expert_share=(1, 4))
    model.eval()
    pool = KVPagePool(num_layers=2, num_pages=8, page_size=8, row_width=128,
                      arrays=1)
    progs = LatentPrograms(model, pool, seq_ladder=[8], decode_rungs=[2],
                           max_seq=32)
    _program_set_fingerprints(progs, "decode/latent", programs, rung_grids)


def _windowed_fingerprints(programs, rung_grids) -> None:
    """The two-lifetime residency's representatives: one prefill chunk and
    one decode rung of a tiny Command A+ (one window layer and one global,
    half of the experts held) over a WindowedPagePools (the jnp decode path:
    the Pallas kernel is a TPU's), retraced abstractly."""
    import paddle_tpu as paddle
    from ..models.cohere2_moe import Cohere2MoEForCausalLM, cohere2_moe_tiny
    from ..serving.decode import WindowedPrograms
    from ..serving.kv_cache import WindowedPagePools

    paddle.seed(0)
    model = Cohere2MoEForCausalLM(cohere2_moe_tiny(
        num_hidden_layers=2, layer_types=["sliding_attention", "full_attention"],
        hidden_size=32, intermediate_size=16, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, num_experts=8, vocab_size=64,
        max_position_embeddings=32), expert_share=(1, 2))
    model.eval()
    pool = WindowedPagePools(1, 1, 6, 8, 8, 2, 8, window=16)
    progs = WindowedPrograms(model, pool, seq_ladder=[8], decode_rungs=[2],
                             max_seq=32)
    _program_set_fingerprints(progs, "decode/windowed", programs, rung_grids)


def _qpsum_fingerprint(programs) -> None:
    """The quantized-allreduce oracle over an awkward (non-multiple)
    shape — the exact wire math, block size pinned so the trace is
    flag-independent."""
    import jax
    import numpy as np

    from ..base.flags import set_flags
    from ..distributed import collective_opt as copt

    set_flags({"comm_quantize_block": 256})
    closed = jax.make_jaxpr(copt.qpsum_reference)(
        jax.ShapeDtypeStruct((4, 33, 65), np.dtype("float32")))
    programs["collective/qpsum"] = fingerprint_jaxpr(closed)


def _reshard_fingerprints(programs, skipped) -> None:
    """The portable reshard route's shard_map program for the flagship
    s_to_s transition (Shard(0) -> Shard(1) over dp=8)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from ..distributed.auto_parallel.placement_type import Shard
    from ..distributed.collective_opt import reshard as rs

    if len(jax.devices()) < 8:
        skipped["reshard/s_to_s"] = 8
        return

    class _MeshView:
        dim_names = ["dp"]
        shape = [8]

    route = rs.plan_route([Shard(0)], [Shard(1)], _MeshView(), (8, 8), 4)
    jmesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    prog = rs._route_program(route, jmesh, P("dp", None), P(None, "dp"),
                             (8, 8), "float32")
    closed = jax.make_jaxpr(prog)(
        jax.ShapeDtypeStruct((8, 8), np.dtype("float32")))
    programs["reshard/s_to_s"] = fingerprint_jaxpr(closed,
                                                   axis_sizes={"dp": 8})


# built once per process and shared by the lint runner, the gate tests
# and --update-lock: the TrainStep tiers are the only builders that
# compile, and even those only once
_live_memo: list = []


def record_drift_programs(refresh: bool = False) -> dict:
    """Build (or return memoized) the live program set: ``{"programs":
    {name: fingerprint}, "rung_grids": {group: [rung, ...]}, "skipped":
    {name: min_devices}}``. ``skipped`` programs need more devices than
    this process has — they become PD1200 *warnings*, never errors."""
    if _live_memo and not refresh:
        return _live_memo[0]
    programs: Dict[str, dict] = {}
    rung_grids: Dict[str, List[str]] = {}
    skipped: Dict[str, int] = {}
    with _hermetic() as env:
        _train_fingerprints(env, programs, skipped)
        _serving_fingerprints(programs, rung_grids)
        _decode_fingerprints(programs, rung_grids)
        _retention_fingerprints(programs, rung_grids)
        _latent_fingerprints(programs, rung_grids)
        _windowed_fingerprints(programs, rung_grids)
        _qpsum_fingerprint(programs)
        _reshard_fingerprints(programs, skipped)
    live = {"programs": programs, "rung_grids": rung_grids,
            "skipped": skipped}
    _live_memo[:] = [live]
    return live


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def _dtype_narrowing(name: str, want: dict, got: dict) -> List[Finding]:
    out: List[Finding] = []
    for wide, width in sorted(_FLOAT_WIDTH.items()):
        w_b, g_b = int(want.get(wide, 0)), int(got.get(wide, 0))
        if w_b <= 0 or g_b >= 0.999 * w_b:
            continue
        narrower = [d for d, wd in _FLOAT_WIDTH.items() if wd < width]
        w_n = sum(int(want.get(d, 0)) for d in narrower)
        g_n = sum(int(got.get(d, 0)) for d in narrower)
        if g_n > w_n:
            out.append(Finding(
                _ANALYZER, "PD1204", "error",
                f"'{name}' narrowed its {wide} traffic: {w_b} -> {g_b} "
                f"operand bytes while narrower-float bytes grew "
                f"{w_n} -> {g_n} — an accumulator or reduction silently "
                "lost precision; if the mixed-precision change is "
                "deliberate, regenerate the lockfile "
                "(python -m tools.lint --update-lock)",
                f"{name}:{wide}"))
    return out


def compare_lock(lock: dict, live: dict) -> List[Finding]:
    """PD120x findings from one locked-vs-live program set pair. Pure —
    unit-testable on synthetic dicts; the ratio caps come from the
    ``FLAGS_drift_max_*_ratio`` tolerance flags. Downward cost drift
    never gates (the lock is a budget, not a checksum): accept an
    improvement by regenerating the lockfile."""
    from ..base.flags import get_flag
    from .cost_model import _COLLECTIVE_PRIMS

    findings: List[Finding] = []

    def add(code, sev, msg, loc):
        findings.append(Finding(_ANALYZER, code, sev, msg, loc))

    locked = lock.get("programs", {}) or {}
    live_p = live.get("programs", {}) or {}
    skipped = live.get("skipped", {}) or {}

    for name in sorted(locked):
        if name in live_p:
            continue
        if name in skipped:
            add("PD1200", "warning",
                f"locked program '{name}' was skipped: it needs >= "
                f"{skipped[name]} devices and this process has fewer — "
                "its drift is UNCHECKED here (the 8-device CPU harness "
                "covers it)", name)
        else:
            add("PD1200", "error",
                f"locked program '{name}' is extinct: no live builder "
                "produces it anymore — if the removal is deliberate, "
                "regenerate the lockfile (python -m tools.lint "
                "--update-lock) and commit it", name)
    for name in sorted(set(live_p) - set(locked)):
        add("PD1200", "error",
            f"live program '{name}' is missing from the lockfile — the "
            "lock is stale; run python -m tools.lint --update-lock and "
            "commit programs.lock.json", name)

    for name in sorted(set(locked) & set(live_p)):
        want, got = locked[name], live_p[name]

        w_prims = want.get("primitives", {}) or {}
        g_prims = got.get("primitives", {}) or {}
        for prim in sorted(set(g_prims) - set(w_prims)):
            add("PD1201", "error",
                f"new primitive '{prim}' (x{g_prims[prim]}) appeared in "
                f"'{name}' — the locked program never runs it; a host "
                "callback, stray cast or collective crept into the "
                "traced step", f"{name}:{prim}")
        for prim in sorted(set(w_prims) - set(g_prims)):
            if prim in _COLLECTIVE_PRIMS:
                add("PD1201", "error",
                    f"locked collective '{prim}' vanished from '{name}' "
                    "— a sharding/sync tier silently disengaged",
                    f"{name}:{prim}")
            else:
                add("PD1201", "warning",
                    f"locked primitive '{prim}' vanished from '{name}' — "
                    "harmless if the op was legitimately fused or "
                    "simplified; regenerate the lockfile to accept",
                    f"{name}:{prim}")

        w_coll = want.get("collectives", {}) or {}
        g_coll = got.get("collectives", {}) or {}
        for ax in sorted(set(w_coll) - set(g_coll)):
            add("PD1201", "error",
                f"'{name}' lost every collective on mesh axis '{ax}' "
                f"(locked {w_coll[ax]}) — the sync tier on that axis "
                "disengaged", f"{name}:axis:{ax}")

        w_cost = want.get("cost", {}) or {}
        g_cost = got.get("cost", {}) or {}
        for metric in sorted(_RATIO_FLAGS):
            flag = _RATIO_FLAGS[metric]
            lo = float(w_cost.get(metric, 0) or 0)
            hi = float(g_cost.get(metric, 0) or 0)
            cap = float(get_flag(flag))
            if lo <= 0 < hi and metric == "comm_bytes":
                add("PD1202", "error",
                    f"'{name}' cost metric comm_bytes appeared from zero "
                    f"(locked 0, live {hi:.0f}) — the locked program "
                    "moves no collective traffic; a new sync entered the "
                    "step", f"{name}:{metric}")
            elif lo > 0 and hi / lo > cap:
                add("PD1202", "error",
                    f"'{name}' cost metric {metric} drifted "
                    f"{hi / lo:.2f}x over the locked value (locked "
                    f"{lo:.0f}, live {hi:.0f}, budget FLAGS_{flag} = "
                    f"{cap}x) — raise the tolerance or regenerate the "
                    "lockfile if the regression is intended",
                    f"{name}:{metric}")
        w_guards = int(w_cost.get("guard_preds", 0) or 0)
        g_guards = int(g_cost.get("guard_preds", 0) or 0)
        if g_guards > w_guards:
            add("PD1202", "error",
                f"'{name}' cost metric guard_preds grew {w_guards} -> "
                f"{g_guards} — every added predicate is a device->host "
                "sync on EVERY call", f"{name}:guard_preds")

        for d in want.get("donation", []) or []:
            if d not in (got.get("donation", []) or []):
                add("PD1203", "error",
                    f"'{name}' lost the donation of {d!r}: the locked "
                    "program donates it, the live one does not — XLA "
                    "loses the in-place buffer reuse and the step's "
                    "residency roughly doubles", f"{name}:{d}")

        findings.extend(_dtype_narrowing(
            name, want.get("dtype_bytes", {}) or {},
            got.get("dtype_bytes", {}) or {}))

    w_grids = lock.get("rung_grids", {}) or {}
    g_grids = live.get("rung_grids", {}) or {}
    for group in sorted(w_grids):
        if group not in g_grids:
            add("PD1205", "error",
                f"rung grid '{group}' vanished: the lock records "
                f"{len(w_grids[group])} rung(s) and no live builder "
                "produces the group anymore", group)
            continue
        missing = [r for r in w_grids[group] if r not in g_grids[group]]
        if missing:
            add("PD1205", "error",
                f"rung grid '{group}' shrank: locked rung(s) {missing} "
                "are no longer built — traffic on those shapes would "
                "retrace at serve time instead of replaying warm", group)
    return findings


def check_drift(live: Optional[dict] = None,
                lock_path: Optional[str] = None) -> List[Finding]:
    """The ``drift`` lint family's entry point: load the committed
    lockfile, build (memoized) the live program set, compare."""
    lock_path = lock_path or default_lock_path()
    if not os.path.isfile(lock_path):
        return [Finding(
            _ANALYZER, "PD1200", "error",
            f"program lockfile '{lock_path}' is missing — run "
            "python -m tools.lint --update-lock and commit "
            f"{LOCK_BASENAME}", lock_path)]
    try:
        with open(lock_path, "r", encoding="utf-8") as fh:
            lock = json.load(fh)
    except (json.JSONDecodeError, UnicodeDecodeError, OSError) as e:
        return [Finding(
            _ANALYZER, "PD999", "error",
            f"program lockfile does not parse: {e} — regenerate it with "
            "python -m tools.lint --update-lock", lock_path)]
    if live is None:
        live = record_drift_programs()
    return compare_lock(lock, live)


# ---------------------------------------------------------------------------
# lockfile generation
# ---------------------------------------------------------------------------

def render_lock(live: dict) -> str:
    """The lockfile text for one live program set: sorted keys, two-space
    indent, trailing newline, no timestamps — byte-deterministic."""
    doc = {"version": LOCK_VERSION,
           "programs": live["programs"],
           "rung_grids": live["rung_grids"]}
    return json.dumps(doc, sort_keys=True, indent=2) + "\n"


def update_lock(lock_path: Optional[str] = None,
                refresh: bool = True) -> str:
    """Regenerate the lockfile from a fresh build of every program.
    Refuses to write when any program was skipped for insufficient
    devices: a shrunken lockfile would silently stop gating the
    multi-device tiers."""
    lock_path = lock_path or default_lock_path()
    live = record_drift_programs(refresh=refresh)
    if live["skipped"]:
        need = max(live["skipped"].values())
        raise RuntimeError(
            "refusing to write a shrunken lockfile: "
            f"{sorted(live['skipped'])} need >= {need} devices and this "
            "process has fewer — regenerate under the 8-device CPU "
            "harness (JAX_PLATFORMS=cpu python -m tools.lint "
            "--update-lock)")
    text = render_lock(live)
    with open(lock_path, "w", encoding="utf-8") as fh:
        fh.write(text)
    return lock_path
