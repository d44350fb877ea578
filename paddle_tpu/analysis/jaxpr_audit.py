"""Jaxpr auditor: trace-level verification of compiled programs (JX3xx).

PR 1's analysis tier stops at the AST (:mod:`trace_safety`) and the
recorded static ``Program`` (:mod:`program_verify`); this pass inspects
what the functionalizer actually hands to XLA — the ClosedJaxpr of every
``CompiledFunction`` cache entry, re-derived with ``jax.make_jaxpr`` over
the entry's recorded ``pure`` wrapper (trace only, no XLA compilation).
TPU-fatal defects that only exist at this level:

JX300  audit retrace failed    the entry's pure wrapper no longer traces
JX301  host callback           pure_callback/io_callback/debug_callback
                               (jax.debug.print) inside the compiled
                               program — a per-step host round-trip on TPU
JX302  64-bit dtype leak       float64/complex128 aval (error) or
                               int64/uint64 aval (warning) in the program:
                               silently 3-8x slower or unsupported on TPU
JX303  dead value              a user output that is a trace-time constant
                               (baked at trace), or a captured cell the
                               program neither reads nor updates
                               (over-capture) — warnings
JX304  donation alias          a user-visible output aliases a donated
                               cell buffer: the next step's donation
                               invalidates the array the caller still holds
JX305  dynamic shape           an aval whose dim is not a concrete int —
                               XLA on TPU compiles static shapes only
JX306  guard coverage          a guarded family whose recorded branch
                               signature has no specialization (error), or
                               that degraded to committed eager fallback
                               (warning, with the recorded reason)

Recompilation audit (cache-key cardinality, on the same findings stream):

JX310  cache growth            distinct cache keys exceed the
                               ``jaxpr_audit_max_cache_keys`` flag —
                               unbounded retrace suspect (warning)
JX311  float static key        ``static_key_fn`` returned a float-valued
                               key: every distinct value compiles a new
                               program (error)
JX312  unhashable static key   ``static_key_fn`` result is unhashable —
                               the cache lookup itself would raise (error)
JX313  bucket ladder           a ``BucketedFunction`` ladder implying more
                               programs than the cache-key budget, or a
                               non-monotonic bucket list (error)

Eager kernel-cache audit (JX32x, over ``core.kernel_cache.stats()``
counters — the per-op dispatch fast path, not the whole-step jit tier;
see :func:`audit_kernel_cache`):

JX320  bypass storm            an op whose fast-path bypasses are dominated
                               by unhashable signatures: it never enters
                               the cache and silently pays trace-per-call
JX321  miss ladder             an op with more cache misses than the key
                               budget and fewer hits than misses — its key
                               churns and every step compiles anew
JX322  eviction thrash         evictions rival hits across the cache: the
                               LRU capacity is below the working set

Serving audit (JX33x, over a ``serving.ServingEngine``'s warm-compile
counters — the multi-tenant continuous-batching tier; see
:func:`audit_serving`, reported under the ``serving`` lint family):

JX330  serving retrace         the engine's batched program compiled new
                               specializations AFTER warmup — per-request
                               recompiles in the steady state break the
                               latency SLO (a request outside the warmed
                               ladder, or a shape leaking past the
                               pad-to-bucket step) (error)
JX331  cold ladder             the engine serves without warmup, or rungs
                               of its bucket ladder were never
                               warm-compiled: the first live request on a
                               cold rung pays the compile (warning)
JX332  KV pool growth          a decode engine's KV slot pool changed its
                               device footprint after warmup — the pool
                               must be allocated once and reuse slots
                               (O(max_slots) residency, not O(traffic))
                               (error)
JX333  slot leak               KV slots remain allocated with no active
                               request: a retired sequence never released
                               its slot and the pool will exhaust
                               (warning)
JX334  page fragmentation      mean utilization of in-use KV pages sits
                               under the fragmentation watermark: the page
                               size is too coarse for the traffic
                               (warning)
JX335  spec rung parity        a speculating decode engine's draft/verify
                               program grids disagree with each other or
                               with the plain decode grid — the first
                               speculation round on an uncovered (batch ×
                               table) shape traces mid-traffic (warning)

Entry points: ``CompiledFunction.audit()`` / ``TrainStep.audit()`` (this
module's :func:`audit_compiled_function`), and the ``jaxpr`` analyzer of
``python -m tools.lint`` which audits a freshly built representative
train step. ``audit_report()`` is the no-trace companion: per-cache-key
build counts from counters maintained at build time, so the hot
``CompiledFunction.__call__`` path carries zero audit cost.
"""
from __future__ import annotations

from typing import List, Optional

from . import Finding

_ANALYZER = "jaxpr"

# primitives that escape to the host from inside a compiled program
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "debug_print", "callback", "host_callback_call",
                   "outside_call"}
_F64_DTYPES = {"float64", "complex128"}
_I64_DTYPES = {"int64", "uint64"}


def _iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params
    (pjit/scan/while/cond bodies)."""
    from jax.extend import core as jex_core

    seen = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        seen.append(j)
        for eqn in j.eqns:
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else (v,)
                for item in vs:
                    if isinstance(item, jex_core.ClosedJaxpr):
                        stack.append(item.jaxpr)
                    elif isinstance(item, jex_core.Jaxpr):
                        stack.append(item)
    return seen


def _aval_dtype(var):
    aval = getattr(var, "aval", None)
    return str(getattr(aval, "dtype", "")) if aval is not None else ""


def _aval_shape(var):
    aval = getattr(var, "aval", None)
    return getattr(aval, "shape", ()) if aval is not None else ()


def audit_jaxpr(closed_jaxpr, *, location: str = "",
                n_cells: int = 0, n_user_outs: Optional[int] = None,
                donated: bool = False, cell_names=None) -> List[Finding]:
    """Walk one ClosedJaxpr and emit JX301-JX305 findings.

    ``n_cells`` leading invars are the functionalizer's state cells;
    outvars are laid out ``[user outputs..., new cell values..., guard
    predicates...]`` with ``n_user_outs`` user leaves (None disables the
    segment-aware checks JX303-outputs/JX304)."""
    from jax.extend import core as jex_core

    findings: List[Finding] = []

    def add(code, severity, message, loc_suffix=""):
        findings.append(Finding(
            _ANALYZER, code, severity, message,
            f"{location}{loc_suffix}" if location else loc_suffix))

    jaxpr = closed_jaxpr.jaxpr
    seen_cb = set()
    seen_dtype = set()
    for j in _iter_jaxprs(jaxpr):
        for eqn in j.eqns:
            pname = eqn.primitive.name
            if pname in _CALLBACK_PRIMS and pname not in seen_cb:
                seen_cb.add(pname)
                add("JX301", "error",
                    f"host callback primitive '{pname}' inside the compiled "
                    "program — a per-step host round-trip stalls the TPU "
                    "pipeline (jax.debug.print / io_callback / pure_callback "
                    "under trace)")
            for var in list(eqn.invars) + list(eqn.outvars):
                dt = _aval_dtype(var)
                if dt in _F64_DTYPES and dt not in seen_dtype:
                    seen_dtype.add(dt)
                    add("JX302", "error",
                        f"{dt} value inside the compiled program ('{pname}') "
                        "— f64 silently degrades or fails on TPU; cast to "
                        "float32/bfloat16 before trace")
                elif dt in _I64_DTYPES and dt not in seen_dtype:
                    seen_dtype.add(dt)
                    add("JX302", "warning",
                        f"{dt} value inside the compiled program ('{pname}') "
                        "— 64-bit ints are emulated on TPU")
                for dim in _aval_shape(var):
                    if not isinstance(dim, int):
                        add("JX305", "error",
                            f"dynamic dimension {dim!r} in an aval of "
                            f"'{pname}' — XLA TPU programs are static-shape "
                            "only")
                        break

    # 64-bit leaks on the program boundary (inputs/outputs) too
    for var in list(jaxpr.invars) + list(jaxpr.outvars):
        dt = _aval_dtype(var)
        if dt in _F64_DTYPES and dt not in seen_dtype:
            seen_dtype.add(dt)
            add("JX302", "error",
                f"{dt} value on the compiled program boundary — f64 "
                "silently degrades or fails on TPU")

    if n_user_outs is None:
        return findings

    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, jex_core.Var):
                used.add(v)

    cell_invars = list(jaxpr.invars[:n_cells])
    outvars = list(jaxpr.outvars)
    user_outs = outvars[:n_user_outs]
    cell_outs = outvars[n_user_outs:n_user_outs + n_cells]
    constvars = set(jaxpr.constvars)

    # JX303: user outputs that are trace-time constants
    for i, v in enumerate(user_outs):
        if isinstance(v, jex_core.Literal) or v in constvars:
            add("JX303", "warning",
                f"output #{i} is a trace-time constant — it was baked in "
                "during tracing (e.g. a live cell Tensor returned after its "
                "value was restored) and will never change across calls",
                f":out[{i}]")

    # JX303: captured cells the program neither reads nor updates
    for i, (cin, cout) in enumerate(zip(cell_invars, cell_outs)):
        if cin not in used and cout is cin:
            name = None
            if cell_names and i < len(cell_names):
                name = cell_names[i]
            add("JX303", "warning",
                f"captured cell #{i}{f' ({name})' if name else ''} is never "
                "read or updated by the program — discovery over-captured "
                "state", f":cell[{i}]")

    # JX304: user-visible outputs aliasing donated cell buffers
    if donated:
        donated_vars = set(cell_invars)
        cell_out_vars = {v for v in cell_outs if isinstance(v, jex_core.Var)}
        for i, v in enumerate(user_outs):
            if not isinstance(v, jex_core.Var):
                continue
            if v in donated_vars or v in cell_out_vars:
                add("JX304", "error",
                    f"output #{i} aliases a donated cell buffer — the next "
                    "step's donation invalidates the array the caller still "
                    "holds (return a copy, or disable donate_cells)",
                    f":out[{i}]")

    return findings


class RetraceError(RuntimeError):
    """A cache entry that cannot be re-derived into a ClosedJaxpr."""


def retrace_entry(entry):
    """Re-derive one cache entry's ClosedJaxpr from its recorded ``pure``
    wrapper + abstract call (``jax.make_jaxpr`` — trace only, no XLA
    compilation). Shared by the JX3xx auditor and the cost model
    (``analysis/cost_model.py``). Returns ``(closed_jaxpr, n_user_outs,
    n_cells)``; raises :class:`RetraceError` when the entry predates the
    audit tier or no longer traces."""
    import jax
    import numpy as np

    pure = entry.get("pure") or getattr(entry.get("jitted"), "__wrapped__", None)
    abstract_call = entry.get("abstract_call")
    if pure is None or abstract_call is None:
        raise RetraceError(
            "cache entry records no pure wrapper / abstract call "
            "to retrace (entry predates the audit tier?)")
    cells = entry["cells"]
    try:
        cell_sds = [jax.ShapeDtypeStruct(np.shape(c._value), c._value.dtype)
                    for c in cells]
        args, kwargs = abstract_call
        closed, out_shape = jax.make_jaxpr(pure, return_shape=True)(
            cell_sds, args, kwargs)
    except Exception as e:
        raise RetraceError(
            f"audit retrace failed: {str(e).splitlines()[0]}") from e
    n_user_outs = len(jax.tree_util.tree_leaves(out_shape[0]))
    return closed, n_user_outs, len(cells)


def _audit_entry(cf, entry, *, location: str, donated: bool) -> List[Finding]:
    """Retrace one cache entry's pure wrapper (no compilation) and audit
    the resulting ClosedJaxpr."""
    try:
        closed, n_user_outs, n_cells = retrace_entry(entry)
    except RetraceError as e:
        return [Finding(_ANALYZER, "JX300", "error", str(e), location)]
    cells = entry["cells"]
    return audit_jaxpr(
        closed, location=location, n_cells=n_cells,
        n_user_outs=n_user_outs, donated=donated,
        cell_names=[getattr(c, "name", None) for c in cells])


def _contains_float(value) -> bool:
    import numpy as np

    if isinstance(value, (float, np.floating)):
        return True
    if isinstance(value, (tuple, list, set, frozenset)):
        return any(_contains_float(v) for v in value)
    if isinstance(value, dict):
        return any(_contains_float(v) for v in list(value.keys()) + list(value.values()))
    return False


def _max_cache_keys(override=None) -> int:
    if override is not None:
        return int(override)
    try:
        from ..base.flags import get_flag

        return int(get_flag("jaxpr_audit_max_cache_keys"))
    except Exception:
        return 32


def audit_compiled_function(cf, max_cache_keys=None,
                            only_entry=None) -> List[Finding]:
    """Audit every cache entry of one ``CompiledFunction`` plus the
    recompilation heuristics. Tracing only — never compiles.
    ``only_entry`` restricts the per-entry RETRACE audits to that one
    cache entry (by identity) — the runtime build hook's O(1) path; the
    cheap non-retracing checks (guard coverage, cache-key heuristics)
    always run."""
    findings: List[Finding] = []
    name = getattr(cf, "name", "fn")

    for idx, (key, entry) in enumerate(list(cf._cache.items())):
        loc = f"{name}[{idx}]"
        if entry.get("guarded"):
            if entry.get("eager"):
                findings.append(Finding(
                    _ANALYZER, "JX306", "warning",
                    "guard family committed to eager fallback: "
                    f"{cf.fallback_reason or 'unrecorded reason'} — branch "
                    "coverage lost, steps run uncompiled", loc))
                continue
            if entry["last"] not in entry["entries"]:
                findings.append(Finding(
                    _ANALYZER, "JX306", "error",
                    f"recorded branch signature {entry['last']} has no "
                    "specialized entry and no fallback — the next call on "
                    "this path cannot resolve to a program", loc))
            for outcomes, sub in entry["entries"].items():
                if only_entry is not None and sub is not only_entry:
                    continue
                findings.extend(_audit_entry(
                    cf, sub, location=f"{loc}:guards={outcomes}",
                    donated=False))
        elif entry.get("eager"):
            findings.append(Finding(
                _ANALYZER, "JX306", "warning",
                "entry committed to eager fallback: "
                f"{cf.fallback_reason or 'unrecorded reason'}", loc))
        else:
            if only_entry is not None and entry is not only_entry:
                continue
            findings.extend(_audit_entry(
                cf, entry, location=loc,
                donated=bool(getattr(cf, "donate_cells", False))))

    # ---- recompilation audit -------------------------------------------
    limit = _max_cache_keys(max_cache_keys)
    if len(cf._cache) > limit:
        findings.append(Finding(
            _ANALYZER, "JX310", "warning",
            f"{len(cf._cache)} distinct cache keys (> {limit}) — every key "
            "is one compiled program; unbounded key growth means unbounded "
            "retrace (check static_key_fn and input-shape churn)", name))

    key_fn = getattr(cf, "static_key_fn", None)
    if key_fn is not None:
        try:
            static_key = key_fn()
        except Exception as e:
            findings.append(Finding(
                _ANALYZER, "JX312", "error",
                f"static_key_fn raised at audit time: {e}", name))
        else:
            try:
                hash(static_key)
            except TypeError:
                findings.append(Finding(
                    _ANALYZER, "JX312", "error",
                    f"static_key_fn returned an unhashable "
                    f"{type(static_key).__name__} — the compile-cache lookup "
                    "itself raises on every call", name))
            else:
                if _contains_float(static_key):
                    findings.append(Finding(
                        _ANALYZER, "JX311", "error",
                        f"static_key_fn returned a float-valued key "
                        f"{static_key!r} — every distinct value compiles a "
                        "new program (quantize it, or pass it as a traced "
                        "input)", name))
    return findings


def audit_bucketed_function(bf, max_cache_keys=None) -> List[Finding]:
    """Audit a ``BucketedFunction``: the wrapped cache plus the ladder
    heuristics (JX313)."""
    findings = audit_compiled_function(bf._compiled,
                                       max_cache_keys=max_cache_keys)
    name = bf._compiled.name
    buckets = list(bf.buckets)
    if any(b >= c for b, c in zip(buckets, buckets[1:])):
        findings.append(Finding(
            _ANALYZER, "JX313", "error",
            f"bucket ladder {buckets} is not strictly increasing — "
            "bucket_for resolves lengths to the wrong program", name))
    limit = _max_cache_keys(max_cache_keys)
    if len(buckets) > limit:
        findings.append(Finding(
            _ANALYZER, "JX313", "error",
            f"bucket ladder has {len(buckets)} rungs (> {limit}) — each rung "
            "is one compiled program per static key; this config implies "
            "unbounded cache growth", name))
    if not bf.bucket_axes:
        findings.append(Finding(
            _ANALYZER, "JX313", "warning",
            "BucketedFunction declares no bucket_axes — every distinct "
            "input shape compiles its own program (the ladder never "
            "engages)", name))
    return findings


def audit_kernel_cache(stats=None, max_keys_per_op=None,
                       bypass_threshold=64) -> List[Finding]:
    """JX32x: health of the eager dispatch kernel cache
    (``core/kernel_cache.py``) from its ``stats()`` counters. Pure counter
    arithmetic — safe to run on the live process or on a recorded
    snapshot; pass ``stats`` (either the full ``stats()`` dict or its
    per-op ``"ops"`` mapping) for seeded/offline audits."""
    findings: List[Finding] = []
    if stats is None:
        from ..core import kernel_cache

        stats = kernel_cache.stats()
    ops = stats.get("ops", stats)
    limit = _max_cache_keys(max_keys_per_op)

    total_hits = 0
    total_evictions = 0
    # key=str: op names are arbitrary caller strings (a None or other
    # non-string name must not crash the analyzer, just sort textually)
    for op, s in sorted(ops.items(), key=lambda kv: str(kv[0])):
        hits = int(s.get("hits", 0))
        misses = int(s.get("misses", 0))
        bypasses = int(s.get("bypasses", 0))
        total_hits += hits
        total_evictions += int(s.get("evictions", 0))

        # only the 'unhashable' reason is a storm: hook gates (amp/
        # discovery/observer) and array/PRNG-key captures (dropout's
        # per-call key) are deliberate bypasses, not defects
        reasons = s.get("bypass_reasons", {})
        unhashable = int(reasons.get("unhashable", 0))
        if unhashable >= bypass_threshold:
            findings.append(Finding(
                _ANALYZER, "JX320", "warning",
                f"{unhashable} fast-path bypasses for unhashable signatures "
                f"(of {bypasses} total) — the op never enters the kernel "
                "cache and pays a fresh trace per call (make its attrs/"
                "closure values hashable, or deny-list it deliberately)",
                f"kernel_cache:{op}"))

        if misses > limit and hits < misses:
            findings.append(Finding(
                _ANALYZER, "JX321", "warning",
                f"{misses} cache misses vs {hits} hits (> {limit} distinct "
                "signatures) — the op's key churns (per-step scalar attrs or "
                "shape ladder?) and every miss compiles a new executable",
                f"kernel_cache:{op}"))

    if total_evictions > 0 and total_evictions >= max(total_hits, 1):
        findings.append(Finding(
            _ANALYZER, "JX322", "warning",
            f"{total_evictions} evictions vs {total_hits} hits — the LRU "
            "working set exceeds FLAGS_eager_kernel_cache_max_entries; "
            "executables are rebuilt as fast as they are reused",
            "kernel_cache"))
    return findings


def audit_serving(engine) -> List[Finding]:
    """JX33x: the serving tier's retrace-free contract, from a
    ``ServingEngine``'s (or any duck-typed equivalent's) warm-compile
    counters. Pure counter reads — safe on a live engine mid-traffic.

    The contract: after ``warmup()`` compiled every rung of the bucket
    ladder, steady-state traffic replays those executables and NEVER
    traces again — ``compiles_after_warmup`` must stay 0. Anything else
    means a per-request compile is hiding inside the latency SLO.
    """
    findings: List[Finding] = []
    name = "serving"
    delta = getattr(engine, "compiles_after_warmup", None)
    if delta is None:
        findings.append(Finding(
            "serving", "JX331", "warning",
            "engine serves without warmup(): the first request on every "
            "bucket rung pays its compile inside the request latency",
            name))
    elif delta > 0:
        findings.append(Finding(
            "serving", "JX330", "error",
            f"{delta} new compiled specialization(s) AFTER warmup — "
            "steady-state serving must replay the warmed ladder only; a "
            "request shape is escaping the pad-to-bucket step or the "
            "ladder does not cover the traffic", name))

    # ladder coverage: rungs never warmed serve their first request cold
    predictor = getattr(engine, "predictor", None)
    prog = getattr(predictor, "_batch_program", None)
    if prog is not None and getattr(prog, "warmed", None) is not None:
        rungs = getattr(prog, "rungs", None) or prog.ladder
        missing = sorted(set(rungs) - set(prog.warmed))
        if missing and delta is not None:
            findings.append(Finding(
                "serving", "JX331", "warning",
                f"bucket rungs {missing} were never warm-compiled — the "
                "first live batch assembled at those rungs compiles "
                "mid-traffic", name))

    # KV-cache decode engines (serving/kv_cache.py): the pool — slot
    # rows or pages — must be allocated ONCE; steady state reuses freed
    # units, never grows
    pool = getattr(engine, "kv_pool", None)
    if pool is not None:
        paged = getattr(pool, "page_size", None) is not None
        unit = "page" if paged else "slot"
        baseline = getattr(pool, "bytes_at_warmup", None)
        if baseline is not None and pool.device_bytes() != baseline:
            findings.append(Finding(
                "serving", "JX332", "error",
                f"KV {unit} pool device bytes changed after warmup "
                f"({baseline} -> {pool.device_bytes()}) — the pool must be "
                f"allocated once and reuse {unit}s; growth means decode "
                "memory is O(traffic), not O(pool)", name))
        if (not getattr(engine, "active_requests", lambda: 0)()
                and pool.in_use() > 0):
            findings.append(Finding(
                "serving", "JX333", "warning",
                f"{pool.in_use()} KV {unit}(s) still allocated with no "
                f"active request — a retired sequence leaked its {unit}s "
                "and the pool will exhaust under sustained traffic", name))
        # JX334: paged pools only — fragmentation watermark. Low mean
        # utilization of IN-USE pages means the page size is too coarse
        # for the traffic (most of each borrowed page is dead capacity).
        util = getattr(pool, "utilization_report", None)
        if util is not None:
            from ..base.flags import get_flag

            rep = util()
            floor = float(get_flag("serving_frag_warn_utilization"))
            if rep["samples"] >= 8 and rep["mean"] < floor:
                findings.append(Finding(
                    "serving", "JX334", "warning",
                    f"mean KV page utilization {rep['mean']:.2f} over "
                    f"{rep['samples']} decode steps is below the "
                    f"fragmentation watermark ({floor}) — live tokens fill "
                    "little of the pages they hold; shrink "
                    "FLAGS_serving_page_size so residency tracks live "
                    "tokens, not page granularity", name))
    # JX335: self-speculation rung-grid parity (paged decode engines
    # built with speculate_k > 0). The draft and verify families must
    # cover the SAME (batch × table) grid as plain decode — any hole is
    # a cold-path retrace waiting for the first speculation round that
    # assembles at that shape (warning: it bites only when it lands).
    progs = getattr(engine, "programs", None)
    if progs is not None and getattr(progs, "speculate_k", 0):
        grid = list(getattr(progs, "warmed", None)
                    or getattr(progs, "rungs", ()) or ())
        decodes = {k[1:] for k in grid if k[0] == "decode"}
        drafts = {k[1:] for k in grid if k[0] == "draft"}
        verifies = {k[1:] for k in grid if k[0] == "verify"}
        holes = sorted((drafts ^ verifies)
                       | (decodes - drafts) | (decodes - verifies))
        if holes:
            findings.append(Finding(
                "serving", "JX335", "warning",
                f"draft/verify rung grid out of parity at {holes}: every "
                "(batch × table) rung plain decode serves needs BOTH a "
                "draft and a verify executable, or toggling speculation "
                "mid-flight compiles inside the request latency", name))
    return findings


def record_demo_engine(tmpdir: str):
    """Build, warm and briefly drive the representative serving engine the
    ``serving`` lint analyzer audits: a tiny exported MLP behind a 3-rung
    ladder serving two tenants' mixed-size requests. One definition so the
    CLI and the test gate audit the SAME engine."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from ..base import global_state
    from ..profiler.pipeline import ServingStats

    gen = global_state.default_generator
    prev_seed = gen._seed
    prev_cell = gen._cell
    prev_key = None if prev_cell is None else prev_cell._value
    try:
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        net.eval()
        prefix = tmpdir + "/demo_served"
        paddle.jit.save(net, prefix,
                        input_spec=[paddle.static.InputSpec([None, 8],
                                                            "float32")])
    finally:
        gen._seed = prev_seed
        if prev_cell is None:
            gen._cell = None
        else:
            gen._cell = prev_cell
            prev_cell._replace_value(prev_key)

    from ..serving import ServingEngine

    engine = ServingEngine(prefix, buckets=[1, 2, 4],
                           stats=ServingStats())  # private stats: no global bleed
    engine.warmup()
    rs = np.random.RandomState(0)
    for tenant, n in (("a", 1), ("b", 3), ("a", 2), ("b", 4)):
        engine.run(tenant, rs.randn(n, 8).astype(np.float32))
    engine.shutdown(drain=True)
    return engine


def record_demo_decode_engine():
    """Build, warm and briefly drive the representative DECODE engine the
    ``serving`` lint analyzer audits alongside the batch demo: a tiny GPT
    behind a paged KV pool, two tenants' mixed prompts joining and
    leaving the running batch. Exercises the full KV path — prefill
    grid, (batch × table) decode rungs, draft/verify speculation rungs,
    page alloc/release and speculative rollback — so JX330-JX335 all
    see real state. One definition so the CLI and the test gate audit
    the SAME engine."""
    import numpy as np

    import paddle_tpu as paddle
    from ..base import global_state
    from ..profiler.pipeline import ServingStats

    gen = global_state.default_generator
    prev_seed = gen._seed
    prev_cell = gen._cell
    prev_key = None if prev_cell is None else prev_cell._value
    try:
        paddle.seed(0)
        from ..models.gpt import GPTForCausalLM, gpt_tiny

        model = GPTForCausalLM(gpt_tiny(
            num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
            max_position_embeddings=32))
        model.eval()
    finally:
        gen._seed = prev_seed
        if prev_cell is None:
            gen._cell = None
        else:
            gen._cell = prev_cell
            prev_cell._replace_value(prev_key)

    from ..serving import DecodeEngine

    engine = DecodeEngine(model, max_slots=2, max_seq=16, seq_buckets=[8],
                          prefill_max_batch=2, speculate_k=2,
                          spec_draft_layers=1, stats=ServingStats())
    engine.warmup()
    rs = np.random.RandomState(0)
    reqs = [engine.submit(t, rs.randint(0, 512, size=n).astype(np.int32),
                          max_new_tokens=3)
            for t, n in (("a", 4), ("b", 6), ("a", 3))]
    for r in reqs:
        r.result(60)
    engine.shutdown(drain=True)
    return engine


def record_demo_step():
    """Build, run (two steps) and return the representative whole-step
    ``TrainStep`` the ``jaxpr`` lint analyzer audits — one definition so
    the CLI and the test gates audit the SAME program (mirrors
    ``program_verify.record_demo_program``)."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from ..base import global_state
    from ..jit.api import TrainStep

    # the demo needs a deterministic init, but an in-process health check
    # must not reseed the caller's RNG stream: save/restore the generator
    gen = global_state.default_generator
    prev_seed = gen._seed
    prev_cell = gen._cell
    prev_key = None if prev_cell is None else prev_cell._value
    try:
        paddle.seed(0)
        model = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        crit = nn.MSELoss()
        step = TrainStep(model=model, optimizer=opt,
                         loss_fn=lambda x, y: crit(model(x), y))
        x = paddle.Tensor(np.ones((2, 8), np.float32), stop_gradient=True)
        y = paddle.Tensor(np.zeros((2, 4), np.float32), stop_gradient=True)
        step(x, y)
        step(x, y)
    finally:
        gen._seed = prev_seed
        if prev_cell is None:
            gen._cell = None  # recreate lazily from the restored seed
        else:
            gen._cell = prev_cell
            prev_cell._replace_value(prev_key)
    return step
