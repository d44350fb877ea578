"""Layered process-level flag system.

TPU-native analog of the reference's gflags registry
(/root/reference/paddle/common/flags.h, flags.cc): flags are defined in-process,
overridable by ``FLAGS_<name>`` environment variables, and settable at runtime via
:func:`set_flags` (mirroring ``paddle.set_flags``).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, Iterable, Mapping

# bootstrap layer: this module is imported before (and BY)
# observability.locks, so its guard stays a bare primitive
_lock = threading.Lock()  # noqa: CX1003 — flags bootstrap precedes the registry
_registry: Dict[str, "_Flag"] = {}
# flag name -> callbacks fired (outside the lock) after set_flags changes
# it — for subsystems that mirror a flag into a hot-path attribute (the
# span tracer's `enabled`) instead of re-reading the registry per event
_on_change: Dict[str, list] = {}


class _Flag:
    __slots__ = ("name", "default", "value", "help", "type")

    def __init__(self, name: str, default: Any, help: str):
        self.name = name
        self.default = default
        self.help = help
        self.type = type(default)
        env = os.environ.get("FLAGS_" + name)
        self.value = _parse(env, self.type) if env is not None else default


def _parse(text: str, ty: type) -> Any:
    if ty is bool:
        return text.strip().lower() in ("1", "true", "yes", "on")
    if ty is int:
        return int(text)
    if ty is float:
        return float(text)
    return text


def define_flag(name: str, default: Any, help: str = "") -> None:
    """Register a flag (idempotent; later definitions keep the first default)."""
    with _lock:
        if name not in _registry:
            _registry[name] = _Flag(name, default, help)


def get_flag(name: str) -> Any:
    f = _registry.get(name)
    if f is None:
        raise KeyError(f"flag '{name}' is not defined")
    return f.value


def get_flags(names: Iterable[str] | str | None = None) -> Dict[str, Any]:
    if names is None:
        return {k: f.value for k, f in _registry.items()}
    if isinstance(names, str):
        names = [names]
    return {n: get_flag(n) for n in names}


def set_flags(flags: Mapping[str, Any]) -> None:
    with _lock:
        for name, value in flags.items():
            f = _registry.get(name)
            if f is None:
                raise KeyError(f"flag '{name}' is not defined")
            f.value = _parse(value, f.type) if isinstance(value, str) and f.type is not str else f.type(value)
    for name in flags:
        for fn in _on_change.get(name, ()):
            fn(_registry[name].value)


def on_flag_change(name: str, fn) -> None:
    """Register ``fn(new_value)`` to fire after :func:`set_flags` changes
    ``name``. The flag must already be defined."""
    if name not in _registry:
        raise KeyError(f"flag '{name}' is not defined")
    _on_change.setdefault(name, []).append(fn)


# Core flags (subset of the reference's 183 exported flags that are meaningful on TPU).
define_flag("check_nan_inf", False, "scan op outputs for NaN/Inf and raise")
define_flag("check_nan_inf_level", 0, "0: raise on nan/inf; >0: log only")
define_flag("benchmark", False, "synchronize after each op for timing")
define_flag("eager_jit_ops", True, "superseded by eager_kernel_cache (kept for compat)")
define_flag("eager_kernel_cache", True,
            "eager dispatch fast path: serve ops from the signature-keyed "
            "cache of jitted forward(+VJP) executables "
            "(paddle_tpu.core.kernel_cache) when the call is semantically "
            "transparent; 0 forces every op down the trace-per-call slow path")
define_flag("eager_kernel_cache_max_entries", 512,
            "LRU capacity of the eager kernel cache (one entry = one "
            "compiled executable per op signature); <=0 means unbounded")
define_flag("use_pallas_kernels", True, "use Pallas TPU kernels for fused ops when available")
define_flag("log_level", 1, "framework log verbosity (higher = chattier)")
define_flag("allocator_strategy", "xla", "memory allocator strategy (informational on TPU; XLA owns HBM)")
define_flag("embedding_deterministic", False, "deterministic embedding grad accumulation")
define_flag("static_verify_program", False,
            "run the analysis verify pass over a static Program before "
            "Executor.run compiles it (paddle_tpu.analysis.program_verify)")
define_flag("jaxpr_audit_max_cache_keys", 32,
            "CompiledFunction.audit() / BucketedFunction.audit() flag "
            "threshold: more distinct compile-cache keys (or bucket-ladder "
            "rungs) than this raises a JX310/JX313 unbounded-retrace finding")
define_flag("jaxpr_audit_runtime", False,
            "debug: run audit() + cost() on every CompiledFunction program "
            "at BUILD time (cache misses only — the hot replay path is "
            "untouched), logging JX3xx findings and the cost summary "
            "through base.log instead of waiting for an on-demand call")
define_flag("cost_max_intermediate_bytes", 2 << 30,
            "cost-model lint (CM501): one equation materializing a result "
            "larger than this is flagged as an oversized intermediate")
define_flag("cost_hbm_budget_bytes", 16 << 30,
            "cost-model lint (CM504): per-device HBM budget the liveness "
            "peak-residency estimate is checked against (under the active "
            "Plan's model-sharding degrees)")
define_flag("cost_min_arith_intensity", 0.25,
            "cost-model lint (CM502): matmul-free programs moving real "
            "bytes below this flops/byte ratio are flagged memory-bound")
define_flag("cost_intensity_min_bytes", 32 << 20,
            "cost-model lint (CM502): programs moving fewer bytes than "
            "this are never intensity-flagged (too small to matter)")
define_flag("cost_mesh_bandwidth_gbps", 100.0,
            "cost-model lint (CM503): declared per-link mesh bandwidth the "
            "static collective volume is priced against")
define_flag("cost_device_tflops", 197.0,
            "cost-model lint (CM503): nominal device peak used to price "
            "compute time against collective time")
define_flag("cudnn_deterministic", False, "accepted for compat; XLA is deterministic by default")
define_flag("device_prefetch", 0,
            "DataLoader default for device_prefetch=N: stage N collated "
            "batches onto the device ahead of the train loop "
            "(io/device_prefetch.py DeviceLoader); 0 disables")
define_flag("metric_sync_every", 0,
            "hapi.Model.fit default for how often (in steps) the "
            "MetricBuffer materializes device metrics to host floats; "
            "0 defers to the loop's log_freq (log-boundary syncs only)")
define_flag("serving_max_batch", 64,
            "serving tier: largest batch bucket — the batch ladder is the "
            "powers-of-two rungs up to this; one warm-compiled "
            "specialization per rung (paddle_tpu.serving)")
define_flag("serving_max_queue", 1024,
            "serving tier: global admission cap on queued samples across "
            "tenants; a submit beyond it is rejected (AdmissionError)")
define_flag("serving_tenant_quota", 256,
            "serving tier: per-tenant cap on in-flight samples "
            "(queued + executing); <=0 disables the per-tenant gate")
define_flag("serving_batch_timeout_ms", 2.0,
            "serving tier: how long the scheduler waits for more requests "
            "before dispatching a partially filled batch (continuous "
            "batching window)")
define_flag("serving_slo_ms", 50.0,
            "serving tier: the latency SLO the bench/stats report "
            "requests/sec against (enqueue->complete, per request)")
define_flag("serving_max_slots", 8,
            "decode serving: KV cache slots held device-resident by "
            "KVSlotPool — the hard cap on concurrently decoding sequences "
            "(serving/kv_cache.py); memory is allocated ONCE at this size")
define_flag("serving_max_seq", 0,
            "decode serving: longest sequence (prompt + generated) a slot "
            "holds; 0 defers to the model's max_position_embeddings")
define_flag("serving_seq_bucket_min", 16,
            "decode serving: smallest rung of the sequence-length bucket "
            "ladder (powers of two from here up to serving_max_seq); "
            "prefill prompts pad up to their rung")
define_flag("serving_prefill_max_batch", 4,
            "decode serving: largest prefill batch rung — prompts sharing "
            "a seq rung group up to this many per prefill program call")
define_flag("serving_request_ttl_ms", 0.0,
            "serving tier: expire requests whose queue wait exceeds this "
            "(AdmissionError reason='ttl', serving.expired counter) "
            "instead of executing dead work; <=0 disables")
define_flag("serving_bulk_queue_share", 0.5,
            "serving tier: fraction of serving_max_queue a bulk-tier "
            "tenant may fill — the headroom above it is reserved for "
            "interactive tiers (AdmissionController.set_tier)")
define_flag("serving_page_size", 16,
            "decode serving: tokens per KV page — KVPagePool allocates "
            "device memory in fixed pages this long instead of full "
            "max_seq slot rows (serving/kv_cache.py); must be a power "
            "of two so the block-table ladder stays aligned")
define_flag("serving_pool_pages", 0,
            "decode serving: total pages the paged KV pool holds "
            "device-resident (allocated ONCE); 0 sizes it equal-bytes "
            "to the slot pool it replaces: max_slots * max_seq tokens")
define_flag("serving_frag_warn_utilization", 0.2,
            "decode serving: JX334 page-fragmentation watermark — warn "
            "when mean live-token utilization of in-use pages sampled "
            "across the run falls below this fraction")
define_flag("serving_spec_k", 0,
            "decode serving: draft tokens proposed per self-speculation "
            "round — one truncated-layer draft program proposes k "
            "tokens, one full-model verify pass scores all k+1 "
            "positions (serving/decode.py); 0 disables speculation and "
            "the draft/verify program families entirely")
define_flag("serving_spec_draft_layers", 1,
            "decode serving: transformer layers in the truncated-layer "
            "draft prefix of self-speculative decoding (clamped to the "
            "model's layer count; the draft shares the serving weights "
            "zero-copy — no second model, no extra weight memory)")
define_flag("serving_spec_min_accept", 0.3,
            "decode serving: rolling draft-acceptance floor — a "
            "speculating request whose acceptance rate drops below this "
            "fraction auto-disables its own speculation lane (the batch "
            "falls back to plain decode once every lane has disabled)")
define_flag("cost_while_default_trips", 1,
            "cost model: trip-count multiplier assumed for a while-loop "
            "whose counter pattern cannot be statically derived (1 keeps "
            "the historical single-iteration lower bound)")
define_flag("telemetry_trace", False,
            "observability: record structured spans (dispatch compiles, "
            "train-loop phases, serving requests) into the process span "
            "tracer for chrome://tracing / Perfetto export "
            "(paddle_tpu.observability.tracing); off = one bool check per "
            "instrumented site, zero recording")
define_flag("telemetry_trace_max_events", 65536,
            "observability: span-tracer ring capacity — the trace keeps "
            "the most recent N events so a long-running process never "
            "grows its timeline without bound")
define_flag("telemetry_memory_sample_every", 8,
            "observability: sample device-memory telemetry (jax "
            "live_arrays bytes + backend memory_stats watermarks) every "
            "N-th step/batch boundary the train loop or serving scheduler "
            "crosses; 0 disables sampling entirely. Boundary-only and "
            "sync-free by contract (OB602 gates the sampler source)")
define_flag("telemetry_port", 0,
            "observability egress: default port for the telemetry HTTP "
            "exporter (/metrics Prometheus text, /healthz, /snapshot.json, "
            "/trace.json). >0: ServingEngine.warmup() (and `python -m "
            "tools.telemetry --serve`) binds it on 127.0.0.1; 0 disables "
            "the engine-owned exporter (an explicit "
            "serve_telemetry_port=0 still means 'pick an ephemeral port')")
define_flag("telemetry_device_trace_max_events", 20000,
            "observability: cap on XLA device-trace events merged into "
            "the unified timeline per process (the most recent window is "
            "kept — same bounded-ring discipline as the host span ring); "
            "<=0 means unbounded, which the OB604 audit flags when an "
            "exporter serves the trace")
define_flag("telemetry_anomaly", False,
            "observability: feed the anomaly flight recorder "
            "(observability/anomaly.py AnomalyMonitor) at train-step "
            "close, serving batch close and metric-flush boundaries; off "
            "= one attribute read per boundary, zero recording")
define_flag("telemetry_dump_dir", "",
            "anomaly flight recorder: directory for forensic bundles "
            "(last-N spans + metrics snapshot + detector verdict + "
            "step-time window) dumped on a detector trigger or an "
            "uncaught train/serving-worker exception; empty disables "
            "dumping (triggers still tick the anomaly.* counters)")
define_flag("anomaly_step_mad", 8.0,
            "anomaly flight recorder: a step slower than "
            "median + N*MAD of the rolling step-time window trips the "
            "step-time regression detector; <=0 disables it")
define_flag("anomaly_dump_cooldown_s", 60.0,
            "anomaly flight recorder: per-anomaly-kind dedup window — "
            "repeat triggers of the same kind inside it tick "
            "anomaly.suppressed instead of writing another bundle")
define_flag("anomaly_reject_burst", 16,
            "anomaly flight recorder: admission rejections within one "
            "second that count as a rejection burst; <=0 disables the "
            "burst watcher")
define_flag("comm_quantize_dp_grads", False,
            "comm-efficient collectives (distributed/collective_opt): "
            "sync dp gradients through the blockwise-int8 quantized "
            "allreduce tier (qpsum) instead of full-precision psum — "
            "TrainStep's dp grad-sync stage, dist.spmd collectives and "
            "communication.all_reduce all consult this; per-call override "
            "via all_reduce(quantized=...) or amp.auto_cast("
            "comm_dtype='int8')")
define_flag("comm_quantize_min_bytes", 2048,
            "quantized allreduce: tensors smaller than this stay on the "
            "full-precision path (scale overhead + quantization noise "
            "beat the bandwidth win on tiny buffers — layernorm gains, "
            "biases); <=0 quantizes everything eligible")
define_flag("comm_quantize_block", 256,
            "quantized allreduce: elements per quantization block (one "
            "fp32 scale per block on the wire; bigger blocks amortize "
            "scale overhead, smaller blocks track local dynamic range)")
define_flag("comm_portable_reshard", True,
            "auto_parallel.reshard: route supported placement "
            "transitions (s_to_s axis moves, r_to_s, s_to_r) through "
            "composed all_to_all/slice/all_gather sequences that keep "
            "peak per-device residency at O(shard); 0 restores the "
            "legacy whole-array device_put path for every transition")
define_flag("sharding_stage", "",
            "ZeRO sharded weight update (distributed/sharding/zero1.py): "
            "'zero1' shards optimizer states and the weight update across "
            "the dp/sharding mesh axis — reduce-scatter(grads) → per-shard "
            "optimizer update → all-gather(updated weights), ~1/dp "
            "optimizer-state bytes per replica; '' (default) keeps the "
            "replicated update. TrainStep(sharding=...) overrides per "
            "step program; flips retrace (the tier is in the static "
            "compile key). The weight all-gather rides the int8 "
            "blockwise-scale wire when the comm quantized tier is engaged "
            "(FLAGS_comm_quantize_dp_grads / amp comm_dtype)")
define_flag("fault_inject", "",
            "reliability fault injection (paddle_tpu.reliability.faults): "
            "'site:rate:kind[:delay_ms][,...]' arms the process "
            "FaultInjector at that seeded schedule (kinds: raise, "
            "latency, corrupt; seed from FLAGS_fault_seed); empty "
            "disarms — the production default. FT900 errors on an "
            "injector left armed outside a chaos/test run")
define_flag("fault_seed", 0,
            "reliability fault injection: seed of the per-site "
            "deterministic RNG streams — the same (seed, spec) pair "
            "replays the same fault schedule exactly")
define_flag("retry_max_attempts", 3,
            "reliability RetryPolicy default: bounded attempts per "
            "wrapped call (transient failures only; fatal errors "
            "propagate on the first attempt)")
define_flag("retry_deadline_s", 30.0,
            "reliability RetryPolicy default: wall-clock budget across "
            "all attempts of one wrapped call — no retry starts past it "
            "(FT901 errors on a policy without a deadline)")
define_flag("retry_base_delay_ms", 20.0,
            "reliability RetryPolicy default: first backoff delay; "
            "doubles per attempt (deterministic, no jitter — chaos "
            "schedules replay exactly)")
define_flag("circuit_failure_threshold", 5,
            "reliability CircuitBreaker default: consecutive failures "
            "before a key (tenant/program) flips open and admission "
            "sheds its load (AdmissionError reason='circuit')")
define_flag("circuit_cooldown_s", 30.0,
            "reliability CircuitBreaker default: how long an open "
            "breaker sheds before half-opening for probe traffic")
define_flag("train_snapshot_every", 0,
            "hapi.Model.fit default for snapshot_every: land an atomic "
            "rolling train-state snapshot (step, params, optimizer "
            "shards, RNG, loader cursor) every N steps into "
            "snapshot_dir; 0 disables the cadence (a preemption "
            "SIGTERM still snapshots when snapshot_dir is set)")
define_flag("train_snapshot_keep", 2,
            "reliability TrainSnapshotter: rolling window — newest N "
            "snapshots survive, older ones are pruned after each commit")
define_flag("concurrency_witness", False,
            "concurrency lint family (observability/locks.py): record "
            "every named-lock acquire into the process lock-order witness "
            "— per-thread held stacks, acquire/contended/hold-time "
            "counters, order-graph edges; a cycle-closing edge is a "
            "CX1004 inversion fed to the anomaly flight recorder. Off "
            "(the default) = one bool read per acquire, zero recording")
define_flag("concurrency_max_hold_ms", 0.0,
            "concurrency witness: a lit-mode lock hold longer than this "
            "records a CX1005 violation (blocking work is living under a "
            "lock); <=0 disables the hold-time watcher — compile/warmup "
            "phases legitimately hold program locks for seconds")
define_flag("numerics_witness", False,
            "numerics lint family (observability/numerics.py): arm the "
            "runtime NaN/Inf + dynamic-range witness — every watch() "
            "site (loss, unscaled grads, zero1 updates, quantized comm, "
            "KV commits) checks finiteness and tracks a per-name max-abs "
            "watermark + underflow fraction; a non-finite value is an "
            "NM1104 verdict, a range collapse vs the rolling watermark "
            "is NM1105, both fed to the anomaly flight recorder. Off "
            "(the default) = one bool read per watch site, zero work")
define_flag("numerics_bf16_reduce_limit", 4096,
            "numerics lint (NM1106): a bf16/fp16 reduction whose reduced "
            "extent exceeds this element count is flagged — bf16 has 8 "
            "mantissa bits, so summing >~2^12 same-sign terms loses the "
            "small addends entirely; widen to fp32 for the accumulation "
            "(preferred_element_type) and cast back. <=0 disables")
define_flag("numerics_widen_warn_ratio", 0.25,
            "numerics lint (NM1103): widening a narrow-float dot's "
            "accumulator to float32 adds out_numel*(4-itemsize) bytes of "
            "result traffic (cost_model.accumulation_width_delta). When "
            "that price stays at or below this fraction of the whole "
            "program's read+write bytes the fix is cheap and the finding "
            "is an error; above it the program is dot-output-bound and "
            "the finding downgrades to a warning carrying the priced "
            "delta (a deliberate narrow accumulator needs a noqa and a "
            "measured loss gate). <=0 makes every NM1103 an error")
define_flag("numerics_collapse_ratio", 1e-4,
            "numerics witness (NM1105): once a watched tensor's max-abs "
            "watermark is established, a later sample whose max-abs "
            "falls below watermark*ratio records a range-collapse "
            "verdict (grads flushed to zero, a dead quantizer scale, an "
            "underflowed loss). <=0 disables the collapse watcher")
define_flag("cost_max_guard_preds", 8,
            "cost-model lint (CM505): a speculative branch family "
            "verifying more guard predicates than this per call is "
            "flagged — every predicate is a device→host fetch on each "
            "call to validate the speculation")
define_flag("drift_max_flops_ratio", 1.25,
            "drift lint (PD1202): a locked program whose live FLOPs "
            "exceed lockfile FLOPs by more than this ratio fails the "
            "program-drift gate")
define_flag("drift_max_bytes_ratio", 1.25,
            "drift lint (PD1202): tolerance ratio for bytes_read / "
            "bytes_written growth over the locked program")
define_flag("drift_max_comm_ratio", 1.25,
            "drift lint (PD1202): tolerance ratio for collective comm "
            "byte growth over the locked program (comm appearing from "
            "zero always fails)")
define_flag("drift_max_peak_ratio", 1.25,
            "drift lint (PD1202): tolerance ratio for liveness "
            "peak-residency growth over the locked program")


def enable_check_model_nan_inf():
    """(reference op: enable_check_model_nan_inf)."""
    set_flags({"check_nan_inf": True})


def disable_check_model_nan_inf():
    """(reference op: disable_check_model_nan_inf)."""
    set_flags({"check_nan_inf": False})


enable_check_nan_inf = enable_check_model_nan_inf
disable_check_nan_inf = disable_check_model_nan_inf
