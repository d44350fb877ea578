"""paddle_tpu.observability — unified runtime telemetry.

The production observability layer over the whole runtime (ISSUE 7): the
paper's L5–L8 profiler stack (state machine, RecordEvent, chrome-trace
export, summaries) reproduced as ONE substrate instead of per-subsystem
fragments. Three pieces:

- :mod:`metrics` — a process-wide registry of Counter/Gauge/Histogram
  instruments with labels plus pull-time collectors that re-home the
  pre-existing silos (kernel-cache, pipeline, serving, compile counters)
  into one namespace. :func:`snapshot` is the JSON surface.
- :mod:`tracing` — a structured span tracer unifying ``RecordEvent``
  host spans, dispatch events (cache hit/miss/compile), train-loop
  phases (prefetch wait, step, metric flush) and per-request serving
  spans onto one chrome://tracing / Perfetto timeline with correlated
  track ids. :func:`span` / :func:`export_trace` are the entry points;
  ``FLAGS_telemetry_trace`` gates recording. Every event has an ``id``
  and a ``parent`` (the innermost span open on its thread, or the one
  ``emit(parent=)`` names). The decode scheduler's beat is one
  ``serving.beat`` span tiled by ``serving.admit`` / ``serving.build`` /
  ``serving.decode`` (holding ``serving.dispatch`` and ``serving.read``,
  the read being of the call dispatched a beat earlier: ``of_beat``)
  / ``serving.absorb``; a request's phases are ``serving.request.queue``
  / ``.prefill`` (or ``.failed``) sharing ``request=<id>``, from its
  ``t_first_token`` stamp. On the device side the programs name their
  regions with ``jax.named_scope`` from one vocabulary,
  :mod:`paddle_tpu.base.regions` (``attn/qkv``, ``attn/kv_gather``,
  ``mlp``, ``optimizer``, ...; README "Program regions"), and their
  Pallas kernels (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``).
- :mod:`memory` — a device-memory telemetry sampler (jax ``live_arrays``
  + backend ``memory_stats`` watermarks) sampled at step/batch
  boundaries only, never forcing a device sync, feeding gauges
  comparable against the CM5xx peak-residency estimate.

Egress + forensics (ISSUE 8) sit on top:

- :mod:`export` — Prometheus-text / JSON exposition of ``snapshot()``
  and the :class:`TelemetryServer` HTTP thread (``/metrics``,
  ``/healthz``, ``/snapshot.json``, ``/trace.json``), owned by
  ``ServingEngine(serve_telemetry_port=...)`` / ``FLAGS_telemetry_port``
  or started standalone via ``python -m tools.telemetry --serve``.
- :mod:`anomaly` — the :class:`AnomalyMonitor` flight recorder: rolling
  median+MAD step-time regression, serving SLO-breach and
  rejection-burst watchers, device-memory watermark-vs-budget, each
  dumping a bounded, rate-limited forensic bundle (last-N spans + full
  snapshot + verdict + step window) to ``FLAGS_telemetry_dump_dir``.
- ``SpanTracer.capture_device`` — ``jax.profiler`` windows fused into
  the SAME chrome-trace export as the host spans (``device.*`` tracks),
  on the host's clock: aligned on a ``paddle_tpu.sync`` annotation
  emitted inside the capture, whose host time is read inside it.
- :mod:`locks` — the named-lock registry + runtime lock-order witness
  (concurrency lint family, CX10xx): every runtime lock/condition is a
  ``named_lock``/``named_condition``; ``FLAGS_concurrency_witness``
  records acquisition order, contention and hold times, flags order
  inversions (CX1004) into the anomaly flight recorder.

The OB6xx telemetry lint family (``analysis/telemetry_check.py``, run by
``python -m tools.lint``) gates the contract: no unclosed span at
export, no duplicate metric registration, no device sync inside a
sampler, no dead (never-fed) anomaly detector, no unbounded
exporter/dump surface. ``python -m tools.telemetry`` dumps a demo
snapshot + trace.
"""
from __future__ import annotations

from .adapters import register_default_collectors
from .anomaly import AnomalyMonitor, monitor
from .locks import (NamedCondition, NamedLock, named_condition, named_lock,
                    set_witness, witness_enabled, witness_report)
from .memory import DeviceMemorySampler, device_memory_stats, sampler
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, registry
from .tracing import SpanTracer, tracer

__all__ = [
    "AnomalyMonitor", "Counter", "DeviceMemorySampler", "Gauge",
    "Histogram", "MetricsRegistry", "NamedCondition", "NamedLock",
    "SpanTracer", "TelemetryServer",
    "counter", "device_memory_stats", "export_trace", "gauge", "histogram",
    "monitor", "named_condition", "named_lock", "prometheus_text",
    "registry", "register_default_collectors", "sampler", "set_witness",
    "snapshot", "span", "tracer", "witness_enabled", "witness_report",
]

register_default_collectors(registry)


def __getattr__(name: str):
    # lazy egress re-exports: every `import paddle_tpu` reaches this
    # package via tracing's consumers, and the stdlib http.server chain
    # behind export.py is too heavy to pay at cold start for a surface
    # that is off by default (FLAGS_telemetry_port=0)
    if name in ("TelemetryServer", "prometheus_text"):
        from . import export

        return getattr(export, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")

# FLAGS_telemetry_trace / FLAGS_telemetry_anomaly are mirrored into the
# tracer's / monitor's hot-path `enabled` attributes (instrumented sites
# pay one attribute read, never a registry lookup); these hooks keep a
# runtime paddle.set_flags(...) in sync with them
try:
    from ..base.flags import on_flag_change as _on_flag_change

    _on_flag_change("telemetry_trace",
                    lambda v: tracer.enable() if v else tracer.disable())
    _on_flag_change("telemetry_anomaly",
                    lambda v: setattr(monitor, "enabled", bool(v)))
    from .locks import set_witness as _set_witness

    _on_flag_change("concurrency_witness",
                    lambda v: _set_witness(bool(v)))
    from .numerics import set_witness as _set_num_witness

    _on_flag_change("numerics_witness",
                    lambda v: _set_num_witness(bool(v)))
except Exception:
    pass


# ----------------------------------------------------------------- sugar
def counter(name: str, help: str = "") -> Counter:
    return registry.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return registry.gauge(name, help)


def histogram(name: str, help: str = "", max_samples: int = 2048) -> Histogram:
    return registry.histogram(name, help, max_samples=max_samples)


def snapshot() -> dict:
    """The process-wide metrics snapshot (instruments + collectors)."""
    return registry.snapshot()


def span(name: str, track: str = "host", **args):
    """``with observability.span("phase", track="train_loop"): ...``"""
    return tracer.span(name, track, **args)


def export_trace(path: str) -> str:
    """Write the unified timeline as chrome-trace JSON to ``path``."""
    return tracer.export(path)
