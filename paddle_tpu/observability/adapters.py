"""Collectors re-homing the pre-existing stats silos into the registry.

Each subsystem that predates ``paddle_tpu.observability`` keeps its own
counter surface (their public APIs are unchanged — ``kernel_cache.stats()``,
``pipeline_stats.summary()``, ``serving_stats.summary()``,
``CompiledFunction._compile_counts``); these pull-time collectors project
them into the one ``snapshot()`` namespace:

====================== ====================================================
namespace              source silo
====================== ====================================================
dispatch.kernel_cache  ``core.kernel_cache.stats()`` (hits/misses/bypasses/
                       evictions + per-op breakdown + size/capacity)
pipeline               ``profiler.pipeline.pipeline_stats.summary()``
                       (h2d wait/issue, dispatch, host syncs, overlap)
serving                ``profiler.pipeline.serving_stats.summary()``
                       (latency percentiles, rps@SLO, fill, depth,
                       per-tenant breakdowns)
jit.compile            process-wide program-build counters: whole-step
                       ``CompiledFunction`` builds (jit/functionalize) and
                       serving ``_BatchProgram`` trace count (inference)
concurrency            ``observability.locks.witness_stats()`` (named-lock
                       registry size, witness acquires/contended/hold_ms,
                       order-graph edges, CX1004/CX1005 violation counts)
numerics               ``observability.numerics.witness_stats()`` (watched
                       tensor count, checks, NM1104 non-finite / NM1105
                       range-collapse violation counts)
====================== ====================================================

Registered once at ``paddle_tpu.observability`` import; every import in
the collectors is lazy so pulling a snapshot never forces a subsystem
that the process hasn't touched to load.
"""
from __future__ import annotations

from .metrics import MetricsRegistry, registry

__all__ = ["register_default_collectors"]


def _collect_kernel_cache() -> dict:
    from ..core import kernel_cache

    return kernel_cache.stats()


def _collect_pipeline() -> dict:
    from ..profiler.pipeline import pipeline_stats

    return pipeline_stats.summary()


def _collect_serving() -> dict:
    from ..profiler.pipeline import serving_stats

    return serving_stats.summary()


def _collect_compile() -> dict:
    from ..jit.functionalize import build_totals

    out = {"program_builds": build_totals()}
    try:
        from ..inference import batch_trace_total

        out["serving_batch_traces"] = batch_trace_total()
    except Exception:
        pass
    return out


def _collect_concurrency() -> dict:
    # pull-time by design: a per-acquire instrument update would recurse
    # (the instruments' own guards are named locks)
    from .locks import witness_stats

    return witness_stats()


def _collect_numerics() -> dict:
    from .numerics import witness_stats

    return witness_stats()


def register_default_collectors(reg: MetricsRegistry = registry) -> None:
    reg.register_collector("dispatch.kernel_cache", _collect_kernel_cache)
    reg.register_collector("pipeline", _collect_pipeline)
    reg.register_collector("serving", _collect_serving)
    reg.register_collector("jit.compile", _collect_compile)
    reg.register_collector("concurrency", _collect_concurrency)
    reg.register_collector("numerics", _collect_numerics)
