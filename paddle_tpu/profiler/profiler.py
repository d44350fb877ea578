"""Profiler (reference: python/paddle/profiler/profiler.py:358 — Profiler
state machine CLOSED/READY/RECORD/RECORD_AND_RETURN driven by a per-step
scheduler; host events via RecordEvent; chrome://tracing export via
chrometracing_logger.cc; summaries in profiler_statistic.py).

TPU-native: host-side events are recorded in-process (RecordEvent context
manager / dispatcher hook); device-side timelines come from `jax.profiler`
(XLA's own tracer) when `ProfilerTarget.TPU` is requested — the
jax.profiler trace dir can be opened in TensorBoard/XProf, while the host
events export to chrome://tracing JSON directly.
"""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Iterable, Optional

from ..base.log import get_logger
from ..observability.locks import named_lock


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3


class _EventStore(threading.local):
    def __init__(self):
        self.events = []
        self.active = False


_store = _EventStore()
_global_events = []
_global_lock = named_lock("profiler.global")


class RecordEvent:
    """Host event span (reference RecordEvent): context manager or begin/end."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._t0 = None

    def begin(self):
        self._t0 = time.perf_counter_ns()

    def end(self):
        if self._t0 is None:
            return
        t1 = time.perf_counter_ns()
        from ..ops import registry

        with _global_lock:
            _global_events.append(
                {"name": self.name, "ts": self._t0 / 1e3, "dur": (t1 - self._t0) / 1e3,
                 "tid": threading.get_ident() % 100000,
                 "cat": registry.profiler_tag(self.name)}
            )
        # same span, unified timeline: host op events land on the shared
        # observability tracer too (same perf_counter clock), so one
        # export correlates them with dispatch/train-loop/serving tracks
        from ..observability.tracing import tracer

        if tracer.enabled:
            tracer.emit(self.name, self._t0 / 1e9, (t1 - self._t0) / 1e9,
                        track="host", cat=registry.profiler_tag(self.name))
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Per-step state schedule (reference make_scheduler)."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback writing chrome://tracing JSON (reference
    export_chrome_tracing)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_time_{int(time.time())}.json")
        events = [
            {"name": e["name"], "ph": "X", "ts": e["ts"], "dur": e["dur"],
             "pid": os.getpid(), "tid": e["tid"], "cat": "host"}
            for e in prof._events
        ]
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        get_logger().info("chrome trace exported to %s", path)
        prof._last_export = path

    return handler


class Profiler:
    def __init__(self, *, targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler=None, on_trace_ready=None, record_shapes=False,
                 profile_memory=False, timer_only=False, emit_nvtx=False):
        if scheduler is None:
            self._scheduler = _default_scheduler
        elif callable(scheduler):
            self._scheduler = scheduler
        else:  # (start, end) tuple like the reference
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start - 1, 0), ready=1 if start > 0 else 0,
                record=end - start, repeat=1)
        self.targets = list(targets or [ProfilerTarget.CPU])
        self.on_trace_ready = on_trace_ready
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._events = []
        self._device_trace_dir = None
        self._device_active = False
        self._last_export = None
        self.timer_only = timer_only
        self._step_times = []
        self._step_t0 = None

    # ------------------------------------------------------------- device
    def _start_device_trace(self):
        if ProfilerTarget.TPU in self.targets and not self._device_active:
            import jax

            self._device_trace_dir = self._device_trace_dir or os.path.join(
                os.getcwd(), "profiler_log", f"xla_{int(time.time())}")
            try:
                jax.profiler.start_trace(self._device_trace_dir)
                self._device_active = True
                # the one point both clocks name, for the unified-timeline
                # fusion below
                from ..observability.tracing import sync_annotation

                self._device_sync_us = sync_annotation()
            except Exception as e:  # already-active tracer etc.
                get_logger().warning("jax trace not started: %s", e)

    def _stop_device_trace(self):
        if self._device_active:
            import jax

            try:
                jax.profiler.stop_trace()
            finally:
                self._device_active = False
            # device-trace fusion (ISSUE 8 / ROADMAP telemetry leftover):
            # with the unified tracer recording, XLA's window lands in the
            # SAME chrome-trace export as the host spans instead of only
            # a separate TensorBoard dir (which is still kept on disk)
            from ..observability.tracing import tracer

            if tracer.enabled:
                tracer.ingest_device_trace_dir(
                    self._device_trace_dir,
                    getattr(self, "_device_sync_us", 0.0))

    # -------------------------------------------------------------- state
    def _sync_op_hook(self):
        """Expose per-op host events through the dispatcher while recording."""
        from ..core import hooks

        recording = self.current_state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN
        )
        hooks.op_profiler = RecordEvent if (recording and not self.timer_only) else None

    def start(self):
        with _global_lock:
            _global_events.clear()
        self.current_state = self._scheduler(self.step_num)
        if self.current_state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_device_trace()
        self._sync_op_hook()
        self._step_t0 = time.perf_counter()

    def stop(self):
        self._collect()
        self._stop_device_trace()
        if self.on_trace_ready is not None and self._events:
            self.on_trace_ready(self)
        self.current_state = ProfilerState.CLOSED
        self._sync_op_hook()

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._step_t0 is not None:
            self._step_times.append(now - self._step_t0)
        self._step_t0 = now
        prev = self.current_state
        self.step_num += 1
        self.current_state = self._scheduler(self.step_num)
        if prev == ProfilerState.RECORD_AND_RETURN or (
            prev in (ProfilerState.RECORD,) and self.current_state == ProfilerState.CLOSED
        ):
            self._collect()
            self._stop_device_trace()
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)
        if self.current_state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_device_trace()
        self._sync_op_hook()

    def _collect(self):
        with _global_lock:
            self._events = list(_global_events)
            _global_events.clear()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # ------------------------------------------------------------ summary
    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit="ms"):
        """Aggregate host events by name (reference profiler_statistic)."""
        agg = {}
        for e in self._events:
            st = agg.setdefault(e["name"], {"calls": 0, "total": 0.0, "max": 0.0,
                                            "min": float("inf")})
            st["calls"] += 1
            st["total"] += e["dur"]
            st["max"] = max(st["max"], e["dur"])
            st["min"] = min(st["min"], e["dur"])
        unit = {"ms": 1e3, "us": 1.0, "s": 1e6}[time_unit]
        rows = sorted(agg.items(), key=lambda kv: -kv[1]["total"])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
                 f"{'Avg':>10}{'Max':>10}{'Min':>10}"]
        for name, st in rows:
            lines.append(
                f"{name[:39]:<40}{st['calls']:>8}{st['total'] / unit:>14.3f}"
                f"{st['total'] / st['calls'] / unit:>10.3f}{st['max'] / unit:>10.3f}"
                f"{st['min'] / unit:>10.3f}"
            )
        text = "\n".join(lines)
        print(text)
        return agg

    def benchmark(self):
        """Step-time stats (reference profiler/timer.py benchmark surface)."""
        if not self._step_times:
            return {}
        import numpy as np

        ts = np.asarray(self._step_times)
        return {"steps": len(ts), "avg_s": float(ts.mean()),
                "p50_s": float(np.percentile(ts, 50)), "max_s": float(ts.max())}
