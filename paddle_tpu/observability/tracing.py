"""Structured span tracer: one chrome-trace timeline for the whole runtime.

The paper's L5–L8 profiler stack exports *host op events* only
(``profiler.RecordEvent`` → chrome JSON). This tracer is the unified
timeline underneath it: dispatch events (kernel-cache compiles with
signature + miss reason + wall time), train-loop phases (prefetch wait,
step, metric flush), per-request serving spans (queue wait → execute,
batch assembly with bucket/fill) and host ``RecordEvent`` spans all land
in ONE bounded event ring with correlated track ids, exportable as
chrome://tracing / Perfetto-loadable JSON (:meth:`SpanTracer.export`).

Tracks are named lanes (``dispatch``, ``train_loop``, ``io.prefetch``,
``serving.scheduler``, ``serving.requests``, ``host``, ``memory``); each
gets a stable tid and a ``thread_name`` metadata row so Perfetto shows
the runtime's layers as parallel swimlanes. All timestamps come from
``time.perf_counter`` (the same clock every existing stats silo stamps
with), so retroactively emitted spans — a serving request's queue phases,
recorded at completion from its ``Request`` timestamps — land correctly
against live-recorded ones.

Cost discipline: ``FLAGS_telemetry_trace`` gates recording. Disabled
(default), every instrumented site pays ONE attribute read
(``tracer.enabled``); there is no allocation, no lock, no clock read.
Enabled, a span costs two ``perf_counter`` calls + one locked append.

Identity: every recorded event carries ``id`` (one process-wide counter,
shared by all tracers) and ``parent`` — the id of the innermost span open
*on the same thread* when it started, or what ``emit(parent=...)`` was
given; ``None`` for a root. Both ride as TOP-LEVEL fields of the chrome
event (``{"ph": "X", ..., "id": 7, "parent": 3}``), never inside ``args``:
``args`` belong to the instrumented site, and chrome/Perfetto ignore
unknown top-level keys on complete events. The thread-local stack behind
``parent`` is touched only when ``enabled``.

Open-span accounting feeds the OB600 telemetry audit: exporting a trace
while spans are still open means an instrumented region leaked its
``end()`` (an exception path without a ``with`` block) and its wall time
is silently missing from the timeline.

**Device-trace fusion** (ISSUE 8, the ROADMAP telemetry leftover): XLA's
own profiler exports on a separate timeline. ``SpanTracer.capture_device``
wraps ``jax.profiler.start_trace``/``stop_trace`` around a window, parses
the chrome-trace JSON the profile run wrote, and puts it on the host's
clock: right after ``start_trace`` returns it emits a
``jax.profiler.TraceAnnotation("paddle_tpu.sync")`` and reads
``perf_counter`` inside it, so the capture holds one event whose host time
is known; every ingested event is shifted by (that stamp - the
annotation's own timestamp). A capture without the annotation is not
ingested: there is nothing to align it on. The events land under
``device.<thread>`` tracks — so ONE ``to_chrome_trace``
export shows host spans and XLA's device lanes side by side. The merged
set is bounded by ``FLAGS_telemetry_device_trace_max_events`` (most
recent kept) and the whole path degrades to a logged no-op when the
profiler is unavailable (already active, unsupported backend, CPU CI
without the plugin).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import List, Optional

from .locks import named_lock

__all__ = ["SpanTracer", "tracer", "SYNC_NAME", "sync_annotation"]

# the annotation that ties a jax.profiler capture to time.perf_counter
SYNC_NAME = "paddle_tpu.sync"

_ids = itertools.count(1)   # span ids: process-wide, next() is atomic


def sync_annotation() -> float:
    """Emit the ``paddle_tpu.sync`` annotation into the running
    ``jax.profiler`` capture and return ``perf_counter`` (microseconds)
    read inside it: the one point both clocks name."""
    import jax

    with jax.profiler.TraceAnnotation(SYNC_NAME):
        return time.perf_counter() * 1e6


class _Span:
    """One open span; ``with tracer.span(...)`` closes it."""

    __slots__ = ("tracer", "name", "track", "args", "t0_us", "id", "parent")

    def __init__(self, tracer_, name, track, args, parent):
        self.tracer = tracer_
        self.name = name
        self.track = track
        self.args = args
        self.id = next(_ids)
        self.parent = parent
        self.t0_us = time.perf_counter() * 1e6

    def end(self) -> None:
        self.tracer._close(self)

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class _NullSpan:
    """The disabled-tracer span: a shared, stateless no-op."""

    __slots__ = ()
    id = None

    def end(self) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


def _load_xla_chrome_trace(log_dir: str) -> Optional[dict]:
    """The chrome-trace JSON an ``xla``/``jax.profiler`` run wrote under
    ``log_dir`` (newest ``plugins/profile/<run>/``), or None. Prefers the
    per-host ``*.trace.json.gz`` (named thread lanes); falls back to
    ``perfetto_trace.json.gz``."""
    import glob
    import gzip

    runs = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*")))
    if not runs:
        return None
    run = runs[-1]
    paths = (sorted(glob.glob(os.path.join(run, "*.trace.json.gz")))
             or glob.glob(os.path.join(run, "perfetto_trace.json.gz")))
    if not paths:
        return None
    with gzip.open(paths[0], "rt") as f:
        return json.load(f)


def _is_modules(thread_name) -> bool:
    """The device lane with one event a program run."""
    return str(thread_name or "").endswith("XLA Modules")


def _device_skew_us(xs: List[dict], threads: dict) -> float:
    """How much later the device's events have to sit, by causality: the
    runtime's ``CompleteCallbacks`` event of a run (a host thread,
    ``args.run_id``) cannot start before the device finished that run
    (the ``XLA Modules`` event with the same ``run_id``), so the least of
    (callback start - program end) over the runs both sides name is what
    the device's clock lags by, less the callback's own latency (tens of
    microseconds). The bound ``benchmark/trace_reduce.device_skew_ns``
    applies to the benchmark's captures. 0 where no run is named on both
    sides."""
    ends, least = {}, None
    for e in xs:
        run = (e.get("args") or {}).get("run_id")
        if run is not None and _is_modules(threads.get((e.get("pid"), e.get("tid")))):
            end = float(e["ts"]) + float(e.get("dur", 0.0))
            ends[str(run)] = max(end, ends.get(str(run), end))
    for e in xs:
        if e.get("name") == "CompleteCallbacks":
            end = ends.get(str((e.get("args") or {}).get("run_id")))
            if end is not None and (least is None or float(e["ts"]) - end < least):
                least = float(e["ts"]) - end
    return least or 0.0


def _normalize_device_events(trace: dict, t_sync_us: float,
                             include_python: bool = False) -> List[tuple]:
    """XLA chrome-trace events → this tracer's event tuples on
    ``device.<thread>`` tracks, on the host's clock: the capture's
    ``paddle_tpu.sync`` annotation happened at host time ``t_sync_us``
    (``perf_counter`` read inside it), so every event moves by the
    difference. A capture without the annotation yields nothing, with a
    warning. The profiler's python-callstack lane duplicates what the
    host tracks already carry; it is dropped unless ``include_python``.

    The chip's clock runs a millisecond or so apart from the host's
    within one capture (a program "starts" before its dispatch), so the
    device's lanes (every event of a process that has an ``XLA Modules``
    lane) move later by :func:`_device_skew_us` as well, where the
    capture lets it be bound. A capture whose events carry no ``run_id``
    on both sides (no ``XLA Modules`` lane, as on the CPU) keeps the
    sync shift alone."""
    events = trace.get("traceEvents", []) if trace else []
    threads = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = (
                e.get("args") or {}).get("name", "")
    xs = [e for e in events if e.get("ph") == "X" and "ts" in e]
    sync = next((e for e in xs if e.get("name") == SYNC_NAME), None)
    if sync is None:
        if xs:  # a clock nobody can place: say so, fuse nothing
            from ..base.log import get_logger

            get_logger().warning(
                "device trace holds no '%s' annotation: %d event(s) not "
                "fused into the timeline", SYNC_NAME, len(xs))
        return []
    shift = t_sync_us - float(sync["ts"])
    skew = _device_skew_us(xs, threads)
    chips = {pid for (pid, _), name in threads.items() if _is_modules(name)}
    out = []
    for e in xs:
        if e is sync:
            continue
        tname = threads.get((e.get("pid"), e.get("tid")),
                            f"tid{e.get('tid')}")
        if not include_python and tname == "python":
            continue
        late = skew if e.get("pid") in chips else 0.0
        out.append(("X", e.get("name", "?"), f"device.{tname}",
                    float(e["ts"]) + shift + late, float(e.get("dur", 0.0)),
                    e.get("args") or None, None, None))
    out.sort(key=lambda ev: ev[3])
    return out


class _DeviceCapture:
    """One ``jax.profiler`` window fused into the owning tracer's export.
    Degrades to a logged no-op when the profiler cannot start (already
    active, missing plugin) — CPU CI must never fail on it."""

    def __init__(self, tracer_: "SpanTracer", log_dir: Optional[str],
                 include_python: bool):
        self.tracer = tracer_
        self._log_dir = log_dir
        self._own_dir = log_dir is None
        self._include_python = include_python
        self._active = False
        self._t_sync_us = 0.0

    def __enter__(self) -> "_DeviceCapture":
        import tempfile

        from ..base.log import get_logger

        if self._log_dir is None:
            self._log_dir = tempfile.mkdtemp(prefix="paddle_device_trace_")
        try:
            import jax

            jax.profiler.start_trace(self._log_dir)
            self._active = True
            self._t_sync_us = sync_annotation()
        except Exception as e:
            get_logger().info("device trace capture unavailable "
                              "(degrading to host-only): %s", e)
        return self

    def __exit__(self, *exc) -> None:
        import shutil

        from ..base.log import get_logger

        try:
            if self._active:
                try:
                    import jax

                    jax.profiler.stop_trace()
                except Exception as e:
                    get_logger().info("device trace stop failed: %s", e)
                    return
                n = self.tracer.ingest_device_trace_dir(
                    self._log_dir, self._t_sync_us,
                    include_python=self._include_python)
                get_logger().info("device trace fused: %d event(s) from %s",
                                  n, self._log_dir)
        finally:
            self._active = False
            if self._own_dir:
                shutil.rmtree(self._log_dir, ignore_errors=True)


class SpanTracer:
    """Bounded, thread-safe event ring with chrome-trace export."""

    def __init__(self, enabled: Optional[bool] = None,
                 max_events: Optional[int] = None):
        self._lock = named_lock("tracing.spans")
        # (ph, name, track, ts_us, dur_us, args, id, parent)
        self._events: List[tuple] = []
        self._device_events: List[tuple] = []  # same tuples, device.* tracks
        self._open: dict = {}            # span id -> _Span
        self._tids: dict = {}            # track name -> tid
        self._dropped = 0
        self._max_events = max_events
        self._cap_now: Optional[int] = None  # the ring bound, read per enable()
        self._stack = threading.local()  # .ids: this thread's open span ids
        if enabled is None:
            try:
                from ..base.flags import get_flag

                enabled = bool(get_flag("telemetry_trace"))
            except Exception:
                enabled = False
        self.enabled = bool(enabled)

    # ------------------------------------------------------------ lifecycle
    def enable(self) -> "SpanTracer":
        self._cap_now = None  # FLAGS_telemetry_trace_max_events is re-read
        self.enabled = True
        return self

    def disable(self) -> "SpanTracer":
        self.enabled = False
        return self

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._device_events.clear()
            self._open.clear()
            self._dropped = 0
        # the calling thread's stack only: another thread's still-open
        # spans close against their own (a stale id is just never matched)
        self._thread_stack().clear()

    def _cap(self) -> int:
        """The host ring's bound: the constructor's, else the flag as it
        stood at the last ``enable()`` (one flag read per enable, not one
        per appended event)."""
        cap = self._cap_now
        if cap is None:
            if self._max_events is not None:
                cap = int(self._max_events)
            else:
                try:
                    from ..base.flags import get_flag

                    cap = int(get_flag("telemetry_trace_max_events"))
                except Exception:
                    cap = 65536
            self._cap_now = cap
        return cap

    def capacity(self) -> int:
        """The ring bound currently in force (<=0 = unbounded — the
        OB604 audit flags that when an exporter is serving this trace)."""
        return self._cap()

    @staticmethod
    def _device_cap() -> int:
        try:
            from ..base.flags import get_flag

            return int(get_flag("telemetry_device_trace_max_events"))
        except Exception:
            return 20000

    # ------------------------------------------------------------ recording
    def span(self, name: str, track: str = "host", **args):
        """Context manager (or explicit ``.end()``) recording one complete
        event. The no-op when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        stack = self._thread_stack()
        s = _Span(self, name, track, args or None,
                  stack[-1] if stack else None)
        stack.append(s.id)
        with self._lock:
            self._open[s.id] = s
        return s

    def _thread_stack(self) -> list:
        try:
            return self._stack.ids
        except AttributeError:
            self._stack.ids = []
            return self._stack.ids

    def _close(self, s: _Span) -> None:
        t1 = time.perf_counter() * 1e6
        stack = self._thread_stack()
        if stack and stack[-1] == s.id:
            stack.pop()
        elif s.id in stack:      # ended out of order, or on another thread
            stack.remove(s.id)
        with self._lock:
            self._open.pop(s.id, None)
            self._append(("X", s.name, s.track, s.t0_us, t1 - s.t0_us, s.args,
                          s.id, s.parent))

    def emit(self, name: str, t0_s: float, dur_s: float,
             track: str = "host", parent: Optional[int] = None,
             **args) -> Optional[int]:
        """Record a complete span from already-measured ``perf_counter``
        timestamps (seconds) — the retroactive path for events whose
        phases were stamped elsewhere (serving requests, RecordEvent).
        ``parent`` names the span that caused it (a retroactive span has
        no place on the thread's stack, so nothing is inferred). Returns
        the new span's id, None when disabled."""
        if not self.enabled:
            return None
        sid = next(_ids)
        with self._lock:
            self._append(("X", name, track, t0_s * 1e6, dur_s * 1e6,
                          args or None, sid, parent))
        return sid

    def instant(self, name: str, track: str = "host", **args) -> None:
        """Zero-duration marker (cache hit, sample tick, rejection)."""
        if not self.enabled:
            return
        stack = self._thread_stack()
        with self._lock:
            self._append(("i", name, track, time.perf_counter() * 1e6, 0.0,
                          args or None, next(_ids),
                          stack[-1] if stack else None))

    def _append(self, event: tuple) -> None:
        # caller holds self._lock
        self._events.append(event)
        cap = self._cap()
        if cap > 0 and len(self._events) > cap:
            drop = len(self._events) - cap
            del self._events[:drop]
            self._dropped += drop

    # ----------------------------------------------------- device fusion
    def capture_device(self, log_dir: Optional[str] = None,
                       include_python: bool = False) -> _DeviceCapture:
        """``with tracer.capture_device(): ...device work...`` — profile
        the window with ``jax.profiler`` and merge XLA's trace events
        into THIS tracer's export under ``device.*`` tracks, clock-aligned
        at the capture boundary. Explicit opt-in: it records regardless
        of ``enabled`` (profiling a window is a deliberate act, not a
        steady-state instrumentation site). ``log_dir=None`` uses a
        temporary directory, deleted after ingestion; pass a real one to
        additionally keep the TensorBoard/XProf artifacts."""
        return _DeviceCapture(self, log_dir, include_python)

    def ingest_device_trace_dir(self, log_dir: str, t_sync_us: float,
                                include_python: bool = False) -> int:
        """Parse an XLA profile run under ``log_dir`` and merge its
        events, aligned on the run's ``paddle_tpu.sync`` annotation
        (``t_sync_us``: what :func:`sync_annotation` returned for it).
        Returns how many landed; 0 — never an exception — when the run
        wrote nothing parseable or holds no such annotation."""
        try:
            trace = _load_xla_chrome_trace(log_dir)
            events = _normalize_device_events(trace, t_sync_us,
                                              include_python=include_python)
        except Exception as e:
            from ..base.log import get_logger

            get_logger().info("device trace parse failed (%s): %s",
                              log_dir, e)
            return 0
        if not events:
            return 0
        cap = self._device_cap()
        with self._lock:
            self._device_events.extend(events)
            if cap > 0 and len(self._device_events) > cap:
                drop = len(self._device_events) - cap
                del self._device_events[:drop]
                self._dropped += drop
        # count and return only what the cap let into the timeline:
        # parsing 5000 events into a 100-slot ring must not read as
        # 5000 fused ("how many landed", per the contract above)
        kept = min(len(events), cap) if cap > 0 else len(events)
        from .metrics import registry

        if kept:
            registry.counter(
                "telemetry.device_trace_events",
                "XLA device-trace events fused into the unified timeline"
            ).inc(kept)
        return kept

    def device_event_count(self) -> int:
        with self._lock:
            return len(self._device_events)

    # ------------------------------------------------------------ reporting
    def open_spans(self) -> List[str]:
        """Names of spans begun but never ended — the OB600 audit input."""
        with self._lock:
            return [s.name for s in self._open.values()]

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def _tid(self, track: str) -> int:
        with self._lock:  # two exporters racing a new track must not
            tid = self._tids.get(track)  # hand two tracks one tid
            if tid is None:
                tid = self._tids[track] = len(self._tids) + 1
            return tid

    def _event_dict(self, event: tuple, pid: int) -> dict:
        ph, name, track, ts, dur, args, sid, parent = event
        ev = {"ph": ph, "name": name, "pid": pid,
              "tid": self._tid(track), "ts": ts, "cat": track}
        if ph == "X":
            ev["dur"] = dur
        else:
            ev["s"] = "t"  # instant scope: thread
        if sid is not None:  # fused device events have no identity here
            ev["id"] = sid
            ev["parent"] = parent
        if args:
            ev["args"] = dict(args)
        return ev

    def tail_chrome_events(self, n: int = 512) -> List[dict]:
        """The most recent ``n`` host events as chrome-trace dicts — the
        anomaly flight recorder's bounded span window."""
        if (n := int(n)) <= 0:
            return []
        pid = os.getpid()
        with self._lock:
            events = list(self._events[-n:])
        return [self._event_dict(e, pid) for e in events]

    def to_chrome_trace(self) -> dict:
        """The timeline as a chrome://tracing / Perfetto JSON object.
        Tracks — host AND any fused ``device.*`` lanes — become named tid
        lanes under one pid; span ``args`` ride through for the Perfetto
        details pane."""
        pid = os.getpid()
        with self._lock:
            events = list(self._events) + list(self._device_events)
            dropped = self._dropped
        out = []
        for track in {e[2] for e in events}:
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": self._tid(track),
                        "args": {"name": track}})
        out.extend(self._event_dict(e, pid) for e in events)
        trace = {"traceEvents": out, "displayTimeUnit": "ms"}
        if dropped:
            trace["otherData"] = {"dropped_events": dropped}
        return trace

    def export(self, path: str) -> str:
        """Write the chrome-trace JSON to ``path`` (create parents).
        Returns the path. Open spans are NOT flushed — they are a
        telemetry bug the OB600 audit reports; run it before trusting an
        export."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


tracer = SpanTracer()
