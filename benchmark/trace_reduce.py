"""From a profiler capture (.xplane.pb) to what the per-layer metrics read:
device operations with their times, the step programs, busy intervals, and
the idle gaps attributed to what the host was doing.

A v5e capture has one plane per chip, `/device:TPU:<n>`, with the lines
`XLA Modules` (one event per program execution) and `XLA Ops` (one per
operation; the event's name is the HLO instruction's text). Host threads are
lines of the plane `/host:CPU`; the benchmark's annotation `bench.sync`
(harness.TraceCapture) is on one of them, and ties the capture's clock to
time.perf_counter. All times returned are seconds on the host's clock.

The chip's clock runs a millisecond or so apart from the host's within one
capture (in the recorded fixture a program "starts" 1.06 ms before the host
dispatched it). `device_skew_ns` bounds the difference by causality: the
runtime's `CompleteCallbacks` event of a run (host plane, stat `run_id`)
cannot start before the device finished that run, so the least of
(callback start - program end) over the runs is the most the device's times
can be moved later. That much is added; what remains is the callback's own
latency, tens of microseconds.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SYNC_NAME = "bench.sync"
# how a Pallas (Mosaic) kernel shows in `category`, until kernels carry names
PALLAS_CALL = "custom-call.tpu_custom_call"

_OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
_FUSION_KIND = re.compile(r"\bkind=(k[A-Za-z]+)")
_CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def category(hlo_text: str) -> str:
    """`fusion.kOutput`, `custom-call.tpu_custom_call`, `copy-done`, ...: the
    instruction's opcode, with a fusion's kind or a custom call's target."""
    _, _, rest = hlo_text.partition(" = ")
    m = _OPCODE.search(rest or hlo_text)
    opcode = m.group(1) if m else hlo_text.split(" ")[0].lstrip("%")
    if opcode == "fusion":
        k = _FUSION_KIND.search(hlo_text)
        return f"fusion.{k.group(1)}" if k else opcode
    if opcode == "custom-call":
        t = _CALL_TARGET.search(hlo_text)
        return f"custom-call.{t.group(1)}" if t else opcode
    return opcode


@dataclass
class DeviceTrace:
    """One chip's events: (start, end, category) per operation and (start,
    end, name) per program execution, sorted by start."""
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    devices: list            # DeviceTrace per chip
    t0: float                # the window on the host's clock
    t1: float
    clock_shift_s: float     # what was added to the capture's times

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def device_skew_ns(data) -> float:
    """See the module's note: least (CompleteCallbacks start - program end)
    over the runs both sides name, or 0 where the capture has no such pair."""
    ends, least = {}, None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        run = _stat(e, "run_id")
                        if run is not None:
                            end = e.start_ns + e.duration_ns
                            ends[str(run)] = max(end, ends.get(str(run), end))
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == "CompleteCallbacks":
                        end = ends.get(str(_stat(e, "run_id")))
                        if end is not None and (least is None or e.start_ns - end < least):
                            least = e.start_ns - end
    return float(least or 0.0)


def load(path: str, t_sync: float, t0: float, t1: float) -> Trace:
    """Read the capture and shift it onto the host's clock: the capture's
    time of `bench.sync` is the host's `t_sync`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    sync_ns = None
    raw = []
    for plane in data.planes:
        if plane.name == HOST_PLANE and sync_ns is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC_NAME:
                        sync_ns = e.start_ns
                        break
                if sync_ns is not None:
                    break
        elif plane.name.startswith(DEVICE_PLANE):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
            raw.append((ops, modules))
    if sync_ns is None:
        raise ValueError(f"{path}: no {SYNC_NAME!r} annotation on {HOST_PLANE}")
    shift = t_sync - sync_ns / 1e9 + device_skew_ns(data) / 1e9
    devices = []
    names = {}
    for ops, modules in raw:
        d = DeviceTrace()
        for start, dur, name in ops:
            cat = names.get(name)
            if cat is None:
                cat = names[name] = category(name)
            s = start / 1e9 + shift
            d.ops.append((s, s + dur / 1e9, cat))
        for start, dur, name in modules:
            s = start / 1e9 + shift
            d.modules.append((s, s + dur / 1e9, name.split("(")[0]))
        d.ops.sort()
        d.modules.sort()
        devices.append(d)
    return Trace(devices=devices, t0=t0, t1=t1, clock_shift_s=shift)


# ---------------------------------------------------------------- arithmetic
def clip(intervals, t0: float, t1: float) -> list:
    """The parts of (start, end, ...) intervals inside [t0, t1]."""
    out = []
    for iv in intervals:
        s, e = max(iv[0], t0), min(iv[1], t1)
        if e > s:
            out.append((s, e) + tuple(iv[2:]))
    return out


def union(intervals) -> list:
    """Merged (start, end) intervals, sorted."""
    merged = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> float:
    return sum(iv[1] - iv[0] for iv in intervals)


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    return sum(total(union(clip(d.ops, trace.t0, trace.t1)))
               for d in trace.devices) / len(trace.devices)


def op_seconds(trace: Trace, t0: float = None, t1: float = None) -> dict:
    """Summed time per category of operation inside the window, averaged
    over the chips."""
    t0 = trace.t0 if t0 is None else t0
    t1 = trace.t1 if t1 is None else t1
    out = {}
    for d in trace.devices:
        for s, e, cat in clip(d.ops, t0, t1):
            out[cat] = out.get(cat, 0.0) + (e - s) / len(trace.devices)
    return out


def whole_modules(device: DeviceTrace, t0: float, t1: float, name: str = None) -> list:
    """Program executions that lie wholly inside [t0, t1]; with no name, those
    of the program that took most of the time."""
    inside = [m for m in device.modules if m[0] >= t0 and m[1] <= t1]
    if name is None and inside:
        by_name = {}
        for s, e, n in inside:
            by_name[n] = by_name.get(n, 0.0) + (e - s)
        name = max(by_name, key=by_name.get)
    return [m for m in inside if m[2] == name]


def flatten(spans) -> list:
    """Host spans (name, start, end, ...) that may nest or overlap, as a
    timeline of disjoint (start, end, name) pieces: where several spans
    cover a moment, the one that started last (the innermost) has it."""
    points = sorted({t for sp in spans for t in (sp[1], sp[2])})
    starts = sorted(spans, key=lambda sp: sp[1])
    out, active, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(starts) and starts[i][1] <= a:
            active.append(starts[i])
            i += 1
        active = [sp for sp in active if sp[2] > a]
        if active:
            name = max(active, key=lambda sp: sp[1])[0]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def idle_gaps(trace: Trace, spans) -> dict:
    """The first chip's idle time inside the window, by what the host was
    doing: each gap's seconds go to the host span that covers them
    (`flatten`), and to `unattributed` where none does."""
    if not trace.devices:
        return {}
    busy = union(clip(trace.devices[0].ops, trace.t0, trace.t1))
    gaps, at = [], trace.t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if trace.t1 > at:
        gaps.append((at, trace.t1))
    pieces = flatten(spans)
    out, j = {}, 0
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            lo, hi = max(g0, pieces[k][0]), min(g1, pieces[k][1])
            if hi > lo:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + (hi - lo)
                covered += hi - lo
            k += 1
        if g1 - g0 > covered:
            out["unattributed"] = out.get("unattributed", 0.0) + (g1 - g0 - covered)
    return out


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
