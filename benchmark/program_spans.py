"""The program's own spans, for the per-layer readers that need more than
`read(trace, spans, facts)` hands them.

The serve drivers enable the program's tracer (`paddle_tpu.observability.
tracing.tracer`) when the traced window opens and only `disable()` it when it
closes, so its ring still holds the window's events when the readers run; they
are on `time.perf_counter`, the clock `trace.t0`/`trace.t1` and the device
times of `trace_reduce` are on. Each span comes as

    (name, t0, t1, id, parent, args)        seconds; id and parent as the
                                            tracer gives them, None where the
                                            program's tracer has none

A program without these spans (an older commit) gives an empty list, and
every reader built on this returns None for it.
"""
from __future__ import annotations


def spans(trace=None, names=None) -> list:
    """The ring's complete spans, in order of start; with `trace`, those that
    touch its window [t0, t1] (a request's phase may begin before it); with
    `names`, those so named."""
    try:
        from paddle_tpu.observability.tracing import tracer
    except ImportError:
        return []
    out = []
    for e in tracer.to_chrome_trace()["traceEvents"]:
        if e.get("ph") != "X" or str(e.get("cat", "")).startswith("device."):
            continue
        if names is not None and e.get("name") not in names:
            continue
        t0 = e["ts"] / 1e6
        t1 = t0 + e.get("dur", 0.0) / 1e6
        if trace is not None and (t1 < trace.t0 or t0 > trace.t1):
            continue
        out.append((e["name"], t0, t1, e.get("id"), e.get("parent"),
                    dict(e.get("args") or {})))
    out.sort(key=lambda s: s[1])
    return out


def inside(trace, names) -> list:
    """Those of `spans` that lie wholly inside the window."""
    return [s for s in spans(trace, names) if s[1] >= trace.t0 and s[2] <= trace.t1]


def busy_inside(busy: list, t0: float, t1: float) -> float:
    """Seconds of the sorted, disjoint (start, end) intervals `busy` that fall
    inside [t0, t1]."""
    import bisect

    i = max(bisect.bisect_right(busy, (t0, t0)) - 1, 0)
    total = 0.0
    while i < len(busy) and busy[i][0] < t1:
        lo, hi = max(busy[i][0], t0), min(busy[i][1], t1)
        if hi > lo:
            total += hi - lo
        i += 1
    return total
