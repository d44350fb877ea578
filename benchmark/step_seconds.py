"""Device seconds by the engine's steps, for per-layer readers that split a
window into its decode steps and its prefill chunks.

The engine's `serving.decode` spans (one a step, `kind` `decode` or
`prefill`) are on the host's clock, as the device's operations are after
`trace_reduce`'s shift; the scheduler's thread waits inside the span for the
step's tokens, so the device's work for a step lies inside its span, and one
program runs at a time. Three sums, each over the steps of one kind that lie
whole inside the traced window:

- `busy_in`: the seconds the first chip was busy inside those spans;
- `region_seconds`: the seconds of the regions (`benchmark/scopes.py`) whose
  path holds the step kind's root (`decode`, `prefill`) and one of `terms`;
- `named_seconds`: the seconds of operations that carry no scope of the
  program's but a name of their own that starts with `prefix`, assigned to a
  kind by the span their midpoint falls in. XLA's grouped-product kernel on a
  TPU is such an operation: it names itself `ragged-dot-...` and drops the
  scope it was traced under.
"""
from __future__ import annotations

import bisect

from benchmark import program_spans, scopes, trace_reduce


def steps(trace, spans, kind: str) -> list:
    """[(t0, t1, args)] of the `serving.decode` spans of `kind` inside the
    window, in order of start."""
    return sorted((t0, t1, args) for name, t0, t1, args in spans
                  if name == "serving.decode" and args.get("kind") == kind
                  and t0 >= trace.t0 and t1 <= trace.t1)


def busy_in(trace, ran: list) -> float:
    if not trace.devices or not ran:
        return 0.0
    busy = trace_reduce.union(trace_reduce.clip(trace.devices[0].ops, trace.t0, trace.t1))
    return sum(program_spans.busy_inside(busy, t0, t1) for t0, t1, _ in ran)


def region_seconds(trace, root: str, *terms: str) -> float:
    regions = scopes.region_seconds(trace)
    if not regions or root is None or None in terms:
        return 0.0
    return sum(v for k, v in regions.items()
               if scopes.holds(k, root) and any(scopes.holds(k, t) for t in terms))


def named_seconds(trace, ran: list, prefix: str) -> float:
    path = scopes.capture_path()
    if not path or not ran or prefix is None:
        return 0.0
    starts = [t0 for t0, _, _ in ran]
    devices = scopes.device_ops(path)
    spent = 0.0
    for start_ns, dur_ns, tf_op, _ in devices[0] if devices else ():
        if not tf_op or not tf_op.startswith(prefix):
            continue
        s = start_ns / 1e9 + trace.clock_shift_s
        mid = s + dur_ns / 2e9
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= ran[i][1]:
            spent += dur_ns / 1e9
    return spent
