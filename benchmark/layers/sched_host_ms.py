"""The scheduler's own time per decode beat: median over the `serving.beat`
spans of kind `decode` inside the traced window of the beat's duration less
the seconds the first chip was busy inside it. What is left is the host:
admission, assembly, dispatch, the read's latency, bookkeeping. Logs the same
for the prefill beats and the sum over all beats, which is the device's idle
time seen from the program's side, how much of the beats their child spans
cover, and where a decode beat's host time lies: the median of each child
(`serving.admit`, `.build`, `.decode`, `.absorb`) and, inside `serving.decode`,
of `serving.dispatch` (until the program call returns) against `serving.read`
(the wait for the tokens, which holds the device's work)."""
from benchmark import harness, program_spans, trace_reduce

_CHILDREN = ("serving.admit", "serving.build", "serving.decode", "serving.absorb")
_CALL = ("serving.dispatch", "serving.read")     # inside serving.decode


def read(trace, spans, facts):
    beats = program_spans.inside(trace, {"serving.beat"})
    if not beats or not trace.devices:
        return None
    busy = trace_reduce.union(trace_reduce.clip(trace.devices[0].ops, trace.t0, trace.t1))
    own = {}
    for _, t0, t1, _, _, args in beats:
        own.setdefault(args.get("kind"), []).append(
            (t1 - t0) - program_spans.busy_inside(busy, t0, t1))
    if not own.get("decode"):
        return None
    kind_of = {b[3]: b[5].get("kind") for b in beats}
    kids = program_spans.spans(trace, set(_CHILDREN + _CALL))
    covered, steps, parts = 0.0, set(), {}
    for name, t0, t1, sid, parent, _ in kids:
        if name in _CHILDREN and parent in kind_of:
            covered += t1 - t0
            if kind_of[parent] == "decode":
                parts.setdefault(name, []).append(t1 - t0)
                if name == "serving.decode":
                    steps.add(sid)
    for name, t0, t1, _, parent, _ in kids:
        if name in _CALL and parent in steps:
            parts.setdefault(name, []).append(t1 - t0)
    harness.log(f"beats: {len(beats)}, their children cover "
                f"{100.0 * covered / sum(b[2] - b[1] for b in beats):.2f}% of them")
    harness.log("a decode beat, medians: " + ", ".join(
        f"{name[8:]} {1e3 * harness.percentile(parts[name], 50.0):.3f} ms"
        for name in _CHILDREN + _CALL if parts.get(name)))
    harness.log("beats' own time (host): " + ", ".join(
        f"{kind} {len(v)} x median {1e3 * harness.percentile(v, 50.0):.2f} ms = "
        f"{sum(v):.3f} s" for kind, v in sorted(own.items(), key=lambda kv: str(kv[0]))))
    return 1e3 * harness.percentile(own["decode"], 50.0)
