"""Of the first chip's idle seconds in the traced window, the share that fell
while the scheduler's thread was inside `serving.dispatch` (its two children
included): the device waiting for the host to hand it the next call. Each idle
gap goes to the innermost span of the beat that covers it
(`trace_reduce.idle_gaps`); the log has the whole table: `serving.admit`,
`.build`, `.dispatch` and its children `.carry` and `.sample_args`, `.read`,
`.absorb`, the rest of `serving.decode` (the pool commit and the launch's
bookkeeping), the rest of a beat, between two beats, and outside any span."""
from benchmark import call_device, harness, program_spans, trace_reduce

_DISPATCH = ("serving.dispatch", "serving.dispatch.carry", "serving.dispatch.sample_args")
_PHASES = ("serving.beat", "serving.admit", "serving.build", "serving.decode",
           "serving.read", "serving.absorb") + _DISPATCH
_BETWEEN = "between beats"


def read(trace, spans, facts):
    if not trace.devices:
        return None
    phases = program_spans.spans(trace, set(_PHASES))
    if not any(s[0] == "serving.dispatch" for s in phases):
        return None
    if call_device.ring_dropped(trace):
        harness.log("idle_dispatch_share: the tracer's ring dropped events; not read")
        return None
    beats = [s for s in phases if s[0] == "serving.beat"]
    between = [(_BETWEEN, a[2], b[1]) for a, b in zip(beats, beats[1:])
               if b[5].get("beat") == a[5].get("beat") + 1 and b[1] > a[2]]
    gaps = trace_reduce.idle_gaps(trace, between + [s[:3] for s in phases])
    idle = sum(gaps.values())
    if idle <= 0:
        return None
    named = idle - gaps.get("unattributed", 0.0)
    harness.log(f"idle {idle:.3f} s of the window's {trace.window_s:.3f}, "
                f"{100.0 * named / idle:.1f}% under a named phase: " + ", ".join(
                    f"{name.replace('serving.', '')} {v:.3f}"
                    for name, v in trace_reduce.top(gaps, len(gaps))))
    return 100.0 * sum(gaps.get(name, 0.0) for name in _DISPATCH) / idle
