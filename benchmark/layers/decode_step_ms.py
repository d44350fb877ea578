"""Median duration of the engine's `serving.decode` spans of kind `decode`
inside the traced window: one decode step as the scheduler's thread sees it,
program call and host read included."""
from benchmark import harness


def read(trace, spans, facts):
    steps = [t1 - t0 for name, t0, t1, args in spans
             if name == "serving.decode" and args.get("kind") == "decode"
             and t0 >= trace.t0 and t1 <= trace.t1]
    return 1e3 * harness.percentile(steps, 50.0) if steps else None
