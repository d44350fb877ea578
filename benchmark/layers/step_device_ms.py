"""Device time of the step program per execution: the mean duration of the
events of the XLA Modules line that lie wholly inside the traced window, for
the program that took most of the time."""
from benchmark import trace_reduce


def read(trace, spans, facts):
    if not trace.devices:
        return None
    steps = trace_reduce.whole_modules(trace.devices[0], trace.t0, trace.t1)
    if not steps:
        return None
    return 1e3 * sum(e - s for s, e, _ in steps) / len(steps)
