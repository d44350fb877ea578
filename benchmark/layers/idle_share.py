"""1 - the union of the device-operation intervals over the traced window,
averaged over the chips."""
from benchmark import trace_reduce


def read(trace, spans, facts):
    if not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_seconds(trace) / trace.window_s)
