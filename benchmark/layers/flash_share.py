"""The Pallas kernels' (`tpu_custom_call`) time over the device's busy time in
the traced window."""
from benchmark import trace_reduce


def read(trace, spans, facts):
    busy = trace_reduce.busy_seconds(trace)
    spent = trace_reduce.op_seconds(trace).get(trace_reduce.PALLAS_CALL, 0.0)
    if busy <= 0 or spent <= 0:
        return None
    return 100.0 * spent / busy
