"""`unscoped_share` for a program whose layers are one `lax.scan`: the
device's busy time in the traced window that no region of the program's
vocabulary names, with the control-flow wrappers left out. The profiler
lists a `while` (and a `conditional`, a `call`) on the `XLA Ops` line beside
the operations of its body, with no scope of its own, so `unscoped_share`
counts a scanned layer stack twice and reads over 100% there; the wrapper is
no work of its own, and what runs inside it is counted where it stands.
None for a program without the retention vocabulary."""
from benchmark import scopes, trace_reduce

WRAPPERS = ("while", "conditional", "call")


def read(trace, spans, facts):
    path = scopes.capture_path()
    if scopes.term("RETN_STATE") is None or not path:
        return None
    devices = scopes.device_ops(path)
    busy = trace_reduce.busy_seconds(trace)
    if busy <= 0 or not any(tf_op for ops in devices for _, _, tf_op, _ in ops):
        return None
    spent = 0.0
    for ops in devices:
        for start_ns, dur_ns, tf_op, text in ops:
            if tf_op and scopes.region_of(tf_op) != scopes.UNSCOPED:
                continue
            if trace_reduce.category(text) in WRAPPERS:
                continue
            s = max(start_ns / 1e9 + trace.clock_shift_s, trace.t0)
            e = min((start_ns + dur_ns) / 1e9 + trace.clock_shift_s, trace.t1)
            spent += max(e - s, 0.0) / len(devices)
    return 100.0 * spent / busy
