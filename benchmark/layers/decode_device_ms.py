"""A decode step on the device: median over the traced window's `serving.call`
spans of kind `decode` of the device seconds of their executions
(`benchmark/call_device.py` ties each call to its `XLA Modules` events), the
token carry's in front included. What `decode_step_ms.*` read before a call's
tokens were read a beat late. Logs the p99, the carry's execution apart, and
the median by rung."""
from benchmark import call_device, harness


def read(trace, spans, facts):
    joined = call_device.usable(trace)
    steps = joined.of_kind("decode") if joined else []
    if not steps:
        return None
    carried = [c.front_s for c in steps if len(c.runs) > 1]
    took, by_rung = [c.device_s for c in steps], {}
    for c in steps:
        by_rung.setdefault(str(c.args.get("rung")), []).append(c.device_s)
    harness.log(f"a decode call on the device: {len(steps)} x median "
                f"{call_device.ms(took, 50.0):.3f} ms, p99 {call_device.ms(took, 99.0):.3f}"
                + (f"; the carry in front of {len(carried)} of them, median "
                   f"{call_device.ms(carried, 50.0):.4f} ms" if carried else ""))
    harness.log("by rung: " + ", ".join(
        f"{rung} {len(v)} x {call_device.ms(v, 50.0):.3f} ms"
        for rung, v in sorted(by_rung.items(), key=lambda kv: -len(kv[1]))[:8]))
    return call_device.ms(took, 50.0)
