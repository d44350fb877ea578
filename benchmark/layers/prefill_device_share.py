"""Prefill's share of the device: device seconds of the executions of the
traced window's `serving.call` spans of kind `prefill`
(`benchmark/call_device.py`), cut to the window, over the first chip's busy
seconds in the stretch of it that the tied calls cover (all of it but an
execution at an edge whose call has no span); None where the tied seconds
are not within 2% of those. What `prefill_share.*` read before a call's tokens were read a
beat late. Logs a prefill call's (a chunk's) median and p99 device ms."""
from benchmark import call_device, harness


def read(trace, spans, facts):
    joined = call_device.usable(trace)
    if joined is None or not joined.covers:
        return None
    chunks = [c.device_s for c in joined.of_kind("prefill")]
    if chunks:
        harness.log(f"a prefill call on the device: {len(chunks)} x median "
                    f"{call_device.ms(chunks, 50.0):.3f} ms, p99 "
                    f"{call_device.ms(chunks, 99.0):.3f}")
    return 100.0 * joined.seconds_inside("prefill") / joined.busy_s
