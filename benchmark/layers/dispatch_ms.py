"""The host's part of a decode call: median duration of the `serving.dispatch`
spans of decode calls inside the traced window, from just before the program
call to its return (the device may still be running). Logs the prefill calls'
median; where a decode dispatch's time lies, by its child spans
(`serving.dispatch.carry`: the token carry's own call; `.sample_args`: the
sampling arguments built lane by lane) and what is left of it, the jitted
call's own argument handling and the runtime; and, from the `serving.call`
spans, a call's wait for its tokens (`read_wait_ms`) at the median and as a
share of the window."""
from benchmark import call_device, harness, program_spans

_PARTS = ("serving.dispatch.carry", "serving.dispatch.sample_args")


def read(trace, spans, facts):
    found = program_spans.inside(trace, {"serving.dispatch", "serving.call", *_PARTS})
    sent = [s for s in found if s[0] == "serving.dispatch" and s[5].get("program")]
    if not sent:
        return None
    if call_device.ring_dropped(trace):
        harness.log("dispatch_ms: the tracer's ring dropped events; not read")
        return None
    by_kind, decode = {}, {}
    for _, t0, t1, sid, _, args in sent:
        by_kind.setdefault(args["program"], []).append(t1 - t0)
        if args["program"] == "decode":
            decode[sid] = t1 - t0
    if not decode:
        return None
    parts = {name: {} for name in _PARTS}
    for name, t0, t1, _, parent, _ in found:
        if name in parts and parent in decode:
            parts[name][parent] = parts[name].get(parent, 0.0) + (t1 - t0)
    own = [d - sum(parts[name].get(sid, 0.0) for name in _PARTS)
           for sid, d in decode.items()]
    harness.log("a decode dispatch, medians: " + ", ".join(
        [f"{name[17:]} {call_device.ms(list(parts[name].values()), 50.0):.3f} ms "
         f"(in {len(parts[name])} of {len(decode)})" for name in _PARTS if parts[name]]
        + [f"self {call_device.ms(own, 50.0):.3f} ms"]))
    harness.log("dispatches: " + ", ".join(
        f"{kind} {len(v)} x median {call_device.ms(v, 50.0):.3f} ms = {sum(v):.3f} s"
        for kind, v in sorted(by_kind.items())))
    waits = [s[5]["read_wait_ms"] for s in found if s[0] == "serving.call"]
    if waits:
        harness.log(f"reads: {len(waits)} x median wait {harness.percentile(waits, 50.0):.3f} "
                    f"ms = {sum(waits) / 1e3:.3f} s, "
                    f"{0.1 * sum(waits) / trace.window_s:.2f}% of the window")
    return call_device.ms(list(decode.values()), 50.0)
