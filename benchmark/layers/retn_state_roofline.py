"""The decode step's retention against its roofline, which is bytes: the
least seconds the chip could take to read and write the live lanes' states
once in every layer (flops_brumby: the symmetric state in float32), summed
over the window's decode steps with their live lanes (`serving.decode`'s
`lanes`), over the device seconds of the region `retn/state` between the
first such step's start and the last one's end. Padded batch lanes cost the
program time and count for nothing here."""
from benchmark import flops_brumby, scopes


def decode_steps(trace, spans):
    return [(t0, t1, args["lanes"]) for name, t0, t1, args in spans
            if name == "serving.decode" and args.get("kind") == "decode"
            and t0 >= trace.t0 and t1 <= trace.t1]


def read(trace, spans, facts):
    term = scopes.term("RETN_STATE")
    steps = decode_steps(trace, spans)
    if term is None or not steps or "state_bytes_per_lane" not in facts:
        return None
    regions = scopes.region_seconds(trace, steps[0][0], steps[-1][1])
    spent = scopes.seconds_in(regions, term) if regions else 0.0
    if spent <= 0:
        return None
    moved = sum(2 * lanes * facts["state_bytes_per_lane"] for _, _, lanes in steps)
    return 100.0 * flops_brumby.bytes_seconds(moved, facts["device_kind"]) / spent
