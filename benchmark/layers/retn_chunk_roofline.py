"""The prefill chunk's retention against its roofline, which is
operations: the recurrence's count for the prompt tokens of the window's
chunks (`serving.decode` spans of kind `prefill`, their `tokens`;
flops_brumby: no recomputation inside a chunk counted) at the chip's bf16
peak, over the device seconds of the region `retn/chunk` between the first
such chunk's start and the last one's end."""
from benchmark import flops_brumby, scopes


def chunks(trace, spans):
    return [(t0, t1, args["tokens"]) for name, t0, t1, args in spans
            if name == "serving.decode" and args.get("kind") == "prefill"
            and "tokens" in args and t0 >= trace.t0 and t1 <= trace.t1]


def read(trace, spans, facts):
    term = scopes.term("RETN_CHUNK")
    ran = chunks(trace, spans)
    if term is None or not ran or "retention_flops_per_token" not in facts:
        return None
    regions = scopes.region_seconds(trace, ran[0][0], ran[-1][1])
    spent = scopes.seconds_in(regions, term) if regions else 0.0
    if spent <= 0:
        return None
    needed = sum(tokens for _, _, tokens in ran) * facts["retention_flops_per_token"]
    return 100.0 * flops_brumby.flops_seconds(needed, facts["device_kind"]) / spent
