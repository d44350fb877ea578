"""Model FLOP/s utilization of the traced window: the operations the forward
and backward passes need per token (flops.train_flops_per_token, no
recomputation counted) times the tokens per second from the first whole step's
start to the last one's end on the device's timeline, over chips x the bf16
peak. An end-to-end utilization, not a kernel's roofline share."""
from benchmark import flops, trace_reduce


def read(trace, spans, facts):
    if not trace.devices or "flops_per_token" not in facts:
        return None
    steps = trace_reduce.whole_modules(trace.devices[0], trace.t0, trace.t1)
    if len(steps) < 2:
        return None
    seconds = steps[-1][1] - steps[0][0]
    rate = len(steps) * facts["tokens_per_step"] / seconds
    peak = flops.peaks(facts["device_kind"])["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * rate * facts["flops_per_token"] / peak
