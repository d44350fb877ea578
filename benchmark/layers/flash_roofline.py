"""The Pallas flash-attention kernels' share of their roofline: the least
time the chip could take for the whole steps' causal attention, forward and
backward (flops.py, from the shapes), over the summed device time of the
`tpu_custom_call` operations inside those steps."""
from benchmark import flops, trace_reduce


def kernel_seconds(trace, steps):
    """Summed kernel time inside the whole steps, first chip."""
    if not steps:
        return 0.0
    t0, t1 = steps[0][0], steps[-1][1]
    return sum(e - s for s, e, cat in trace_reduce.clip(trace.devices[0].ops, t0, t1)
               if cat == trace_reduce.PALLAS_CALL)


def read(trace, spans, facts):
    if not trace.devices or "head_dim" not in facts:
        return None
    steps = trace_reduce.whole_modules(trace.devices[0], trace.t0, trace.t1)
    spent = kernel_seconds(trace, steps)
    if spent <= 0:
        return None
    # under a dp x mp mesh each chip holds its share of the batch and heads
    shape = (facts["batch"], facts["seq"], facts["heads"], facts["head_dim"])
    least, _ = flops.least_seconds(
        flops.causal_attention_train_flops(*shape) / facts["chips"],
        flops.causal_attention_train_bytes(*shape) / facts["chips"],
        facts["device_kind"])
    return 100.0 * least * facts["layers"] * len(steps) / spent
