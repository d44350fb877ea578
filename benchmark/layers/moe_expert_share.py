"""The held experts' work (sort, grouped products, combine) over the device's
busy time in the traced window: the region `moe/experts` plus XLA's own
grouped-product kernel, which names its operations itself (`ragged-dot-...`)
and carries no scope."""
from benchmark import scopes, step_seconds, trace_reduce


def expert_seconds(trace, spans, kind: str) -> float:
    """`moe/experts` seconds of the steps of one kind, the grouped products
    included."""
    ran = step_seconds.steps(trace, spans, kind)
    return (step_seconds.region_seconds(trace, scopes.term(kind.upper()),
                                        scopes.term("MOE_EXPERTS"))
            + step_seconds.named_seconds(trace, ran, scopes.term("RAGGED_DOT")))


def read(trace, spans, facts):
    busy = trace_reduce.busy_seconds(trace)
    if scopes.term("MOE_EXPERTS") is None or busy <= 0:
        return None
    spent = expert_seconds(trace, spans, "decode") + expert_seconds(trace, spans, "prefill")
    return 100.0 * spent / busy if spent > 0 else None
