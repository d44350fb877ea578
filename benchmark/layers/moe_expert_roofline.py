"""The held experts against their roofline: for each step of the window the
least seconds to read the weights of the experts that got a pair
(`experts_hit` x an expert's bytes) or to do the pairs' products (`pairs` x
an expert's operations), whichever is more, summed over the decode steps and
the prefill chunks (the engine's spans carry both counts), over the
`moe/experts` device seconds of those steps, the grouped-product kernel
included. Logs the two kinds apart: decode is bound by the weights' bytes,
a prefill chunk by operations."""
from benchmark import flops_axk1, harness, scopes, step_seconds
from benchmark.layers.moe_expert_share import expert_seconds


def read(trace, spans, facts):
    if scopes.term("MOE_EXPERTS") is None or "expert_bytes" not in facts:
        return None
    least, spent = {}, {}
    for kind in ("decode", "prefill"):
        ran = [a for _, _, a in step_seconds.steps(trace, spans, kind) if "pairs" in a]
        least[kind] = sum(flops_axk1.least_seconds(
            a["pairs"] * facts["expert_flops_per_pair"],
            a["experts_hit"] * facts["expert_bytes"], facts["device_kind"]) for a in ran)
        spent[kind] = expert_seconds(trace, spans, kind) if ran else 0.0
    if sum(spent.values()) <= 0:
        return None
    harness.log("moe/experts, least over spent: " + ", ".join(
        f"{kind} {least[kind]:.4f} / {spent[kind]:.4f} s" for kind in least))
    return 100.0 * sum(least.values()) / sum(spent.values())
