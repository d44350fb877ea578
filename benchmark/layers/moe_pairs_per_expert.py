"""Mean token-expert pairs a held expert computes in a decode step of the
traced window (`pairs` of the engine's decode spans over the held experts of
every sparse layer): how near the load is to the deployment's, in which the
same lanes on every chip that shares the layer would send each expert
`expert_share[1]` times as many."""
from benchmark import step_seconds


def read(trace, spans, facts):
    ran = [a["pairs"] for _, _, a in step_seconds.steps(trace, spans, "decode")
           if "pairs" in a]
    if not ran or not facts.get("held_experts"):
        return None
    return sum(ran) / len(ran) / (facts["sparse_layers"] * facts["held_experts"])
