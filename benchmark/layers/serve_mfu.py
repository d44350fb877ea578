"""Model FLOP/s utilization of a serving window: the operations that the
window's prompt tokens (the `tokens` of its prefill steps) and answer tokens
(the `lanes` of its decode steps) need, matrices and the layers' own
mechanism, as the driver's facts count them per token
(`prompt_flops_per_token`, `answer_flops_per_token`), over the window's
seconds x chips x the bf16 peak. The share of the whole step that a claim in
the cell is bounded by; decode is bound by bytes, so it reads low."""
from benchmark import flops


def read(trace, spans, facts):
    if "answer_flops_per_token" not in facts or trace.window_s <= 0:
        return None
    prompt = answer = 0
    for name, t0, t1, args in spans:
        if name != "serving.decode" or t0 < trace.t0 or t1 > trace.t1:
            continue
        if args.get("kind") == "prefill":
            prompt += args.get("tokens", 0)
        elif args.get("kind") == "decode":
            answer += args.get("lanes", 0)
    if not prompt + answer:
        return None
    needed = (prompt * facts["prompt_flops_per_token"]
              + answer * facts["answer_flops_per_token"])
    peak = flops.peaks(facts["device_kind"])["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * needed / (trace.window_s * peak)
