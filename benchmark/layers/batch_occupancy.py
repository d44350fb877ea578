"""Mean of lanes / lane count over the decode steps of the traced window: how
full the running batch was."""


def read(trace, spans, facts):
    lanes = [args["lanes"] for name, t0, t1, args in spans
             if name == "serving.decode" and args.get("kind") == "decode"
             and t0 >= trace.t0 and t1 <= trace.t1]
    if not lanes or not facts.get("lanes"):
        return None
    return 100.0 * sum(lanes) / len(lanes) / facts["lanes"]
