"""Summed time of the `serving.decode` spans of kind `prefill` over that of
all `serving.decode` spans in the traced window."""


def read(trace, spans, facts):
    by_kind = {}
    for name, t0, t1, args in spans:
        if name == "serving.decode" and t0 >= trace.t0 and t1 <= trace.t1:
            by_kind[args.get("kind")] = by_kind.get(args.get("kind"), 0.0) + (t1 - t0)
    whole = sum(by_kind.values())
    return 100.0 * by_kind.get("prefill", 0.0) / whole if whole > 0 else None
