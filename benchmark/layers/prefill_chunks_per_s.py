"""Prefill chunks run per second of the traced window: the `serving.decode`
spans of kind `prefill` that carry a `chunk` index (programs that prefill a
prompt in pieces), over the window's seconds."""


def read(trace, spans, facts):
    ran = [1 for name, t0, t1, args in spans
           if name == "serving.decode" and args.get("kind") == "prefill"
           and "chunk" in args and t0 >= trace.t0 and t1 <= trace.t1]
    return len(ran) / trace.window_s if ran and trace.window_s > 0 else None
