"""Pages a decode step's lanes hold, over what ONE page lifetime would hold
for the same lanes: a window layer keeps the pages its window covers
(`window_pages_live`), a global layer every page (`pages_live`), and under
one lifetime every layer would keep every page. The mean over the decode
steps of the traced window, from the engine's spans."""
from benchmark import step_seconds


def read(trace, spans, facts):
    ran = [a for _, _, a in step_seconds.steps(trace, spans, "decode")
           if "window_pages_live" in a and a.get("pages_live")]
    if not ran or "window_layers" not in facts:
        return None
    w, f = facts["window_layers"], facts["full_layers"]
    return 100.0 * sum((w * a["window_pages_live"] + f * a["pages_live"])
                       / ((w + f) * a["pages_live"]) for a in ran) / len(ran)
