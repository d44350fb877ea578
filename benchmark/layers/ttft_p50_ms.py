"""Median time to first token over the requests whose first token reached the
host inside the traced window: the request's `serving.request.queue` span's
start (its submit) to its `serving.request.prefill` span's end (the beat's
stamp after the host read). Logs the 90th percentile and the count beside it,
and the requests that closed with `serving.request.failed` in the window, by
reason: they never saw a token, so the times above do not hold them."""
from benchmark import harness, program_spans


def read(trace, spans, facts):
    phases = program_spans.spans(trace, {"serving.request.queue",
                                         "serving.request.prefill"})
    submitted = {a.get("request"): t0 for name, t0, _, _, _, a in phases
                 if name == "serving.request.queue"}
    waits = [t1 - submitted[a.get("request")] for name, _, t1, _, _, a in phases
             if name == "serving.request.prefill" and a.get("request") in submitted
             and trace.t0 <= t1 <= trace.t1]
    failed = {}
    for _, _, t1, _, _, a in program_spans.spans(trace, {"serving.request.failed"}):
        if trace.t0 <= t1 <= trace.t1:
            failed[a.get("reason")] = failed.get(a.get("reason"), 0) + 1
    if failed:
        harness.log(f"ttft: not counted, failed in the window: {failed}")
    if not waits:
        return None
    harness.log(f"ttft: {len(waits)} first tokens in the window, p50 "
                f"{1e3 * harness.percentile(waits, 50.0):.1f} ms, p90 "
                f"{1e3 * harness.percentile(waits, 90.0):.1f} ms")
    return 1e3 * harness.percentile(waits, 50.0)
