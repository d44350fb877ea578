"""95th percentile of the time between two consecutive tokens of one request:
for each request id in the `requests` of the `serving.decode` spans inside the
traced window, the differences of the ends of the consecutive steps it rode.
A gap longer than a decode step is a prefill step that the lane sat out."""
from benchmark import harness, program_spans


def read(trace, spans, facts):
    ends = {}
    for _, _, t1, _, _, args in program_spans.inside(trace, {"serving.decode"}):
        for request in args.get("requests") or ():
            ends.setdefault(request, []).append(t1)
    gaps = [b - a for times in ends.values() for a, b in zip(times, times[1:])]
    if not gaps:
        return None
    harness.log(f"token gaps: {len(gaps)} over {len(ends)} requests, p50 "
                f"{1e3 * harness.percentile(gaps, 50.0):.1f} ms, p95 "
                f"{1e3 * harness.percentile(gaps, 95.0):.1f} ms, longest "
                f"{1e3 * max(gaps):.1f} ms")
    return 1e3 * harness.percentile(gaps, 95.0)
