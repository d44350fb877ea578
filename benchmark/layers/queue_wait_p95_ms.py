"""95th percentile over the window's requests of t_dispatch (handed to the
scheduler) minus the time the request was due."""
from benchmark import harness


def read(trace, spans, facts):
    waits = facts.get("queue_waits_s")
    return 1e3 * harness.percentile(waits, 95.0) if waits else None
