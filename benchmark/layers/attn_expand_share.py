"""What chunking a prompt over a latent cache costs: the region `attn/expand`
(keys and values formed again from the stored latent rows, for every block
of pages a chunk attends over) over the seconds the chip was busy inside the
window's prefill chunks."""
from benchmark import scopes, step_seconds


def read(trace, spans, facts):
    ran = step_seconds.steps(trace, spans, "prefill")
    if scopes.term("ATTN_EXPAND") is None or not ran:
        return None
    spent = step_seconds.region_seconds(trace, scopes.term("PREFILL"), scopes.term("ATTN_EXPAND"))
    busy = step_seconds.busy_in(trace, ran)
    return 100.0 * spent / busy if spent > 0 and busy > 0 else None
