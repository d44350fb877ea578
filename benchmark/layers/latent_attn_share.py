"""Decode's attention over the latent pages as a share of the decode steps'
device time: the seconds of the regions under `decode` that hold `attn/core`
(on a TPU the kernel `latent_paged_attn`), over the seconds the chip was busy
inside the window's decode steps."""
from benchmark import scopes, step_seconds


def read(trace, spans, facts):
    ran = step_seconds.steps(trace, spans, "decode")
    if scopes.term("LATENT_ATTN") is None or not ran:
        return None
    spent = step_seconds.region_seconds(trace, scopes.term("DECODE"), scopes.term("ATTN_CORE"))
    busy = step_seconds.busy_in(trace, ran)
    return 100.0 * spent / busy if spent > 0 and busy > 0 else None
