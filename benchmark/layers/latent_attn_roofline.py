"""Decode's attention over the latent pages against its roofline: the least
seconds the chip could take to read every live latent row once a layer
(flops_axk1: 576 bfloat16 numbers a row; a step's `pages_live` pages hold at
least `(pages_live - lanes) x page + lanes` live rows, each lane's last page
counted as one row) or to do the rows' products for one query a lane,
whichever is more, summed over the window's decode steps, over the device
seconds of decode's `attn/core`. The same work whatever implements it."""
from benchmark import flops_axk1, scopes, step_seconds


def read(trace, spans, facts):
    ran = step_seconds.steps(trace, spans, "decode")
    if scopes.term("LATENT_ATTN") is None or not ran or "latent_row_bytes" not in facts:
        return None
    spent = step_seconds.region_seconds(trace, scopes.term("DECODE"), scopes.term("ATTN_CORE"))
    if spent <= 0:
        return None
    least = 0.0
    for _, _, a in ran:
        rows = ((a.get("pages_live", 0) - a["lanes"]) * facts["page_size"] + a["lanes"]) \
            * facts["layers"]
        least += flops_axk1.least_seconds(rows * facts["latent_flops_per_row"],
                                          rows * facts["latent_row_bytes"],
                                          facts["device_kind"])
    return 100.0 * least / spent
