"""Decode's grouped-query paged attention against its roofline: the least
seconds the chip could take to read, once a layer, the cached K and V rows
that each decode step of the window has to read (flops_cohere2_moe: a global
layer every row up to the lane's position, a window layer the rows its window
covers, from the spans' `pages_live` and `window_pages_live`) or to do their
products for one query a lane, whichever is more, over the device seconds of
the kernel `gqa_paged_attn`. The same work whatever implements it."""
from benchmark import flops_cohere2_moe as fl
from benchmark import scopes, step_seconds


def read(trace, spans, facts):
    ran = [a for _, _, a in step_seconds.steps(trace, spans, "decode")
           if "window_pages_live" in a]
    if scopes.term("GQA_ATTN") is None or not ran or "kv_row_bytes" not in facts:
        return None
    regions = scopes.region_seconds(trace)
    spent = scopes.seconds_in(regions, scopes.term("GQA_ATTN")) if regions else 0.0
    if spent <= 0:
        return None
    least = 0.0
    for a in ran:
        rows = fl.decode_attention_rows(a["pages_live"], a["window_pages_live"],
                                        a["lanes"], facts["page_size"],
                                        facts["window_layers"], facts["full_layers"])
        least += fl.least_seconds(rows * facts["attn_flops_per_row"],
                                  rows * facts["kv_row_bytes"], facts["device_kind"])
    return 100.0 * least / spent
