"""The region `attn/kv_gather` over the device's busy time in the traced window:
the gather of each lane's pages into one contiguous cache view (kv_cache.gather_pages), every layer, every step."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.share(trace, scopes.term("ATTN_KV_GATHER"))
