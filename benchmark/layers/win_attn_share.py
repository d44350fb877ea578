"""Decode's grouped-query attention over the live pages of both page pools,
window layers and global ones (the kernel `gqa_paged_attn`), over the
device's busy time in the traced window."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.share(trace, scopes.term("GQA_ATTN"))
