"""The region `attn/layout` over the device's busy time in the traced window:
the heads-major transposes into and out of the flash kernels, forward and backward."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.share(trace, scopes.term("ATTN_LAYOUT"))
