"""The region `retn/chunk` over the device's busy time in the traced window:
the prefill chunk's retention (phi, the masked quadratic part inside the
chunk, the lane's state carried in and out), every layer, every chunk."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.share(trace, scopes.term("RETN_CHUNK"))
