"""The region `optimizer` over the device's busy time in the traced window:
the optimizer's update (clipping and every parameter's rule)."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.share(trace, scopes.term("OPTIMIZER"))
