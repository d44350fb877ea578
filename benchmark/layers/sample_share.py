"""The region `sample` over the device's busy time in the traced window:
choosing the next token from the head's logits (the sort for top-k and top-p included)."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.share(trace, scopes.term("SAMPLE"))
