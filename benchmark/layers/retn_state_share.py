"""The region `retn/state` over the device's busy time in the traced window:
the decode step's retention (phi of q and k, the update of every touched
lane's state in place, the read for y), every layer, every step."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.share(trace, scopes.term("RETN_STATE"))
