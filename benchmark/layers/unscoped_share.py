"""The device's busy time in the traced window that no region of the
program's vocabulary names: operations the compiler made on its own (layout
and memory-space copies of whole arrays, parameter prefetches), which no
`jax.named_scope` reaches. In the serve cells it is the relayout of the whole
KV pool on entry to and exit from every step. `scopes.log_closure` lists the
instructions behind it. None for a program without the vocabulary, where it
would read 100%."""
from benchmark import scopes


def read(trace, spans, facts):
    if scopes.term("TRAINING") is None:
        return None
    return scopes.share(trace, scopes.UNSCOPED)
