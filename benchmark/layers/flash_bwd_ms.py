"""Mean device time of the two backward Pallas kernels, `flash_bwd_dq` and
`flash_bwd_dkv`, per layer per step, over the step programs whole inside the
traced window."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.kernel_ms_per_layer_step(trace, facts, scopes.term("FLASH_BWD_DQ"),
                                           scopes.term("FLASH_BWD_DKV"))
