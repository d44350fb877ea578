"""Mean device time of the `flash_fwd` Pallas kernel per layer per step, over
the step programs whole inside the traced window."""
from benchmark import scopes


def read(trace, spans, facts):
    return scopes.kernel_ms_per_layer_step(trace, facts, scopes.term("FLASH_FWD"))
