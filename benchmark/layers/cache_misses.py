"""Programs that JAX's persistent compilation cache did not hold during
set-up (jax.monitoring events): 0 in every run after a checkout's first."""


def read(trace, spans, facts):
    return facts.get("cache_misses")
