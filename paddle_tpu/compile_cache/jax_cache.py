"""Where JAX's own persistent compilation cache lives, for every entry point
(``chip_smoke.py``, ``benchmark/run.py``, ``tests/conftest.py``,
``__graft_entry__.py``).

The directory is part of what a later process must agree on to hit the
cache, so it is decided in exactly one place and is never temporary,
pid-based or time-based.
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_jax_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` decides the place when it is set: jax
    reads the variable itself and no directory is configured in code.
    Otherwise the cache is ``<checkout>/.jax_cache``.

    Every executable is kept, however quick its compile: ``TrainStep``'s
    eager discovery pass runs the step op by op, through hundreds of small
    programs, before the one-program compile, and a second process should
    pay for neither.

    Locations carry three frames of the call stack, not ten: a Pallas
    kernel's ``tpu_custom_call`` payload holds its operations' locations
    and is part of the cache key (HLO metadata is not), so with the
    frames of whoever warmed the engine in them, two entry points that
    warm the same engine (the benchmark's closed-loop and open-loop
    drivers) each compiled every program that holds a kernel. Three
    frames from a kernel's body, or from its ``pallas_call``, end inside
    the program that calls it. (They still name files by absolute path:
    two checkouts do not share such a program.)
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_traceback_in_locations_limit", 3)
    return jax.config.jax_compilation_cache_dir
