"""The one compile cache is JAX's persistent compilation cache.

``jax_cache.enable_jax_cache`` decides its directory for every entry
point: ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
"""
