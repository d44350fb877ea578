"""The Pallas kernels of the main path compiled for a TPU v5e that is
described, not attached: what Mosaic would refuse on the chip it refuses
here, for no chip time. Nothing runs, so nothing here says anything about
results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU's library, the test
workers each import every file, and only the worker that runs this file may
ask for it. Keep every such compile in THIS file.
"""
import os
import re

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled_attention(topo):
    """Forward and backward of the flash attention at the train cell's
    shapes (4 x 1024 tokens, 16 heads of 64, bf16), compiled for one chip
    with the persistent cache off (a described chip's entry cannot be read
    back)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import flash_attention as fa

    one_chip = SingleDeviceSharding(topo.devices[0])
    qkv = jax.ShapeDtypeStruct((4, 1024, 16, 64), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention_value(q, k, v, causal=True, scale=0.125)
        return out.astype(jnp.float32).sum()

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(qkv, qkv, qkv).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _kernel_calls(compiled):
    """{instruction name: op_name} of the program's Mosaic custom calls."""
    calls = {}
    for line in compiled.as_text().splitlines():
        if "custom-call(" in line and 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) =", line)
            op_name = re.search(r'op_name="([^"]+)"', line)
            calls[name.group(1)] = op_name.group(1) if op_name else ""
    return calls


def test_the_three_flash_kernels_compile_for_v5e_under_their_names(compiled_attention):
    calls = _kernel_calls(compiled_attention)
    assert sorted(n.split(".")[0] for n in calls) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def test_each_kernel_sits_in_attn_core_and_the_transposes_in_attn_layout(compiled_attention):
    for name, op_name in _kernel_calls(compiled_attention).items():
        kernel = name.split(".")[0]
        assert f"(attn/core)" in op_name and op_name.endswith(f"/{kernel}/pallas_call"), op_name
        assert ("transpose(jvp(" in op_name) == kernel.startswith("flash_bwd")
    text = compiled_attention.as_text()
    assert "jvp(attn/layout)" in text and "transpose(jvp(attn/layout))" in text


def test_the_step_fits_one_chip_with_room_to_spare(compiled_attention):
    m = compiled_attention.memory_analysis()
    # q, k, v in; three gradients out; the saved output and row sums between
    assert m.argument_size_in_bytes == 3 * 4 * 1024 * 16 * 64 * 2
    assert m.temp_size_in_bytes < 1 << 30


# ----------------------------------------------------- paged decode attention
@pytest.fixture(scope="module", params=[1, 5], ids=["S1", "Sk1"])
def compiled_paged_step(topo, request):
    """One layer's write-then-attend of the saturated serve cell's top rung
    (64 lanes, a table of 4 pages of 256, 12 heads of 64, the bf16 pool of
    257 pages a layer whole and donated), compiled for one chip: the rows
    scattered into the pool, then ``paged_attn`` over it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.base import regions
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    from paddle_tpu.serving import kv_cache as kvc

    S = request.param
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = shape((12, 257, 256, 768), jnp.bfloat16)
    rows = shape((64, S, 768), jnp.bfloat16)
    ints = shape((64, S), jnp.int32)

    def step(ck, cv, q, k, v, tables, positions, pages, offsets):
        ck = kvc.append_token_paged(ck, 3, pages, offsets, k)
        cv = kvc.append_token_paged(cv, 3, pages, offsets, v)
        with regions.region(regions.ATTN_CORE):
            att = paged_attention(q, ck, cv, 3, tables, positions, heads=12,
                                  scale=0.125)
        return ck, cv, att

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            pool, pool, rows, rows, rows, shape((64, 4), jnp.int32), ints,
            ints, ints).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_paged_attention_compiles_for_v5e_under_its_name(compiled_paged_step):
    calls = _kernel_calls(compiled_paged_step)
    assert [n.split(".")[0] for n in calls] == ["paged_attn"]
    (op_name,) = calls.values()
    assert "attn/core" in op_name and op_name.endswith("/paged_attn/pallas_call")


def test_the_pool_is_updated_in_place_beside_the_kernel_that_reads_it(
        compiled_paged_step):
    """The write and the kernel's read share the donated pool: both arrays
    aliased input to output, and temporaries far under one layer of it (a
    copy the compiler made to keep the update in place would be 1.21 GB)."""
    m = compiled_paged_step.memory_analysis()
    pool = 12 * 257 * 256 * 768 * 2
    assert m.alias_size_in_bytes == 2 * pool
    assert m.temp_size_in_bytes < 16 << 20


# ------------------------------------------------ latent pages (A.X-K1, PR 34)
@pytest.fixture(scope="module")
def compiled_latent_layer(topo):
    """One layer's two halves over the A.X-K1 cell's latent pool (rows of
    640, 300 pages of 256 a layer here, bf16, whole and donated), compiled
    for one chip: a ONE-PAGE prefill chunk's rows written
    (``write_chunk_pages``), then a decode step's 64 rows appended and
    ``latent_paged_attn`` over a table of 72 with 64 dense q rows a lane."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.base import regions
    from paddle_tpu.ops.pallas.paged_attention import latent_paged_attention
    from paddle_tpu.serving import kv_cache as kvc

    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def step(pool, chunk, chunk_pages, q, rows, tables, positions, pages, offsets):
        pool = kvc.write_chunk_pages(pool, 3, chunk_pages, chunk)
        pool = kvc.append_token_paged(pool, 3, pages, offsets, rows)
        with regions.region(regions.ATTN_CORE):
            o_lat = latent_paged_attention(q, pool, 3, tables, positions,
                                           v_cols=512, scale=0.1309)
        return pool, o_lat

    ints = shape((64,), jnp.int32)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(step, donate_argnums=0).lower(
            shape((7, 301, 256, 640), jnp.bfloat16), shape((256, 640), jnp.bfloat16),
            shape((1,), jnp.int32), shape((64, 64, 640), jnp.bfloat16),
            shape((64, 640), jnp.bfloat16), shape((64, 72), jnp.int32), ints, ints,
            ints).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_latent_attention_compiles_for_v5e_and_leaves_the_pool_where_it_lies(
        compiled_latent_layer):
    """The kernel under its name, the pool aliased, and temporaries far
    under the pool: a one-page chunk written as one scatter had the compiler
    re-lay the WHOLE pool out (3.7 GB at the cell's size, past the chip)."""
    calls = _kernel_calls(compiled_latent_layer)
    assert [n.split(".")[0] for n in calls] == ["latent_paged_attn"]
    (op_name,) = calls.values()
    assert "attn/core" in op_name and op_name.endswith("/latent_paged_attn/pallas_call")
    m = compiled_latent_layer.memory_analysis()
    assert m.alias_size_in_bytes == 7 * 301 * 256 * 640 * 2
    assert m.temp_size_in_bytes < 32 << 20


def test_a_kernels_cache_key_does_not_depend_on_who_warmed_the_engine(
        topo, monkeypatch):
    """The Mosaic payloads (part of JAX's cache key, unlike HLO metadata)
    of a paged decode program lowered from two call stacks are the same
    bytes, as ``enable_jax_cache`` limits the locations' frames: the two
    GPT serve cells warm one engine from two drivers and have to share
    its executables."""
    import jax
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import decode

    assert jax.config.jax_traceback_in_locations_limit == 3
    monkeypatch.setattr(decode.PagedDecodePrograms, "_kernel",
                        staticmethod(lambda: True))
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(
        vocab_size=128, num_hidden_layers=2, hidden_size=256,
        num_attention_heads=2, max_position_embeddings=512))
    model.eval()
    engine = serving.DecodeEngine(model, max_slots=8, max_seq=512,
                                  seq_buckets=[128], page_size=128,
                                  kv_dtype="bfloat16", speculate_k=2)
    one_chip = SingleDeviceSharding(topo.devices[0])
    P = engine.programs

    def payloads(kind):
        key = (kind, 8, 4)
        fn = {"decode": P._decode_fn, "verify": P._verify_fn}[kind]
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            (P._call_params(key), P.pool.k, P.pool.v, *P._zero_args(key)))
        text = jax.jit(fn).lower(*args).as_text()
        return re.findall(r'backend_config\s*=\s*"((?:[^"\\]|\\.)*)"', text)

    def another_driver(kind):
        return [payloads(k) for k in [kind]][0]

    try:
        for kind in ("decode", "verify"):
            first = payloads(kind)
            assert len(first) == 1     # the layers share one lowering of it
            jax.clear_caches()
            assert another_driver(kind) == first
    finally:
        engine.shutdown(drain=False)


# ------------------------------- two page lifetimes (Command A+, PR 36)
@pytest.fixture(scope="module")
def compiled_windowed_layers(topo):
    """A window layer's and a global layer's write-then-attend over the
    Command A+ cell's two pools (rows of 8 x 128, pages of 256, bf16, whole
    and donated; 300 pages a layer here), compiled for one chip: a ONE-PAGE
    prefill chunk's rows written, then a decode step's 64 rows appended and
    ``gqa_paged_attn`` with 128 query heads on 8 K/V heads over a table of
    136 columns, once told the window of 4096 and once told none."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.base import regions
    from paddle_tpu.ops.pallas.paged_attention import gqa_paged_attention
    from paddle_tpu.serving import kv_cache as kvc

    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def layer(kp, vp, li, window, chunk, chunk_pages, q, k, v, table, positions,
              pages, offsets):
        kp = kvc.write_chunk_pages(kp, li, chunk_pages, chunk)
        vp = kvc.write_chunk_pages(vp, li, chunk_pages, chunk)
        kp = kvc.append_token_paged(kp, li, pages, offsets, k)
        vp = kvc.append_token_paged(vp, li, pages, offsets, v)
        with regions.region(regions.ATTN_CORE):
            att = gqa_paged_attention(q, kp, vp, li, table, positions, kv_heads=8,
                                      scale=128 ** -0.5, window=window)
        return kp, vp, att

    def step(wk, wv, fk, fv, chunk, chunk_pages, q, k, v, tables, positions, pages,
             offsets):
        wk, wv, a = layer(wk, wv, 2, 4096, chunk, chunk_pages, q, k, v, tables[:, 0],
                          positions, pages, offsets)
        fk, fv, b = layer(fk, fv, 0, None, chunk, chunk_pages, q, k, v, tables[:, 1],
                          positions, pages, offsets)
        return wk, wv, fk, fv, a + b

    ints = shape((64,), jnp.int32)
    window, full = shape((3, 301, 256, 1024), jnp.bfloat16), shape((1, 301, 256, 1024),
                                                                   jnp.bfloat16)
    rows = shape((64, 1024), jnp.bfloat16)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(step, donate_argnums=(0, 1, 2, 3)).lower(
            window, window, full, full, shape((256, 1024), jnp.bfloat16),
            shape((1,), jnp.int32), shape((64, 16384), jnp.bfloat16), rows, rows,
            shape((64, 2, 136), jnp.int32), ints, ints, ints).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_gqa_paged_attention_compiles_for_v5e_under_its_name_with_and_without_a_window(
        compiled_windowed_layers):
    calls = _kernel_calls(compiled_windowed_layers)
    assert sorted(n.split(".")[0] for n in calls) == ["gqa_paged_attn"] * 2
    for op_name in calls.values():
        assert "attn/core" in op_name and op_name.endswith("/gqa_paged_attn/pallas_call")


def test_both_pools_are_updated_in_place_beside_the_kernel_that_reads_them(
        compiled_windowed_layers):
    """The four pool arrays aliased input to output and temporaries far under
    a layer of either: nothing re-lays a pool out, for a one-page chunk
    either (the fault PR 34 met)."""
    m = compiled_windowed_layers.memory_analysis()
    assert m.alias_size_in_bytes == 2 * (3 + 1) * 301 * 256 * 1024 * 2
    assert m.temp_size_in_bytes < 32 << 20


# ------------------- a prefill chunk's attention over the pages (PR 37)
@pytest.fixture(scope="module")
def compiled_chunk_layers(topo):
    """A window layer's and a global layer's write-then-attend of a 2048-row
    prefill chunk over the Command A+ cell's two pools (300 pages a layer
    here, whole and donated), compiled for one chip: the chunk's eight pages
    written, then ``gqa_chunk_attn`` with 128 query heads on 8 K/V heads
    over a table of 136 columns, ``layer`` and ``start`` traced, once told
    the window of 4096 and once told none."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.base import regions
    from paddle_tpu.ops.pallas.paged_attention import gqa_chunk_attention
    from paddle_tpu.serving import kv_cache as kvc

    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def layer(kp, vp, li, window, q, k, v, table, start):
        pages = jax.lax.dynamic_slice(table, (start // 256,), (8,))
        kp = kvc.write_chunk_pages(kp, li, pages, k)
        vp = kvc.write_chunk_pages(vp, li, pages, v)
        with regions.region(regions.ATTN_CORE):
            att = gqa_chunk_attention(q, kp, vp, li, table, start, kv_heads=8,
                                      scale=128 ** -0.5, window=window)
        return kp, vp, att

    def step(wk, wv, fk, fv, li, q, k, v, tables, start):
        wk, wv, a = layer(wk, wv, li, 4096, q, k, v, tables[0], start)
        fk, fv, b = layer(fk, fv, 0, None, q, k, v, tables[1], start)
        return wk, wv, fk, fv, a + b

    window, full = shape((3, 301, 256, 1024), jnp.bfloat16), shape((1, 301, 256, 1024),
                                                                   jnp.bfloat16)
    rows = shape((2048, 1024), jnp.bfloat16)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(step, donate_argnums=(0, 1, 2, 3)).lower(
            window, window, full, full, shape((), jnp.int32),
            shape((2048, 16384), jnp.bfloat16), rows, rows,
            shape((2, 136), jnp.int32), shape((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_gqa_chunk_attention_compiles_for_v5e_under_a_name_of_its_own(compiled_chunk_layers):
    """Mosaic takes the kernel at the cell's shapes with a window and without
    (lane-aligned slices, the scratch and the blocks inside its VMEM limit),
    and a reader of decode's ``gqa_paged_attn`` does not find it."""
    calls = _kernel_calls(compiled_chunk_layers)
    assert sorted(n.split(".")[0] for n in calls) == ["gqa_chunk_attn"] * 2
    for op_name in calls.values():
        assert "attn/core" in op_name and op_name.endswith("/gqa_chunk_attn/pallas_call")
        assert "gqa_paged_attn" not in op_name


def test_a_chunks_attention_leaves_no_scores_and_no_gathered_keys_in_memory(
        compiled_chunk_layers):
    """The pools aliased, and temporaries a few copies of q (64 MB): a block
    of 2048 keys gathered for 8 K/V heads twice over and the float32 scores
    of 8 query heads against it were 0.5 GB."""
    m = compiled_chunk_layers.memory_analysis()
    assert m.alias_size_in_bytes == 2 * (3 + 1) * 301 * 256 * 1024 * 2
    assert m.temp_size_in_bytes < 200 << 20
