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
