"""What stands between process start and the first device result: device
selection, the Pallas tier's gate, the compile-cache placement and the entry
scripts. Everything here runs on the CPU; `chip_smoke.py` is the same
contract on the chip."""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- compile-cache placement ------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    seen = {}
    monkeypatch.setattr(jax.config, "update", seen.__setitem__)
    return seen


def test_cache_dir_is_left_to_the_env_var_when_set(monkeypatch, config_updates):
    from paddle_tpu.compile_cache.jax_cache import enable_jax_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    enable_jax_cache()
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates  # the cache thresholds are still set


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    from paddle_tpu.compile_cache.jax_cache import enable_jax_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable_jax_cache()
    assert config_updates["jax_compilation_cache_dir"] == os.path.join(
        REPO, ".jax_cache")


# ---- device selection -------------------------------------------------------

def test_set_device_raises_without_an_accelerator():
    import paddle_tpu as paddle

    before = paddle.device.get_device()
    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.device.set_device("tpu")
    with pytest.raises(ValueError, match="out of range"):
        paddle.device.set_device(f"cpu:{len(jax.devices('cpu'))}")
    assert paddle.device.get_device() == before


# ---- the Pallas gate --------------------------------------------------------

def test_pallas_gate_reads_flag_platform_and_mesh(monkeypatch):
    from paddle_tpu.base.flags import get_flag, set_flags
    from paddle_tpu.distributed import env
    from paddle_tpu.ops import pallas

    assert not pallas.enabled()  # this host's platform is cpu
    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    monkeypatch.setattr(env.instance(), "mesh", None)
    assert pallas.enabled()
    flag = get_flag("use_pallas_kernels")
    try:
        set_flags({"use_pallas_kernels": False})
        assert not pallas.enabled()
    finally:
        set_flags({"use_pallas_kernels": flag})
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))
    monkeypatch.setattr(env.instance(), "mesh", mesh)
    assert not pallas.enabled()  # jax cannot auto-partition a Mosaic kernel


def test_kernel_failure_propagates_instead_of_falling_back(monkeypatch):
    """With the gate open the kernel is used, and its failure is the
    caller's: here the compiled kernel cannot lower for the CPU backend."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import env
    from paddle_tpu.ops import pallas

    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    monkeypatch.setattr(env.instance(), "mesh", None)
    q = paddle.randn([1, 128, 2, 64])
    with pytest.raises(Exception, match="[Ii]nterpret"):
        F.scaled_dot_product_attention(q, q, q, is_causal=True)


# ---- re-blocked kernels at the real head geometry ---------------------------

def _qkv(shape_q, shape_k, seed):
    rs = np.random.RandomState(seed)
    q, do = (jnp.asarray(rs.randn(*shape_q).astype(np.float32)) for _ in range(2))
    k, v = (jnp.asarray(rs.randn(*shape_k).astype(np.float32)) for _ in range(2))
    return q, k, v, do


def _assert_close(got, want, names):
    for name, g, w in zip(names, got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=2e-3, err_msg=name)


@pytest.mark.parametrize("sq,sk", [(256, 256), (128, 256)])
def test_flash_h12_d64_matches_xla_attention(sq, sk):
    """H = 12, D = 64 over several q- and k-blocks; sq != sk checks the
    bottom-right causal alignment the XLA composition uses."""
    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import _flash_bwd, _flash_fwd

    q, k, v, do = _qkv((1, sq, 12, 64), (1, sk, 12, 64), 0)
    scale = 1.0 / math.sqrt(64)
    seed = jnp.zeros((1,), jnp.int32)
    blocks = dict(block_q=128, block_k=128, interpret=True)
    out, lse = _flash_fwd(q, k, v, seed, True, scale, **blocks)
    grads = _flash_bwd(q, k, v, out, lse, do, seed, True, scale, **blocks)
    want, vjp = jax.vjp(
        lambda q, k, v: _xla_attention(q, k, v, causal=True, scale=scale),
        q, k, v)
    _assert_close((out, *grads), (want, *vjp(do)), ("out", "dq", "dk", "dv"))


def test_flashmask_h12_d64_matches_xla_attention():
    """Causal document mask, H = 12, D = 64, two q-blocks."""
    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas.flashmask import _fm_bwd, _fm_fwd

    s, split = 512, 200
    q, k, v, do = _qkv((1, s, 12, 64), (1, s, 12, 64), 1)
    scale = 1.0 / math.sqrt(64)
    start = np.full((1, 1, s, 1), s, np.int32)
    start[:, :, :split, 0] = split  # keys of document 1 hide from rows >= split
    idx = jnp.asarray(start)
    out, lse = _fm_fwd(q, k, v, idx, True, scale, interpret=True)
    grads = _fm_bwd(q, k, v, idx, out, lse, do, True, scale, interpret=True)

    rows = np.arange(s)[:, None]
    hidden = rows >= start[0, 0, :, 0][None, :]
    bias = jnp.asarray(np.where(hidden, -1e30, 0.0).astype(np.float32))
    want, vjp = jax.vjp(
        lambda q, k, v: _xla_attention(q, k, v, causal=True, scale=scale,
                                       bias=bias[None, None]),
        q, k, v)
    _assert_close((out, *grads), (want, *vjp(do)), ("out", "dq", "dk", "dv"))


# ---- entry scripts ----------------------------------------------------------

def _run(code_or_script, **env):
    cmd = [sys.executable] + code_or_script
    return subprocess.run(cmd, cwd=REPO, env={**os.environ, **env},
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_has_no_cpu_mode():
    res = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert "no TPU" in res.stderr and "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


def test_imports_initialize_no_backend():
    """The launcher parent must never hold the chip its workers need."""
    code = (
        "import paddle_tpu, paddle_tpu.distributed.launch.main, "
        "paddle_tpu.serving, paddle_tpu.jit.api, paddle_tpu.models\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    res = _run(["-c", code], JAX_PLATFORMS="cpu")
    assert res.returncode == 0, res.stderr
