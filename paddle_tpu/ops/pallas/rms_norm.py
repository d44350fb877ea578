"""Fused RMSNorm Pallas kernel (reference analog:
paddle/phi/kernels/fusion/gpu/rms_norm_kernel.cu).

The kernel fuses mean-of-squares + rsqrt + scale in VMEM, one row-block per
grid step. ``nn.functional.rms_norm`` takes it when ``ops.pallas.enabled()``
says so and the XLA composition otherwise; interpret mode is for the tests,
through ``rms_norm_value(..., interpret=True)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _rms_norm_fwd(x, w, eps=1e-6, interpret=False):
    from jax.experimental import pallas as pl

    orig_shape = x.shape
    d = x.shape[-1]
    xr = x.reshape(-1, d)
    rows = xr.shape[0]
    # ~512 KiB of input per step, on the sublane tiling (16 rows covers bf16
    # and f32). Rows are independent, so a ragged last block is harmless:
    # its out-of-range rows are computed on padding and never written back.
    target = 512 * 1024 // (d * x.dtype.itemsize)
    block_rows = min(rows, max(16, target // 16 * 16))
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        interpret=interpret,
    )(xr, w)
    return out.reshape(orig_shape)


def rms_norm_value(x, w, eps=1e-6, interpret=False):
    """Differentiable fused RMSNorm on raw arrays (custom_vjp)."""
    return _rms_norm_custom(x, w, eps, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm_custom(x, w, eps, interpret):
    return _rms_norm_fwd(x, w, eps=eps, interpret=interpret)


def _fwd(x, w, eps, interpret):
    return _rms_norm_fwd(x, w, eps=eps, interpret=interpret), (x, w)


def _bwd(eps, interpret, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    xn = xf * inv
    dw = jnp.sum(gf * xn, axis=tuple(range(x.ndim - 1))).astype(w.dtype)
    gw = gf * wf
    # d/dx [x * inv]: inv * g - xn * mean(g*xn) * inv
    dx = inv * (gw - xn * jnp.mean(gw * xn, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dw


_rms_norm_custom.defvjp(_fwd, _bwd)


def rms_norm(x, weight, epsilon=1e-6):
    """Tensor-level entry used by nn.functional.rms_norm."""
    from ...core.dispatch import primitive

    return primitive(
        "pallas_rms_norm",
        lambda v, w: rms_norm_value(v, w, epsilon),
        [x, weight],
    )
