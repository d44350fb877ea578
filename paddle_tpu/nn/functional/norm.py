"""Normalization functionals (reference: python/paddle/nn/functional/norm.py;
rms_norm from phi fusion kernels paddle/phi/kernels/fusion/rms_norm* — here a
Pallas kernel with XLA fallback, see paddle_tpu/ops/pallas/rms_norm.py)."""
from __future__ import annotations

import jax.numpy as jnp

from ...core.dispatch import primitive
from ...core.tensor import Tensor, unwrap


def _apply_affine(out, wb, has_w, has_b, shape=None):
    """Scale/shift ``out`` by the trailing ``wb`` args. The norm kernels
    close over presence BOOLEANS, never the weight/bias Tensors themselves:
    a Tensor closure cell would make every call an array_capture
    kernel-cache bypass, keeping the hottest norm ops on the
    trace-per-call slow path."""
    if has_w:
        w = wb[0]
        out = out * (w.reshape(shape) if shape is not None else w)
    if has_b:
        b = wb[1 if has_w else 0]
        out = out + (b.reshape(shape) if shape is not None else b)
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(normalized_shape)
    has_w, has_b = weight is not None, bias is not None

    def fn(v, *wb):
        axes = tuple(range(v.ndim - n_axes, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) * jax_rsqrt(var + epsilon)
        return _apply_affine(out, wb, has_w, has_b)

    args = [x] + [t for t in (weight, bias) if t is not None]
    return primitive("layer_norm", fn, args)


def jax_rsqrt(v):
    from jax import lax

    return lax.rsqrt(v)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm (TPU fusion tier; Pallas kernel when enabled)."""
    from ...ops import pallas

    if pallas.enabled() and weight is not None:
        return pallas.rms_norm.rms_norm(x, weight, epsilon)

    def fn(v, *w):
        ms = jnp.mean(jnp.square(v), axis=-1, keepdims=True)
        out = v * jax_rsqrt(ms + epsilon)
        if w:
            out = out * w[0]
        return out

    args = [x] + ([weight] if weight is not None else [])
    return primitive("rms_norm", fn, args)


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-5,
    data_format="NCHW",
    use_global_stats=None,
    name=None,
):
    """BatchNorm with running-stat update (reference phi batch_norm kernel).

    Running stats are mutated functionally (payload swap) so the jit
    functionalizer captures their update inside compiled steps.
    """
    v = unwrap(x)
    ch_axis = 1 if data_format.startswith("NC") and v.ndim > 1 else v.ndim - 1
    reduce_axes = tuple(i for i in range(v.ndim) if i != ch_axis)
    use_stats = (not training) if use_global_stats is None else use_global_stats

    has_w, has_b = weight is not None, bias is not None

    if use_stats:
        def fn(v, m, var, *wb):
            shape = [1] * v.ndim
            shape[ch_axis] = v.shape[ch_axis]
            out = (v - m.reshape(shape)) * jax_rsqrt(var.reshape(shape) + epsilon)
            return _apply_affine(out, wb, has_w, has_b, shape)

        args = [x, running_mean, running_var] + [t for t in (weight, bias) if t is not None]
        return primitive("batch_norm_infer", fn, args)

    # training: compute batch stats, update running stats
    def fn(v, *wb):
        mean = jnp.mean(v, axis=reduce_axes)
        var = jnp.var(v, axis=reduce_axes)
        shape = [1] * v.ndim
        shape[ch_axis] = v.shape[ch_axis]
        out = (v - mean.reshape(shape)) * jax_rsqrt(var.reshape(shape) + epsilon)
        return _apply_affine(out, wb, has_w, has_b, shape), mean, var

    args = [x] + [t for t in (weight, bias) if t is not None]
    out, batch_mean, batch_var = primitive("batch_norm", fn, args)
    batch_mean.stop_gradient = True
    batch_var.stop_gradient = True
    if running_mean is not None:
        n = 1
        for a in reduce_axes:
            n *= v.shape[a]
        unbiased = batch_var._value * (n / max(n - 1, 1))
        running_mean._replace_value(momentum * running_mean._value + (1 - momentum) * batch_mean._value)
        running_var._replace_value(momentum * running_var._value + (1 - momentum) * unbiased)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None, use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    has_w, has_b = weight is not None, bias is not None

    def fn(v, *wb):
        ch_axis = 1 if data_format.startswith("NC") else v.ndim - 1
        spatial = tuple(i for i in range(2, v.ndim)) if ch_axis == 1 else tuple(range(1, v.ndim - 1))
        mean = jnp.mean(v, axis=spatial, keepdims=True)
        var = jnp.var(v, axis=spatial, keepdims=True)
        out = (v - mean) * jax_rsqrt(var + eps)
        shape = [1] * v.ndim
        shape[ch_axis] = v.shape[ch_axis]
        return _apply_affine(out, wb, has_w, has_b, shape)

    args = [x] + [t for t in (weight, bias) if t is not None]
    return primitive("instance_norm", fn, args)


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None, data_format="NCHW", name=None):
    has_w, has_b = weight is not None, bias is not None

    def fn(v, *wb):
        cl = not data_format.startswith("NC")
        if cl:
            v_t = jnp.moveaxis(v, -1, 1)
        else:
            v_t = v
        b, c = v_t.shape[0], v_t.shape[1]
        rest = v_t.shape[2:]
        g = v_t.reshape((b, num_groups, c // num_groups) + rest)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax_rsqrt(var + epsilon)).reshape(v_t.shape)
        shape = [1] * out.ndim
        shape[1] = c
        out = _apply_affine(out, wb, has_w, has_b, shape)
        if cl:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [x] + [t for t in (weight, bias) if t is not None]
    return primitive("group_norm", fn, args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    def fn(v):
        ch_axis = 1 if data_format.startswith("NC") else v.ndim - 1
        sq = jnp.square(v)
        c = v.shape[ch_axis]
        half = size // 2
        pads = [(0, 0)] * v.ndim
        pads[ch_axis] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        acc = jnp.zeros_like(v)
        for i in range(size):
            sl = [slice(None)] * v.ndim
            sl[ch_axis] = slice(i, i + c)
            acc = acc + padded[tuple(sl)]
        div = (k + alpha * acc) ** beta
        return v / div

    return primitive("local_response_norm", fn, [x])
