"""Attention functionals (reference: python/paddle/nn/functional/
flash_attention.py — flash_attention :195, scaled_dot_product_attention :976).

TPU-native: the fused path is a Pallas flash-attention kernel
(paddle_tpu/ops/pallas/flash_attention.py), taken whenever
``ops.pallas.enabled()`` says so — a lowering error there propagates, it
never downgrades the step. Elsewhere an XLA composition (which XLA still
fuses well) is used. The primitive's name says which ran: ``sdpa_flash`` /
``flash_attention`` against ``sdpa_xla`` / ``flash_attention_xla``. Layout
follows paddle: [batch, seqlen, num_heads, head_dim].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...base import regions
from ...core.dispatch import primitive
from ...core.tensor import unwrap


def _seed_from_key(key):
    """(1,) int32 seed for the in-kernel dropout PRNG, derived from (and
    threaded through compilation like) the framework RNG stream."""
    return jax.random.randint(key, (1,), 0, 2**31 - 1, jnp.int32)


@regions.region(regions.ATTN_CORE)
def _xla_attention(q, k, v, *, causal, scale, bias=None, dropout=0.0, dropout_key=None):
    # q,k,v: [B, S, H, D] -> einsum over head dim. The whole composition is
    # one region; the Pallas path names its layout and core regions itself.
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), t - s)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def flash_attention(
    query,
    key,
    value,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    from ...base import global_state
    from ...ops import pallas
    from ...ops.pallas import flash_attention as pallas_fa

    scale = 1.0 / math.sqrt(unwrap(query).shape[-1])
    dkey = global_state.default_generator.split() if (dropout > 0.0 and training) else None

    if return_softmax:
        # The flash kernel never materializes the probability matrix — the
        # debug contract (reference flash_attention return_softmax=True)
        # is served by the XLA composition, which does.
        def fn(q, k, v):
            logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
            if causal:
                s, t = logits.shape[-2], logits.shape[-1]
                mask = jnp.tril(jnp.ones((s, t), bool), t - s)
                logits = jnp.where(mask, logits, -1e30)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)  # noqa: NM1101 — widening for softmax stability, cast back after
            p = probs
            if dropout > 0.0 and training and dkey is not None:
                keep = jax.random.bernoulli(dkey, 1.0 - dropout, p.shape)
                p = jnp.where(keep, p / (1.0 - dropout), 0.0)
            return jnp.einsum("bhst,bthd->bshd", p, v), probs

        out, probs = primitive("flash_attention_xla", fn, [query, key, value])
        return out, probs

    if pallas.enabled():
        drop_eff = dropout if training else 0.0
        seed = _seed_from_key(dkey) if drop_eff > 0.0 else None
        out = primitive(
            "flash_attention",
            lambda q, k, v: pallas_fa.flash_attention_value(
                q, k, v, causal=causal, scale=scale, dropout=drop_eff,
                seed=seed),
            [query, key, value],
        )
    else:
        out = primitive(
            "flash_attention_xla",
            lambda q, k, v: _xla_attention(
                q, k, v, causal=causal, scale=scale, dropout=dropout if training else 0.0, dropout_key=dkey
            ),
            [query, key, value],
        )
    return out, None


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, name=None
):
    """paddle.nn.functional.scaled_dot_product_attention parity
    (q/k/v: [B, S, H, D]; attn_mask broadcastable to [B, H, S, T])."""
    from ...base import global_state
    from ...ops import pallas
    from ...ops.pallas import flash_attention as pallas_fa

    scale = 1.0 / math.sqrt(unwrap(query).shape[-1])
    if attn_mask is None and pallas.enabled():
        drop_eff = dropout_p if training else 0.0
        seed = (_seed_from_key(global_state.default_generator.split())
                if drop_eff > 0.0 else None)
        return primitive(
            "sdpa_flash",
            lambda q, k, v: pallas_fa.flash_attention_value(
                q, k, v, causal=is_causal, scale=scale, dropout=drop_eff,
                seed=seed),
            [query, key, value],
        )
    dkey = global_state.default_generator.split() if (dropout_p > 0.0 and training) else None
    if attn_mask is not None:
        mask_v = unwrap(attn_mask)
        if mask_v.dtype == jnp.bool_:
            bias = jnp.where(mask_v, 0.0, -1e30)
        else:
            bias = mask_v

        return primitive(
            "sdpa_xla",
            lambda q, k, v, b: _xla_attention(
                q, k, v, causal=is_causal, scale=scale, bias=b,
                dropout=dropout_p if training else 0.0, dropout_key=dkey,
            ),
            [query, key, value, attn_mask if mask_v.dtype != jnp.bool_ else __wrap(bias)],
        )
    return primitive(
        "sdpa_xla",
        lambda q, k, v: _xla_attention(
            q, k, v, causal=is_causal, scale=scale, dropout=dropout_p if training else 0.0, dropout_key=dkey
        ),
        [query, key, value],
    )


def __wrap(arr):
    from ...core.tensor import Tensor

    return Tensor(arr)


class sdp_kernel:
    """Context manager selecting attention backends (compat shim)."""

    def __init__(self, enable_flash=True, enable_math=True, enable_mem_efficient=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
