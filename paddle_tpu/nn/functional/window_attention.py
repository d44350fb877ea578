"""Grouped-query attention with a sliding window or without, in the forms
the stack of ``models/cohere2_moe.py`` needs: ``heads`` query heads on
``kv_heads`` K/V heads (query head ``h`` uses K/V head ``h // (heads //
kv_heads)``), key ``j`` visible to query ``i`` iff ``j <= i`` and, given a
``window``, ``i - j < window`` (the query's own key among the ``window``).

- :func:`attend_grouped`: one query a lane over a dense view of its rows,
  heads merged in the minor dimension as the page pool holds them. What the
  decode programs run off the TPU, and the oracle of the kernel
  ``ops/pallas/paged_attention.py:gqa_paged_attention``.
- :func:`start_blocks` / :func:`grouped_block` / :func:`finish_blocks`: a
  chunk of queries against one block of keys under a running softmax, one
  K/V head's scores at a time (a ``[chunk x group, block]`` float32 matrix
  is all that exists at once, and it goes to memory and back). What a
  prefill chunk runs off the TPU, the Layer path's core, and the oracle of
  the kernel ``ops/pallas/paged_attention.py:gqa_chunk_attention``, which
  is what a prefill chunk runs on a TPU.
- :func:`rope_interleaved`: GPT-J rotary, the pairs ``(x[2i], x[2i+1])``.
- :func:`windowed_attention`: the Layer path, whole sequences from 0.

Scores and softmax are float32; probabilities are cast to the values' dtype
before the weighted sum, as the paged kernels do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import primitive

NEG = -1e30

__all__ = ["rope_interleaved", "attend_grouped", "start_blocks",
           "grouped_block", "finish_blocks", "windowed_attention"]


def rope_interleaved(x, positions, theta: float):
    """``x`` ``[N, heads, d]`` at ``positions`` ``[N]`` -> float32, every
    pair ``(x[2i], x[2i+1])`` turned by ``positions x theta^(-2i/d)``. The
    partner of a column is its neighbour: two rolls along the lanes and a
    select, no strided slice."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[:, None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[:, None, :]
    xf = x.astype(jnp.float32)
    even = jnp.arange(d) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1))
    return xf * cos + partner * sin


def _seen(cols, positions, window):
    """``[..., S, T]``: query at ``positions[..., S]`` sees column ``cols[T]``."""
    seen = cols <= positions[..., None]
    if window is not None:
        seen &= positions[..., None] - cols < window
    return seen


def attend_grouped(q, keys, vals, positions, kv_heads: int, scale: float,
                   window=None):
    """``q`` ``[B, heads x d]``, ``keys``/``vals`` ``[B, T, kv_heads x d]``
    (column ``j`` is position ``j``), ``positions`` ``[B]`` -> ``[B, heads x
    d]`` in ``q``'s dtype."""
    B, T, KD = keys.shape
    d = KD // kv_heads
    qg = q.reshape(B, kv_heads, -1, d)
    logits = jnp.einsum("bgrd,btgd->bgrt", qg, keys.reshape(B, T, kv_heads, d),
                        preferred_element_type=jnp.float32) * scale
    seen = _seen(jnp.arange(T), positions, window)            # [B, T]
    logits = jnp.where(seen[:, None, None, :], logits, NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(vals.dtype)
    out = jnp.einsum("bgrt,btgd->bgrd", probs, vals.reshape(B, T, kv_heads, d))
    return out.reshape(B, -1).astype(q.dtype)


def start_blocks(S: int, kv_heads: int, group: int, d: int):
    return (jnp.full((kv_heads, S, group), NEG, jnp.float32),
            jnp.zeros((kv_heads, S, group), jnp.float32),
            jnp.zeros((kv_heads, S, group, d), jnp.float32))


def grouped_block(carry, q, k, v, seen, scale: float):
    """One block of ``T`` keys for ``S`` queries: ``q`` ``[G, S, group, d]``
    (K/V head major), ``k``/``v`` ``[G, T, d]``, ``seen`` ``[S, T]``;
    ``carry`` ``(m, l [G, S, group], acc [G, S, group, d])`` float32. The K/V
    heads go one after another (``lax.map``), so one head's scores exist at a
    time. A query that sees no key of the block leaves its carry as it was."""
    def head(a):
        qh, kh, vh, m, l, acc = a
        logits = jnp.einsum("srd,td->srt", qh, kh,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(seen[:, None, :], logits, NEG)
        m_new = jnp.maximum(m, logits.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen[:, None, :], jnp.exp(logits - m_new[..., None]), 0.0)
        return (m_new, alpha * l + p.sum(-1),
                alpha[..., None] * acc + jnp.einsum(
                    "srt,td->srd", p.astype(vh.dtype), vh,
                    preferred_element_type=jnp.float32))

    return jax.lax.map(head, (q, k, v) + tuple(carry))


def finish_blocks(carry, dtype):
    """``(m, l, acc)`` -> ``[S, heads x d]``."""
    _, l, acc = carry
    out = (acc / l[..., None]).transpose(1, 0, 2, 3)        # [S, G, group, d]
    return out.reshape(out.shape[0], -1).astype(dtype)


def windowed_attention(q, k, v, *, window=None, theta=None):
    """The Layer path: ``q`` ``[B, T, heads, d]``, ``k``/``v`` ``[B, T,
    kv_heads, d]`` from position 0, not yet rotated; ``theta`` given, q and k
    are rotated (interleaved pairs); ``window`` given, the mask has it ->
    ``[B, T, heads x d]``, through the autograd dispatcher."""
    def fn(q, k, v):
        T, G, d = k.shape[1:]
        pos = jnp.arange(T)
        seen = _seen(pos, pos, window)
        scale = d ** -0.5

        def one(q, k, v):
            if theta is not None:
                q = rope_interleaved(q, pos, theta).astype(v.dtype)
                k = rope_interleaved(k, pos, theta).astype(v.dtype)
            qg = q.reshape(T, G, -1, d).transpose(1, 0, 2, 3)
            carry = grouped_block(start_blocks(T, G, qg.shape[2], d), qg,
                                  k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                                  seen, scale)
            return finish_blocks(carry, v.dtype)

        return jax.vmap(one)(q, k, v)

    return primitive("windowed_attention", fn, [q, k, v])
