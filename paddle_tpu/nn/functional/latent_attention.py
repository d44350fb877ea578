"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434), in its
two forms, with YaRN rotary positions (Peng et al., arXiv:2309.00071).

A token's memory in a layer is ONE latent row ``[c_kv | k_rope]``
(``kv_lora_rank + qk_rope_head_dim`` numbers, 576 for 512 + 64) shared by
every head: head ``h``'s key is ``[c_kv W_k[h] | k_rope]`` and its value
``c_kv W_v[h]``, where ``W_k``/``W_v`` are the two halves of the source's
``kv_b_proj``, kept apart here (``[rank, heads x dim]`` each) so that neither
form slices a strided half out of the other's.

- **Expanded** (prefill, training, the model's forward): keys and values
  are formed from the latent rows and the attention is the usual one, with
  q/k 192 wide and v 128. :func:`expanded_block` is one block of keys under
  a running-maximum softmax, so a long context is a loop of it
  (``serving/decode.py``: a prefill chunk over the pages before its cursor);
  :func:`attend_expanded` is one block over a whole sequence.
- **Absorbed** (decode): ``W_k[h]`` moves onto the query (:func:`absorb_q`,
  ``q_lat[h] = W_k[h] q_nope[h]``, 512 wide), the scores are ``q_lat . c_kv
  + q_rope . k_rope``, the probabilities weigh the latent rows themselves
  and ``W_v[h]`` comes after (:func:`unabsorb`). No per-head key or value
  of the context is ever formed: the cache row is K and V at once.

The same function both ways; the absorbed form costs 2.8 times the
expanded one's operations per key where many queries share the keys (a
prefill chunk), and a sixteenth of its bytes where one query a lane reads
them (a decode step).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...base import regions
from ...core.dispatch import primitive

__all__ = ["yarn_inv_freq", "yarn_mscale", "softmax_scale", "rope",
           "start_blocks", "expanded_block", "finish_blocks", "attend_expanded", "absorb_q",
           "attend_absorbed", "unabsorb", "latent_attention", "NEG"]

NEG = -1e30


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict | None) -> np.ndarray:
    """The ``dim // 2`` rotary frequencies. Without ``scaling`` the plain
    ``theta ** (-2i / dim)``; with a YaRN ``rope_scaling`` block, each pair
    between the interpolated frequency (``/ factor``, the slow pairs) and
    the plain one (the fast pairs) along a linear ramp from ``low`` to
    ``high``, the pair indices at which ``beta_fast`` and ``beta_slow``
    turns fit into ``original_max_position_embeddings``."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return extra.astype(np.float32)
    factor, orig = scaling["factor"], scaling["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_of(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1.0 - ramp
    return (extra / factor * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(qk_head_dim: int, scaling: dict | None) -> float:
    """``qk_head_dim ** -0.5``, times the square of YaRN's ``mscale`` for
    ``mscale_all_dim`` where the config has one. (Cos and sin are multiplied
    by ``mscale(mscale) / mscale(mscale_all_dim)``, which is 1 where the two
    are equal, the only case built here.)"""
    scale = qk_head_dim ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        if scaling.get("mscale", 1) != scaling["mscale_all_dim"]:
            raise ValueError("rope_scaling: mscale != mscale_all_dim would "
                             "scale cos and sin; not built")
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def rope(x, positions, inv_freq):
    """Rotate-halves RoPE over the last dimension: ``x`` ``[N, ..., d]`` at
    ``positions`` ``[N]``, computed in float32, returned in ``x``'s dtype."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],))
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = xf * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)
    return out.astype(x.dtype)


# --------------------------------------------------------------- expanded
def expanded_block(carry, q, c_kv, k_rope, w_k, w_v, mask, scale):
    """One block of ``T`` keys for ``S`` queries of ``G`` heads, running
    softmax carried: ``q`` ``[S, G, dn + dr]`` (``[q_nope | q_rope]``, the
    rotary part rotated), ``c_kv`` ``[T, rank]``, ``k_rope`` ``[T, dr]``,
    ``w_k`` ``[rank, G, dn]``, ``w_v`` ``[rank, G, dv]``, ``mask`` ``[S, T]``
    (True where the query sees the key). ``carry`` is ``(m [G, S], l [G, S],
    acc [G, S, dv])`` in float32. The keys and values of the block are
    formed here, from the latent rows, for these heads only; a head's key is
    ``[c_kv W_k[h] | k_rope]`` WHOLE, one product with q over ``dn + dr``: a
    separate product of the rotary parts against a slice of the pool's rows
    had the TPU compiler re-lay the whole pool out (3.7 GB) for it."""
    m, l, acc = carry
    with regions.region(regions.ATTN_EXPAND):
        k_nope = jnp.einsum("tc,cgd->tgd", c_kv, w_k)
        v = jnp.einsum("tc,cgd->tgd", c_kv, w_v)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, None, :].astype(k_nope.dtype),
            k_nope.shape[:2] + k_rope.shape[-1:])], axis=-1)
    with regions.region(regions.ATTN_CORE):
        logits = jnp.einsum("sgd,tgd->gst", q, k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask[None], logits, NEG)
        m_new = jnp.maximum(m, logits.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l = alpha * l + p.sum(-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "gst,tgd->gsd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc


def start_blocks(S: int, G: int, dv: int):
    return (jnp.full((G, S), NEG, jnp.float32), jnp.zeros((G, S), jnp.float32),
            jnp.zeros((G, S, dv), jnp.float32))


def finish_blocks(carry, dtype):
    """``(m, l, acc)`` -> ``[S, G, dv]``."""
    _, l, acc = carry
    return (acc / l[..., None]).transpose(1, 0, 2).astype(dtype)


def attend_expanded(q_nope, q_rope, c_kv, k_rope, w_k, w_v, scale):
    """Causal attention of one whole sequence ``[T]`` in one block:
    ``q_nope`` ``[T, H, dn]``, ``q_rope`` ``[T, H, dr]`` (rotated),
    ``c_kv`` ``[T, rank]`` (normed), ``k_rope`` ``[T, dr]`` (rotated),
    ``w_k``/``w_v`` ``[rank, H x d]`` -> ``[T, H x dv]``."""
    T, H, dn = q_nope.shape
    rank = c_kv.shape[-1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    wk, wv = w_k.reshape(rank, H, dn), w_v.reshape(rank, H, -1)
    out = finish_blocks(expanded_block(
        start_blocks(T, H, wv.shape[-1]), jnp.concatenate([q_nope, q_rope], -1),
        c_kv, k_rope, wk, wv, causal, scale), q_nope.dtype)
    return out.reshape(T, -1)


# --------------------------------------------------------------- absorbed
def absorb_q(q_nope, w_k):
    """``q_nope`` ``[B, H, dn]``, ``w_k`` ``[rank, H x dn]`` -> ``q_lat``
    ``[B, H, rank]``: the key's up-projection moved onto the query."""
    B, H, dn = q_nope.shape
    return jnp.einsum("bhd,chd->bhc", q_nope, w_k.reshape(-1, H, dn))


def unabsorb(o_lat, w_v):
    """``o_lat`` ``[B, H, rank]`` -> ``[B, H x dv]``: the value's
    up-projection, after the probabilities."""
    B, H, rank = o_lat.shape
    return jnp.einsum("bhc,chd->bhd", o_lat,
                      w_v.reshape(rank, H, -1)).reshape(B, -1)


def attend_absorbed(q_lat, q_rope, rows, positions, rank, scale):
    """The absorbed attention over a dense view of each lane's latent rows:
    ``q_lat`` ``[B, H, rank]``, ``q_rope`` ``[B, H, dr]``, ``rows`` ``[B, T,
    W]`` with ``W >= rank + dr`` (columns ``[c_kv | k_rope | padding]``),
    ``positions`` ``[B]`` (the lane sees columns ``<= positions[b]``) ->
    ``o_lat`` ``[B, H, rank]``. The oracle of the paged kernel, and what
    runs off the TPU."""
    dr = q_rope.shape[-1]
    c_kv, k_rope = rows[..., :rank], rows[..., rank:rank + dr]
    logits = (jnp.einsum("bhc,btc->bht", q_lat, c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,btr->bht", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    col = jnp.arange(rows.shape[1])
    logits = jnp.where(col[None, None, :] <= positions[:, None, None], logits, NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(rows.dtype)
    return jnp.einsum("bht,btc->bhc", probs, c_kv)


# ------------------------------------------------------------ Tensor level
def latent_attention(q, c_kv, k_rope, w_k, w_v, *, nope: int, inv_freq, scale: float):
    """The Layer path (``models/axk1.py``): batched whole sequences from
    position 0. ``q`` ``[B, T, H, nope + dr]`` and ``k_rope`` ``[B, T, dr]``
    not yet rotated, ``c_kv`` ``[B, T, rank]`` normed -> ``[B, T, H x dv]``,
    through the autograd dispatcher."""
    def fn(q, c_kv, k_rope, w_k, w_v):
        pos = jnp.arange(q.shape[1])

        def one(q, c, kr):
            return attend_expanded(q[..., :nope], rope(q[..., nope:], pos, inv_freq),
                                   c, rope(kr, pos, inv_freq), w_k, w_v, scale)

        return jax.vmap(one)(q, c_kv, k_rope)

    return primitive("latent_attention", fn, [q, c_kv, k_rope, w_k, w_v])
