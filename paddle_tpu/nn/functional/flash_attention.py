"""Flash-attention API family (reference:
python/paddle/nn/functional/flash_attention.py — flash_attention :195,
flash_attn_qkvpacked, flash_attn_unpadded :695, flashmask_attention :1098).

The dense fused path runs the Pallas TPU kernel
(paddle_tpu/ops/pallas/flash_attention.py); the variants here reshape /
mask / unpad around it. Flashmask's column-sparse mask semantics
(LTS/UTE start-end rows) follow the reference's startend_row_indices
contract.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.dispatch import primitive
from ...core.tensor import Tensor, unwrap
from .attention import _xla_attention, flash_attention, scaled_dot_product_attention  # noqa: F401


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False, return_softmax=False,
                         fixed_seed_offset=None, rng_name="", training=True,
                         name=None):
    """Packed (B, S, 3, H, D) QKV flash attention (reference:
    flash_attn_qkvpacked)."""
    v = unwrap(qkv)
    q, k, vv = (Tensor(v[:, :, 0]), Tensor(v[:, :, 1]), Tensor(v[:, :, 2]))
    if not qkv.stop_gradient:
        # re-slice through the autograd tape so grads flow back into the pack
        from ...ops.manipulation import getitem

        q = getitem(qkv, (slice(None), slice(None), 0))
        k = getitem(qkv, (slice(None), slice(None), 1))
        vv = getitem(qkv, (slice(None), slice(None), 2))
    return flash_attention(q, k, vv, dropout=dropout, causal=causal,
                           return_softmax=return_softmax, training=training)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen attention over packed (total_tokens, H, D) tensors with
    cumulative sequence offsets (reference: flash_attn_unpadded). On TPU the
    ragged batch is computed as one dense masked attention per sequence via
    a segment-id mask — static shapes, MXU-friendly."""
    sc = scale if scale is not None else 1.0 / math.sqrt(unwrap(query).shape[-1])
    cq = jnp.asarray(unwrap(cu_seqlens_q))
    ck = jnp.asarray(unwrap(cu_seqlens_k))

    def fn(q, k, v):
        tq, H, D = q.shape
        tk = k.shape[0]
        seg_q = jnp.cumsum(jnp.zeros(tq, jnp.int32).at[cq[1:-1]].add(1))
        seg_k = jnp.cumsum(jnp.zeros(tk, jnp.int32).at[ck[1:-1]].add(1))
        same = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(tq) - cq[seg_q]
            pos_k = jnp.arange(tk) - ck[seg_k]
            same = same & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.einsum("qhd,khd->hqk", q, k) * sc
        logits = jnp.where(same[None], logits, -1e30)
        probs = jax.nn.softmax(logits, -1)
        out = jnp.einsum("hqk,khd->qhd", probs, v)
        if return_softmax:
            return out, probs
        return out

    if return_softmax:
        out, probs = primitive("flash_attn_unpadded", fn, [query, key, value])
        return out, probs
    out = primitive("flash_attn_unpadded", fn, [query, key, value])
    return out, None


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                                max_seqlen_k, scale=None, dropout=0.0,
                                causal=False, return_softmax=False,
                                fixed_seed_offset=None, rng_name="",
                                training=True, varlen_padded=True, name=None):
    """(reference: flash_attn_varlen_qkvpacked)."""
    v = unwrap(qkv)
    q, k, vv = Tensor(v[:, 0]), Tensor(v[:, 1]), Tensor(v[:, 2])
    return flash_attn_unpadded(q, k, vv, cu_seqlens_q, cu_seqlens_k,
                               max_seqlen_q, max_seqlen_k, scale=scale,
                               dropout=dropout, causal=causal,
                               return_softmax=return_softmax, training=training)


def flashmask_attention(query, key, value, startend_row_indices=None,
                        dropout=0.0, causal=True, window_size=None, name=None):
    """Column-sparse masked attention (reference: flashmask_attention :1098).

    startend_row_indices (B, H|1, S_k, 1|2|4) gives, per key column, the query
    rows where masking starts/ends — the compressed representation of
    causal-document / sliding-window / shared-prefix masks. The fused TPU
    path is the Pallas flashmask kernel (ops/pallas/flashmask.py); fallback
    composes the dense mask in XLA.
    """
    from ...ops import pallas

    scale = 1.0 / math.sqrt(unwrap(query).shape[-1])
    if startend_row_indices is None:
        return flash_attention(query, key, value, dropout=dropout,
                               causal=causal)[0]

    if window_size is not None:
        raise NotImplementedError("window_size with startend_row_indices")

    idx = jnp.asarray(unwrap(startend_row_indices))

    if pallas.enabled() and dropout == 0.0:
        from ...ops.pallas.flashmask import flashmask_value

        return primitive(
            "flashmask_attention",
            lambda q, k, v: flashmask_value(q, k, v, idx, causal=causal,
                                            scale=scale),
            [query, key, value],
        )

    from ...base import global_state

    dkey = global_state.default_generator.split() if dropout > 0.0 else None

    def fn(q, k, v):
        B, S, H, D = q.shape
        Sk = k.shape[1]
        rows = jnp.arange(S)[:, None]  # query row index
        # expand the compressed columns to a dense (B, Hm, S, Sk) bool mask
        if causal:
            if idx.shape[-1] == 1:
                start = idx[..., 0]  # (B, Hm, Sk): mask rows >= start
                masked = rows[None, None] >= start[:, :, None, :]
            else:
                start = idx[..., 0]
                end = idx[..., 1]
                masked = ((rows[None, None] >= start[:, :, None, :])
                          & (rows[None, None] < end[:, :, None, :]))
            base = rows < jnp.arange(Sk)[None, :]  # causal upper triangle
            disallowed = masked | base[None, None]
        else:
            lts, lte = idx[..., 0], idx[..., 1]
            uts, ute = idx[..., 2], idx[..., 3]
            lower = ((rows[None, None] >= lts[:, :, None, :])
                     & (rows[None, None] < lte[:, :, None, :]))
            upper = ((rows[None, None] >= uts[:, :, None, :])
                     & (rows[None, None] < ute[:, :, None, :]))
            disallowed = lower | upper
        bias = jnp.where(disallowed, -1e30, 0.0)
        return _xla_attention(q, k, v, causal=False, scale=scale, bias=bias,
                              dropout=dropout, dropout_key=dkey)

    return primitive("flashmask_attention_xla", fn, [query, key, value])


def calc_reduced_attn_scores(query, key, softmax_lse=None, name=None):
    """Mean-over-queries attention scores per key (reference op:
    calc_reduced_attn_scores — used by sparse-attention score pruning)."""

    def fn(q, k):
        scale = 1.0 / math.sqrt(q.shape[-1])
        logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
        probs = jax.nn.softmax(logits, -1)
        return probs.mean(axis=2)  # (B, H, S_k)

    return primitive("calc_reduced_attn_scores", fn, [query, key])


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """Block-sparse attention with CSR connectivity (reference op:
    sparse_attention). TPU path: densify the per-row allowed set into a mask
    (XLA) — the CSR pattern is static so the mask folds at compile time."""
    off = jnp.asarray(unwrap(sparse_csr_offset))
    cols = jnp.asarray(unwrap(sparse_csr_columns))

    def fn(q, k, v):
        B, H, S, D = q.shape  # reference uses (B, H, S, D) here
        counts = off[..., 1:] - off[..., :-1]
        # dense mask from CSR: row r attends to cols[off[r]:off[r+1]]
        row_of_entry = jnp.repeat(jnp.arange(S), counts.reshape(-1)[:S], total_repeat_length=cols.shape[-1]) \
            if cols.ndim == 1 else None
        if cols.ndim == 1:
            mask = jnp.zeros((S, S), bool).at[row_of_entry, cols].set(True)
            mask = mask[None, None]
        else:
            flat_cols = cols.reshape(B, H, -1)
            mask = jnp.zeros((B, H, S, S), bool)
            rows = jnp.repeat(jnp.arange(S)[None, None, :], B, 0)
            # per (b, h): scatter
            def scatter_bh(m, c, o):
                r = jnp.searchsorted(o, jnp.arange(c.shape[0]), side="right") - 1
                return m.at[r, c].set(True)
            mask = jax.vmap(jax.vmap(scatter_bh))(mask, flat_cols, off[..., :-1])
        scale = 1.0 / math.sqrt(D)
        logits = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, -1)
        return jnp.einsum("bhst,bhtd->bhsd", probs, v)

    return primitive("sparse_attention", fn, [query, key, value])


def fused_softmax_mask(x, mask, name=None):
    """softmax(x + mask) fused (reference fused op: fused_softmax_mask)."""
    return primitive("fused_softmax_mask",
                     lambda v, m: jax.nn.softmax(v + m, -1), [x, mask])


def fused_softmax_mask_upper_triangle(x, name=None):
    """Causal-masked softmax (reference fused op:
    fused_softmax_mask_upper_triangle)."""

    def fn(v):
        S, T = v.shape[-2], v.shape[-1]
        mask = jnp.tril(jnp.ones((S, T), bool))
        return jax.nn.softmax(jnp.where(mask, v, -1e30), -1)

    return primitive("fused_softmax_mask_upper_triangle", fn, [x])
