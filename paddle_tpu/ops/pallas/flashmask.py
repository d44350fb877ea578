"""FlashMask: column-sparse-mask flash attention, Pallas TPU kernels.

Rebuild of the reference's flashmask_attention
(python/paddle/nn/functional/flash_attention.py:1098 + its CUDA kernels):
the attention mask is represented column-compressed — for every key column
j, `startend_row_indices` gives the query-row interval(s) that are masked.
This covers causal-document masks, sliding windows, shared prefixes and
arbitrary block layouts at O(S) mask storage instead of O(S²).

Kernels mirror ops/pallas/flash_attention.py and share its helpers:
heads-major [B, H, S, D] operands (the block layout Mosaic accepts for any
H), streamed K/V blocks over a (batch, heads, row-blocks, col-blocks) grid,
VMEM scratch accumulators, online-softmax forward saving lse as a
[B, H, 1, S] row, and a two-pass recompute backward whose dk/dv pass works
on transposed tiles. The interval bounds reach each pass in the
orientation its tiles need: the forward and dq passes read
[B, Hm, ncol, Sk] (one lane-major row of bounds per column kind), the
dk/dv pass reads [B, Hm, Sk, ncol] (one column per kind). The mask is an
elementwise compare per tile — no O(S²) mask tensor ever exists in HBM,
and K/V never load whole-sequence.

Index layouts (matching the reference contract):
- causal, last dim 1: [LTS]            — rows >= LTS[j] masked (plus causal)
- causal, last dim 2: [LTS, LTE]       — rows in [LTS, LTE) masked
- full,   last dim 4: [LTS, LTE, UTS, UTE]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import (
    _NN,
    _NT,
    NEG_INF,
    _block,
    _block_spec,
    _col_to_row,
    _dot,
    _hm,
    _positions,
    _qk_specs,
    _row_to_col,
    _tile_live,
)


def _disallowed(bounds, q_pos, k_pos, causal):
    """Disallowed-mask for one score tile. ``bounds`` holds the tile's
    interval bounds, one array per index column, each broadcastable against
    the tile along the key axis."""
    if causal:
        masked = q_pos >= bounds[0]
        if len(bounds) > 1:
            masked = masked & (q_pos < bounds[1])
        return masked | (q_pos < k_pos)
    lts, lte, uts, ute = bounds
    return ((q_pos >= lts) & (q_pos < lte)) | ((q_pos >= uts) & (q_pos < ute))


def _fm_fwd_kernel(q_ref, k_ref, v_ref, idx_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, scale, causal, ncol, block_q,
                   block_k, nk):
    from jax.experimental import pallas as pl

    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        idx = idx_ref[0, 0]
        q_pos, k_pos = _positions(iq, ik, block_q, block_k, 0, 0)
        disallowed = _disallowed([idx[j:j + 1, :] for j in range(ncol)],
                                 q_pos, k_pos, causal)
        s = jnp.where(disallowed, NEG_INF, _dot(q, k, _NT) * scale)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # fully-masked rows: m stays NEG_INF, exp(NEG_INF - NEG_INF)=1 would
        # poison l; zero those columns explicitly
        p = jnp.where(disallowed, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + _dot(p.astype(v.dtype), v, _NN)

    if causal:
        pl.when(_tile_live(iq, ik, block_q, block_k, 0))(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _col_to_row(m_scr[...] + jnp.log(l))


def _fm_bwd_dq_kernel(q_ref, k_ref, v_ref, idx_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, scale, causal, ncol, block_q,
                      block_k, nk):
    from jax.experimental import pallas as pl

    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        idx = idx_ref[0, 0]
        lse = _row_to_col(lse_ref[0, 0])
        delta = _row_to_col(delta_ref[0, 0])
        q_pos, k_pos = _positions(iq, ik, block_q, block_k, 0, 0)
        disallowed = _disallowed([idx[j:j + 1, :] for j in range(ncol)],
                                 q_pos, k_pos, causal)
        s = _dot(q, k, _NT) * scale
        p = jnp.where(disallowed, 0.0, jnp.exp(s - lse))
        ds = p * (_dot(do, v, _NT) - delta)
        dq_scr[...] += _dot(ds.astype(k.dtype), k, _NN)

    if causal:
        pl.when(_tile_live(iq, ik, block_q, block_k, 0))(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _emit():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _fm_bwd_dkv_kernel(q_ref, k_ref, v_ref, idx_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                       causal, ncol, block_q, block_k, nq):
    """Transposed [block_k, block_q] tiles, as in flash_attention's dk/dv
    pass: lse/Δ rows and the per-key bound columns broadcast as they are."""
    from jax.experimental import pallas as pl

    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        idx = idx_ref[0, 0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        q_pos, k_pos = _positions(iq, ik, block_q, block_k, 0, 1)
        disallowed = _disallowed([idx[:, j:j + 1] for j in range(ncol)],
                                 q_pos, k_pos, causal)
        st = _dot(k, q, _NT) * scale
        pt = jnp.where(disallowed, 0.0, jnp.exp(st - lse))
        dv_scr[...] += _dot(pt.astype(do.dtype), do, _NN)
        dst = pt * (_dot(v, do, _NT) - delta)
        dk_scr[...] += _dot(dst.astype(q.dtype), q, _NN)

    if causal:
        pl.when(_tile_live(iq, ik, block_q, block_k, 0))(_body)
    else:
        _body()

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _fm_specs(idx, block_q, block_k, d, q_minor):
    """flash_attention's q/k/row specs plus the bounds spec: a
    (ncol, block_k) slab of [B, Hm, ncol, Sk] for the forward/dq grid, a
    (block_k, ncol) slab of [B, Hm, Sk, ncol] for the dk/dv grid
    (``q_minor``). Hm == 1 broadcasts one index set across heads."""
    hm, ncol = idx.shape[1], idx.shape[3]

    def head(ih):
        return ih if hm > 1 else 0

    if q_minor:
        ispec = _block_spec((1, 1, block_k, ncol),
                            lambda ib, ih, iq, ik: (ib, head(ih), ik, 0), True)
    else:
        ispec = _block_spec((1, 1, ncol, block_k),
                            lambda ib, ih, iq, ik: (ib, head(ih), 0, ik), False)
    return (*_qk_specs(block_q, block_k, d, q_minor), ispec)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret"))
def _fm_fwd_hm(q, k, v, idx, causal, scale, interpret=False):
    """Heads-major forward: q [B, H, Sq, D], k/v [B, H, Sk, D], idx
    [B, Hm, Sk, ncol] -> (out [B, H, Sq, D], lse [B, H, 1, Sq])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    idx = idx.astype(jnp.int32)
    block_q, block_k = _block(sq, 256), _block(sk, 512)
    nq, nk = sq // block_q, sk // block_k
    qspec, kspec, rowspec, ispec = _fm_specs(idx, block_q, block_k, d, False)
    return pl.pallas_call(
        functools.partial(_fm_fwd_kernel, scale=scale, causal=causal,
                          ncol=idx.shape[3], block_q=block_q, block_k=block_k,
                          nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[qspec, kspec, kspec, ispec],
        out_specs=[qspec, rowspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, jnp.swapaxes(idx, 2, 3))


@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret"))
def _fm_bwd_hm(q, k, v, idx, o, lse, do, causal, scale, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    idx = idx.astype(jnp.int32)
    block_q, block_k = _block(sq, 256), _block(sk, 512)
    nq, nk = sq // block_q, sk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)[:, :, None, :]
    static = dict(scale=scale, causal=causal, ncol=idx.shape[3],
                  block_q=block_q, block_k=block_k)

    qspec, kspec, rowspec, ispec = _fm_specs(idx, block_q, block_k, d, False)
    dq = pl.pallas_call(
        functools.partial(_fm_bwd_dq_kernel, nk=nk, **static),
        grid=(b, h, nq, nk),
        in_specs=[qspec, kspec, kspec, ispec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, jnp.swapaxes(idx, 2, 3), do, lse, delta)

    qspec, kspec, rowspec, ispec = _fm_specs(idx, block_q, block_k, d, True)
    dk, dv = pl.pallas_call(
        functools.partial(_fm_bwd_dkv_kernel, nq=nq, **static),
        grid=(b, h, nk, nq),
        in_specs=[qspec, kspec, kspec, ispec, qspec, rowspec, rowspec],
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, idx, do, lse, delta)
    return dq, dk, dv


def _fm_fwd(q, k, v, idx, causal, scale, interpret=False):
    """[B, S, H, D] forward -> (out [B, Sq, H, D], lse [B, H, 1, Sq])."""
    out, lse = _fm_fwd_hm(_hm(q), _hm(k), _hm(v), idx, causal, scale,
                          interpret=interpret)
    return _hm(out), lse


def _fm_bwd(q, k, v, idx, o, lse, do, causal, scale, interpret=False):
    """[B, S, H, D] backward -> (dq, dk, dv) in the same layout."""
    grads = _fm_bwd_hm(_hm(q), _hm(k), _hm(v), idx, _hm(o), lse, _hm(do),
                       causal, scale, interpret=interpret)
    return tuple(_hm(g) for g in grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flashmask_value(q, k, v, startend_row_indices, causal=True, scale=1.0,
                    interpret=False):
    return _fm_fwd(q, k, v, startend_row_indices, causal, scale,
                   interpret=interpret)[0]


def _fm_vjp_fwd(q, k, v, idx, causal, scale, interpret):
    qt, kt, vt = _hm(q), _hm(k), _hm(v)
    ot, lse = _fm_fwd_hm(qt, kt, vt, idx, causal, scale, interpret=interpret)
    return _hm(ot), (qt, kt, vt, idx, ot, lse)


def _fm_vjp_bwd(causal, scale, interpret, res, g):
    qt, kt, vt, idx, ot, lse = res
    dq, dk, dv = _fm_bwd_hm(qt, kt, vt, idx, ot, lse, _hm(g), causal, scale,
                            interpret=interpret)
    return _hm(dq), _hm(dk), _hm(dv), None


flashmask_value.defvjp(_fm_vjp_fwd, _fm_vjp_bwd)
