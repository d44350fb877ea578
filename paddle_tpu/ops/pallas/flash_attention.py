"""Flash attention Pallas TPU kernels, forward AND backward.

TPU-native replacement for the reference's FlashAttention-2 integration
(third_party/flashattn + paddle/phi/kernels/gpu/flash_attn_kernel.cu fwd,
flash_attn_grad_kernel.cu bwd): online-softmax tiled forward saving the
per-row logsumexp, and the standard two-pass recompute backward — a dq pass
(per q-block, streaming k-blocks) and a dk/dv pass (per k-block, streaming
q-blocks), each recomputing the probabilities from (q, k, lse) so attention
scores are never materialized at O(S²) in HBM.

Layout: the public entry points take the paddle-convention [batch, seq,
heads, head_dim] arrays; the kernels run on heads-major [batch, heads, seq,
head_dim] copies over a (batch, heads, row-blocks, col-blocks) grid. Mosaic
requires a block's last two dims to be multiples of (8, 128) or the array's
full extent, and a one-head block of a [B, S, H, D] array is (block, 1, D):
1 against H is refused for every H > 1. Heads-major makes the last two dims
(block, D), legal for any H; the custom VJP keeps the heads-major copies as
its residuals, so the transposes are paid once per direction. K/V (resp.
Q/dO) stream through block-sized VMEM tiles and accumulators live in VMEM
scratch across the sequential minormost grid dim. Dots take the operands'
own dtype (bf16 feeds the MXU directly) and accumulate in f32.

Row statistics: m and l are (block_q, 1) columns; lse and Δ travel as
[B, H, 1, S] rows, compact in HBM. The dk/dv pass works on transposed
(block_k, block_q) score tiles, where a row broadcasts for free; the dq pass
turns the two rows into columns once per tile.

Causal masking is bottom-right aligned like the XLA composition
(``jnp.tril(..., sk - sq)``): query i sees keys up to i + sk - sq.

Dropout runs INSIDE the kernel: the keep mask is a counter-based hash of
(seed, batch, head, q-block, k-block, row, col) with a traced int32 seed
(scalar prefetch), so the dq/dkv recompute passes replay the exact forward
mask — the in-kernel analog of the framework's fold-per-tick RNG idiom.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from ...base import regions

NEG_INF = -1e30
_LANES = 128

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _dot(a, b, dims):
    """MXU dot with an f32 accumulator. The package pins jax's default matmul
    precision to "highest" for f32 parity; Mosaic refuses that on bf16
    operands ("Bad lhs type"), where one bf16 pass loses nothing anyway."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _block(extent: int, target: int) -> int:
    """Largest multiple of 128 that divides ``extent`` and is at most
    ``target``; the whole extent when there is none. Both are legal block
    dims under Mosaic's (8, 128)-or-full rule, on the sublane axis of a
    q/k/v tile and on the lane axis of an lse row alike."""
    for b in range(min(target, extent) // _LANES * _LANES, 0, -_LANES):
        if extent % b == 0:
            return b
    return extent


def _row_to_col(row):
    """(1, n) lane-major row -> (n, 1) column, through an aligned 2-D
    transpose (the relayout Mosaic has a native op for)."""
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, n)))[:, :1]


def _col_to_row(col):
    """(n, 1) column -> (1, n) lane-major row."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[:1, :]


def _positions(iq, ik, block_q, block_k, offset, q_axis):
    """Global (query, key) positions of one score tile; ``q_axis`` is the
    tile axis the query index runs along (0: [bq, bk], 1: [bk, bq]). The
    query position carries the bottom-right causal offset sk - sq."""
    shape = (block_q, block_k) if q_axis == 0 else (block_k, block_q)
    q_pos = offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, q_axis)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - q_axis)
    return q_pos, k_pos


def _tile_live(iq, ik, block_q, block_k, offset):
    """False for a tile strictly above the causal diagonal: it contributes
    nothing, so its compute is skipped (its DMA still runs)."""
    return ik * block_k <= offset + iq * block_q + block_q - 1


def _dropout_mask(seed_ref, ids, shape, dropout, q_axis):
    """Per-element keep mask from a counter-based hash of
    (seed, b, h, iq, ik, row, col) — pure 32-bit integer vector ops
    (murmur3 finalizer), so it lowers identically under Mosaic and
    interpret mode and replays bit-exactly in the dq/dkv recompute passes,
    whichever way the tile is oriented."""
    ib, ih, iq, ik = ids
    key = seed_ref[0].astype(jnp.uint32)
    for part, mult in ((ib, 0x9E3779B9), (ih, 0x85EBCA6B),
                       (iq, 0xC2B2AE35), (ik, 0x27D4EB2F)):
        key = (key ^ (part.astype(jnp.uint32) * jnp.uint32(mult))) * jnp.uint32(0x01000193)
    r = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis).astype(jnp.uint32)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis).astype(jnp.uint32)
    x = (r * jnp.uint32(0x9E3779B9)) ^ (c * jnp.uint32(0x85EBCA6B)) ^ key
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(int((1.0 - dropout) * 0xFFFFFFFF))
    return x <= thresh


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, dropout,
                block_q, block_k, nk, offset):
    from jax.experimental import pallas as pl

    ib, ih, iq, ik = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                      pl.program_id(3))

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = _dot(q, k, _NT) * scale
        if causal:
            q_pos, k_pos = _positions(iq, ik, block_q, block_k, offset, 0)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            # a row with no visible key yet has m == NEG_INF, and
            # exp(NEG_INF - NEG_INF) = 1 would poison l
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        if dropout > 0.0:
            keep = _dropout_mask(seed_ref, (ib, ih, iq, ik), p.shape, dropout, 0)
            p_av = jnp.where(keep, p / (1.0 - dropout), 0.0)
        else:
            p_av = p
        alpha = jnp.exp(m_prev - m_new)
        # l tracks the UNdropped row sum (softmax normalizer)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + _dot(p_av.astype(v.dtype), v, _NN)

    if causal:
        pl.when(_tile_live(iq, ik, block_q, block_k, offset))(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _col_to_row(m_scr[...] + jnp.log(l))


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, scale, causal, dropout, block_q,
                   block_k, nk, offset):
    """dQ pass: q-block fixed per (iq), k-blocks stream on the minormost
    grid dim. dS = P ∘ (dO·Vᵀ − Δ); dQ = scale · dS·K with P recomputed
    from (q, k, lse)."""
    from jax.experimental import pallas as pl

    ib, ih, iq, ik = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                      pl.program_id(3))

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = _row_to_col(lse_ref[0, 0])
        delta = _row_to_col(delta_ref[0, 0])
        s = _dot(q, k, _NT) * scale
        p = jnp.exp(s - lse)
        if causal:
            q_pos, k_pos = _positions(iq, ik, block_q, block_k, offset, 0)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = _dot(do, v, _NT)
        if dropout > 0.0:
            keep = _dropout_mask(seed_ref, (ib, ih, iq, ik), p.shape, dropout, 0)
            dp = jnp.where(keep, dp / (1.0 - dropout), 0.0)
        ds = p * (dp - delta)
        dq_scr[...] += _dot(ds.astype(k.dtype), k, _NN)

    if causal:
        pl.when(_tile_live(iq, ik, block_q, block_k, offset))(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _emit():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, dropout,
                    block_q, block_k, nq, offset):
    """dK/dV pass: k-block fixed per (ik), q-blocks stream on the minormost
    grid dim. Works on TRANSPOSED [block_k, block_q] tiles (Sᵀ = K·Qᵀ), so
    the lse/Δ rows broadcast as they are and every dot is a plain A·B or
    A·Bᵀ: dV = (P∘keep)ᵀ·dO; dK = scale · dSᵀ·Q."""
    from jax.experimental import pallas as pl

    ib, ih, ik, iq = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                      pl.program_id(3))

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        st = _dot(k, q, _NT) * scale
        pt = jnp.exp(st - lse)
        if causal:
            q_pos, k_pos = _positions(iq, ik, block_q, block_k, offset, 1)
            pt = jnp.where(q_pos >= k_pos, pt, 0.0)
        if dropout > 0.0:
            keep = _dropout_mask(seed_ref, (ib, ih, iq, ik), pt.shape, dropout, 1)
            pt_av = jnp.where(keep, pt / (1.0 - dropout), 0.0)
        else:
            pt_av = pt
        dv_scr[...] += _dot(pt_av.astype(do.dtype), do, _NN)
        dpt = _dot(v, do, _NT)
        if dropout > 0.0:
            dpt = jnp.where(keep, dpt / (1.0 - dropout), 0.0)
        dst = pt * (dpt - delta)
        dk_scr[...] += _dot(dst.astype(q.dtype), q, _NN)

    if causal:
        pl.when(_tile_live(iq, ik, block_q, block_k, offset))(_body)
    else:
        _body()

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _grid_spec(num_prefetch, grid, in_specs, out_specs, scratch):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch)


def _block_spec(shape, index, q_minor):
    """BlockSpec whose ``index`` is written for (ib, ih, iq, ik). The dk/dv
    grid streams q-blocks minormost and passes (ib, ih, ik, iq); a scalar
    prefetch ref, when there is one, trails and is ignored."""
    from jax.experimental import pallas as pl

    if q_minor:
        return pl.BlockSpec(
            shape, lambda ib, ih, ik, iq, *_: index(ib, ih, iq, ik))
    return pl.BlockSpec(shape, lambda ib, ih, iq, ik, *_: index(ib, ih, iq, ik))


def _qk_specs(block_q, block_k, d, q_minor=False):
    """(q-like, k-like, lse-row) block specs over heads-major operands."""
    return (
        _block_spec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0), q_minor),
        _block_spec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0), q_minor),
        _block_spec((1, 1, 1, block_q), lambda ib, ih, iq, ik: (ib, ih, 0, iq), q_minor),
    )


def _hm(*xs):
    """[B, S, H, D] <-> heads-major [B, H, S, D], a region of their own:
    the transposes into and out of the kernels."""
    with regions.region(regions.ATTN_LAYOUT):
        out = tuple(jnp.swapaxes(x, 1, 2) for x in xs)
    return out if len(out) > 1 else out[0]


# the kernel calls themselves (and the backward's row-sum)
_core = functools.partial(regions.region, regions.ATTN_CORE)


_STATIC = ("causal", "scale", "dropout", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_fwd_hm(q, k, v, seed, causal, scale, dropout=0.0, block_q=256,
                  block_k=512, interpret=False):
    """Heads-major forward: q [B, H, Sq, D], k/v [B, H, Sk, D] ->
    (out [B, H, Sq, D], lse [B, H, 1, Sq])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _block(sq, block_q), _block(sk, block_k)
    nq, nk = sq // block_q, sk // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, dropout=dropout,
        block_q=block_q, block_k=block_k, nk=nk, offset=sk - sq)
    qspec, kspec, rowspec = _qk_specs(block_q, block_k, d)
    return pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            1, (b, h, nq, nk),
            [qspec, kspec, kspec],
            [qspec, rowspec],
            [
                pltpu.VMEM((block_q, 1), jnp.float32),   # m
                pltpu.VMEM((block_q, 1), jnp.float32),   # l
                pltpu.VMEM((block_q, d), jnp.float32),   # acc
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        interpret=interpret,
        name=regions.FLASH_FWD,
    )(seed, q, k, v)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_bwd_hm(q, k, v, o, lse, do, seed, causal, scale, dropout=0.0,
                  block_q=256, block_k=512, interpret=False):
    """Heads-major two-pass recompute backward (reference capability:
    paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu). Δ = rowsum(dO ∘ O) is
    a cheap XLA reduction; the O(S²) recompute stays in VMEM tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _block(sq, block_q), _block(sk, block_k)
    nq, nk = sq // block_q, sk // block_k
    # delta in the same [b, h, 1, sq] row layout as lse
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)[:, :, None, :]
    static = dict(scale=scale, causal=causal, dropout=dropout,
                  block_q=block_q, block_k=block_k, offset=sk - sq)

    qspec, kspec, rowspec = _qk_specs(block_q, block_k, d)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **static),
        grid_spec=_grid_spec(
            1, (b, h, nq, nk),
            [qspec, kspec, kspec, qspec, rowspec, rowspec],
            qspec,
            [pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interpret,
        name=regions.FLASH_BWD_DQ,
    )(seed, q, k, v, do, lse, delta)

    qspec2, kspec2, rowspec2 = _qk_specs(block_q, block_k, d, q_minor=True)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, **static),
        grid_spec=_grid_spec(
            1, (b, h, nk, nq),
            [qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
            [kspec2, kspec2],
            [pltpu.VMEM((block_k, d), jnp.float32),
             pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        interpret=interpret,
        name=regions.FLASH_BWD_DKV,
    )(seed, q, k, v, do, lse, delta)
    return dq, dk, dv


def _flash_fwd(q, k, v, seed, causal, scale, dropout=0.0, block_q=256,
               block_k=512, interpret=False):
    """[B, S, H, D] forward -> (out [B, Sq, H, D], lse [B, H, 1, Sq])."""
    with _core():
        out, lse = _flash_fwd_hm(*_hm(q, k, v), seed, causal, scale,
                                 dropout, block_q, block_k, interpret)
    return _hm(out), lse


def _flash_bwd(q, k, v, o, lse, do, seed, causal, scale, dropout=0.0,
               block_q=256, block_k=512, interpret=False):
    """[B, S, H, D] backward -> (dq, dk, dv) in the same layout."""
    qt, kt, vt, ot, dot = _hm(q, k, v, o, do)
    with _core():
        grads = _flash_bwd_hm(qt, kt, vt, ot, lse, dot, seed, causal, scale,
                              dropout, block_q, block_k, interpret)
    return _hm(*grads)


def _xla_reference(q, k, v, causal, scale):
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), t - s)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _zero_seed():
    """The seed of a call without dropout: a host constant, so that a call
    made under a trace leaves no tracer behind."""
    return np.zeros((1,), np.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 7))
def _fa_value(q, k, v, causal, scale, dropout, seed, interpret):
    return _flash_fwd(q, k, v, seed, causal, scale, dropout,
                      interpret=interpret)[0]


def _fa_fwd(q, k, v, causal, scale, dropout, seed, interpret):
    qt, kt, vt = _hm(q, k, v)
    with _core():
        ot, lse = _flash_fwd_hm(qt, kt, vt, seed, causal, scale, dropout,
                                interpret=interpret)
    return _hm(ot), (qt, kt, vt, ot, lse, seed)


def _fa_bwd(causal, scale, dropout, interpret, res, g):
    qt, kt, vt, ot, lse, seed = res
    gt = _hm(g)
    with _core():
        dq, dk, dv = _flash_bwd_hm(qt, kt, vt, ot, lse, gt, seed, causal,
                                   scale, dropout, interpret=interpret)
    dseed = np.zeros((1,), jax.dtypes.float0)
    return (*_hm(dq, dk, dv), dseed)


_fa_value.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_value(q, k, v, causal=False, scale=1.0, dropout=0.0,
                          seed=None, interpret=False):
    """Fused attention with optional in-kernel dropout. ``seed``: (1,) int32
    array (traced OK); required when dropout > 0 (defaults to a fixed zero
    seed, which only makes sense for dropout == 0)."""
    seed = seed if seed is not None else _zero_seed()
    return _fa_value(q, k, v, causal, scale, dropout, seed, interpret)


def flash_attention_interpret_test(q, k, v, causal, dropout=0.0, seed=None):
    """Test hook: run the pallas kernel in interpret mode on CPU."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    seed = seed if seed is not None else _zero_seed()
    return _flash_fwd(q, k, v, seed, causal, scale, dropout,
                      interpret=True)[0]


def flash_attention_grad_interpret_test(q, k, v, do, causal, dropout=0.0,
                                        seed=None):
    """Test hook: full fwd+bwd through the Pallas kernels in interpret mode,
    for parity checks against the XLA composition's VJP."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    seed = seed if seed is not None else _zero_seed()
    out, lse = _flash_fwd(q, k, v, seed, causal, scale, dropout,
                          interpret=True)
    return out, _flash_bwd(q, k, v, out, lse, do, seed, causal, scale,
                           dropout, interpret=True)
