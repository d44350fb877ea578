"""A dropless sparse-expert layer that is told which experts it holds.

The router scores a token against EVERY routed expert of the model and
picks ``top_k`` of them (:func:`route`: sigmoid scores, group-limited
top-k, weights normalised over the chosen and scaled). This process holds
the experts ``[first, first + held)`` only: :func:`held_experts` computes
what those give, ``sum over chosen k whose expert is held of w_k E_k(x)``,
with the weights normalised over ALL the chosen, as expert parallelism
needs it before its exchange. With every expert held that is the whole
routed part. No token is dropped and there is no capacity: the
token-expert pairs are sorted by expert and go through one grouped product
a projection (``jax.lax.ragged_dot``; on a TPU XLA's own grouped-matmul
kernel, which visits the tiles that hold rows of a group and no others).

The pair count is static, ``tokens x top_k``: a token can pick ``top_k``
held experts, so no smaller bound holds. A pair whose expert is absent
sorts past the last held group and is never dropped from the array; the
sorted pairs are worked off in windows, as many as the live pairs fill, so
the absent pairs cost a sort and nothing else.

``E(x) = (silu(x W_g) * (x W_u)) W_d`` with ``[W_g | W_u]`` one matrix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...base import regions

__all__ = ["route", "held_experts", "swiglu"]


def swiglu(x, gate_up, down):
    """``(silu(x W_g) * (x W_u)) W_d`` with ``gate_up = [W_g | W_u]``."""
    gate, up = jnp.split(x @ gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ down


def _grouped(rows, weights, sizes):
    """``rows`` ``[M, k]`` by the group's ``weights[g]`` ``[k, n]``, rows
    sorted by group, ``sizes[g]`` of them each. The precision is stated: the
    package pins the default to "highest", which XLA's TPU kernel refuses
    for bfloat16 operands (whose products are exact in one pass anyway)."""
    return jax.lax.ragged_dot(rows, weights, sizes,
                              precision=jax.lax.Precision.DEFAULT)


def route(x, w_router, *, n_group: int, topk_group: int, top_k: int,
          scaling: float, norm_topk: bool = True, group_limited: bool = True):
    """``x`` ``[N, hidden]``, ``w_router`` ``[hidden, E]`` -> the chosen
    experts ``[N, top_k]`` int32 and their weights ``[N, top_k]`` float32.

    In float32 throughout: ``s = sigmoid(x W_r)``; the ``E`` experts lie in
    ``n_group`` groups of consecutive ones; a group's score is the sum of
    its two largest ``s``; the ``topk_group`` best groups stay and among
    their experts the ``top_k`` largest ``s`` are chosen; ``w = s / (sum of
    the chosen + 1e-20) x scaling``. No score-correction bias."""
    with regions.region(regions.MOE_ROUTE):
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                   w_router.astype(jnp.float32)))
        N, E = s.shape
        pick = s
        if group_limited and n_group > 1:
            grouped = s.reshape(N, n_group, E // n_group)
            score = jax.lax.top_k(grouped, 2)[0].sum(-1)          # [N, groups]
            kept = jax.lax.top_k(score, topk_group)[1]            # [N, topk_group]
            keep = (kept[:, :, None] == jnp.arange(n_group)[None, None, :]).any(1)
            # a sigmoid is positive: -1 lies below every score
            pick = jnp.where(keep[:, :, None], grouped, -1.0).reshape(N, E)
        _, idx = jax.lax.top_k(pick, top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scaling


def held_experts(x, idx, w, gate_up, down, *, first: int, held: int,
                 group_offset=0, window: int | None = None):
    """What the held experts give: ``x`` ``[N, hidden]``, ``idx``/``w``
    ``[N, top_k]`` from :func:`route` -> (``y`` ``[N, hidden]`` in ``x``'s
    dtype, ``counts`` ``[held]`` int32: the pairs each held expert
    computed).

    ``gate_up`` ``[G, hidden, 2 x width]`` and ``down`` ``[G, width,
    hidden]`` hold the experts ``first .. first + held - 1`` as their groups
    ``group_offset .. group_offset + held - 1``; ``G`` may be larger (a
    stack of layers viewed as one run of groups, ``group_offset`` traced:
    the other groups get no rows, and a grouped product passes them by, so
    a layer's experts are never copied out of the stack).

    The sorted pairs are worked off in windows of ``window`` rows (as many
    rows as there are tokens, unless given), as many windows as the LIVE
    pairs fill (a traced trip count): the temporaries are a window's,
    whatever ``tokens x top_k`` is, and the pairs of absent experts, which
    sort last, cost nothing. A share that holds an eighth of the experts or
    less fills one window with room to spare; one that holds them all
    fills ``top_k``. The window is also what the grouped product pads a
    group's rows to (XLA's TPU kernel takes row tiles of ``min(rows,
    512)``): with 64 tokens and 2.7 rows a group, a window of ``64 x 8``
    pairs spent six times the weights' read time multiplying padding. Each window gathers its
    tokens' rows, runs the two grouped products over the window's part of
    each group, weighs the rows and adds them to their tokens through a
    0/1 matrix (a product on the matrix unit, not a scatter)."""
    with regions.region(regions.MOE_EXPERTS):
        N, K = idx.shape
        M, G = N * K, gate_up.shape[0]
        W = min(M, int(window or max(N, 8)))
        local = idx - first
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(M)     # absent pairs last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, -M % W))
        counts = (key[:, None] == jnp.arange(held)[None, :]).sum(0).astype(jnp.int32)
        ends = jnp.cumsum(counts)
        starts, live = ends - counts, ends[-1]
        offset = (jnp.asarray(group_offset, jnp.int32),)
        flat_w = w.reshape(M)
        tokens = jnp.arange(N, dtype=jnp.int32)

        def one_window(i, y):
            lo = i * W
            pairs = jax.lax.dynamic_slice(order, (lo,), (W,))   # pair = token x K + k
            valid = lo + jnp.arange(W) < live
            sizes = jnp.clip(ends, lo, lo + W) - jnp.clip(starts, lo, lo + W)
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((G,), jnp.int32), sizes.astype(jnp.int32), offset)
            token = pairs // K
            rows = x[token]                                     # [W, hidden], by expert
            gate, up = jnp.split(_grouped(rows, gate_up, sizes), 2, axis=-1)
            out = _grouped(jax.nn.silu(gate) * up, down, sizes)
            # rows past the last live pair were multiplied by nothing:
            # whatever the product left there, they count as zero
            weight = jnp.where(valid, flat_w[pairs], 0.0)
            out = jnp.where(valid[:, None], out.astype(jnp.float32) * weight[:, None],
                            0.0).astype(x.dtype)
            mine = ((token[None, :] == tokens[:, None]) & valid[None, :]).astype(x.dtype)
            return y + jnp.dot(mine, out, preferred_element_type=jnp.float32)

        y = jax.lax.fori_loop(0, (live + W - 1) // W, one_window,
                              jnp.zeros((N, x.shape[-1]), jnp.float32))
        return y.astype(x.dtype), counts
