"""Power retention, degree 2 (Buckman, Gelada et al., "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239), as a recurrence over a
state of constant size.

For one key/value head, with ``q`` already scaled by ``head_dim ** -0.5``::

    A[t, s] = (q[t] . k[s]) ** 2 * exp(c[t] - c[s])      s <= t,  c = cumsum(log g)
    y[t]    = sum_s A[t, s] v[s] / (sum_s A[t, s] + eps)

``phi`` is the symmetric second power, ``phi(a) . phi(b) == (a . b) ** 2``
exactly, so the same function is the recurrence ``S = g S + phi(k) v^T``,
``z = g z + phi(k)``, ``y = phi(q)^T S / (phi(q)^T z + eps)``. The state
of a head is ONE array ``[head_dim + 8, D]``: row ``r < head_dim`` is
``S[:, r]`` (the value's component ``r``), row ``head_dim`` is ``z`` (the
state of a value that is 1 everywhere), the other seven rows stay zero.
``D`` runs along the minor dimension, which a TPU holds in lanes of 128:
the update ``g state + vext (x) phi(k)`` and the read ``phi(q) . state``
are then one pass over whole tiles.

Two forms share ``phi`` and the layout: :func:`retention_chunk` (a stretch
of a sequence: the masked quadratic form inside it, the state in and out
across it) and :func:`retention_step` (one token a lane, the decode step).
:func:`power_retention` runs a whole sequence through chunks and is what
``models/brumby.py`` calls.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...base import regions
from ...core.dispatch import primitive

PHI_BLOCK = 8      # phi keeps whole PHI_BLOCK x PHI_BLOCK blocks of the outer product
STATE_PAD = 8      # rows under the values' in a head's state: z, then zeros
EPS = 1e-6

__all__ = ["phi", "phi_dim", "state_rows", "retention_chunk", "retention_step",
           "power_retention", "PHI_BLOCK", "STATE_PAD", "EPS"]


def phi_dim(head_dim: int) -> int:
    """Length of ``phi`` of a ``head_dim`` vector: one 8 x 8 block of the
    outer product for every pair of blocks ``I <= J``. 8704 for 128 (68 x
    128 lanes; the packed triangle would be 8256)."""
    nb = head_dim // PHI_BLOCK
    return nb * (nb + 1) // 2 * PHI_BLOCK * PHI_BLOCK


def state_rows(head_dim: int) -> int:
    return head_dim + STATE_PAD


def _pairs(head_dim: int):
    nb = head_dim // PHI_BLOCK
    left, right = np.triu_indices(nb)
    coef = np.where(left == right, 1.0, math.sqrt(2.0)).astype(np.float32)
    return left, right, coef


def phi(a):
    """``[..., d] -> [..., phi_dim(d)]`` with ``phi(a) . phi(b) == (a . b)
    ** 2``: the outer product ``a a^T`` cut into blocks, the blocks on and
    above the diagonal kept whole, those above it times sqrt 2 (each
    stands for its mirror image too). No term of the square is dropped."""
    d = a.shape[-1]
    if d % PHI_BLOCK:
        raise ValueError(f"phi: head_dim {d} is not a multiple of {PHI_BLOCK}")
    left, right, coef = _pairs(d)
    blocks = a.reshape(a.shape[:-1] + (d // PHI_BLOCK, PHI_BLOCK))
    outer = (jnp.take(blocks, left, axis=-2)[..., :, None]
             * jnp.take(blocks, right, axis=-2)[..., None, :]
             * coef[:, None, None].astype(a.dtype))
    return outer.reshape(a.shape[:-1] + (phi_dim(d),))


def _extended(v):
    """``[..., d] -> [..., d + 8]``: the value, a one (whose state is
    ``z``), seven zeros."""
    one = jnp.ones(v.shape[:-1] + (1,), v.dtype)
    pad = jnp.zeros(v.shape[:-1] + (STATE_PAD - 1,), v.dtype)
    return jnp.concatenate([v, one, pad], axis=-1)


def _chunk_head(q, k, v, log_g, state, valid):
    """One key/value head over one chunk. ``q`` ``[C, G, d]`` (the G query
    heads that read this state), ``k``/``v`` ``[C, d]``, ``log_g`` ``[C]``,
    ``state`` ``[d + 8, D]``, ``valid`` ``[C]`` (False past a ragged
    chunk's end: such a token weighs nothing and leaves the state as it
    was). All float32."""
    d = k.shape[-1]
    k = jnp.where(valid[:, None], k, 0.0)
    c = jnp.cumsum(jnp.where(valid, log_g, 0.0))
    scores = jnp.einsum("tgd,sd->gts", q, k) ** 2
    causal = jnp.tril(jnp.ones((k.shape[0],) * 2, bool))
    decay = jnp.exp(jnp.where(causal, c[:, None] - c[None, :], -jnp.inf))
    vext = _extended(v)
    total = jnp.einsum("gts,sr->tgr", scores * decay[None], vext)
    carried = jnp.einsum("tgD,rD->tgr", phi(q), state)
    total = total + carried * jnp.exp(c)[:, None, None]
    y = total[..., :d] / (total[..., d:d + 1] + EPS)
    left = jnp.exp(c[-1] - c)
    state = jnp.exp(c[-1]) * state + jnp.einsum(
        "sr,sD->rD", vext * left[:, None], phi(k))
    return y, state


def retention_chunk(q, k, v, log_g, state, valid=None):
    """One chunk of one sequence: ``q`` ``[C, Hq, d]``, ``k``/``v`` ``[C,
    Hkv, d]``, ``log_g`` ``[C, Hkv]``, ``state`` ``[Hkv, d + 8, D]``
    float32 -> (``y`` ``[C, Hq, d]`` float32, the state after the chunk's
    last valid token). Inside the chunk the masked quadratic form, across
    it the state. Key/value heads run one after another (``lax.map``), so
    the ``[C, G, D]`` of ``phi(q)`` exists for one head at a time."""
    C, Hq, d = q.shape
    Hkv = k.shape[1]
    if valid is None:
        valid = jnp.ones((C,), bool)
    with regions.region(regions.RETN_CHUNK):
        qg = (q.astype(jnp.float32) * d ** -0.5).reshape(C, Hkv, Hq // Hkv, d)
        heads = (qg.transpose(1, 0, 2, 3),
                 k.astype(jnp.float32).transpose(1, 0, 2),
                 v.astype(jnp.float32).transpose(1, 0, 2),
                 log_g.astype(jnp.float32).T, state)
        y, state = jax.lax.map(
            lambda a: _chunk_head(*a, valid), heads)
        return y.transpose(1, 0, 2, 3).reshape(C, Hq, d), state


def step_operands(q, k, v, log_g):
    """What one decode step brings to the state, all float32: the gate
    ``g`` ``[B, Hkv]``, the extended value ``[B, Hkv, d + 8]``, ``phi(k)``
    ``[B, Hkv, D]`` and ``phi(q)`` ``[B, Hkv, G, D]`` (``q`` scaled). The
    jnp step below and the Pallas kernel share them."""
    B, Hq, d = q.shape
    Hkv = k.shape[1]
    qg = (q.astype(jnp.float32) * d ** -0.5).reshape(B, Hkv, Hq // Hkv, d)
    return (jnp.exp(log_g.astype(jnp.float32)),
            _extended(v.astype(jnp.float32)),
            phi(k.astype(jnp.float32)), phi(qg))


def finish_step(total):
    """``[B, Hkv, G, d + 8]`` of ``phi(q) . state`` -> ``y`` ``[B, Hq,
    d]``: the values' rows over the row of ``z``."""
    B, Hkv, G, rows = total.shape
    d = rows - STATE_PAD
    y = total[..., :d] / (total[..., d:d + 1] + EPS)
    return y.reshape(B, Hkv * G, d)


def retention_step(q, k, v, log_g, state):
    """One token a lane: ``q`` ``[B, Hq, d]``, ``k``/``v`` ``[B, Hkv, d]``,
    ``log_g`` ``[B, Hkv]``, ``state`` ``[B, Hkv, d + 8, D]`` float32 ->
    (``y`` ``[B, Hq, d]`` float32, the new state). The read is a multiply
    and a sum, not a product of matrices: five rows against a state is the
    vector unit's work."""
    with regions.region(regions.RETN_STATE):
        g, vext, pk, pq = step_operands(q, k, v, log_g)
        state = (g[..., None, None] * state
                 + vext[..., :, None] * pk[..., None, :])
        total = (pq[:, :, :, None, :] * state[:, :, None]).sum(-1)
        return finish_step(total), state


def power_retention(q, k, v, log_g, chunk: int = 128):
    """A whole sequence from an empty state: ``q`` ``[B, T, Hq, d]``,
    ``k``/``v`` ``[B, T, Hkv, d]``, ``log_g`` ``[B, T, Hkv]`` -> ``[B, T,
    Hq, d]`` in ``q``'s dtype. ``T`` need not be a multiple of ``chunk``:
    the last chunk is padded and its padding masked."""
    from ...core.tensor import unwrap

    B, T, Hq, d = unwrap(q).shape
    Hkv = unwrap(k).shape[2]
    C = min(int(chunk), T)
    n = -(-T // C)

    def one(q, k, v, lg):
        pad = n * C - T

        def cut(a):
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            return a.reshape((n, C) + a.shape[1:])

        valid = (jnp.arange(n * C) < T).reshape(n, C)

        def body(state, xs):
            y, state = retention_chunk(*xs[:4], state, xs[4])
            return state, y

        state = jnp.zeros((Hkv, state_rows(d), phi_dim(d)), jnp.float32)
        _, y = jax.lax.scan(body, state, (cut(q), cut(k), cut(v), cut(lg), valid))
        return y.reshape(n * C, Hq, d)[:T]

    def fn(q, k, v, lg):
        return jax.vmap(one)(q, k, v, lg).astype(q.dtype)

    return primitive("power_retention", fn, [q, k, v, log_g])
