"""The decode step of power retention against the state lane pool, as one
Pallas kernel: each touched lane's state is read once, updated, written
back to the same place, and contracted with ``phi(q)`` on the way.

    state[l, slot[b], j] = g[b, j] * state[l, slot[b], j] + vext[b, j] (x) phi(k)[b, j]
    total[b, j, h]       = phi(q)[b, j, h] . state[l, slot[b], j]        (over D)

The pool ``[layers, lanes + 1, kv_heads, rows, D]`` is aliased input to
output, and a lane's blocks are found through the *prefetched* slot ids
and layer index (the block index maps read them), so nothing is gathered
into a batch and nothing scattered back: the step's bytes are the touched
states', once in and once out. Blocks no grid step names keep what they
held. Padded batch lanes name the pad lane (the pool's last), which takes
their writes.

A state tile is ``[rows, tile]`` with ``D`` along the lanes. The update
needs ``phi(k)`` along lanes (a row, broadcast down the sublanes) and
``vext`` along sublanes (passed already spread over one vreg's lanes,
``[rows, 128]``); the read multiplies by a row of ``phi(q)`` and adds up
lane tiles, leaving ``[rows, 128]`` partial sums per query head whose last
reduction (over 128 lanes) is XLA's. Five query rows against a state is
the vector unit's work: the matrix unit would load every state tile as
weights to push five rows through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...base import regions

LANES = 128
SUBLANES = 8


def _tile(D: int) -> int:
    """The widest tile of ``D`` that is whole vregs and at most 2304 lanes
    (a ``[136, 2304]`` float32 block is 1.25 MB; in and out, double
    buffered, 5 MB of VMEM): 2176 for 8704. All of ``D`` where it is no
    multiple of 128 (interpret mode at test sizes)."""
    if D % LANES:
        return D
    best = LANES
    for n in range(1, D // LANES + 1):
        if (D // LANES) % n == 0 and n * LANES <= 2304:
            best = n * LANES
    return best


def _kernel(slots_ref, layer_ref, st_ref, g_ref, v_ref, pk_ref, pq_ref,
            out_ref, acc_ref, *, rows, chunks, width, heads):
    del slots_ref, layer_ref   # read by the index maps

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...]                                  # [1, width]

    def row_group(r, carry):
        at = pl.ds(pl.multiple_of(r * SUBLANES, SUBLANES), SUBLANES)
        v = v_ref[at, :]                            # [8, width]
        acc = [jnp.zeros((SUBLANES, width), jnp.float32)] * heads
        for c in range(chunks):
            lanes = slice(c * width, (c + 1) * width)
            st = st_ref[at, lanes] * g + v * pk_ref[:, lanes]
            out_ref[at, lanes] = st
            acc = [a + st * pq_ref[h:h + 1, lanes] for h, a in enumerate(acc)]
        for h in range(heads):
            acc_ref[h, at, :] += acc[h]
        return carry

    lax.fori_loop(0, rows // SUBLANES, row_group, 0)


def retention_step(state, layer, slot_ids, g, vext, pk, pq, *,
                   interpret=False):
    """``state`` ``[L, N, Hkv, rows, D]`` float32 (updated in place where
    the caller donates it), ``layer`` a scalar, ``slot_ids`` ``[B]``, and
    ``power_retention.step_operands``' four: ``g`` ``[B, Hkv]``, ``vext``
    ``[B, Hkv, rows]``, ``pk`` ``[B, Hkv, D]``, ``pq`` ``[B, Hkv, G, D]``.
    Returns the pool and ``total`` ``[B, Hkv, G, rows]``."""
    _, _, Hkv, rows, D = state.shape
    B, G = slot_ids.shape[0], pq.shape[2]
    tile = _tile(D)
    width = LANES if tile % LANES == 0 else tile
    f32 = jnp.float32

    def lane(b, j, t, slots, layer):
        return (layer[0], slots[b], j, 0, t)

    def per_head(*tail):
        return lambda b, j, t, slots, layer: (b, j) + tail

    state_spec = pl.BlockSpec((None, None, None, rows, tile), lane)
    new, acc = pl.pallas_call(
        functools.partial(_kernel, rows=rows, chunks=tile // width,
                          width=width, heads=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, D // tile),
            in_specs=[
                state_spec,
                pl.BlockSpec((None, None, 1, width), per_head(0, 0)),
                pl.BlockSpec((None, None, rows, width), per_head(0, 0)),
                pl.BlockSpec((None, None, 1, tile),
                             lambda b, j, t, slots, layer: (b, j, 0, t)),
                pl.BlockSpec((None, None, G, tile),
                             lambda b, j, t, slots, layer: (b, j, 0, t)),
            ],
            out_specs=[
                state_spec,
                pl.BlockSpec((None, None, G, rows, width), per_head(0, 0, 0)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((B, Hkv, G, rows, width), f32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=regions.RETN_STEP,
    )(slot_ids.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      state,
      jnp.broadcast_to(g.astype(f32)[..., None, None], (B, Hkv, 1, width)),
      jnp.broadcast_to(vext.astype(f32)[..., None], (B, Hkv, rows, width)),
      pk.astype(f32)[:, :, None, :], pq.astype(f32))
    return new, acc.sum(-1)
