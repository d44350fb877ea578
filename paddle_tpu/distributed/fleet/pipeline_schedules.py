"""Pipeline-parallel schedules: SPMD rotation (1F1B) + interleaved VPP.

Reference: fleet/meta_parallel/pipeline_parallel.py — 1F1B
`forward_backward_pipeline` (:575), interleaved virtual-pipeline variant
(:1174), FthenB (:2256) — multi-process schedules exchanging activations
over P2pHelper batched isend/irecv (pp_utils/p2p_communication.py:651).

TPU-native design — one compiled program, not N processes:

The decoder stack's weights live stacked along a leading layer dim that is
sharded over the `pp` mesh axis, so stage s's chunk of layers physically
resides on stage s's devices. Inside a `shard_map` over `pp`, a tick loop
(`lax.scan`) runs the classic rotation schedule: at tick t every stage
applies its chunk to the activation it received last tick, then `ppermute`s
the result one hop around the pp ring while stage 0 injects microbatch
t and the last stage emits finished microbatches. All p stages compute
simultaneously on different microbatches — real stage parallelism with the
canonical bubble fraction (p-1)/(m·v + p - 1):

- `num_chunks=1` — each device owns one contiguous chunk; the tick loop is
  the 1F1B/FthenB pipeline (they differ only in memory policy here, which
  `remat` controls: backward recomputes each chunk from its saved input
  instead of storing per-layer activations — 1F1B's O(in-flight) activation
  recipe).
- `num_chunks=v>1` — Megatron interleaved VPP: device d owns chunks
  {d, d+p, …, d+(v-1)p}; microbatches rotate around the ring v times,
  entering in groups of p, which cuts the bubble from (p-1)/(m+p-1) to
  (p-1)/(m·v+p-1).

Backward is jax AD through the scan+ppermute: the cotangent pipeline runs
the same rotation in reverse (ppermute transposes to the inverted ring),
so the backward pass is stage-parallel too.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from ...core.dispatch import primitive
from ...core.tensor import Tensor
from ...nn.layer.layers import Layer
from .. import env as env_mod


def _dp_grad_sync(grads, batch_axis: str, mesh):
    """dp gradient sync for the pipelined schedules' accumulated weight
    grads: each leaf rides the blockwise-int8 qpsum tier when the
    quantized-comm policy engages (FLAGS_comm_quantize_dp_grads /
    amp comm_dtype, size+dtype gates in collective_opt), plain psum
    otherwise."""
    from ..collective_opt import maybe_qpsum

    n = int(dict(mesh.shape).get(batch_axis, 1))
    return [maybe_qpsum(g, batch_axis, n) for g in grads]


def chunk_permutation(num_layers: int, num_stages: int, num_chunks: int) -> List[int]:
    """Layer order for stacking so a contiguous `pp` shard of the leading dim
    holds device d's chunks {d, d+p, …, d+(v-1)p} in local slot order.

    Returns perm with perm[new_position] = original_layer_index.
    """
    p, v = num_stages, num_chunks
    k = num_layers // (p * v)
    order = []
    for d in range(p):
        for j in range(v):
            c = j * p + d
            order.extend(range(c * k, (c + 1) * k))
    return order



def _chunk_run(apply_layer, chunk_leaves, xc, key):
    """Apply one chunk's layers (lax.scan over the leading layer dim) with
    ``key`` installed as the framework RNG stream — the single RNG-cell-swap
    protocol shared by every schedule's forward/recompute path."""
    def one(xin, layer_leaves):
        return apply_layer(layer_leaves, xin), None

    def run(cl, xx):
        return jax.lax.scan(one, xx, cl)[0]

    if key is None:
        return run(chunk_leaves, xc)
    from ...base import global_state

    cell = Tensor(key, name="pp_tick_rng", stop_gradient=True)
    prev = global_state.swap_rng_cell(cell)
    try:
        return run(chunk_leaves, xc)
    finally:
        global_state.swap_rng_cell(prev)


def _solve_tick(t, d, *, p: int, v: int, m: int):
    """Which (local chunk slot j, microbatch i) is active on device d at tick
    t. Microbatch i enters chunk 0 at tick inj_i = (i//p)·v·p + i%p and moves
    one chunk per tick; chunk c lives on device c % p. At most one (j, i) is
    active per device per tick (groups of p microbatches are spaced v·p ticks
    = exactly one group's worth of per-device work)."""
    L = v * p
    cs = d + p * jnp.arange(v)  # global chunk ids of my local slots
    inj = t - cs  # required injection tick per slot
    r = inj % L
    q = inj // L
    i_cand = q * p + r
    valid = (inj >= 0) & (r < p) & (i_cand < m)
    j = jnp.argmax(valid)  # the (at most one) active slot
    c = cs[j]
    i = jnp.clip(i_cand[j], 0, m - 1)
    return j, c, i, jnp.any(valid)


def pipeline_spmd(
    apply_layer: Callable,
    stacked_leaves: Sequence,
    x,
    *,
    num_stages: int,
    num_microbatches: int,
    num_chunks: int = 1,
    mesh=None,
    axis: str = "pp",
    batch_axis: Optional[str] = None,
    remat: bool = True,
    rng_key=None,
    schedule: str = "rotation",
):
    """Run x [B, ...] through the pipelined layer stack; returns [B, ...].

    apply_layer(leaves, x_local) -> y_local applies ONE layer given its
    parameter leaves; stacked_leaves are arrays with leading dim num_layers
    in `chunk_permutation` order, sharded over `axis`.

    rng_key: optional PRNG key. When given, every (stage, tick) folds a
    distinct subkey and installs it as the framework RNG stream while the
    chunk applies — dropout inside pipelined layers draws an independent
    mask per (stage, microbatch, chunk), the SPMD analog of the reference's
    per-stage RNG state tracker (fleet/meta_parallel/mpu/random.py:34).
    Folding is deterministic, so jax.checkpoint recompute replays the exact
    masks in backward.
    """
    mesh = mesh or env_mod.get_mesh()
    p, v, m = num_stages, num_chunks, num_microbatches

    def with_tick_rng(fn, key, xc, chunk):
        """Run fn(chunk, xc) with the folded key installed as the global RNG
        stream (object-level cell swap; trace-safe per swap_rng_cell)."""
        if key is None:
            return fn(chunk, xc)
        from ...base import global_state

        cell = Tensor(key, name="pp_tick_rng", stop_gradient=True)
        prev = global_state.swap_rng_cell(cell)
        try:
            return fn(chunk, xc)
        finally:
            global_state.swap_rng_cell(prev)

    if p <= 1:
        def body(xc, scanned):
            t, leaves = scanned
            key = (jax.random.fold_in(rng_key, t) if rng_key is not None else None)
            out = with_tick_rng(apply_layer, key, xc, leaves) if key is not None \
                else apply_layer(leaves, xc)
            return out, None

        idx = jnp.arange(stacked_leaves[0].shape[0])
        return jax.lax.scan(body, x, (idx, stacked_leaves))[0]
    if m % p != 0:
        raise ValueError(f"num_microbatches {m} must divide by pp degree {p}")
    b = x.shape[0]
    if b % m != 0:
        raise ValueError(f"batch {b} must divide into {m} microbatches")

    has_rng = rng_key is not None

    if schedule in ("1f1b", "eager_1f1b", "zb", "zbh1"):
        if v != 1:
            if schedule in ("zb", "zbh1"):
                raise ValueError(
                    "ZB-H1 covers num_chunks == 1; interleaved stacks use "
                    "schedule='1f1b' (tick-interleaved VPP) or 'rotation'")
            return _pipeline_vpp_1f1b(
                apply_layer, stacked_leaves, x, p=p, v=v, m=m, mesh=mesh,
                axis=axis, batch_axis=batch_axis, rng_key=rng_key)
        return _pipeline_1f1b(
            apply_layer, stacked_leaves, x, p=p, m=m, mesh=mesh, axis=axis,
            batch_axis=batch_axis, rng_key=rng_key,
            variant="zb" if schedule in ("zb", "zbh1") else "combined")
    if schedule != "rotation":
        raise ValueError(f"unknown pipeline schedule {schedule!r}")

    def shard_body(x_mb, *args):
        if has_rng:
            rng, *leaves = args
        else:
            rng, leaves = None, list(args)
        d = jax.lax.axis_index(axis)
        n_local = leaves[0].shape[0]  # v·k layers on this device
        k = n_local // v
        local = [a.reshape((v, k) + a.shape[1:]) for a in leaves]

        def apply_chunk(chunk_leaves, xc, key):
            def one(xin, layer_leaves):
                return apply_layer(layer_leaves, xin), None

            def run(cl, xx):
                return jax.lax.scan(one, xx, cl)[0]

            return with_tick_rng(run, key, xc, chunk_leaves)

        def apply_chunk_entry(chunk_leaves, xc, key):
            return apply_chunk(chunk_leaves, xc, key)

        if remat:
            apply_chunk_entry = jax.checkpoint(
                apply_chunk_entry, policy=jax.checkpoint_policies.nothing_saveable)

        T = m * v + p - 1
        out0 = jnp.zeros(x_mb.shape, x_mb.dtype)
        cur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
        stage_rng = (jax.random.fold_in(rng, d) if has_rng else None)

        def tick(carry, t):
            cur, out = carry
            j, c, i, active = _solve_tick(t, d, p=p, v=v, m=m)
            chunk = [jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False)
                     for a in local]
            x_in = jnp.where(
                c == 0, jax.lax.dynamic_index_in_dim(x_mb, i, 0, keepdims=False), cur)
            key = (jax.random.fold_in(stage_rng, t) if has_rng else None)
            y = apply_chunk_entry(chunk, x_in, key)
            # emit finished microbatch (only ever true on the last stage)
            done = active & (c == v * p - 1)
            slot = jax.lax.dynamic_index_in_dim(out, i, 0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(done, y, slot), i, 0)
            # one hop around the ring; receivers only read slots their
            # schedule marks active, so inactive ticks carry harmless zeros
            nxt = jax.lax.ppermute(
                y, axis, [(s, (s + 1) % p) for s in range(p)])
            return (nxt, out), None

        (_, out), _ = jax.lax.scan(tick, (cur0, out0), jnp.arange(T))
        # outputs were written on the last stage only; psum replicates them
        # across the ring (the reference's "send outputs downstream" step)
        return jax.lax.psum(out, axis)

    mb_shape = (m, b // m) + tuple(x.shape[1:])
    x_mb = x.reshape(mb_shape)
    x_spec = P(None, batch_axis, *([None] * (len(mb_shape) - 2)))
    leaf_specs = tuple(P(axis, *([None] * (a.ndim - 1))) for a in stacked_leaves)
    rng_specs = (P(),) if has_rng else ()

    # Compiled-callable cache: eager calls reuse one jitted shard_map per
    # (apply_layer, degrees, shapes, dtypes) key instead of rebuilding (and
    # recompiling) per call. Under an outer trace the jit inlines as before.
    cache_key = (
        apply_layer, p, v, m, axis, batch_axis, remat, mesh, has_rng,
        tuple(mb_shape), str(x_mb.dtype),
        tuple((tuple(a.shape), str(a.dtype)) for a in stacked_leaves),
    )
    jitted = _COMPILED.get(cache_key)
    if jitted is not None:
        _COMPILED.move_to_end(cache_key)  # LRU touch
    if jitted is None:
        # manual only over the pp ring (+ the batch axis when microbatches
        # ride dp); other mesh axes (mp/sep) stay GSPMD-auto, so tensor-
        # parallel layers inside the pipelined template keep their sharding
        # semantics — pp×mp composes in one program
        manual = {axis} | ({batch_axis} if batch_axis else set())
        shmap = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(x_spec,) + rng_specs + leaf_specs,
            out_specs=x_spec,
            axis_names=frozenset(manual),
            check_vma=False,
        )
        # the remat'd scan inside shard_map requires a jit scope (harmless
        # when we are already under an outer trace — it inlines)
        jitted = jax.jit(shmap)
        _COMPILED[cache_key] = jitted
        while len(_COMPILED) > _COMPILED_MAX:
            # bounded LRU: old entries pin stacked params + executables of
            # discarded stacks; evict oldest
            _COMPILED.popitem(last=False)
    if not isinstance(x_mb, jax.core.Tracer):
        x_mb = jax.device_put(x_mb, NamedSharding(mesh, x_spec))
    rng_args = (rng_key,) if has_rng else ()
    out = jitted(x_mb, *rng_args, *stacked_leaves)
    return out.reshape(x.shape)


def _pipeline_1f1b(apply_layer, stacked_leaves, x, *, p, m, mesh, axis,
                   batch_axis, rng_key, variant="combined"):
    """True tick-interleaved 1F1B (reference:
    fleet/meta_parallel/pipeline_parallel.py:575 — in-flight microbatches
    capped per stage, unlike the rotation schedule's O(m) scan residuals).

    custom_vjp around the whole pipeline call:

    - fwd: the rotation forward scan with NO AD — nothing is stacked across
      ticks; residuals are just (x_mb, rng, leaves).
    - bwd: ONE combined scan where step u does one forward unit AND one
      backward unit per stage: F(s, i) at u = i + s, B(s, i) at
      u = i + 2(p-1) - s (the last stage turns a microbatch around in the
      same step, consuming the output cotangent g[i] directly). Forward
      chunk inputs park in a 2p-slot ring buffer until their backward tick
      recomputes the chunk under jax.vjp (same folded RNG key → identical
      dropout masks) and accumulates parameter grads in-place.

    Per-device live activation state: ≤ 2(p-1-s) saved microbatch inputs on
    stage s (≤ 2p buffer slots), independent of m — vs the rotation
    schedule's m + p - 1 stacked residuals. Cost: one extra forward stream
    inside bwd (the recompute rotation saved by storing), ≈ +25% step FLOPs
    at m ≫ p; every step does real F and B work, so SPMD predication wastes
    nothing in steady state.
    """
    b = x.shape[0]
    mb_shape = (m, b // m) + tuple(x.shape[1:])
    x_mb = x.reshape(mb_shape)
    x_spec = P(None, batch_axis, *([None] * (len(mb_shape) - 2)))
    leaf_specs = tuple(P(axis, *([None] * (a.ndim - 1))) for a in stacked_leaves)
    has_rng = rng_key is not None
    rng = rng_key if has_rng else jax.random.PRNGKey(0)

    cache_key = (
        "1f1b", variant, apply_layer, p, m, axis, batch_axis, mesh, has_rng,
        tuple(mb_shape), str(x_mb.dtype),
        tuple((tuple(a.shape), str(a.dtype)) for a in stacked_leaves),
    )
    jitted = _COMPILED.get(cache_key)
    if jitted is not None:
        _COMPILED.move_to_end(cache_key)
    if jitted is None:
        ring_fwd = [(s, (s + 1) % p) for s in range(p)]
        ring_bwd = [(s, (s - 1) % p) for s in range(p)]

        def chunk_run(leaves_chunk, xc, key):
            return _chunk_run(apply_layer, leaves_chunk, xc, key)

        def fwd_body(x_mb, rng, *leaves):
            d = jax.lax.axis_index(axis)
            leaves = list(leaves)
            stage_rng = jax.random.fold_in(rng, d) if has_rng else None
            T = m + p - 1
            out0 = jnp.zeros(x_mb.shape, x_mb.dtype)
            cur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)

            def tick(carry, t):
                cur, out = carry
                i = t - d
                active = (i >= 0) & (i < m)
                ic = jnp.clip(i, 0, m - 1)
                x_in = jnp.where(
                    d == 0,
                    jax.lax.dynamic_index_in_dim(x_mb, ic, 0, keepdims=False),
                    cur)
                key = (jax.random.fold_in(stage_rng, ic) if has_rng else None)
                y = chunk_run(leaves, x_in, key)
                done = active & (d == p - 1)
                slot = jax.lax.dynamic_index_in_dim(out, ic, 0, keepdims=False)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, jnp.where(done, y, slot), ic, 0)
                nxt = jax.lax.ppermute(y, axis, ring_fwd)
                return (nxt, out), None

            (_, out), _ = jax.lax.scan(tick, (cur0, out0), jnp.arange(T))
            return jax.lax.psum(out, axis)

        def bwd_body(g, x_mb, rng, *leaves):
            d = jax.lax.axis_index(axis)
            leaves = list(leaves)
            stage_rng = jax.random.fold_in(rng, d) if has_rng else None
            # last active tick: B(0, m-1) at u = m-1 + 2(p-1)
            T2 = m + 2 * (p - 1)
            nbuf = 2 * p
            fbuf0 = jnp.zeros((nbuf,) + x_mb.shape[1:], x_mb.dtype)
            fcur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
            bcur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
            gacc0 = [jnp.zeros_like(a) for a in leaves]
            dx0 = jnp.zeros(x_mb.shape, x_mb.dtype)

            def tick(carry, u):
                fbuf, fcur, bcur, gacc, dxout = carry
                # forward sub-tick: F(d, i_f) scheduled at u = i_f + d
                i_f = u - d
                act_f = (i_f >= 0) & (i_f < m)
                icf = jnp.clip(i_f, 0, m - 1)
                x_in = jnp.where(
                    d == 0,
                    jax.lax.dynamic_index_in_dim(x_mb, icf, 0, keepdims=False),
                    fcur)
                slot_f = jnp.mod(icf, nbuf)
                old = jax.lax.dynamic_index_in_dim(fbuf, slot_f, 0, keepdims=False)
                fbuf = jax.lax.dynamic_update_index_in_dim(
                    fbuf, jnp.where(act_f, x_in, old), slot_f, 0)
                key_f = (jax.random.fold_in(stage_rng, icf) if has_rng else None)
                y = chunk_run(leaves, x_in, key_f)
                # backward sub-tick: B(d, i_b) scheduled at u = i_b + 2(p-1) - d
                i_b = u - 2 * (p - 1) + d
                act_b = (i_b >= 0) & (i_b < m)
                icb = jnp.clip(i_b, 0, m - 1)
                ct = jnp.where(
                    d == p - 1,
                    jax.lax.dynamic_index_in_dim(g, icb, 0, keepdims=False),
                    bcur).astype(x_mb.dtype)
                x_b = jax.lax.dynamic_index_in_dim(
                    fbuf, jnp.mod(icb, nbuf), 0, keepdims=False)
                key_b = (jax.random.fold_in(stage_rng, icb) if has_rng else None)
                _, vjp_fn = jax.vjp(
                    lambda cl, xx: chunk_run(cl, xx, key_b), leaves, x_b)
                dleaves, dx = vjp_fn(ct)
                gacc = [ga + jnp.where(act_b, dl, jnp.zeros_like(dl))
                        for ga, dl in zip(gacc, dleaves)]
                cur_slot = jax.lax.dynamic_index_in_dim(dxout, icb, 0, keepdims=False)
                dxout = jax.lax.dynamic_update_index_in_dim(
                    dxout, jnp.where(act_b & (d == 0), dx, cur_slot), icb, 0)
                fcur = jax.lax.ppermute(y, axis, ring_fwd)
                bcur = jax.lax.ppermute(dx, axis, ring_bwd)
                return (fbuf, fcur, bcur, gacc, dxout), None

            (_, _, _, gacc, dxout), _ = jax.lax.scan(
                tick, (fbuf0, fcur0, bcur0, gacc0, dx0), jnp.arange(T2))
            dxout = jax.lax.psum(dxout, axis)  # only stage 0 wrote real rows
            if batch_axis:
                gacc = _dp_grad_sync(gacc, batch_axis, mesh)
            return (dxout, *gacc)

        def bwd_body_zb(g, x_mb, rng, *leaves):
            """ZB-H1 backward (reference pipeline_zero_bubble.py:66 —
            BACKWARD split into _b (input-grad, critical path) and _w
            (weight-grad, bubble filler)), re-designed for the lockstep SPMD
            tick loop. Here every traced tick costs its full body whether a
            stage is active or not, so "filling the bubble" means *shrinking
            the traced body of bubble ticks*, not reordering async jobs:

            - warmup scan (p-1 ticks): forward units only — no stage has a
              backward yet, so no vjp is traced at all (the combined 1f1b
              body pays a full predicated vjp here);
            - steady scan (m ticks): F + combined vjp, as 1f1b — dx and dW
              share one chunk recompute, which a dB/dW split would double;
            - drain scan (p-1 ticks): dx-only vjp keeps the inter-stage
              cotangent ring (the critical path) moving; the cotangents are
              parked (the chunk inputs are still in the forward ring buffer);
            - dW epilogue scan (p-1 ticks): the parked (input, cotangent)
              pairs' weight-grads — the reference's deferred _w jobs — run
              as one contiguous MXU-friendly block.

            Per-stage activation memory stays O(p) (the 2p-slot forward ring
            plus a (p-1)-slot cotangent park). Traced-unit accounting vs the
            combined schedule: schedule_cost_report()."""
            d = jax.lax.axis_index(axis)
            leaves = list(leaves)
            stage_rng = jax.random.fold_in(rng, d) if has_rng else None
            nbuf = 2 * p
            fbuf0 = jnp.zeros((nbuf,) + x_mb.shape[1:], x_mb.dtype)
            fcur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
            bcur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
            gacc0 = [jnp.zeros_like(a) for a in leaves]
            dx0 = jnp.zeros(x_mb.shape, x_mb.dtype)

            def f_subtick(fbuf, fcur, u):
                """F(d, i_f) at u = i_f + d; parks the chunk input."""
                i_f = u - d
                act_f = (i_f >= 0) & (i_f < m)
                icf = jnp.clip(i_f, 0, m - 1)
                x_in = jnp.where(
                    d == 0,
                    jax.lax.dynamic_index_in_dim(x_mb, icf, 0, keepdims=False),
                    fcur)
                slot_f = jnp.mod(icf, nbuf)
                old = jax.lax.dynamic_index_in_dim(fbuf, slot_f, 0, keepdims=False)
                fbuf = jax.lax.dynamic_update_index_in_dim(
                    fbuf, jnp.where(act_f, x_in, old), slot_f, 0)
                key_f = (jax.random.fold_in(stage_rng, icf) if has_rng else None)
                y = chunk_run(leaves, x_in, key_f)
                return fbuf, jax.lax.ppermute(y, axis, ring_fwd)

            def b_inputs(fbuf, bcur, u):
                """Cotangent + parked input for B(d, i_b) at
                u = i_b + 2(p-1) - d."""
                i_b = u - 2 * (p - 1) + d
                act_b = (i_b >= 0) & (i_b < m)
                icb = jnp.clip(i_b, 0, m - 1)
                ct = jnp.where(
                    d == p - 1,
                    jax.lax.dynamic_index_in_dim(g, icb, 0, keepdims=False),
                    bcur).astype(x_mb.dtype)
                x_b = jax.lax.dynamic_index_in_dim(
                    fbuf, jnp.mod(icb, nbuf), 0, keepdims=False)
                key_b = (jax.random.fold_in(stage_rng, icb) if has_rng else None)
                return act_b, icb, ct, x_b, key_b

            def warmup_tick(carry, u):
                fbuf, fcur = carry
                fbuf, fcur = f_subtick(fbuf, fcur, u)
                return (fbuf, fcur), None

            def steady_tick(carry, u):
                fbuf, fcur, bcur, gacc, dxout = carry
                fbuf, fcur = f_subtick(fbuf, fcur, u)
                act_b, icb, ct, x_b, key_b = b_inputs(fbuf, bcur, u)
                _, vjp_fn = jax.vjp(
                    lambda cl, xx: chunk_run(cl, xx, key_b), leaves, x_b)
                dleaves, dx = vjp_fn(ct)
                gacc = [ga + jnp.where(act_b, dl, jnp.zeros_like(dl))
                        for ga, dl in zip(gacc, dleaves)]
                cur_slot = jax.lax.dynamic_index_in_dim(dxout, icb, 0, keepdims=False)
                dxout = jax.lax.dynamic_update_index_in_dim(
                    dxout, jnp.where(act_b & (d == 0), dx, cur_slot), icb, 0)
                bcur = jax.lax.ppermute(dx, axis, ring_bwd)
                return (fbuf, fcur, bcur, gacc, dxout), None

            def drain_tick(carry, u):
                fbuf, bcur, gacc, dxout, wq_ct = carry
                act_b, icb, ct, x_b, key_b = b_inputs(fbuf, bcur, u)
                # dx-only vjp: the dW half of this microbatch's backward is
                # deferred to the epilogue (the chunk input stays parked in
                # fbuf; only the cotangent needs a slot)
                _, vjp_x = jax.vjp(lambda xx: chunk_run(leaves, xx, key_b), x_b)
                (dx,) = vjp_x(ct)
                j = u - (m + p - 1)
                old_ct = jax.lax.dynamic_index_in_dim(wq_ct, j, 0, keepdims=False)
                wq_ct = jax.lax.dynamic_update_index_in_dim(
                    wq_ct, jnp.where(act_b, ct, old_ct), j, 0)
                cur_slot = jax.lax.dynamic_index_in_dim(dxout, icb, 0, keepdims=False)
                dxout = jax.lax.dynamic_update_index_in_dim(
                    dxout, jnp.where(act_b & (d == 0), dx, cur_slot), icb, 0)
                bcur = jax.lax.ppermute(dx, axis, ring_bwd)
                return (fbuf, bcur, gacc, dxout, wq_ct), None

            def dw_tick(carry, j):
                fbuf, gacc, wq_ct = carry
                # deferred _w job j of this stage: B(d, i) drained at
                # u = m+p-1+j ⇒ i = m + j + d - (p-1); active while
                # j < p-1-d (stage p-1 deferred nothing)
                i = m + j + d - (p - 1)
                act = (i >= 0) & (i < m)
                ic = jnp.clip(i, 0, m - 1)
                x_b = jax.lax.dynamic_index_in_dim(
                    fbuf, jnp.mod(ic, nbuf), 0, keepdims=False)
                ct = jax.lax.dynamic_index_in_dim(wq_ct, j, 0, keepdims=False)
                key_b = (jax.random.fold_in(stage_rng, ic) if has_rng else None)
                _, vjp_w = jax.vjp(lambda cl: chunk_run(cl, x_b, key_b), leaves)
                (dleaves,) = vjp_w(ct)
                gacc = [ga + jnp.where(act, dl, jnp.zeros_like(dl))
                        for ga, dl in zip(gacc, dleaves)]
                return (fbuf, gacc, wq_ct), None

            wq_ct0 = jnp.zeros((max(p - 1, 1),) + x_mb.shape[1:], x_mb.dtype)
            (fbuf, fcur), _ = jax.lax.scan(
                warmup_tick, (fbuf0, fcur0), jnp.arange(p - 1))
            (fbuf, fcur, bcur, gacc, dxout), _ = jax.lax.scan(
                steady_tick, (fbuf, fcur, bcur0, gacc0, dx0),
                jnp.arange(p - 1, m + p - 1))
            (fbuf, bcur, gacc, dxout, wq_ct), _ = jax.lax.scan(
                drain_tick, (fbuf, bcur, gacc, dxout, wq_ct0),
                jnp.arange(m + p - 1, m + 2 * (p - 1)))
            (_, gacc, _), _ = jax.lax.scan(
                dw_tick, (fbuf, gacc, wq_ct), jnp.arange(p - 1))
            dxout = jax.lax.psum(dxout, axis)  # only stage 0 wrote real rows
            if batch_axis:
                gacc = _dp_grad_sync(gacc, batch_axis, mesh)
            return (dxout, *gacc)

        if variant == "zb":
            bwd_body = bwd_body_zb

        manual = {axis} | ({batch_axis} if batch_axis else set())
        fwd_shmap = jax.shard_map(
            fwd_body, mesh=mesh,
            in_specs=(x_spec, P()) + leaf_specs, out_specs=x_spec,
            axis_names=frozenset(manual), check_vma=False)
        bwd_shmap = jax.shard_map(
            bwd_body, mesh=mesh,
            in_specs=(x_spec, x_spec, P()) + leaf_specs,
            out_specs=(x_spec,) + leaf_specs,
            axis_names=frozenset(manual), check_vma=False)

        @jax.custom_vjp
        def call(x_mb, rng, *leaves):
            return fwd_shmap(x_mb, rng, *leaves)

        def call_fwd(x_mb, rng, *leaves):
            return fwd_shmap(x_mb, rng, *leaves), (x_mb, rng, leaves)

        def call_bwd(res, gout):
            x_mb, rng, leaves = res
            outs = bwd_shmap(gout, x_mb, rng, *leaves)
            drng = np.zeros(np.shape(rng), jax.dtypes.float0)
            return (outs[0], drng) + tuple(outs[1:])

        call.defvjp(call_fwd, call_bwd)
        jitted = jax.jit(call)
        _COMPILED[cache_key] = jitted
        while len(_COMPILED) > _COMPILED_MAX:
            _COMPILED.popitem(last=False)

    if not isinstance(x_mb, jax.core.Tracer):
        x_mb = jax.device_put(x_mb, NamedSharding(mesh, x_spec))
    out = jitted(x_mb, rng, *stacked_leaves)
    return out.reshape(x.shape)


def _pipeline_vpp_1f1b(apply_layer, stacked_leaves, x, *, p, v, m, mesh,
                       axis, batch_axis, rng_key):
    """Tick-interleaved 1F1B for the INTERLEAVED (virtual pipeline) stack
    (reference pipeline_vpp.py — Megatron VPP is 1F1B-interleaved). Closes
    the rotation schedule's O(m·v) activation residency for v > 1:

    custom_vjp around the whole pipelined call, like _pipeline_1f1b:

    - fwd: the rotation scan with NO AD (residuals: x_mb, rng, leaves).
    - bwd: ONE combined scan. With L = v·p global chunks and the rotation
      injection inj(i) = (i//p)·L + i%p, the sub-tick schedule is
          F(chunk c, mb i) at u = inj(i) + c
          B(chunk c, mb i) at u = inj(i) + 2L − 1 − c
      so B(L−1, i) turns a microbatch around one tick after its last F,
      dx hops the reverse ring once per tick (chunk c lives on device
      c % p), and F work fills the backward's warmup exactly as in the
      flat 1F1B. Chunk inputs park in a per-local-slot ring buffer until
      the backward tick recomputes the chunk under jax.vjp (same folded
      key → identical dropout masks) and accumulates parameter grads into
      the stacked leaves at the slot's row block.

    Per-device live activations: ≤ 4p microbatch inputs per local slot
    (v slots) — O(v·p), INDEPENDENT of m, vs the rotation schedule's
    m·v + p − 1 stacked residuals. Ticks: m·v + v·p + p − 1 per direction
    — the canonical interleaved bubble (p−1)/(m·v + p − 1) plus the drain.
    """
    b = x.shape[0]
    L = v * p
    mb_shape = (m, b // m) + tuple(x.shape[1:])
    x_mb = x.reshape(mb_shape)
    x_spec = P(None, batch_axis, *([None] * (len(mb_shape) - 2)))
    leaf_specs = tuple(P(axis, *([None] * (a.ndim - 1))) for a in stacked_leaves)
    has_rng = rng_key is not None
    rng = rng_key if has_rng else jax.random.PRNGKey(0)

    cache_key = (
        "vpp1f1b", apply_layer, p, v, m, axis, batch_axis, mesh, has_rng,
        tuple(mb_shape), str(x_mb.dtype),
        tuple((tuple(a.shape), str(a.dtype)) for a in stacked_leaves),
    )
    jitted = _COMPILED.get(cache_key)
    if jitted is not None:
        _COMPILED.move_to_end(cache_key)
    if jitted is None:
        ring_fwd = [(s, (s + 1) % p) for s in range(p)]
        ring_bwd = [(s, (s - 1) % p) for s in range(p)]

        def chunk_run(chunk_leaves, xc, key):
            return _chunk_run(apply_layer, chunk_leaves, xc, key)

        def slot_chunk(local, j):
            """local: leaves reshaped (v, k, ...); pick slot j's (k, ...)."""
            return [jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False)
                    for a in local]

        def fwd_body(x_mb, rng, *leaves):
            d = jax.lax.axis_index(axis)
            leaves = list(leaves)
            k = leaves[0].shape[0] // v
            local = [a.reshape((v, k) + a.shape[1:]) for a in leaves]
            stage_rng = jax.random.fold_in(rng, d) if has_rng else None
            T = m * v + p - 1
            out0 = jnp.zeros(x_mb.shape, x_mb.dtype)
            cur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)

            def tick(carry, t):
                cur, out = carry
                j, c, i, active = _solve_tick(t, d, p=p, v=v, m=m)
                chunk = slot_chunk(local, j)
                x_in = jnp.where(
                    c == 0,
                    jax.lax.dynamic_index_in_dim(x_mb, i, 0, keepdims=False),
                    cur)
                key = (jax.random.fold_in(stage_rng, t) if has_rng else None)
                y = chunk_run(chunk, x_in, key)
                done = active & (c == L - 1)
                slot = jax.lax.dynamic_index_in_dim(out, i, 0, keepdims=False)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, jnp.where(done, y, slot), i, 0)
                nxt = jax.lax.ppermute(y, axis, ring_fwd)
                return (nxt, out), None

            (_, out), _ = jax.lax.scan(tick, (cur0, out0), jnp.arange(T))
            return jax.lax.psum(out, axis)

        def _solve_b(u, d):
            """Which (slot j, chunk c, mb i) has its BACKWARD on device d at
            tick u: B(c, i) at u = inj(i) + 2L − 1 − c, c ∈ {d, d+p, ...}."""
            cs = d + p * jnp.arange(v)
            inj = u - (2 * L - 1) + cs
            r = jnp.mod(inj, L)
            q = inj // L
            i_cand = q * p + r
            valid = (inj >= 0) & (r < p) & (i_cand < m)
            j = jnp.argmax(valid)
            c = cs[j]
            i = jnp.clip(i_cand[j], 0, m - 1)
            return j, c, i, jnp.any(valid)

        def bwd_body(g, x_mb, rng, *leaves):
            d = jax.lax.axis_index(axis)
            leaves = list(leaves)
            k = leaves[0].shape[0] // v
            local = [a.reshape((v, k) + a.shape[1:]) for a in leaves]
            stage_rng = jax.random.fold_in(rng, d) if has_rng else None
            T2 = m * v + v * p + p - 1
            nbuf = 4 * p
            # per-slot parked chunk inputs: [v, nbuf, ...]
            fbuf0 = jnp.zeros((v, nbuf) + x_mb.shape[1:], x_mb.dtype)
            fcur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
            bcur0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
            gacc0 = [jnp.zeros_like(a) for a in local]  # (v, k, ...)
            dx0 = jnp.zeros(x_mb.shape, x_mb.dtype)

            def tick(carry, u):
                fbuf, fcur, bcur, gacc, dxout = carry
                # ---- forward sub-tick: F(c_f, i_f) at u = inj(i_f) + c_f
                jf, cf, i_f, act_f = _solve_tick(u, d, p=p, v=v, m=m)
                x_in = jnp.where(
                    cf == 0,
                    jax.lax.dynamic_index_in_dim(x_mb, i_f, 0, keepdims=False),
                    fcur)
                slot_f = jnp.mod(i_f, nbuf)
                old = fbuf[jf, slot_f]
                fbuf = fbuf.at[jf, slot_f].set(jnp.where(act_f, x_in, old))
                key_f = (jax.random.fold_in(stage_rng, u) if has_rng else None)
                y = chunk_run(slot_chunk(local, jf), x_in, key_f)
                # ---- backward sub-tick: B(c_b, i_b) mirrored
                jb, cb, i_b, act_b = _solve_b(u, d)
                ct = jnp.where(
                    cb == L - 1,
                    jax.lax.dynamic_index_in_dim(g, i_b, 0, keepdims=False),
                    bcur).astype(x_mb.dtype)
                x_b = fbuf[jb, jnp.mod(i_b, nbuf)]
                # refold the key F(c_b, i_b) used: its forward tick
                u_f = u - 2 * (L - 1 - cb) - 1
                key_b = (jax.random.fold_in(stage_rng, u_f) if has_rng
                         else None)
                _, vjp_fn = jax.vjp(
                    lambda cl, xx: chunk_run(cl, xx, key_b),
                    slot_chunk(local, jb), x_b)
                dchunk, dx = vjp_fn(ct)
                gacc = [ga.at[jb].add(jnp.where(act_b, dl, jnp.zeros_like(dl)))
                        for ga, dl in zip(gacc, dchunk)]
                cur_slot = jax.lax.dynamic_index_in_dim(
                    dxout, i_b, 0, keepdims=False)
                dxout = jax.lax.dynamic_update_index_in_dim(
                    dxout, jnp.where(act_b & (cb == 0), dx, cur_slot), i_b, 0)
                fcur = jax.lax.ppermute(y, axis, ring_fwd)
                bcur = jax.lax.ppermute(dx, axis, ring_bwd)
                return (fbuf, fcur, bcur, gacc, dxout), None

            (_, _, _, gacc, dxout), _ = jax.lax.scan(
                tick, (fbuf0, fcur0, bcur0, gacc0, dx0), jnp.arange(T2))
            dxout = jax.lax.psum(dxout, axis)  # only chunk 0's device wrote
            gout = [ga.reshape((v * k,) + ga.shape[2:]) for ga in gacc]
            if batch_axis:
                gout = _dp_grad_sync(gout, batch_axis, mesh)
            return (dxout, *gout)

        manual = {axis} | ({batch_axis} if batch_axis else set())
        fwd_shmap = jax.shard_map(
            fwd_body, mesh=mesh,
            in_specs=(x_spec, P()) + leaf_specs, out_specs=x_spec,
            axis_names=frozenset(manual), check_vma=False)
        bwd_shmap = jax.shard_map(
            bwd_body, mesh=mesh,
            in_specs=(x_spec, x_spec, P()) + leaf_specs,
            out_specs=(x_spec,) + leaf_specs,
            axis_names=frozenset(manual), check_vma=False)

        @jax.custom_vjp
        def call(x_mb, rng, *leaves):
            return fwd_shmap(x_mb, rng, *leaves)

        def call_fwd(x_mb, rng, *leaves):
            return fwd_shmap(x_mb, rng, *leaves), (x_mb, rng, leaves)

        def call_bwd(res, gout):
            x_mb, rng, leaves = res
            outs = bwd_shmap(gout, x_mb, rng, *leaves)
            drng = np.zeros(np.shape(rng), jax.dtypes.float0)
            return (outs[0], drng) + tuple(outs[1:])

        call.defvjp(call_fwd, call_bwd)
        jitted = jax.jit(call)
        _COMPILED[cache_key] = jitted
        while len(_COMPILED) > _COMPILED_MAX:
            _COMPILED.popitem(last=False)

    if not isinstance(x_mb, jax.core.Tracer):
        x_mb = jax.device_put(x_mb, NamedSharding(mesh, x_spec))
    out = jitted(x_mb, rng, *stacked_leaves)
    return out.reshape(x.shape)


def schedule_cost_report(p: int, m: int, schedule: str) -> dict:
    """Traced-unit accounting for one train step of the tick-interleaved
    schedules (the SPMD analog of the reference's per-stage job-list bubble
    accounting). Unit model, with per-chunk remat: F = 1 unit,
    combined vjp = 3 (recompute + dx + dW), dx-only vjp = 2, dW-only
    vjp = 2. In the lockstep tick loop every traced tick costs its full
    body on every stage, active or not, so wasted = total − useful is the
    bubble — the quantity ZB-H1 shrinks by giving warmup ticks an F-only
    body and bubble-filling the deferred dW jobs.
    """
    useful = 4 * m  # per stage: m forwards + m combined backwards
    if schedule in ("1f1b", "eager_1f1b"):
        total = (m + 2 * (p - 1)) * 4  # every tick: F + combined vjp
    elif schedule in ("zb", "zbh1"):
        total = ((p - 1) * 1          # warmup: F only
                 + m * 4              # steady: F + combined vjp
                 + (p - 1) * 2        # drain: dx-only vjp
                 + (p - 1) * 2)       # epilogue: deferred dW block
    else:
        raise ValueError(f"no cost model for schedule {schedule!r}")
    return {
        "schedule": schedule, "p": p, "m": m,
        "total_units": total, "useful_units": useful,
        "wasted_units": total - useful,
        "bubble_fraction": (total - useful) / total,
    }


import collections

_COMPILED: "collections.OrderedDict" = collections.OrderedDict()
_COMPILED_MAX = 32


class PipelinedStack(Layer):
    """A stack of homogeneous layers executed with the SPMD pipeline schedule
    (the TPU analog of PipelineLayer's segment-per-stage + the reference's
    1F1B/interleave runtime, pipeline_parallel.py:575/:1174).

    Parameters are stored STACKED: one Parameter per template weight with a
    leading num_layers dim in `chunk_permutation` order, sharded over `pp`.
    The template layer instance is used purely as a tracing shell (its
    forward defines the per-layer computation; dropout/stateful buffers are
    not supported inside the stack — matches the reference's constraint that
    pp stage boundaries carry activations only).
    """

    def __init__(self, layer_factory: Callable[[], Layer], num_layers: int,
                 num_stages: Optional[int] = None, num_chunks: int = 1,
                 num_microbatches: Optional[int] = None, remat: bool = True,
                 schedule: str = "rotation"):
        super().__init__()
        degrees = env_mod.instance().axis_degrees or {}
        self.num_stages = num_stages or max(degrees.get("pp", 1), 1)
        self.num_chunks = num_chunks
        self.num_layers = num_layers
        self.remat = remat
        if schedule not in ("rotation", "1f1b", "eager_1f1b", "zb", "zbh1"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        if schedule in ("zb", "zbh1") and num_chunks != 1:
            raise ValueError(
                "ZB-H1 covers num_chunks == 1; interleaved stacks use "
                "schedule='1f1b' (tick-interleaved VPP) or 'rotation'")
        self.schedule = schedule
        if num_layers % (self.num_stages * num_chunks) != 0:
            raise ValueError(
                f"num_layers {num_layers} must divide by "
                f"num_stages*num_chunks {self.num_stages * num_chunks}")
        self.num_microbatches = num_microbatches or 2 * self.num_stages

        self.template = layer_factory()
        self._param_names = [n for n, _ in self.template.named_parameters()]
        perm = chunk_permutation(num_layers, self.num_stages, self.num_chunks)
        # independent per-layer inits, stacked in permuted order → exact
        # numeric parity with a serial LayerList of the same factory
        inits = [self.template] + [layer_factory() for _ in range(num_layers - 1)]
        mesh = env_mod.get_mesh()
        for name in self._param_names:
            vals = [dict(l.named_parameters())[name]._value for l in inits]
            stacked = jnp.stack([vals[orig] for orig in perm], 0)
            if self.num_stages > 1 and mesh is not None and mesh.shape.get("pp", 1) == self.num_stages:
                spec = P("pp", *([None] * (stacked.ndim - 1)))
                stacked = jax.device_put(stacked, NamedSharding(mesh, spec))
            pname = "stack_" + name.replace(".", "__")
            param = self.create_parameter(
                shape=list(stacked.shape), dtype=str(stacked.dtype))
            param._replace_value(stacked)
            setattr(self, pname, param)
        self._stacked_names = ["stack_" + n.replace(".", "__") for n in self._param_names]

    def _template_params(self):
        named = dict(self.template.named_parameters())
        return [named[n] for n in self._param_names]

    def _apply_layer(self, leaves, xv):
        """Functional application of the template with given leaf values —
        runs the eager layer on tracers with the framework tape off (jax AD
        differentiates through it; the tape sees only the outer primitive)."""
        from ...base import global_state

        tparams = self._template_params()
        saved = [tp._value for tp in tparams]
        for tp, lv in zip(tparams, leaves):
            tp._value = lv
        try:
            with global_state.no_grad_guard():
                out = self.template(Tensor(xv, stop_gradient=True))
            return out._value if hasattr(out, "_value") else out
        finally:
            for tp, sv in zip(tparams, saved):
                tp._value = sv

    def forward(self, x):
        stacked = [getattr(self, n) for n in self._stacked_names]
        mesh = env_mod.get_mesh()
        xv0 = x._value if hasattr(x, "_value") else x

        # training mode: thread a PRNG key so dropout inside the stack folds
        # per (stage, tick) — see pipeline_spmd's rng_key contract
        rng_key = None
        if self.training:
            from ...base import global_state

            rng_key = global_state.default_generator.split()

        # adapt the microbatch count to the incoming batch: largest m ≤ the
        # configured one with m % p == 0 and batch % m == 0; a batch that
        # cannot even split into p microbatches runs the serial scan path
        # (correct, no stage parallelism — the reference errors out here
        # instead; degrading keeps small-batch eval/debug usable)
        p = self.num_stages
        batch = xv0.shape[0]
        m_eff = 0
        m = (self.num_microbatches // p) * p
        while m >= p:
            if batch % m == 0:
                m_eff = m
                break
            m -= p
        stages_eff = p if m_eff else 1
        if not m_eff and self.num_chunks > 1:
            # serial fallback would replay the chunk-permuted stacking order;
            # interleaved stacks keep the strict divisibility contract
            raise ValueError(
                f"batch {batch} cannot split into ≥{p} microbatches for the "
                f"interleaved pipeline (num_chunks={self.num_chunks})")
        m_eff = m_eff or 1

        # dp sharding decision must follow the EFFECTIVE microbatch split
        dp = mesh.shape.get("dp", 1) if mesh is not None else 1
        mb = batch // m_eff
        batch_axis = "dp" if (dp > 1 and stages_eff > 1 and mb % dp == 0) else None

        def fn(xv, *leaf_vals):
            return pipeline_spmd(
                self._apply_layer, list(leaf_vals), xv,
                num_stages=stages_eff,
                num_microbatches=m_eff,
                num_chunks=self.num_chunks,
                batch_axis=batch_axis,
                remat=self.remat,
                rng_key=rng_key,
                schedule=self.schedule if stages_eff > 1 else "rotation",
            )

        return primitive("pipelined_stack", fn, [x, *stacked])

    def layer_state_dict(self, idx: int):
        """Un-permuted single-layer weights (for export / parity checks)."""
        perm = chunk_permutation(self.num_layers, self.num_stages, self.num_chunks)
        pos = perm.index(idx)
        return {
            n: getattr(self, sn)._value[pos]
            for n, sn in zip(self._param_names, self._stacked_names)
        }


def forward_backward_pipeline_rotation(stack: PipelinedStack, x):
    """Rotation schedule, one chunk per stage — schedule-wise a rotation
    GPipe: all-forward ticks, then jax-AD-reversed backward with per-chunk
    remat. In-flight activation memory is O(m·v) per device (each stage's
    saved chunk inputs); prefer schedule='1f1b' at m ≫ p."""
    assert stack.num_chunks == 1
    return stack(x)


def forward_backward_pipeline_1f1b(stack: PipelinedStack, x):
    """True tick-interleaved 1F1B (reference pipeline_parallel.py:575):
    in-flight microbatches capped per stage at ≤ 2(p-1-s) instead of the
    rotation schedule's m + p - 1 stacked residuals. Runs the stack's
    forward with the 1f1b schedule regardless of its configured default."""
    assert stack.num_chunks == 1
    prev, stack.schedule = stack.schedule, "1f1b"
    try:
        return stack(x)
    finally:
        stack.schedule = prev


def forward_backward_pipeline_zero_bubble(stack: PipelinedStack, x):
    """ZB-H1 (reference pipeline_zero_bubble.py:66): backward split into
    dB (input-grad, kept on the inter-stage critical path) and dW
    (weight-grad, deferred into the drain bubble as a batched epilogue).
    See bwd_body_zb for the lockstep-SPMD redesign; schedule_cost_report
    quantifies the traced-unit saving vs the combined 1F1B body."""
    assert stack.num_chunks == 1
    prev, stack.schedule = stack.schedule, "zb"
    try:
        return stack(x)
    finally:
        stack.schedule = prev


def forward_backward_pipeline_eager_1f1b(stack: PipelinedStack, x):
    """Eager 1F1B (reference pipeline_eager_1f1b.py:36: warmup runs
    2(p−s)−1 forwards instead of p−s, trading in-flight activations for
    overlap). In the lockstep SPMD tick loop F(s, i) already runs at the
    earliest dependency-feasible tick u = i + s and each stage parks
    ≤ 2(p−1−s) inputs — exactly the eager profile — so this IS the 1f1b
    tick mapping; the lazy/standard variant would park the same-sized
    tensors one hop later with zero memory or tick difference here."""
    assert stack.num_chunks == 1
    prev, stack.schedule = stack.schedule, "eager_1f1b"
    try:
        return stack(x)
    finally:
        stack.schedule = prev


def forward_backward_pipeline_interleave(stack: PipelinedStack, x):
    """Reference-named entry (pipeline_parallel.py:1174): interleaved VPP
    chunk placement (device d owns chunks {d, d+p, ...}); same rotation tick
    loop, bubble (p-1)/(m·v+p-1)."""
    assert stack.num_chunks > 1
    return stack(x)


