"""Context parallelism: ring attention + Ulysses (DeepSpeed-style) all_to_all.

The reference snapshot has NO ring/Ulysses/blockwise CP (SURVEY.md §2.14 —
long sequences are handled by the SEP hybrid axis + Megatron-SP +
flashmask). This module is the TPU-idiomatic superset: the sequence is a
mesh axis (`sep`), and

- `ring_attention` runs blockwise attention with online-softmax
  accumulation while K/V blocks rotate around the ring via `ppermute`
  (one ICI hop per step, compute/comm overlapped by XLA's latency-hiding
  scheduler inside the shard_map body);
- `ulysses_attention` trades sequence sharding for head sharding with two
  `all_to_all`s and runs a fully-local attention in between (cheaper when
  num_heads >= sep degree and sequence fits per-device HBM after the swap).

Both are differentiable (ppermute/all_to_all have transpose rules; the ring
loop is rematerialized per step so backward recomputes block scores instead
of storing them — the Blockwise/RingAttention memory recipe).

Layout is [batch, seq, heads, head_dim] throughout (TPU-friendly, matching
nn.functional.flash_attention).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core.dispatch import primitive
from .. import env as env_mod

_NEG = -1e30


def _ring_body(q, k, v, *, axis: str, n: int, causal: bool, scale: float):
    """shard_map body: q,k,v are the local [B, S/n, H, D] blocks."""
    idx = jax.lax.axis_index(axis)
    chunk = q.shape[1]
    q_pos = idx * chunk + jnp.arange(chunk)  # global positions of local queries

    qf = q.astype(jnp.float32) * scale
    acc = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full(q.shape[:3], _NEG, jnp.float32)  # [B, Sq, H] running max
    l = jnp.zeros(q.shape[:3], jnp.float32)  # running denom
    perm = [(i, (i + 1) % n) for i in range(n)]

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def step(t, carry_kv, acc, m, l):
        k_t, v_t = carry_kv
        # device idx holds K/V block (idx - t) mod n at step t
        j = (idx - t) % n
        k_pos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, k_t.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
            s = jnp.where(mask[None, :, None, :], s, _NEG)
        s_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, s_max)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p, v_t.astype(jnp.float32)
        )
        return acc_new, m_new, l_new

    k_t, v_t = k, v
    for t in range(n):
        acc, m, l = step(t, (k_t, v_t), acc, m, l)
        if t + 1 < n:
            k_t = jax.lax.ppermute(k_t, axis, perm)
            v_t = jax.lax.ppermute(v_t, axis, perm)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _ulysses_body(q, k, v, *, axis: str, causal: bool, scale: float, dropout: float):
    """shard_map body: seq-sharded -> all_to_all -> head-sharded local attn."""
    from ...nn.functional.attention import _xla_attention

    swap = functools.partial(jax.lax.all_to_all, axis_name=axis, tiled=True)
    qh = swap(q, split_axis=2, concat_axis=1)  # [B, S, H/n, D]
    kh = swap(k, split_axis=2, concat_axis=1)
    vh = swap(v, split_axis=2, concat_axis=1)
    out = _xla_attention(qh, kh, vh, causal=causal, scale=scale, dropout=dropout)
    return swap(out, split_axis=1, concat_axis=2)  # back to [B, S/n, H, D]


def _cp_call(body_builder, q, k, v, axis: str, extra_check=None):
    mesh = env_mod.get_mesh()
    n = mesh.shape.get(axis, 1)
    qv = q._value if hasattr(q, "_value") else q
    if n > 1 and qv.shape[1] % n != 0:
        raise ValueError(f"sequence length {qv.shape[1]} not divisible by {axis}={n}")
    if extra_check:
        extra_check(n, qv)

    def fn(qq, kk, vv):
        if n == 1:  # degenerate mesh: plain attention
            from ...nn.functional.attention import _xla_attention

            scale = 1.0 / math.sqrt(qq.shape[-1])
            return _xla_attention(qq, kk, vv, causal=body_builder.keywords["causal"], scale=scale)
        # Nested-manual support (pp pipeline shard_map around a cp block):
        # when tracing inside an enclosing shard_map, the inner shard_map
        # must be built on the CONTEXT's abstract mesh, and axes the outer
        # region already made Manual (pp, dp) must not appear in the specs —
        # the operands are already per-shard along them.
        from .mpu import _manual_axes

        manual = _manual_axes()
        use_mesh = jax.sharding.get_abstract_mesh() if manual else mesh
        dp = mesh.shape.get("dp", 1)
        batch_axis = ("dp" if (dp > 1 and qv.shape[0] % dp == 0
                               and "dp" not in manual) else None)
        spec = P(batch_axis, axis, None, None)
        shmap = jax.shard_map(
            body_builder,
            mesh=use_mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        if not isinstance(qq, jax.core.Tracer):
            sh = NamedSharding(mesh, spec)
            qq, kk, vv = (jax.device_put(x, sh) for x in (qq, kk, vv))
        return shmap(qq, kk, vv)

    return primitive("context_parallel_attention", fn, [q, k, v])


def ring_attention(q, k, v, causal=True, axis="sep"):
    """Ring attention over the ``axis`` mesh dimension.

    q/k/v: [B, S, H, D] with S sharded over ``axis``. Returns [B, S, H, D]
    sharded the same way. Exact (not approximate): computes full attention
    blockwise with online softmax.
    """
    qv = q._value if hasattr(q, "_value") else q
    scale = 1.0 / math.sqrt(qv.shape[-1])
    mesh = env_mod.get_mesh()
    n = mesh.shape.get(axis, 1)
    body = functools.partial(_ring_body, axis=axis, n=n, causal=causal, scale=scale)
    return _cp_call(body, q, k, v, axis)


def ulysses_attention(q, k, v, causal=True, axis="sep", dropout=0.0):
    """Ulysses/all-to-all sequence parallelism: swap seq<->head sharding,
    attend locally, swap back. Requires num_heads % axis degree == 0."""
    qv = q._value if hasattr(q, "_value") else q
    scale = 1.0 / math.sqrt(qv.shape[-1])

    def check(n, val):
        if n > 1 and val.shape[2] % n != 0:
            raise ValueError(f"num_heads {val.shape[2]} not divisible by {axis}={n}")

    body = functools.partial(_ulysses_body, axis=axis, causal=causal, scale=scale, dropout=dropout)
    return _cp_call(body, q, k, v, axis, extra_check=check)
