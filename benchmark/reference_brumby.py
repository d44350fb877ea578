"""The plain reference for Brumby (power retention, degree 2): the forward
pass in `jax.numpy`, float32, every product under
`jax.default_matmul_precision("highest")`, in the QUADRATIC form: for each
head a `[T, T]` matrix of weights, no state, no chunks, no cache, no `phi`.
It shares no function and no algorithm with `paddle_tpu/` (the program
serves the same function as a recurrence over a state), so agreement
between the two is evidence about both.

It follows Buckman, Gelada et al., "Scaling Context Requires Rethinking
Attention" (arXiv:2507.04239) for the mechanism and the Qwen3 skeleton the
Brumby checkpoint was retrained from for the rest. `config.json` has no key
for the following, each *assumed* here, named again under `assumed` in
`benchmark/configs/brumby-14b-base.json`:

  (a) the degree p = 2: a weight is the SQUARE of the scaled product;
  (b) the gate: one per key/value head, log g = log sigmoid(a W_g + b_g),
      in float32, W_g `[hidden, kv_heads]`;
  (c) its offset b_g (the config's `attention_bias: false` is read as
      covering q, k, v and o only): without one a seeded W_g gives g near
      1/2, a memory of two tokens;
  (d) the normaliser: y = sum_s A v / (sum_s A + eps), eps 1e-6, float32;
  (e) RMSNorm over each head's 128 of q and k, then RoPE (theta from the
      config, rotate-halves, all 128 dimensions, positions from 0): kept
      from the parent checkpoint's architecture;
  (f) the scale 1 / sqrt(head_dim) inside the square.

Departures from a textbook forward, for room on a chip that also holds the
engine: weights come in the dtype they are served in (bfloat16-rounded) and
are upcast one matrix at a time inside each product; heads run one after
another (`lax.map`), so one `[T, T]` matrix exists at a time; the output
head is applied to chosen rows only, in blocks of the vocabulary. None of
them changes a number.

A `weights` tree is `{"embed": [V, H], "norm": [H], "head": [H, V],
"layers": {name: [L, ...]}}` with the layers stacked on a leading axis,
names as in `LAYER_KEYS`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LAYER_KEYS = ("input_norm", "qkv_proj", "g_proj", "g_bias",
              "q_norm", "k_norm", "o_proj", "post_norm", "gate_up_proj", "down_proj")
EPS = 1e-6   # (d)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope(x, theta):
    """`[T, heads, d]`, positions 0..T-1, rotate-halves."""
    T, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def retention_quadratic(q, k, v, log_g, eps=EPS):
    """`q` `[T, Hq, d]`, `k`/`v` `[T, Hkv, d]`, `log_g` `[T, Hkv]` -> `[T,
    Hq, d]`. Query head h reads key/value head h // (Hq // Hkv)."""
    T, Hq, d = q.shape
    group = Hq // k.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(h):
        j = h // group
        c = jnp.cumsum(log_g[:, j])
        weight = (q[:, h] @ k[:, j].T / jnp.sqrt(jnp.float32(d))) ** 2      # (a), (f)
        weight = weight * jnp.exp(jnp.where(causal, c[:, None] - c[None, :], -jnp.inf))
        return weight @ v[:, j] / (weight.sum(-1, keepdims=True) + eps)      # (d)

    return jax.lax.map(head, jnp.arange(Hq)).transpose(1, 0, 2)


def layer_forward(x, w, cfg):
    """One layer on one sequence: `x` `[T, H]` float32, `w` the layer's
    weights (one index of the stacked tree)."""
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    T = x.shape[0]
    a = _rms(x, w["input_norm"], eps)
    qkv = a @ _f32(w["qkv_proj"])                     # columns [q | k | v]
    q = qkv[:, :hq * d].reshape(T, hq, d)
    k = qkv[:, hq * d:(hq + hkv) * d].reshape(T, hkv, d)
    v = qkv[:, (hq + hkv) * d:].reshape(T, hkv, d)
    log_g = jax.nn.log_sigmoid(a @ _f32(w["g_proj"]) + _f32(w["g_bias"]))     # (b), (c)
    q = _rope(_rms(q, w["q_norm"], eps), cfg["rope_theta"])                   # (e)
    k = _rope(_rms(k, w["k_norm"], eps), cfg["rope_theta"])
    y = retention_quadratic(q, k, v, log_g)
    x = x + y.reshape(T, hq * d) @ _f32(w["o_proj"])
    b = _rms(x, w["post_norm"], eps)
    gate, up = jnp.split(b @ _f32(w["gate_up_proj"]), 2, axis=-1)
    return x + (jax.nn.silu(gate) * up) @ _f32(w["down_proj"])


@functools.partial(jax.jit, static_argnames=("cfg",))
def _layer(x, w, cfg):
    with jax.default_matmul_precision("highest"):
        return layer_forward(x, w, dict(cfg))


def hidden_states(weights, ids, cfg):
    """`ids` `[T]` -> the last layer's output `[T, H]` (before the final
    norm), one layer at a time: one compiled program, called per layer."""
    key = tuple(sorted((k, cfg[k]) for k in (
        "rms_norm_eps", "head_dim", "num_attention_heads", "num_key_value_heads",
        "rope_theta")))
    x = _f32(weights["embed"][ids])
    layers = weights["layers"]
    for i in range(layers["qkv_proj"].shape[0]):
        x = _layer(x, {name: layers[name][i] for name in LAYER_KEYS}, key)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "block"))
def logits_at(weights, rows, eps, block=None):
    """The final norm and the untied head on `rows` `[N, H]` -> `[N, V]`,
    the head in `block` columns of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = _rms(rows, weights["norm"], eps)
        head = weights["head"]
        V = head.shape[1]
        block = block or V
        return jnp.concatenate([x @ _f32(head[:, i:i + block])
                                for i in range(0, V, block)], axis=-1)


def forward_logits(weights, ids, cfg):
    """Every position's logits for one sequence `[T]` -> `[T, V]`."""
    return logits_at(weights, hidden_states(weights, ids, cfg), cfg["rms_norm_eps"])
