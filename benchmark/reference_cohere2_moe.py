"""The plain reference for Command A+ (`model_type: cohere2_moe`): the
forward pass in `jax.numpy`, float32, every product under
`jax.default_matmul_precision("highest")`. A `[rows, T]` matrix of scores a
query head, the experts in a Python loop, the shared experts one by one and
averaged, no cache, no pages, no chunks, no kernel, no sorting and no grouped
product. It shares no function with `paddle_tpu/`, so agreement between the
two is evidence about both.

The equations (ISSUE 36 wrote them down from the catalog row's `config` and
`described_as`; `h` one token's row, `l` a layer):

    x = E[token]
    n = LN(x) = (x - mean) / sqrt(var + layer_norm_eps) * g_l     (no bias)
    x <- x + Attn_l(n) + FFN_l(n)                   (ONE norm, both read n)
    q = n W_q (heads of head_dim), k = n W_k, v = n W_v (kv heads), no bias;
      query head h uses K/V head h // (heads / kv_heads); scale head_dim^-0.5
      sliding_attention: q, k rotated, pairs (x[2i], x[2i+1]) by
        pos x theta^(-2i/head_dim); key j visible to query i iff j <= i and
        i - j < sliding_window
      full_attention: NO rotation; key j visible iff j <= i
    Attn = concat_h(softmax(q_h . k x scale) v) W_o
    FFN = sum_{k in top} w_k E_k(n) + (1 / S) sum_{s < S} Shared_s(n)
      E(n) = (silu(n W_g) * (n W_u)) W_d
      s = sigmoid(n W_r) over ALL experts, the num_experts_per_tok largest
      chosen, w = s / (sum of the chosen)
    logits = LN_f(x) E^T x logit_scale

*Assumed*, each named again under `assumed` in the configuration's file:

  (a) `shared_expert_combination_strategy: "average"` is the MEAN of the
      shared experts' outputs, added to the routed sum;
  (b) `intermediate_size` is the width of one expert, routed or shared;
  (c) the `prefix_dense_*` keys are unused (`first_k_dense_replace` is 0);
  (d) the window's edge as above (Hugging Face's `kv_idx > q_idx -
      sliding_window`): `sliding_window` keys, the query's own among them.

**The share.** `share = (r, R)`: the weights hold the routed experts
`[r x E / R, (r + 1) x E / R)`; the router scores all `E`, the weights are
normalised over all the chosen, and the sum runs over the chosen experts
that are held. The vocabulary is whatever rows `embed` has.

Departures from a textbook forward, for room on a chip that also holds the
engine's weights (a 20k-token sequence has to fit beside them): weights come
in the dtype they are served in and are upcast one matrix at a time; a
layer's attention runs `block` rows of queries at a time (keys and values
are whole), the query heads one after another, so one `[block, T]` matrix of
scores exists at a time; the loop over the experts runs over the HELD experts and weighs
each token by the weight it gave that expert (0 if it did not choose it);
the head is applied to chosen rows only, in blocks of the vocabulary. None
of them changes a number.

A `weights` tree is `{"embed": [V, H], "norm": [H], "layers": [{"ln",
"q_proj", "k_proj", "v_proj", "o_proj", "router", "experts_gate_up" [held,
H, 2F] ([W_g | W_u]), "experts_down" [held, F, H], "shared_gate_up" [H, 2SF]
([W_g of every shared expert | W_u of every one]), "shared_down" [SF, H]}]}`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CFG_KEYS = ("layer_norm_eps", "num_attention_heads", "num_key_value_heads",
            "head_dim", "sliding_window", "num_experts_per_tok",
            "num_shared_experts", "norm_topk_prob", "rope_theta")
WINDOW = "sliding_attention"


def _f32(a):
    return a.astype(jnp.float32)


def layer_norm(x, g, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(g)


def rope(x, theta, positions):
    """`[T, heads, d]` at `positions` `[T]`: interleaved pairs."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def attention(q, k, v, positions, window, scale):
    """`q` `[S, H, d]` (rotated already where the layer rotates) at
    `positions` `[S]` of a sequence whose keys and values are `k`, `v` `[T,
    G, d]`, key `j` at position `j` -> `[S, H, d]`. One query head at a
    time. `window` None: causal only."""
    S, H, d = q.shape
    T, G, _ = k.shape
    col = jnp.arange(T)[None, :]
    seen = col <= positions[:, None]
    if window is not None:
        seen &= positions[:, None] - col < window

    def head(a):
        qh, g = a
        scores = jnp.where(seen, qh @ k[:, g].T * scale, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[:, g]

    out = jax.lax.map(head, (q.transpose(1, 0, 2), jnp.arange(H) // (H // G)))
    return out.transpose(1, 0, 2)


def router(n, w_r, cfg):
    """`[T, hidden]` -> the chosen experts `[T, k]` and their weights."""
    s = jax.nn.sigmoid(n @ _f32(w_r))
    idx = jnp.argsort(-s, axis=-1)[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return idx, w


def _expert(n, gate, up, down):
    return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def shared_part(n, w, cfg, average=True):
    """(a): the mean of the shared experts' outputs. `average` False is the
    control that has to fail: their sum."""
    S = cfg["num_shared_experts"]
    f = w["shared_down"].shape[0] // S
    gate_up, y = w["shared_gate_up"], 0.0
    for s in range(S):
        y = y + _expert(n, gate_up[:, s * f:(s + 1) * f],
                        gate_up[:, (S + s) * f:(S + s + 1) * f],
                        w["shared_down"][s * f:(s + 1) * f])
    return y / S if average else y


def routed_part(n, w, cfg, first):
    """`sum over chosen k whose expert is held of w_k E_k(n)`; the held
    experts are `first .. first + held - 1`."""
    idx, wt = router(n, w["router"], cfg)
    y = 0.0
    for e in range(w["experts_gate_up"].shape[0]):
        mine = jnp.where(idx == first + e, wt, 0.0).sum(-1)      # 0 if not chosen
        gate, up = jnp.split(w["experts_gate_up"][e], 2, axis=-1)
        y = y + mine[:, None] * _expert(n, gate, up, w["experts_down"][e])
    return y


def layer_forward(x, w, cfg, first, windowed, block, average=True):
    """One layer on one sequence: `x` `[T, H]` float32 (`T` a multiple of
    `block`), `w` the layer's weights, `windowed` its kind. Attention runs
    `block` rows of queries at a time; the experts see every row at once, so
    each matrix is upcast once."""
    T = x.shape[0]
    H, G, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    n = layer_norm(x, w["ln"], cfg["layer_norm_eps"])
    at = jnp.arange(T)
    k = (n @ _f32(w["k_proj"])).reshape(T, G, d)
    v = (n @ _f32(w["v_proj"])).reshape(T, G, d)
    if windowed:
        k = rope(k, cfg["rope_theta"], at)

    def rows(a):
        nb, pos = a
        q = (nb @ _f32(w["q_proj"])).reshape(-1, H, d)
        if windowed:
            q = rope(q, cfg["rope_theta"], pos)
        y = attention(q, k, v, pos, cfg["sliding_window"] if windowed else None,
                      d ** -0.5)
        return y.reshape(-1, H * d) @ _f32(w["o_proj"])

    blocks = lambda a: a.reshape((T // block, block) + a.shape[1:])
    att = jax.lax.map(rows, (blocks(n), blocks(at))).reshape(T, -1)
    return x + att + routed_part(n, w, cfg, first) + shared_part(n, w, cfg, average)


@functools.partial(jax.jit, static_argnames=("key", "first", "windowed", "block",
                                             "average"))
def _layer(x, w, key, first, windowed, block, average):
    with jax.default_matmul_precision("highest"):
        return layer_forward(x, w, dict(key), first, windowed, block, average)


def first_held(weights, share) -> int:
    return int(share[0]) * weights["layers"][0]["experts_gate_up"].shape[0]


def hidden_states(weights, ids, cfg, share=(0, 1), block=None, average=True):
    """`ids` `[T]` -> the last layer's output `[T, H]` (before the final
    norm), one layer at a time, `block` rows of queries at a time (`T` is a
    multiple of it; None: all at once)."""
    key = tuple((k, cfg[k]) for k in CFG_KEYS)
    first = first_held(weights, share)
    x = _f32(weights["embed"][ids])
    for w, kind in zip(weights["layers"], cfg["layer_types"]):
        x = _layer(x, w, key, first, kind == WINDOW, block or len(ids), average)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "scale", "block"))
def logits_at(weights, rows, eps, scale=1.0, block=None):
    """The final norm and the tied head on `rows` `[N, H]` -> `[N, V]`, the
    head in `block` rows of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = layer_norm(rows, weights["norm"], eps)
        embed = weights["embed"]
        V = embed.shape[0]
        block = block or V
        return jnp.concatenate([x @ _f32(embed[i:i + block]).T
                                for i in range(0, V, block)], axis=-1) * scale


def forward_logits(weights, ids, cfg, share=(0, 1)):
    """Every position's logits for one sequence `[T]` -> `[T, V]`."""
    return logits_at(weights, hidden_states(weights, ids, cfg, share),
                     cfg["layer_norm_eps"], cfg.get("logit_scale", 1.0))
