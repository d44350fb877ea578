"""The plain reference for A.X-K1 (`model_type: axk1`): the forward pass in
`jax.numpy`, float32, every product under
`jax.default_matmul_precision("highest")`. Attention in the EXPANDED form
only (per-head keys and values formed from the latent, a `[T, T]` matrix of
scores a head), the experts in a Python loop, no cache, no pages, no chunks,
no absorbed product, no sorting and no grouped product. It shares no function
with `paddle_tpu/`, so agreement between the two is evidence about both.

The equations (DeepSeek-V2/V3's, at the sizes of skt/A.X-K1's config.json):

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h));  final RMSNorm; head
    c_q = RMSNorm(x W_qa);  q = c_q W_qb = heads of [q_nope | q_rope]
    x W_kva = [c_kv' | k_rope'];  c_kv = RMSNorm(c_kv');  k_rope = RoPE(k_rope')
    k[h] = [c_kv W_k[h] | k_rope];  v[h] = c_kv W_v[h];  q_rope = RoPE(q_rope)
    Attn = concat_h(softmax_causal(q[h] . k[h] x scale) v[h]) W_o
    FFN, layer < first_k_dense_replace: (silu(x W_g) * (x W_u)) W_d
    FFN, otherwise: sum_k w_k E_{e_k}(x) + E_shared(x)
    s = sigmoid(x W_r); groups of E / n_group consecutive experts; a group's
    score is the sum of its two largest s; the topk_group best groups stay;
    among their experts the num_experts_per_tok largest s; w = s / (sum of
    the chosen + 1e-20) x routed_scaling_factor

*Assumed*, each named again under `assumed` in the configuration's file:

  (a) `topk_method: "none"` beside `n_group: 8`, `topk_group: 4` is read as
      the group-limited top-k those keys state, with NO score-correction
      bias (DeepSeek-V3's `noaux_tc` has one);
  (b) RoPE is rotate-halves on the 64 rotary columns, YaRN as the config's
      `rope_scaling` block states (`inv_freq` between the interpolated and
      the plain frequency along a ramp over the pairs `low..high`; `mscale`
      = `mscale_all_dim`, so cos and sin are not scaled and `scale =
      192^-0.5 x (0.1 ln factor + 1)^2`);
  (c) `kv_b_proj`'s columns come as two matrices, the keys' `W_k` and the
      values' `W_v`, each `[kv_lora_rank, heads x 128]`.

**The share.** `share = (r, R)`: the weights hold the routed experts
`[r x E / R, (r + 1) x E / R)`; the router scores all `E`, the weights are
normalised over all the chosen, and the sum runs over the chosen experts
that are held. What the absent ones would add is left out, here as in the
program. The vocabulary is whatever rows `embed` and `head` have.

Departures from a textbook forward, for room on a chip that also holds the
engine's weights: weights come in the dtype they are served in
(bfloat16-rounded) and are upcast one matrix at a time inside each product;
heads run one after another (`lax.map`), so one `[T, T]` matrix exists at a
time; the loop over the experts runs over the HELD experts and weighs each
token by the weight it gave that expert (0 if it did not choose it), which
is the sum over the chosen experts read the other way; the output head is
applied to chosen rows only, in blocks of the vocabulary. None of them
changes a number.

A `weights` tree is `{"embed": [V, H], "norm": [H], "head": [H, V],
"dense": {name: [k, ...]}, "sparse": {name: [L, ...]}}`, the layers of each
kind stacked on a leading axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CFG_KEYS = ("rms_norm_eps", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "n_group",
            "topk_group", "num_experts_per_tok", "routed_scaling_factor",
            "norm_topk_prob", "rope_theta")


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def inv_freq(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """(b): YaRN's frequencies for the `dim // 2` pairs."""
    extra = theta ** (-np.arange(0, dim, 2) / dim)
    inter = extra / scaling["factor"]

    def pair(turns):
        return (dim * math.log(scaling["original_max_position_embeddings"]
                               / (turns * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(pair(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair(scaling["beta_slow"])), dim - 1)
    mask = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    s = cfg["rope_scaling"]
    return d ** -0.5 * (0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0) ** 2


def rope(x, freqs, positions=None):
    """`[T, ..., d]`, positions 0..T-1 unless given, rotate-halves."""
    T = x.shape[0]
    at = jnp.arange(T) if positions is None else positions
    ang = at.astype(jnp.float32)[:, None] * freqs[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1).reshape((T,) + (1,) * (x.ndim - 2) + (-1,))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def attention(q_nope, q_rope, c_kv, k_rope, w_k, w_v, scale, positions=None):
    """Expanded causal attention of one sequence: `q_nope` `[S, H, dn]`,
    `q_rope` `[S, H, dr]` and `k_rope` `[T, dr]` already rotated, `c_kv`
    `[T, rank]` already normed, `w_k` `[rank, H x dn]`, `w_v` `[rank, H x
    dv]` -> `[S, H, dv]`. The queries are the sequence's own `T` tokens, or
    those at `positions` `[S]` of it. One head at a time."""
    _, H, dn = q_nope.shape
    T = c_kv.shape[0]
    at = jnp.arange(T) if positions is None else positions
    causal = jnp.arange(T)[None, :] <= at[:, None]
    wk = _f32(w_k).reshape(-1, H, dn).transpose(1, 0, 2)
    wv = _f32(w_v).reshape(w_v.shape[0], H, -1).transpose(1, 0, 2)

    def head(a):
        qn, qr, wk_h, wv_h = a
        k = jnp.concatenate([c_kv @ wk_h, k_rope], axis=-1)
        q = jnp.concatenate([qn, qr], axis=-1)
        scores = jnp.where(causal, q @ k.T * scale, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ (c_kv @ wv_h)

    out = jax.lax.map(head, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2), wk, wv))
    return out.transpose(1, 0, 2)


def router(x, w_r, cfg, group_limited=True):
    """`[T, hidden]` -> the chosen experts `[T, k]` and their weights."""
    s = jax.nn.sigmoid(x @ _f32(w_r))
    T, E = s.shape
    groups, k = cfg["n_group"], cfg["num_experts_per_tok"]
    pick = s
    if group_limited:                                             # (a)
        per = s.reshape(T, groups, E // groups)
        score = jnp.sort(per, axis=-1)[..., -2:].sum(-1)
        kept = jnp.argsort(-score, axis=-1)[:, :cfg["topk_group"]]
        keep = jnp.zeros((T, groups), bool).at[jnp.arange(T)[:, None], kept].set(True)
        pick = jnp.where(keep[:, :, None], per, -1.0).reshape(T, E)
    idx = jnp.argsort(-pick, axis=-1)[:, :k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def _expert(x, gate_up, down):
    gate, up = jnp.split(x @ _f32(gate_up), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ _f32(down)


def sparse_ffn(x, w, cfg, first):
    """`sum over chosen k whose expert is held of w_k E_k(x)` plus the
    shared expert; the held experts are `first .. first + held - 1`."""
    idx, wt = router(x, w["router"], cfg)
    y = _expert(x, w["shared_gate_up"], w["shared_down"])
    for e in range(w["experts_gate_up"].shape[0]):
        mine = jnp.where(idx == first + e, wt, 0.0).sum(-1)      # 0 if not chosen
        y = y + mine[:, None] * _expert(x, w["experts_gate_up"][e], w["experts_down"][e])
    return y


def layer_forward(x, w, cfg, first):
    """One layer on one sequence: `x` `[T, H]` float32, `w` the layer's
    weights (one index of a stacked tree; sparse if it has a router)."""
    eps, H = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    dn, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    T = x.shape[0]
    freqs = jnp.asarray(inv_freq(cfg["qk_rope_head_dim"], cfg["rope_theta"],
                                 cfg["rope_scaling"]))
    a = _rms(x, w["input_norm"], eps)
    c_q = _rms(a @ _f32(w["q_a_proj"]), w["q_a_norm"], eps)
    q = (c_q @ _f32(w["q_b_proj"])).reshape(T, H, -1)
    kv = a @ _f32(w["kv_a_proj"])
    c_kv = _rms(kv[:, :rank], w["kv_a_norm"], eps)
    out = attention(q[..., :dn], rope(q[..., dn:], freqs), c_kv, rope(kv[:, rank:], freqs),
                    w["k_b_proj"], w["v_b_proj"], softmax_scale(cfg))
    x = x + out.reshape(T, -1) @ _f32(w["o_proj"])
    b = _rms(x, w["post_norm"], eps)
    if "router" in w:
        return x + sparse_ffn(b, w, cfg, first)
    return x + _expert(b, w["gate_up_proj"], w["down_proj"])


def _key(cfg):
    scaling = tuple(sorted(cfg["rope_scaling"].items()))
    return tuple((k, cfg[k]) for k in CFG_KEYS) + (("rope_scaling", scaling),)


@functools.partial(jax.jit, static_argnames=("key", "first"))
def _layer(x, w, key, first):
    cfg = dict(key)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    with jax.default_matmul_precision("highest"):
        return layer_forward(x, w, cfg, first)


def first_held(weights, share) -> int:
    return int(share[0]) * weights["sparse"]["experts_gate_up"].shape[1]


def hidden_states(weights, ids, cfg, share=(0, 1)):
    """`ids` `[T]` -> the last layer's output `[T, H]` (before the final
    norm), one layer at a time: one compiled program a kind of layer."""
    key, first = _key(cfg), first_held(weights, share)
    x = _f32(weights["embed"][ids])
    for kind in ("dense", "sparse"):
        stack = weights[kind]
        for i in range(stack["input_norm"].shape[0]):
            x = _layer(x, {name: a[i] for name, a in stack.items()}, key, first)
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


def forward_logits(weights, ids, cfg, share=(0, 1)):
    """Every position's logits for one sequence `[T]` -> `[T, V]`."""
    return logits_at(weights, hidden_states(weights, ids, cfg, share), cfg["rms_norm_eps"])
