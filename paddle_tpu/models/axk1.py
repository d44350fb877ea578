"""A.X-K1 (SK Telecom, 2026-01, ``model_type: axk1``): a decoder-only LM with
multi-head latent attention and sparse experts, the DeepSeek-V3 skeleton at
its own sizes.

- **Attention** is latent (``nn/functional/latent_attention.py``): a
  low-rank query (``q_a`` 7168 -> 1536, RMSNorm, ``q_b`` -> 64 heads of
  ``[q_nope 128 | q_rope 64]``), one latent row a token (``kv_a`` 7168 ->
  ``[c_kv 512 | k_rope 64]``, RMSNorm on ``c_kv``), per-head keys and values
  from ``c_kv`` (``kv_b``), YaRN RoPE on the 64 rotary columns, one
  ``k_rope`` for all heads.
- **The first** ``first_k_dense_replace`` **layers** have a dense SwiGLU of
  ``intermediate_size``; **every other layer** a router over
  ``n_routed_experts`` experts of ``moe_intermediate_size`` (sigmoid scores,
  ``n_group`` groups of which ``topk_group`` stay, ``num_experts_per_tok``
  chosen, weights normalised and times ``routed_scaling_factor``) plus
  ``n_shared_experts`` shared ones (``nn/functional/sparse_experts.py``).
- RMSNorm before each half, no biases, an untied head.

**The share.** ``AXK1ForCausalLM(config, expert_share=(r, R))`` holds the
routed experts ``[r x E / R, (r + 1) x E / R)`` of every sparse layer, as
one of ``R`` processes that share each layer by expert parallelism would.
The router keeps all ``E`` outputs and the weights are normalised over all
the chosen; the layer returns what ITS experts give plus the shared expert,
and that partial result goes on to the next layer. Nothing stands in for
the other processes or their exchange. ``vocab_slice=(lo, hi)`` holds the
rows ``lo .. hi - 1`` of the vocabulary: a sliced vocabulary is a smaller
one, token ids count from ``lo`` and the logits are over the slice.

*Assumed*, where ``config.json`` leaves a choice (named again in
``benchmark/reference_axk1.py`` and the benchmark's configuration file):
``topk_method: "none"`` beside ``n_group``/``topk_group`` is read as the
group-limited top-k those two keys state, with no score-correction bias;
the rotary convention is rotate-halves; ``kv_b_proj``'s columns are kept as
two matrices, keys' and values' (``k_b_proj``, ``v_b_proj``).

The layers' weights are STACKED on a leading axis, the dense layers in one
stack and the sparse ones in another (``AXK1Stack``), as
``models/brumby.py`` stacks its own: the serving programs scan over that
axis and share the arrays without a copy.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

from ..base import dtype as dtype_mod
from ..core.dispatch import primitive
from ..core.tensor import Parameter
from ..nn import functional as F
from ..nn.functional import latent_attention as la
from ..nn.functional import sparse_experts as se
from ..nn.layer.layers import Layer
from ..ops import manipulation
from ..ops.math import matmul
from .brumby import _drawn_normal, _pieces

__all__ = ["AXK1Config", "AXK1Stack", "AXK1Model", "AXK1ForCausalLM",
           "axk1_tiny"]


def _yarn():
    return {"type": "yarn", "factor": 32, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class AXK1Config:
    """The source's own key names (``config.json`` of skt/A.X-K1);
    ``dtype`` and ``initializer_layers`` are this program's (the depth the
    output projections' initialiser divides by: a stage cut out of a
    deeper model gives the model's own; None is ``num_hidden_layers``)."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "none"
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = field(default_factory=_yarn)
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    model_type: str = "axk1"
    dtype: str = "float32"         # the dtype the weights are DRAWN in
    initializer_layers: Optional[int] = None

    def __post_init__(self):
        if (self.attention_bias or self.tie_word_embeddings
                or self.hidden_act != "silu" or self.scoring_func != "sigmoid"):
            raise ValueError("A.X-K1 has no projection bias, an untied head, a "
                             "silu gate and sigmoid router scores; this model "
                             "builds nothing else")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A token's cache row in a layer: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return la.softmax_scale(self.qk_head_dim, self.rope_scaling)

    def inv_freq(self):
        return la.yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                                self.rope_scaling)


class AXK1Stack(Layer):
    """``layers`` layers of one kind, their weights stacked: ``[layers, in,
    out]`` matrices (the framework's ``Linear`` layout), ``[layers, width]``
    norms. ``sparse`` layers hold a router over all the model's experts, the
    shared expert and the routed experts ``first .. first + held - 1``;
    dense ones a SwiGLU of ``intermediate_size``."""

    def __init__(self, config: AXK1Config, layers: int, sparse: bool,
                 first: int = 0, held: Optional[int] = None):
        super().__init__()
        c = self.config = config
        self.sparse, self.first = bool(sparse), int(first)
        self.held = int(c.n_routed_experts if held is None else held)
        L, h, H = int(layers), c.hidden_size, c.num_attention_heads
        self.n_layers = L
        dt = dtype_mod.np_dtype(c.dtype)
        std = c.initializer_range
        out_std = std / math.sqrt(2.0 * (c.initializer_layers or c.num_hidden_layers))
        add = self.add_parameter

        def ones(*shape):
            return Parameter(jnp.ones(shape, dt))

        def drawn(name, shape, s=std, dtype=dt):
            return add(name, _drawn_normal(shape, s, dtype, L))

        f = c.moe_intermediate_size
        if sparse:
            # the largest first: each is drawn a layer at a time and joined,
            # which holds it twice for a moment
            self.experts_gate_up = drawn("experts_gate_up", (L, self.held, h, 2 * f))
            self.experts_down = drawn("experts_down", (L, self.held, f, h), out_std)
            self.shared_gate_up = drawn("shared_gate_up", (L, h, 2 * f * c.n_shared_experts))
            self.shared_down = drawn("shared_down", (L, f * c.n_shared_experts, h), out_std)
            self.router = drawn("router", (L, h, c.n_routed_experts), dtype=jnp.float32)
        else:
            self.gate_up_proj = drawn("gate_up_proj", (L, h, 2 * c.intermediate_size))
            self.down_proj = drawn("down_proj", (L, c.intermediate_size, h), out_std)
        self.input_norm = add("input_norm", ones(L, h))
        self.q_a_proj = drawn("q_a_proj", (L, h, c.q_lora_rank))
        self.q_a_norm = add("q_a_norm", ones(L, c.q_lora_rank))
        # 64 heads of [q_nope | q_rope]
        self.q_b_proj = drawn("q_b_proj", (L, c.q_lora_rank, H * c.qk_head_dim))
        # [c_kv | k_rope]
        self.kv_a_proj = drawn("kv_a_proj", (L, h, c.latent_width))
        self.kv_a_norm = add("kv_a_norm", ones(L, c.kv_lora_rank))
        # kv_b_proj's columns, the keys' and the values' apart
        self.k_b_proj = drawn("k_b_proj", (L, c.kv_lora_rank, H * c.qk_nope_head_dim))
        self.v_b_proj = drawn("v_b_proj", (L, c.kv_lora_rank, H * c.v_head_dim))
        self.o_proj = drawn("o_proj", (L, H * c.v_head_dim, h), out_std)
        self.post_norm = add("post_norm", ones(L, h))

    def _ffn(self, b, i: int):
        from ..ops.activation import swiglu

        c = self.config
        if not self.sparse:
            return matmul(swiglu(matmul(b, self.gate_up_proj[i])), self.down_proj[i])
        shared = matmul(swiglu(matmul(b, self.shared_gate_up[i])), self.shared_down[i])

        def routed(x, router, gate_up, down):
            flat = x.reshape(-1, x.shape[-1])
            idx, w = se.route(flat, router, n_group=c.n_group, topk_group=c.topk_group,
                              top_k=c.num_experts_per_tok,
                              scaling=c.routed_scaling_factor,
                              norm_topk=c.norm_topk_prob)
            y, _ = se.held_experts(flat, idx, w, gate_up, down, first=self.first,
                                   held=self.held)
            return y.reshape(x.shape)

        return shared + primitive("sparse_experts", routed, [
            b, self.router[i], self.experts_gate_up[i], self.experts_down[i]])

    def layer(self, x, i: int):
        c = self.config
        b, t, H = x.shape[0], x.shape[1], c.num_attention_heads
        eps, rank = c.rms_norm_eps, c.kv_lora_rank
        a = F.rms_norm(x, self.input_norm[i], eps)
        c_q = F.rms_norm(matmul(a, self.q_a_proj[i]), self.q_a_norm[i], eps)
        q = manipulation.reshape(matmul(c_q, self.q_b_proj[i]), [b, t, H, c.qk_head_dim])
        kv = matmul(a, self.kv_a_proj[i])
        c_kv = F.rms_norm(kv[:, :, :rank], self.kv_a_norm[i], eps)
        y = la.latent_attention(q, c_kv, kv[:, :, rank:], self.k_b_proj[i],
                                self.v_b_proj[i], nope=c.qk_nope_head_dim,
                                inv_freq=c.inv_freq(), scale=c.softmax_scale)
        x = x + matmul(y, self.o_proj[i])
        return x + self._ffn(F.rms_norm(x, self.post_norm[i], eps), i)

    def forward(self, x):
        for i in range(self.n_layers):
            x = self.layer(x, i)
        return x


class AXK1Model(Layer):
    def __init__(self, config: AXK1Config, expert_share: Tuple[int, int] = (0, 1),
                 vocab_slice: Optional[Tuple[int, int]] = None):
        super().__init__()
        self.config = config
        r, R = (int(v) for v in expert_share)
        if not 0 <= r < R or config.n_routed_experts % R:
            raise ValueError(f"expert_share {expert_share}: need 0 <= r < R and R "
                             f"dividing the {config.n_routed_experts} routed experts")
        held = config.n_routed_experts // R
        lo, hi = vocab_slice or (0, config.vocab_size)
        self.vocab_rows = int(hi) - int(lo)
        dt = dtype_mod.np_dtype(config.dtype)
        k = config.first_k_dense_replace
        L = config.num_hidden_layers
        if not 0 < k < L:
            raise ValueError("this model builds leading dense layers and sparse "
                             "layers after them: 0 < first_k_dense_replace < layers")
        self.sparse = AXK1Stack(config, L - k, True, first=r * held, held=held)
        self.dense = AXK1Stack(config, k, False)
        self.embed_tokens = self.add_parameter("embed_tokens", _drawn_normal(
            (self.vocab_rows, config.hidden_size), config.initializer_range,
            dt, _pieces(self.vocab_rows)))
        self.norm = self.add_parameter(
            "norm", Parameter(jnp.ones((config.hidden_size,), dt)))

    def forward(self, input_ids):
        x = self.sparse(self.dense(F.embedding(input_ids, self.embed_tokens)))
        return F.rms_norm(x, self.norm, self.config.rms_norm_eps)


class AXK1ForCausalLM(Layer):
    #: what ``serving.DecodeEngine`` holds of a sequence: latent rows in
    #: pages, one row a token a layer, K and V at once
    serving_residency = "latent"

    def __init__(self, config: AXK1Config, expert_share: Tuple[int, int] = (0, 1),
                 vocab_slice: Optional[Tuple[int, int]] = None):
        super().__init__()
        self.config = config
        self.expert_share = tuple(int(v) for v in expert_share)
        self.axk1 = AXK1Model(config, expert_share, vocab_slice)
        self.lm_head = self.add_parameter("lm_head", _drawn_normal(
            (config.hidden_size, self.axk1.vocab_rows), config.initializer_range,
            dtype_mod.np_dtype(config.dtype), _pieces(config.hidden_size)))

    def forward(self, input_ids):
        return matmul(self.axk1(input_ids), self.lm_head)


def axk1_tiny(**overrides) -> AXK1Config:
    """Test scale: one dense layer and two sparse ones, 16 experts in 4
    groups of which 2 stay, 4 a token, a rotary part that YaRN bends."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
                num_experts_per_tok=4, n_group=4, topk_group=2,
                max_position_embeddings=512,
                rope_scaling=dict(_yarn(), factor=4,
                                  original_max_position_embeddings=64))
    base.update(overrides)
    return AXK1Config(**base)
