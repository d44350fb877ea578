"""Command A+ (Cohere, 2026-05, ``model_type: cohere2_moe``): a decoder-only
LM whose layers come in periods of three *window* layers and one *global*
layer, every layer a PARALLEL block over sparse experts.

- **The block**: ONE LayerNorm (no bias) feeds the attention AND the
  feed-forward, and both are added to the residual: ``x <- x + Attn(LN(x)) +
  FFN(LN(x))``.
- **Attention** is grouped-query: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``, no bias, no q/k norm. A
  ``sliding_attention`` layer rotates q and k (``rope_gptj``: interleaved
  pairs, all ``head_dim`` columns) and sees the last ``sliding_window`` keys,
  the query's own among them; a ``full_attention`` layer has NO positions at
  all and sees every key before it (``nn/functional/window_attention.py``).
- **The feed-forward** is ``num_experts`` routed SwiGLU experts of
  ``intermediate_size``, ``num_experts_per_tok`` a token chosen by sigmoid
  scores (no groups, no score-correction bias, weights normalised over the
  chosen), plus ``num_shared_experts`` shared ones whose outputs are
  AVERAGED and added (``nn/functional/sparse_experts.py``). The shared
  experts are held as one SwiGLU of ``num_shared_experts`` times the width
  whose output is divided by their number: the same sum.
- Tied embeddings, a final LayerNorm, ``logit_scale``.

**The share.** ``Cohere2MoEForCausalLM(config, expert_share=(r, R))`` holds
the routed experts ``[r x E / R, (r + 1) x E / R)`` of every layer, as one of
``R`` processes that share each layer by expert parallelism would; the
router keeps all ``E`` outputs and the weights are normalised over all the
chosen. ``vocab_slice=(lo, hi)`` holds those rows of the vocabulary
(``models/axk1.py`` has the same two cuts and says what they mean).

*Assumed*, where ``config.json`` leaves a choice (named again in
``benchmark/reference_cohere2_moe.py`` and the benchmark's configuration
file): ``shared_expert_combination_strategy: "average"`` is the mean of the
shared experts' outputs, added to the routed sum; ``intermediate_size`` is
the width of ONE expert, routed or shared; ``first_k_dense_replace`` 0 means
that no layer is dense and the ``prefix_dense_*`` keys are unused; the
window's edge is Hugging Face's ``kv_idx > q_idx - sliding_window``. The
vision tower the release lists is not built: this is the language model.

Each layer is a :class:`Cohere2MoEBlock` with its own parameters (nothing is
stacked: the layers are of two kinds over two cache pools, and the serving
programs unroll them).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax.numpy as jnp

from ..base import dtype as dtype_mod
from ..core.dispatch import primitive
from ..core.tensor import Parameter
from ..nn import functional as F
from ..nn.functional import sparse_experts as se
from ..nn.functional import window_attention as wa
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..ops import manipulation
from ..ops.math import matmul
from .brumby import _drawn_normal, _pieces

__all__ = ["Cohere2MoEConfig", "Cohere2MoEBlock", "Cohere2MoEModel",
           "Cohere2MoEForCausalLM", "cohere2_moe_tiny"]

WINDOW, GLOBAL = "sliding_attention", "full_attention"


@dataclass
class Cohere2MoEConfig:
    """The source's own key names (``config.json`` of
    CohereLabs/command-a-plus-05-2026); ``dtype`` and ``initializer_layers``
    are this program's (``models/axk1.py:AXK1Config`` says what they are).
    ``layer_types`` None is the published pattern, ``layer_switch - 1``
    window layers and a global one, repeated."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Optional[List[str]] = None
    layer_switch: int = 4
    sliding_window: int = 4096
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    expert_selection_fn: str = "sigmoid"
    shared_expert_combination_strategy: str = "average"
    first_k_dense_replace: int = 0
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rotary_pct: float = 1.0
    position_embedding_type: str = "rope_gptj"
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    tie_word_embeddings: bool = True
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    use_gated_activation: bool = True
    attention_bias: bool = False
    hidden_act: str = "silu"
    initializer_range: float = 0.02
    model_type: str = "cohere2_moe"
    dtype: str = "float32"         # the dtype the weights are DRAWN in
    initializer_layers: Optional[int] = None
    # the tied embedding's own spread (None: ``initializer_range``). Drawn as
    # wide as the matrices, the head gives the INPUT token a logit of
    # ``hidden x range^2 / spread of the residual`` (16 at the published
    # sizes against a spread of 1.3 for every other word): seeded weights
    # then repeat their input whatever the layers compute
    embedding_initializer_range: Optional[float] = None

    def __post_init__(self):
        if self.layer_types is None:
            period = [WINDOW] * (self.layer_switch - 1) + [GLOBAL]
            self.layer_types = (period * self.num_hidden_layers)[:self.num_hidden_layers]
        self.layer_types = list(self.layer_types)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {WINDOW, GLOBAL}):
            raise ValueError(f"layer_types names {self.num_hidden_layers} layers, "
                             f"each {WINDOW!r} or {GLOBAL!r}")
        if (self.attention_bias or self.use_qk_norm or not self.tie_word_embeddings
                or not self.use_parallel_block or not self.use_gated_activation
                or self.hidden_act != "silu" or self.expert_selection_fn != "sigmoid"
                or self.shared_expert_combination_strategy != "average"
                or self.first_k_dense_replace or self.rotary_pct != 1
                or self.position_embedding_type != "rope_gptj"):
            raise ValueError(
                "Command A+ has a parallel block, tied embeddings, no projection "
                "bias, no q/k norm, gated silu experts chosen by sigmoid scores, "
                "shared experts averaged, no leading dense layer and GPT-J "
                "rotary on every column of a window layer's head; this model "
                "builds nothing else")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")

    def window_of(self, layer: int) -> Optional[int]:
        """The layer's window, None for a global layer."""
        return self.sliding_window if self.layer_types[layer] == WINDOW else None


class Cohere2MoEBlock(Layer):
    """One layer. Matrices are ``[in, out]`` (the framework's ``Linear``
    layout); the routed experts ``first .. first + held - 1`` are ``[held,
    hidden, 2 x width]`` (``[W_g | W_u]``) and ``[held, width, hidden]``; the
    shared experts one SwiGLU ``[hidden, 2 x S x width]``, ``[S x width,
    hidden]``; the router float32 over all ``num_experts``."""

    def __init__(self, config: Cohere2MoEConfig, layer: int, first: int, held: int):
        super().__init__()
        c = self.config = config
        self.window = c.window_of(layer)
        self.first, self.held = int(first), int(held)
        h, d = c.hidden_size, c.head_dim
        H, G, f, S = (c.num_attention_heads, c.num_key_value_heads,
                      c.intermediate_size, c.num_shared_experts)
        dt = dtype_mod.np_dtype(c.dtype)
        std = c.initializer_range
        out_std = std / math.sqrt(2.0 * (c.initializer_layers or c.num_hidden_layers))

        def drawn(name, shape, s=std, dtype=dt, pieces=None):
            return self.add_parameter(name, _drawn_normal(
                shape, s, dtype, pieces or _pieces(shape[0])))

        # the largest first: each is drawn in pieces and joined, which holds
        # it twice for a moment
        self.experts_gate_up = drawn("experts_gate_up", (held, h, 2 * f), pieces=held)
        self.experts_down = drawn("experts_down", (held, f, h), out_std, pieces=held)
        self.shared_gate_up = drawn("shared_gate_up", (h, 2 * S * f))
        self.shared_down = drawn("shared_down", (S * f, h), out_std)
        self.router = drawn("router", (h, c.num_experts), dtype=jnp.float32)
        self.ln = self.add_parameter("ln", Parameter(jnp.ones((h,), dt)))
        self.q_proj = drawn("q_proj", (h, H * d))
        self.k_proj = drawn("k_proj", (h, G * d))
        self.v_proj = drawn("v_proj", (h, G * d))
        self.o_proj = drawn("o_proj", (H * d, h), out_std)

    def _ffn(self, n):
        from ..ops.activation import swiglu

        c = self.config
        shared = matmul(swiglu(matmul(n, self.shared_gate_up)), self.shared_down)

        def routed(x, router, gate_up, down):
            flat = x.reshape(-1, x.shape[-1])
            idx, w = se.route(flat, router, n_group=1, topk_group=1,
                              top_k=c.num_experts_per_tok, scaling=1.0,
                              norm_topk=c.norm_topk_prob, group_limited=False)
            y, _ = se.held_experts(flat, idx, w, gate_up, down, first=self.first,
                                   held=self.held)
            return y.reshape(x.shape)

        return shared * (1.0 / c.num_shared_experts) + primitive(
            "sparse_experts", routed,
            [n, self.router, self.experts_gate_up, self.experts_down])

    def forward(self, x):
        c = self.config
        b, t, d = x.shape[0], x.shape[1], c.head_dim
        n = F.layer_norm(x, [c.hidden_size], weight=self.ln,
                         epsilon=c.layer_norm_eps)
        q = manipulation.reshape(matmul(n, self.q_proj), [b, t, c.num_attention_heads, d])
        k = manipulation.reshape(matmul(n, self.k_proj), [b, t, c.num_key_value_heads, d])
        v = manipulation.reshape(matmul(n, self.v_proj), [b, t, c.num_key_value_heads, d])
        y = wa.windowed_attention(
            q, k, v, window=self.window,
            theta=c.rope_theta if self.window is not None else None)
        return x + matmul(y, self.o_proj) + self._ffn(n)


class Cohere2MoEModel(Layer):
    def __init__(self, config: Cohere2MoEConfig,
                 expert_share: Tuple[int, int] = (0, 1),
                 vocab_slice: Optional[Tuple[int, int]] = None):
        super().__init__()
        self.config = config
        r, R = (int(v) for v in expert_share)
        if not 0 <= r < R or config.num_experts % R:
            raise ValueError(f"expert_share {expert_share}: need 0 <= r < R and R "
                             f"dividing the {config.num_experts} routed experts")
        held = config.num_experts // R
        lo, hi = vocab_slice or (0, config.vocab_size)
        self.vocab_rows = int(hi) - int(lo)
        dt = dtype_mod.np_dtype(config.dtype)
        self.layers = LayerList([
            Cohere2MoEBlock(config, i, first=r * held, held=held)
            for i in range(config.num_hidden_layers)])
        self.embed_tokens = self.add_parameter("embed_tokens", _drawn_normal(
            (self.vocab_rows, config.hidden_size),
            config.embedding_initializer_range or config.initializer_range,
            dt, _pieces(self.vocab_rows)))
        self.norm = self.add_parameter(
            "norm", Parameter(jnp.ones((config.hidden_size,), dt)))

    def forward(self, input_ids):
        x = F.embedding(input_ids, self.embed_tokens)
        for block in self.layers:
            x = block(x)
        return F.layer_norm(x, [self.config.hidden_size], weight=self.norm,
                            epsilon=self.config.layer_norm_eps)


class Cohere2MoEForCausalLM(Layer):
    #: what ``serving.DecodeEngine`` holds of a sequence: K/V pages of two
    #: lifetimes, the window layers' and the global layers'
    serving_residency = "windowed"

    def __init__(self, config: Cohere2MoEConfig,
                 expert_share: Tuple[int, int] = (0, 1),
                 vocab_slice: Optional[Tuple[int, int]] = None):
        super().__init__()
        self.config = config
        self.expert_share = tuple(int(v) for v in expert_share)
        self.model = Cohere2MoEModel(config, expert_share, vocab_slice)

    def forward(self, input_ids):
        logits = matmul(self.model(input_ids), self.model.embed_tokens,
                        transpose_y=True)
        scale = self.config.logit_scale
        return logits if scale == 1 else logits * scale


def cohere2_moe_tiny(**overrides) -> Cohere2MoEConfig:
    """Test scale: one period (three window layers of 16 keys, one global),
    8 query heads on 2 K/V heads, 16 experts of which 4 a token, 2 shared."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                num_hidden_layers=4, num_attention_heads=8,
                num_key_value_heads=2, head_dim=16, sliding_window=16,
                num_experts=16, num_experts_per_tok=4, num_shared_experts=2,
                max_position_embeddings=512)
    base.update(overrides)
    return Cohere2MoEConfig(**base)
