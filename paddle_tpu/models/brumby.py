"""Brumby: a decoder-only LM whose every layer is a power-retention layer
(Manifest AI, Brumby-14B-Base, 2025-10; the mechanism is Buckman, Gelada et
al., arXiv:2507.04239, degree 2). No attention layer and no K/V cache: a
layer's memory of the sequence is a state of constant size per key/value
head (``nn/functional/power_retention.py``).

The skeleton is the Qwen3 one the checkpoint was retrained from: RMSNorm
before each half, grouped query/key/value projections without bias,
RMSNorm over each head's 128 of q and k, RoPE on all of them, SwiGLU, an
untied head. What ``config.json`` has no key for is *assumed* and marked
so: one gate per key/value head, ``log g = log sigmoid(a W_g + b_g)``,
and its offset ``b_g``, drawn so that the heads' memories span tens to
thousands of tokens (a seeded ``W_g`` alone gives ``g`` near one half, a
memory of two tokens, which no deployment has and no check could tell
from a lost state).

The layers' weights are STACKED on a leading axis (``BrumbyLayers``): one
parameter ``[layers, ...]`` per matrix. The serving programs scan over
that axis (``serving/decode.py``), so one layer is compiled once and the
engine shares the arrays with the model without a copy; a pipeline stage
is a slice of it. The forward here walks the axis with the framework's
own ops: ``F.rms_norm`` (``nn.RMSNorm``'s function),
``fused_rotary_position_embedding`` and ``swiglu`` over one fused
``[gate | up]`` projection, as ``models/llama.py`` has them.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..base import dtype as dtype_mod
from ..base import global_state
from ..core.dispatch import primitive
from ..core.tensor import Parameter
from ..nn import functional as F
from ..nn.functional.power_retention import power_retention
from ..nn.layer.layers import Layer
from ..ops import manipulation
from ..ops.math import matmul

__all__ = ["BrumbyConfig", "BrumbyLayers", "BrumbyModel", "BrumbyForCausalLM",
           "brumby_tiny"]


@dataclass
class BrumbyConfig:
    """The source's own key names (``config.json`` of
    manifestai/Brumby-14B-Base); ``retention_chunk`` and ``dtype`` are
    this program's."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    model_type: str = "brumby"
    retention_chunk: int = 128     # the Layer path's chunk; serving has its own
    dtype: str = "float32"         # the dtype the weights are DRAWN in

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")
        if self.attention_bias or self.tie_word_embeddings or self.hidden_act != "silu":
            raise ValueError("Brumby has no projection bias, an untied head and "
                             "a silu gate; this model builds nothing else")


def _pieces(n: int) -> int:
    return 8 if n % 8 == 0 else 1


def gate_offsets(num_kv_heads: int) -> np.ndarray:
    """*Assumed.* ``b_g`` of one layer: ``sigmoid(b)`` remembers ``1 / (1 -
    g) = 1 + e^b`` tokens; the heads span 16 to 4096, evenly in the log."""
    horizons = np.geomspace(16.0, 4096.0, num_kv_heads)
    return np.log(horizons - 1.0).astype(np.float32)


def _drawn_normal(shape, std: float, dtype, pieces: int) -> Parameter:
    """A parameter drawn on the device in ``pieces`` slabs along its first
    axis, in ``dtype``: the largest is 2.9 GB in bfloat16 at the published
    widths, and a float32 draw of it whole (or a host copy) would not fit
    beside the rest. A stacked ``[layers, ...]`` parameter is drawn a
    layer at a time."""
    slab = (shape[0] // pieces,) + tuple(shape[1:])
    keys = [global_state.default_generator.split() for _ in range(pieces)]
    draw = jax.jit(lambda key: (std * jax.random.normal(key, slab)).astype(dtype))
    return Parameter(jnp.concatenate([draw(k) for k in keys]))


class BrumbyLayers(Layer):
    """Every layer's weights, stacked: ``[layers, in, out]`` matrices (the
    framework's ``Linear`` layout), ``[layers, width]`` norms."""

    def __init__(self, config: BrumbyConfig):
        super().__init__()
        self.config = config
        c = config
        L, h, d, f = c.num_hidden_layers, c.hidden_size, c.head_dim, c.intermediate_size
        hq, hkv = c.num_attention_heads, c.num_key_value_heads
        dt = dtype_mod.np_dtype(c.dtype)
        std, out_std = c.initializer_range, c.initializer_range / math.sqrt(2.0 * L)

        add = self.add_parameter

        def ones(*shape):
            return Parameter(jnp.ones(shape, dt))

        self.input_norm = add("input_norm", ones(L, h))
        # [q | k | v] in one projection, as models/llama.py packs them
        self.qkv_proj = add("qkv_proj", _drawn_normal(
            (L, h, (hq + 2 * hkv) * d), std, dt, L))
        self.g_proj = add("g_proj", _drawn_normal((L, h, hkv), std, dt, L))
        self.g_bias = add("g_bias", Parameter(jnp.asarray(
            np.tile(gate_offsets(hkv), (L, 1)), jnp.float32)))
        self.q_norm = add("q_norm", ones(L, d))
        self.k_norm = add("k_norm", ones(L, d))
        self.o_proj = add("o_proj", _drawn_normal((L, hq * d, h), out_std, dt, L))
        self.post_norm = add("post_norm", ones(L, h))
        self.gate_up_proj = add("gate_up_proj", _drawn_normal((L, h, 2 * f), std, dt, L))
        self.down_proj = add("down_proj", _drawn_normal((L, f, h), out_std, dt, L))

    def layer(self, x, i: int):
        from ..ops.activation import swiglu
        from ..ops.fused_ops import fused_rotary_position_embedding

        c = self.config
        b, t = x.shape[0], x.shape[1]
        d, hq, hkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
        a = F.rms_norm(x, self.input_norm[i], c.rms_norm_eps)
        qkv = matmul(a, self.qkv_proj[i])
        q = manipulation.reshape(qkv[:, :, : hq * d], [b, t, hq, d])
        k = manipulation.reshape(qkv[:, :, hq * d: (hq + hkv) * d], [b, t, hkv, d])
        v = manipulation.reshape(qkv[:, :, (hq + hkv) * d:], [b, t, hkv, d])
        log_g = primitive(
            "log_sigmoid",
            lambda s, bias: jax.nn.log_sigmoid(s.astype(jnp.float32) + bias),
            [matmul(a, self.g_proj[i]), self.g_bias[i]])
        q = F.rms_norm(q, self.q_norm[i], c.rms_norm_eps)
        k = F.rms_norm(k, self.k_norm[i], c.rms_norm_eps)
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, rotary_emb_base=c.rope_theta, use_neox_rotary_style=True)
        y = power_retention(q, k, v, log_g, chunk=c.retention_chunk)
        x = x + matmul(manipulation.reshape(y, [b, t, hq * d]), self.o_proj[i])
        h = F.rms_norm(x, self.post_norm[i], c.rms_norm_eps)
        return x + matmul(swiglu(matmul(h, self.gate_up_proj[i])), self.down_proj[i])

    def forward(self, x):
        for i in range(self.config.num_hidden_layers):
            x = self.layer(x, i)
        return x


class BrumbyModel(Layer):
    def __init__(self, config: BrumbyConfig):
        super().__init__()
        self.config = config
        dt = dtype_mod.np_dtype(config.dtype)
        self.embed_tokens = self.add_parameter("embed_tokens", _drawn_normal(
            (config.vocab_size, config.hidden_size), config.initializer_range,
            dt, _pieces(config.vocab_size)))
        self.layers = BrumbyLayers(config)
        self.norm = self.add_parameter(
            "norm", Parameter(jnp.ones((config.hidden_size,), dt)))

    def forward(self, input_ids):
        x = self.layers(F.embedding(input_ids, self.embed_tokens))
        return F.rms_norm(x, self.norm, self.config.rms_norm_eps)


class BrumbyForCausalLM(Layer):
    #: what ``serving.DecodeEngine`` holds a lane of: a recurrent state,
    #: not keys and values
    serving_residency = "state"

    def __init__(self, config: BrumbyConfig):
        super().__init__()
        self.config = config
        self.brumby = BrumbyModel(config)
        self.lm_head = self.add_parameter("lm_head", _drawn_normal(
            (config.hidden_size, config.vocab_size), config.initializer_range,
            dtype_mod.np_dtype(config.dtype), _pieces(config.hidden_size)))

    def forward(self, input_ids):
        return matmul(self.brumby(input_ids), self.lm_head)


def brumby_tiny(**overrides) -> BrumbyConfig:
    """Test scale: grouped heads exercised, two layers."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16,
                max_position_embeddings=256, retention_chunk=8)
    base.update(overrides)
    return BrumbyConfig(**base)

