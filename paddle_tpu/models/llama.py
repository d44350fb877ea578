"""LLaMA decoder-only LM family — the long-context flagship.

Reference capability: the PaddleNLP/fleet LLaMA pretrain path exercised by
the reference's hybrid-parallel stack (BASELINE.md row "LLaMA-2-7B pretrain
throughput"), built from the same mpu layers as GPT
(fleet/layers/mpu/mp_layers.py) plus rotary embeddings
(paddle/phi/kernels/fusion/gpu/fused_rope_*), RMSNorm and SwiGLU
(fused_ops.yaml: fused_rms_norm / swiglu).

TPU-native design mirrors models/gpt.py and adds:
- RMSNorm via the Pallas rms_norm kernel path (nn.RMSNorm),
- rotary position embeddings via ops.fused_ops.fused_rotary_position_embedding
  (one traced composite; XLA fuses the rotate-halves chain),
- SwiGLU MLP (gate/up column-parallel in ONE fused projection, down
  row-parallel — same collective count as GPT's MLP),
- grouped-query attention: num_key_value_heads < num_attention_heads stores
  KV once per group; heads stay the sharded dim under mp,
- the same sequence/context/pipeline parallel switches as GPTConfig.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.layers import Layer
from ..ops import creation, manipulation
from .gpt import shift_labels


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 0  # 0 → MHA (= num_attention_heads)
    intermediate_size: int = 0  # 0 → LLaMA's 8/3 rule rounded to 256
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    hidden_dropout_prob: float = 0.0
    attention_dropout_prob: float = 0.0
    tensor_parallel: bool = False
    sequence_parallel: bool = False
    use_flash_attention: bool = True
    context_parallel: str = ""  # "", "ring", "ulysses"
    pipeline_parallel: bool = False
    virtual_pp_degree: int = 1
    pp_num_microbatches: int = 0

    def __post_init__(self):
        if self.num_key_value_heads == 0:
            self.num_key_value_heads = self.num_attention_heads
        if self.intermediate_size == 0:
            ffn = int(self.hidden_size * 8 / 3)
            self.intermediate_size = 256 * ((ffn + 255) // 256)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def _init_attr(config, scaled_layers: int = 0):
    std = config.initializer_range
    if scaled_layers:
        std = std / math.sqrt(2.0 * scaled_layers)
    return nn.ParamAttr(initializer=Normal(mean=0.0, std=std))


def _linear(config, n_in, n_out, *, column: bool, scaled: int = 0):
    if config.tensor_parallel:
        from ..distributed.fleet.mpu import ColumnParallelLinear, RowParallelLinear

        if column:
            return ColumnParallelLinear(n_in, n_out, weight_attr=_init_attr(config, scaled),
                                        has_bias=False, gather_output=False)
        return RowParallelLinear(n_in, n_out, weight_attr=_init_attr(config, scaled),
                                 has_bias=False, input_is_parallel=True)
    return nn.Linear(n_in, n_out, weight_attr=_init_attr(config, scaled), bias_attr=False)


class LlamaAttention(Layer):
    """GQA self-attention with rotary embeddings. Projections pack
    [q | k | v] in one column-parallel matmul (heads shard over mp); rope
    applies post-split through the fused composite."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, d = config.hidden_size, config.head_dim
        kv_out = config.num_key_value_heads * d
        self.qkv_proj = _linear(config, h, h + 2 * kv_out, column=True)
        self.out_proj = _linear(config, h, h, column=False,
                                scaled=config.num_hidden_layers)

    def forward(self, x):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        d = cfg.head_dim
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        qkv = self.qkv_proj(x)
        # local head counts under mp sharding
        total = qkv.shape[-1] // d
        hq = total * group // (group + 2)
        hkv = hq // group
        q = manipulation.reshape(qkv[:, :, : hq * d], [b, s, hq, d])
        k = manipulation.reshape(qkv[:, :, hq * d: (hq + hkv) * d], [b, s, hkv, d])
        v = manipulation.reshape(qkv[:, :, (hq + hkv) * d:], [b, s, hkv, d])

        from ..ops.fused_ops import fused_rotary_position_embedding

        q, k, _ = fused_rotary_position_embedding(
            q, k, None, rotary_emb_base=cfg.rope_theta,
            use_neox_rotary_style=True)

        if group > 1:
            # expand KV groups to full heads; XLA turns the repeat into a
            # broadcast feeding the attention matmul (no materialized copy)
            k = manipulation.repeat_interleave(k, group, axis=2)
            v = manipulation.repeat_interleave(v, group, axis=2)

        if cfg.context_parallel:
            from ..distributed.fleet.context_parallel import (
                ring_attention,
                ulysses_attention,
            )

            cp = ring_attention if cfg.context_parallel == "ring" else ulysses_attention
            out = cp(q, k, v, causal=True)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=cfg.attention_dropout_prob, training=self.training)
        out = manipulation.reshape(out, [b, s, hq * d])
        return self.out_proj(out)


class LlamaMLP(Layer):
    """SwiGLU MLP: one column-parallel [gate | up] projection, silu-gate,
    row-parallel down (reference swiglu fused op semantics)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, ffn = config.hidden_size, config.intermediate_size
        self.gate_up_proj = _linear(config, h, 2 * ffn, column=True)
        self.down_proj = _linear(config, ffn, h, column=False,
                                 scaled=config.num_hidden_layers)

    def forward(self, x):
        from ..ops.activation import swiglu

        return self.down_proj(swiglu(self.gate_up_proj(x)))


def _seq_constrain(x, config: LlamaConfig):
    if not config.sequence_parallel:
        return x
    from jax.sharding import PartitionSpec as P

    from ..distributed.fleet.mpu import _constrain

    return _constrain(x, P("dp", "mp", None))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x):
        cfg = self.config
        h = self.self_attn(self.input_layernorm(x))
        h = F.dropout(h, cfg.hidden_dropout_prob, training=self.training)
        x = _seq_constrain(x + h, cfg)
        h = self.mlp(self.post_attention_layernorm(x))
        h = F.dropout(h, cfg.hidden_dropout_prob, training=self.training)
        return _seq_constrain(x + h, cfg)


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        if config.tensor_parallel:
            from ..distributed.fleet.mpu import VocabParallelEmbedding

            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size, weight_attr=_init_attr(config))
        else:
            self.embed_tokens = nn.Embedding(
                config.vocab_size, config.hidden_size, weight_attr=_init_attr(config))
        if config.pipeline_parallel:
            from ..distributed.fleet.pipeline_schedules import PipelinedStack

            self.layers = PipelinedStack(
                lambda: LlamaDecoderLayer(config),
                num_layers=config.num_hidden_layers,
                num_chunks=max(config.virtual_pp_degree, 1),
                num_microbatches=config.pp_num_microbatches or None)
        else:
            self.layers = nn.LayerList(
                [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        x = _seq_constrain(x, self.config)
        if self.config.pipeline_parallel:
            x = self.layers(x)
        else:
            for block in self.layers:
                x = block(x)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = _linear(config, config.hidden_size, config.vocab_size,
                                   column=True)

    def forward(self, input_ids):
        x = self.llama(input_ids)
        if self.lm_head is not None:
            logits = self.lm_head(x)
            if self.config.tensor_parallel:
                from jax.sharding import PartitionSpec as P

                from ..distributed.fleet.mpu import _constrain

                logits = _constrain(logits, P("dp", None, None))
            return logits
        from ..ops.math import matmul

        w = self.llama.embed_tokens.weight
        return matmul(x, manipulation.transpose(w, [1, 0]))


class LlamaPretrainingCriterion(Layer):
    """Shifted causal-LM cross entropy (same contract as the GPT criterion)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config

    def forward(self, logits, labels):
        flat = manipulation.reshape(logits, [-1, self.config.vocab_size])
        return F.cross_entropy(
            flat, manipulation.reshape(shift_labels(labels), [-1]))


def llama_tiny(**overrides) -> LlamaConfig:
    """Test/CI scale with GQA exercised."""
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=128, max_position_embeddings=128)
    base.update(overrides)
    return LlamaConfig(**base)


def llama2_7b(**overrides) -> LlamaConfig:
    base = dict(vocab_size=32000, hidden_size=4096, num_hidden_layers=32,
                num_attention_heads=32, num_key_value_heads=32,
                intermediate_size=11008, max_position_embeddings=4096)
    base.update(overrides)
    return LlamaConfig(**base)


def llama3_8b(**overrides) -> LlamaConfig:
    base = dict(vocab_size=128256, hidden_size=4096, num_hidden_layers=32,
                num_attention_heads=32, num_key_value_heads=8,
                intermediate_size=14336, max_position_embeddings=8192,
                rope_theta=500000.0)
    base.update(overrides)
    return LlamaConfig(**base)
