"""GPT decoder-only LM — the hybrid-parallel flagship.

Reference capability: the Fleet GPT path (SURVEY.md §3.4) — a transformer LM
trained with dp+mp+pp+sharding over fleet/layers/mpu/mp_layers.py
(ColumnParallelLinear :336 / RowParallelLinear :543 / VocabParallelEmbedding
:49 / ParallelCrossEntropy :744) and nn/layer/transformer.py building blocks.

TPU-native design:
- `tensor_parallel=True` builds attention/MLP from the mpu layers, whose
  weights carry NamedShardings over the `mp` mesh axis; GSPMD inserts the
  identity/allreduce movements the reference hand-codes, and whole-step jit
  overlaps them with compute.
- attention runs through F.scaled_dot_product_attention → Pallas flash
  attention on TPU, XLA attention elsewhere ([B, S, H, D] layout — the
  TPU-friendly head-inner layout, no [B, H, S, D] transposes).
- `sequence_parallel=True` keeps activations sequence-sharded between blocks
  (Megatron-SP; reference fleet/utils/sequence_parallel_utils.py) via a
  sharding constraint instead of explicit scatter/gather ops.
- the whole model is a pytree of Parameters, so one `jit` over the train step
  compiles embedding→blocks→loss into a single XLA program.
- the forward runs under the regions of `base/regions.py` (`embed`, `ln`,
  `attn/qkv`, `attn/out`, `mlp`, `lm_head`, `loss`; the attention op adds
  `attn/layout` and `attn/core`, the optimizer `optimizer`). They are HLO
  metadata only; backward operations keep their forward region
  (`transpose(jvp(mlp))`), and a device trace names every operation's.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field


from ..base import regions
from ..base.regions import region
from ..nn import functional as F
from .. import nn
from ..nn.initializer import Constant, Normal
from ..nn.layer.layers import Layer
from ..ops import creation, manipulation


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 0  # 0 → 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    tensor_parallel: bool = False  # use mpu layers sharded over the mp axis
    sequence_parallel: bool = False  # keep activations seq-sharded between blocks
    use_flash_attention: bool = True
    # long-context: shard the sequence over the `sep` mesh axis and attend
    # via "ring" (ppermute blockwise) or "ulysses" (all_to_all head swap)
    context_parallel: str = ""
    # pipeline parallel: run decoder blocks as a PipelinedStack (SPMD 1F1B
    # rotation over the pp mesh axis; virtual_pp_degree>1 = interleaved VPP)
    pipeline_parallel: bool = False
    virtual_pp_degree: int = 1
    pp_num_microbatches: int = 0  # 0 → 2 * pp degree
    # "rotation" | "1f1b" | "eager_1f1b" | "zb" (ZB-H1) — see
    # fleet/pipeline_schedules.py
    pp_schedule: str = "rotation"

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide num_attention_heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def _init_attr(config, scaled_layers: int = 0):
    std = config.initializer_range
    if scaled_layers:
        std = std / math.sqrt(2.0 * scaled_layers)
    return nn.ParamAttr(initializer=Normal(mean=0.0, std=std))


class GPTAttention(Layer):
    """Causal self-attention (fused qkv projection → flash attention → output
    projection). TP: qkv is column-parallel (heads sharded over mp), output
    row-parallel — the Megatron split the reference builds in mp_layers.py."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        if config.tensor_parallel:
            from ..distributed.fleet.mpu import ColumnParallelLinear, RowParallelLinear

            self.qkv_proj = ColumnParallelLinear(h, 3 * h, weight_attr=_init_attr(config),
                                                 has_bias=True, gather_output=False)
            self.out_proj = RowParallelLinear(h, h, weight_attr=_init_attr(config, config.num_hidden_layers),
                                              has_bias=True, input_is_parallel=True)
        else:
            self.qkv_proj = nn.Linear(h, 3 * h, weight_attr=_init_attr(config))
            self.out_proj = nn.Linear(h, h, weight_attr=_init_attr(config, config.num_hidden_layers))

    def forward(self, x):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        with region(regions.ATTN_QKV):
            qkv = self.qkv_proj(x)
            # [B, S, 3H] -> [B, S, H_local, 3, D]; under mp the head dim is
            # sharded.
            heads = qkv.shape[-1] // (3 * cfg.head_dim)
            qkv = manipulation.reshape(qkv, [b, s, heads, 3, cfg.head_dim])
            q = qkv[:, :, :, 0, :]
            k = qkv[:, :, :, 1, :]
            v = qkv[:, :, :, 2, :]
        if cfg.context_parallel:
            from ..distributed.fleet.context_parallel import (
                ring_attention,
                ulysses_attention,
            )

            cp = ring_attention if cfg.context_parallel == "ring" else ulysses_attention
            with region(regions.ATTN_CORE):
                out = cp(q, k, v, causal=True)
        else:
            # names its own regions: attn/layout and attn/core
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=cfg.attention_dropout_prob, training=self.training,
            )
        with region(regions.ATTN_OUT):
            out = manipulation.reshape(out, [b, s, heads * cfg.head_dim])
            return self.out_proj(out)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, ffn = config.hidden_size, config.intermediate_size
        if config.tensor_parallel:
            from ..distributed.fleet.mpu import ColumnParallelLinear, RowParallelLinear

            self.fc1 = ColumnParallelLinear(h, ffn, weight_attr=_init_attr(config),
                                            has_bias=True, gather_output=False)
            self.fc2 = RowParallelLinear(ffn, h, weight_attr=_init_attr(config, config.num_hidden_layers),
                                         has_bias=True, input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(h, ffn, weight_attr=_init_attr(config))
            self.fc2 = nn.Linear(ffn, h, weight_attr=_init_attr(config, config.num_hidden_layers))

    def forward(self, x):
        with region(regions.MLP):
            return self.fc2(F.gelu(self.fc1(x), approximate=True))


def _seq_constrain(x, config: GPTConfig):
    """Megatron-SP analog: pin the residual stream sequence-sharded over the
    mp axis between blocks (reference sequence_parallel_utils.py Scatter/
    AllGather ops); GSPMD materializes the gather/scatter around the TP
    matmuls automatically."""
    if not config.sequence_parallel:
        return x
    from jax.sharding import PartitionSpec as P

    from ..distributed.fleet.mpu import _constrain

    return _constrain(x, P("dp", "mp", None))


class GPTDecoderLayer(Layer):
    """Pre-LN transformer block (reference nn/layer/transformer.py
    TransformerDecoderLayer with normalize_before=True)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)

    def forward(self, x):
        cfg = self.config
        with region(regions.LN):
            h = self.ln_1(x)
        h = self.attn(h)
        with region(regions.ATTN_OUT):  # its dropout and residual
            h = F.dropout(h, cfg.hidden_dropout_prob, training=self.training)
            x = _seq_constrain(x + h, cfg)
        with region(regions.LN):
            h = self.ln_2(x)
        h = self.mlp(h)
        with region(regions.MLP):       # its dropout and residual
            h = F.dropout(h, cfg.hidden_dropout_prob, training=self.training)
            return _seq_constrain(x + h, cfg)


class GPTEmbeddings(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        if config.tensor_parallel:
            from ..distributed.fleet.mpu import VocabParallelEmbedding

            self.word_embeddings = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size, weight_attr=_init_attr(config))
        else:
            self.word_embeddings = nn.Embedding(
                config.vocab_size, config.hidden_size, weight_attr=_init_attr(config))
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size, weight_attr=_init_attr(config))

    def forward(self, input_ids, position_ids=None):
        with region(regions.EMBED):
            if position_ids is None:
                s = input_ids.shape[-1]
                position_ids = creation.arange(0, s, dtype="int64")
                position_ids = manipulation.expand(
                    manipulation.unsqueeze(position_ids, 0), [input_ids.shape[0], s])
            x = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
            return F.dropout(x, self.config.hidden_dropout_prob, training=self.training)


class GPTModel(Layer):
    """Transformer trunk: embeddings → N decoder blocks → final LN."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        if config.pipeline_parallel:
            from ..distributed.fleet.pipeline_schedules import PipelinedStack

            # dropout>0 is supported inside the stack: pipeline_spmd folds a
            # per-(stage, tick) RNG key so every microbatch/chunk draws an
            # independent mask (the SPMD analog of the reference's
            # RNGStatesTracker, fleet/meta_parallel/mpu/random.py:34)
            self.h = PipelinedStack(
                lambda: GPTDecoderLayer(config),
                num_layers=config.num_hidden_layers,
                num_chunks=max(config.virtual_pp_degree, 1),
                num_microbatches=config.pp_num_microbatches or None,
                schedule=config.pp_schedule,
            )
        else:
            self.h = nn.LayerList(
                [GPTDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None):
        x = self.embeddings(input_ids, position_ids)
        x = _seq_constrain(x, self.config)
        if self.config.pipeline_parallel:
            x = self.h(x)
        else:
            for block in self.h:
                x = block(x)
        with region(regions.LN):
            return self.ln_f(x)


class GPTForCausalLM(Layer):
    """Trunk + LM head. With tie_word_embeddings the head reuses the (possibly
    vocab-sharded) embedding matrix — under mp the logits matmul is a
    column-parallel projection exactly like the reference's parallel lm-head."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     weight_attr=_init_attr(config), bias_attr=False)

    def forward(self, input_ids, position_ids=None):
        x = self.gpt(input_ids, position_ids)
        with region(regions.LM_HEAD):
            if self.config.tie_word_embeddings:
                w = self.gpt.embeddings.word_embeddings.weight  # [V, H]
                return F.linear(x, manipulation.transpose(w, [1, 0]))
            return self.lm_head(x)


class GPTPretrainingCriterion(Layer):
    """Next-token cross entropy; under mp uses ParallelCrossEntropy
    (reference mp_layers.py:744) so vocab-sharded logits never gather."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        if config.tensor_parallel:
            from ..distributed.fleet.mpu import ParallelCrossEntropy

            self._parallel_ce = ParallelCrossEntropy()
        else:
            self._parallel_ce = None

    def forward(self, logits, labels):
        # Next-token shift: logits at position i predict token i+1. Callers
        # pass the raw token ids as labels; the shift happens here so the
        # objective is a real causal-LM loss, not a copy task.
        with region(regions.LOSS):
            v = logits.shape[-1]
            flat = manipulation.reshape(logits, [-1, v])
            flat_labels = manipulation.reshape(shift_labels(labels), [-1])
            if self._parallel_ce is not None:
                from ..ops import math as ops_math

                loss = self._parallel_ce(flat, flat_labels)  # 0 where ignored
                b, s = labels.shape
                return ops_math.sum(loss) / float(b * (s - 1))
            return F.cross_entropy(flat, flat_labels, reduction="mean")


IGNORE_INDEX = -100  # F.cross_entropy's default ignore_index


def shift_labels(labels):
    """[B, S] token ids -> [B, S] next-token targets, the last position
    (which has no next token) set to ``IGNORE_INDEX``. The logits keep their
    full [B, S, V] shape this way: dropping their last position instead
    leaves S-1 rows per sequence, off the TPU's sublane tiling, and
    flattening that is a relayout of the largest tensor in the step, one
    XLA's TPU compiler took minutes over (three compiles of 167-285 s at
    8 x 1023 x 50304 on a v5e)."""
    pad = creation.full([labels.shape[0], 1], IGNORE_INDEX, dtype=labels.dtype)
    return manipulation.concat([labels[:, 1:], pad], axis=1)


# ---------------------------------------------------------------- presets

def gpt_tiny(**overrides) -> GPTConfig:
    """Test/CI scale."""
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, max_position_embeddings=128,
                hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    base.update(overrides)
    return GPTConfig(**base)


def gpt2_small(**overrides) -> GPTConfig:
    base = dict(vocab_size=50304, hidden_size=768, num_hidden_layers=12,
                num_attention_heads=12, max_position_embeddings=1024)
    base.update(overrides)
    return GPTConfig(**base)


def gpt2_medium(**overrides) -> GPTConfig:
    base = dict(vocab_size=50304, hidden_size=1024, num_hidden_layers=24,
                num_attention_heads=16, max_position_embeddings=1024)
    base.update(overrides)
    return GPTConfig(**base)


def gpt_1p3b(**overrides) -> GPTConfig:
    base = dict(vocab_size=50304, hidden_size=2048, num_hidden_layers=24,
                num_attention_heads=16, max_position_embeddings=2048)
    base.update(overrides)
    return GPTConfig(**base)
