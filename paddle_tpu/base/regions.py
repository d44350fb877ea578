"""The names of the program's regions: one vocabulary, kept here.

A region is a ``jax.named_scope`` around a stretch of a traced program.
The name is HLO metadata only (the program computes the same); a device
trace carries it as each operation's ``tf_op``, and ``benchmark/scopes.py``
reduces a capture to seconds per region by these names. They are a
contract: a change that moves work between regions keeps the names, and a
reader names a region by its constant here, never by a string of its own.

    with region(MLP): ...            jax.named_scope("mlp")

Training (``models/gpt.py``, ``nn/functional/attention.py``, the Pallas
kernels' wrappers, ``optimizer.step``) uses ``TRAINING``; backward
operations keep their forward region (``core/autograd.py`` re-enters it
around each pullback). A serving program (``serving/decode.py``,
``serving/kv_cache.py``) runs under one of ``ROOTS`` and uses ``SERVING``
inside it; the programs of a model whose layers hold a recurrent state
(``models/brumby.py``) use ``RETENTION`` there, those of a model with latent attention and sparse
experts (``models/axk1.py``) ``LATENT_MOE``, those of a model with window and
global layers under a parallel block (``models/cohere2_moe.py``)
``WINDOWED_MOE``. ``KERNELS`` are the
``pl.pallas_call(name=...)`` of ``ops/pallas/flash_attention.py``,
``ops/pallas/retention.py`` and ``ops/pallas/paged_attention.py``. A kernel
has a name of its own even where it shares a file and a region with another:
a reader asks for decode's ``gqa_paged_attn`` and a prefill chunk's
``gqa_chunk_attn``, both under ``attn/core``, apart.
"""
from __future__ import annotations

from jax import named_scope as region  # noqa: F401  (the one way to enter one)

EMBED = "embed"
LN = "ln"
ATTN_QKV = "attn/qkv"
ATTN_LAYOUT = "attn/layout"        # heads-major transposes around the kernels
ATTN_CORE = "attn/core"            # the attention itself (the kernel calls)
ATTN_OUT = "attn/out"
ATTN_KV_WRITE = "attn/kv_write"    # every write into the KV pool
ATTN_KV_GATHER = "attn/kv_gather"  # a lane's pages gathered into one view
RETN_GATE = "retn/gate"          # the gate's projection, log-sigmoid, cumulative sum
RETN_CHUNK = "retn/chunk"        # prefill: phi, the masked quadratic part, the carry in and out
RETN_STATE = "retn/state"        # decode: phi, the update of the state, the read for y
ATTN_LATENT_PROJ = "attn/latent_proj"  # latent attention: q_a, q_b, kv_a, their norms, the two absorb products
ATTN_EXPAND = "attn/expand"      # prefill: keys and values formed from the stored latent rows
MOE_ROUTE = "moe/route"          # the router's scores, the group limit, the top-k and its weights
MOE_EXPERTS = "moe/experts"      # the held experts: sort, grouped products, combine
MOE_SHARED = "moe/shared"        # the shared expert
NORM = "norm"                    # RMSNorm (the retention and latent models'; GPT's LayerNorm is `ln`)
ROPE = "rope"
MLP = "mlp"
LM_HEAD = "lm_head"
LOSS = "loss"
OPTIMIZER = "optimizer"
SAMPLE = "sample"

PREFILL = "prefill"
DECODE = "decode"
DRAFT = "draft"
VERIFY = "verify"

FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
RETN_STEP = "retn_step"          # ops/pallas/retention.py: the decode step's state kernel
PAGED_ATTN = "paged_attn"        # ops/pallas/paged_attention.py: decode attention over live pages
LATENT_ATTN = "latent_paged_attn"  # the same file: absorbed attention over latent pages, a page K and V at once
GQA_ATTN = "gqa_paged_attn"      # the same file: grouped-query decode attention over live pages, given a window or none
GQA_CHUNK_ATTN = "gqa_chunk_attn"  # the same file: a prefill chunk's grouped-query attention over the lane's pages, a flash kernel
# XLA's own grouped-product kernel on a TPU (what `lax.ragged_dot` becomes).
# It names its operations itself (`ragged-dot-none`, `ragged-dot-metadata`)
# and drops the scope it was traced under: a reader of `moe/experts` adds the
# operations whose name starts with this.
RAGGED_DOT = "ragged-dot"

TRAINING = (EMBED, LN, ATTN_QKV, ATTN_LAYOUT, ATTN_CORE, ATTN_OUT, MLP,
            LM_HEAD, LOSS, OPTIMIZER)
SERVING = (EMBED, ATTN_QKV, ATTN_KV_WRITE, ATTN_KV_GATHER, ATTN_CORE,
           ATTN_OUT, MLP, LM_HEAD, SAMPLE)
RETENTION = (EMBED, NORM, ATTN_QKV, ROPE, RETN_GATE, RETN_CHUNK, RETN_STATE,
             ATTN_OUT, MLP, LM_HEAD, SAMPLE)
LATENT_MOE = (EMBED, NORM, ATTN_LATENT_PROJ, ROPE, ATTN_KV_WRITE, ATTN_EXPAND,
              ATTN_CORE, ATTN_OUT, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED, MLP,
              LM_HEAD, SAMPLE)
# a parallel block (one LayerNorm feeding attention and the experts), window
# and global layers in one stack (``models/cohere2_moe.py``)
WINDOWED_MOE = (EMBED, LN, ATTN_QKV, ROPE, ATTN_KV_WRITE, ATTN_KV_GATHER,
                ATTN_CORE, ATTN_OUT, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED,
                LM_HEAD, SAMPLE)
ROOTS = (PREFILL, DECODE, DRAFT, VERIFY)
KERNELS = (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV, RETN_STEP, PAGED_ATTN,
           LATENT_ATTN, GQA_ATTN, GQA_CHUNK_ATTN)
