"""True continuous batching for GPT decode (serving phase 2).

The batch tier (``ServingEngine``) batches at assembly time: stack, run
once, scatter — so autoregressive decode would degenerate into
batch-per-token re-assembly, and one long request holds every
co-batched one hostage. This module serves decode the TPU-native way:

- :class:`DecodePrograms` — functional prefill and decode-step programs
  built straight from a ``models.gpt.GPTForCausalLM``'s parameters
  (plain jnp math, no Tensor dispatch), operating against the
  device-resident :class:`~.kv_cache.KVSlotPool`. One compiled
  specialization per bucket rung — ``(batch, seq)`` pairs for prefill,
  batch rungs for decode — all AOT-warmed through the persistent compile
  cache (a warm-disk replica restores the WHOLE program set with zero
  traces).
- :class:`DecodeEngine` — the serving front door
  (:class:`~.engine.EngineBase`): admission control with priority tiers
  and TTL, per-tenant stats lanes, telemetry egress, and a
  :class:`~.scheduler.DecodeScheduler` thread running the join/leave
  loop: requests enter a running batch the step after a slot frees and
  leave the step they finish — no full re-assembly, ever.

Decoding is greedy (argmax), which makes the bit-exactness contract
testable: the tokens a request receives are identical whether it decoded
alone or joined a full batch mid-flight (per-lane math touches only the
lane's own slot; masked pad columns contribute exact zeros).

Every program body runs under the regions of ``base/regions.py`` — a root
per program (``prefill``/``decode``/``draft``/``verify``) and, inside, the
serving vocabulary (``embed`` ... ``attn/kv_gather`` ... ``sample``). The
names are HLO metadata only (the program computes the same); a device
trace carries them as each operation's ``tf_op``. On one TPU the paged
programs hold nothing under ``attn/kv_gather``: their attention is the
kernel ``paged_attn`` under ``attn/core``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..base import regions
from ..base.flags import get_flag
from ..base.regions import region
from ..observability.locks import named_lock
from ..profiler.pipeline import serving_stats
from . import kv_cache as kvc
from .engine import EngineBase
from .kv_cache import KVPagePool, KVSlotPool, StateLanePool, WindowedPagePools
from .request_queue import DecodeRequest
from .scheduler import (DecodeScheduler, PagedDecodeScheduler,
                        WindowedDecodeScheduler)

__all__ = ["DecodeEngine", "DecodePrograms", "PagedDecodePrograms",
           "RetentionPrograms", "LatentPrograms", "WindowedPrograms"]


def _extract_gpt(model):
    """The model's parameters as a plain pytree (shared device arrays,
    zero-copy) plus its config. Only the single-device GPT path serves
    here — parallel layouts keep their training-side machinery."""
    cfg = model.config
    if (cfg.tensor_parallel or cfg.pipeline_parallel
            or cfg.sequence_parallel or cfg.context_parallel):
        raise ValueError(
            "decode serving builds single-device programs; export the "
            "model unsharded (tensor/pipeline/sequence/context-parallel "
            "configs are training layouts)")

    def val(p):
        return p._value

    blocks = []
    for blk in model.gpt.h:
        a, m = blk.attn, blk.mlp
        blocks.append({
            "ln1_w": val(blk.ln_1.weight), "ln1_b": val(blk.ln_1.bias),
            "qkv_w": val(a.qkv_proj.weight), "qkv_b": val(a.qkv_proj.bias),
            "out_w": val(a.out_proj.weight), "out_b": val(a.out_proj.bias),
            "ln2_w": val(blk.ln_2.weight), "ln2_b": val(blk.ln_2.bias),
            "fc1_w": val(m.fc1.weight), "fc1_b": val(m.fc1.bias),
            "fc2_w": val(m.fc2.weight), "fc2_b": val(m.fc2.bias),
        })
    params = {
        "wte": val(model.gpt.embeddings.word_embeddings.weight),
        "wpe": val(model.gpt.embeddings.position_embeddings.weight),
        "lnf_w": val(model.gpt.ln_f.weight),
        "lnf_b": val(model.gpt.ln_f.bias),
        "blocks": blocks,
    }
    if not cfg.tie_word_embeddings:
        params["head_w"] = val(model.lm_head.weight)
    return params, cfg


def _ln(x, w, b, eps):
    import jax.numpy as jnp
    from jax import lax

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * w + b


def _mlp(x, blk, eps):
    """The block's second half: LN, the two projections, the residual."""
    import jax

    with region(regions.MLP):
        h2 = _ln(x, blk["ln2_w"], blk["ln2_b"], eps)
        return x + jax.nn.gelu(h2 @ blk["fc1_w"] + blk["fc1_b"],
                               approximate=True) @ blk["fc2_w"] + blk["fc2_b"]


def _attend_merged(q, keys, vals, positions, heads, scale):
    """Attention of ``S`` query positions a lane over a gathered paged
    view whose minor dimension is heads and head_dim MERGED, read
    without splitting it: ``q`` is ``[B, S, heads*dim]``, ``keys`` /
    ``vals`` ``[B, cols, heads*dim]`` (``kv_cache.gather_pages``),
    ``positions`` ``[B, S]``; returns ``[B, S, heads*dim]``. Query
    ``s`` of lane ``b`` sees columns ``<= positions[b, s]``.

    ``q`` is spread block-diagonally — ``qbd[b, s, h, g*dim + d]`` is
    ``q[b, s, h*dim + d]`` where ``g == h`` and 0 elsewhere — so one
    contraction over the whole merged dimension gives head ``h`` its
    own logits (the other heads' columns add exact zeros), and after
    the softmax head ``h`` keeps its own ``dim`` columns of ``probs @
    vals``. The same products, mask, float32 softmax and dtypes as the
    split ``"bhd,bthd->bht"`` form, at ``heads`` times its FLOPs on a
    step bound by bytes — and no array of the cache's size is ever
    reshaped to a ``(heads, dim)`` tail, which a TPU would pad to its
    tile and relay out (see :class:`~.kv_cache.KVPagePool`)."""
    import jax
    import jax.numpy as jnp

    HD = q.shape[-1]
    own = (jnp.arange(HD) // (HD // heads))[None, :] == jnp.arange(heads)[:, None]
    qbd = jnp.where(own, q[:, :, None, :], 0)
    logits = jnp.einsum("bshk,btk->bhst", qbd, keys) * scale
    col = jnp.arange(keys.shape[1])
    mask = col[None, None, None, :] <= positions[:, None, :, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    full = jnp.einsum("bhst,btk->bshk", probs, vals)
    return jnp.where(own, full, 0).sum(axis=2)


class DecodePrograms:
    """The decode tier's compiled program set over one GPT's weights.

    Two program families, each specialized per bucket rung:

    - ``prefill``: ``[B, S]`` prompt tokens → per-layer K/V written into
      the pool's slots (``lax.dynamic_update_slice`` on the B=1
      interactive path, one all-layer scatter otherwise) + the first
      generated token per lane (greedy, from each lane's last real
      position);
    - ``decode``: ``[B]`` last tokens → one attention step per lane over
      its own slot's cached rows (cols ≤ position), K/V appended at the
      lane's write position, next token per lane.

    Both take and return the pool buffers functionally; KV args are
    donated on accelerators so XLA aliases output onto input — zero
    per-step reallocation. ``traces`` ticks inside the traced bodies
    (the zero-retrace proof).
    """

    def __init__(self, model, pool: KVSlotPool, *,
                 seq_ladder: Sequence[int],
                 prefill_batch_rungs: Sequence[int],
                 decode_rungs: Sequence[int]):
        import jax

        params, cfg = self._extract(model)
        self.params = jax.device_put(params)
        self.pool = pool
        self.seq_ladder = sorted(int(s) for s in seq_ladder)
        self.prefill_batch_rungs = sorted(int(b) for b in prefill_batch_rungs)
        self.decode_rungs = sorted(int(b) for b in decode_rungs)
        self.traces = 0
        self.warmed: List[tuple] = []
        self._lock = named_lock("serving.decode.programs")
        backend = jax.devices()[0].platform
        # serving-step donation idiom: the pool buffers are dead after the
        # call (the scheduler commits the outputs), so donate them and XLA
        # updates the KV cache in place. CPU ignores donation — skip the
        # warning noise there; the footprint proof holds either way
        # (commit() pins shape/dtype, device_bytes stays constant).
        self._donate = (tuple(range(1, 1 + len(pool.arrays())))
                        if backend != "cpu" else ())
        self._bind_config(cfg)
        self._jit_prefill = jax.jit(self._prefill_fn,
                                    donate_argnums=self._donate)
        self._jit_decode = jax.jit(self._decode_fn,
                                   donate_argnums=self._donate)
        self._jit_carry = jax.jit(self._carry_fn)

    #: programs that prefill a prompt in pieces set this; the scheduler
    #: then keeps a cursor per pending request (one chunk a beat)
    chunked = False
    _extract = staticmethod(_extract_gpt)

    @staticmethod
    def _kernel() -> bool:
        """Whether a family's fused step (the paged attention over the
        pool, the retention state update) is its Pallas kernel: the
        repo's one gate, asked while the program is traced."""
        from ..ops import pallas

        return bool(pallas.enabled())

    def _bind_config(self, cfg) -> None:
        """The model's constants the traced bodies bake in."""
        self._heads = cfg.num_attention_heads
        self._head_dim = cfg.head_dim
        self._hidden = cfg.hidden_size
        self._max_pos = int(cfg.max_position_embeddings)
        self._eps = float(cfg.layer_norm_epsilon)
        self._tied = bool(cfg.tie_word_embeddings)
        self._scale = 1.0 / math.sqrt(cfg.head_dim)

    # ------------------------------------------------------------ programs
    def _logits_head(self, params, x):
        """Final LN + the (tied) output projection of ``x``."""
        with region(regions.LM_HEAD):
            hfin = _ln(x, params["lnf_w"], params["lnf_b"], self._eps)
            w = params["wte"].T if self._tied else params["head_w"]
            return hfin @ w

    def _prefill_trunk(self, params, tokens, lengths):
        """The prefill transformer body shared by the slot and paged
        program families: ``[B, S]`` prompt tokens → per-lane head
        logits at the last real position plus the stacked per-layer K/V
        rows ``[layers, B, S, heads, head_dim]``. Pure function of the
        prompt — cache writing is the caller's (pool-specific) job."""
        import jax
        import jax.numpy as jnp

        self.traces += 1  # runs under trace only: the recompile proof
        B, S = tokens.shape
        eps = self._eps
        with region(regions.EMBED):
            x = params["wte"][tokens] + params["wpe"][:S][None, :, :]
        ks, vs = [], []
        for blk in params["blocks"]:
            with region(regions.ATTN_QKV):
                h = _ln(x, blk["ln1_w"], blk["ln1_b"], eps)
                qkv = (h @ blk["qkv_w"] + blk["qkv_b"]).reshape(
                    B, S, self._heads, 3, self._head_dim)
                q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
            ks.append(k)
            vs.append(v)
            with region(regions.ATTN_CORE):
                logits = jnp.einsum("bshd,bthd->bhst", q, k) * self._scale
                causal = jnp.tril(jnp.ones((S, S), bool))
                logits = jnp.where(causal[None, None], logits, -1e30)
                probs = jax.nn.softmax(logits.astype(jnp.float32),
                                       axis=-1).astype(x.dtype)
                att = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(
                    B, S, self._hidden)
            with region(regions.ATTN_OUT):
                x = x + att @ blk["out_w"] + blk["out_b"]
            x = _mlp(x, blk, eps)
        # each lane's next token comes from its LAST REAL position (rows
        # past the prompt are garbage, never attended by real rows)
        with region(regions.LM_HEAD):
            idx = (lengths - 1).astype(jnp.int32)
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        head = self._logits_head(params, x_last)
        with region(regions.ATTN_KV_WRITE):
            krows = jnp.stack(ks)  # [layers, B, S, heads, head_dim]
            vrows = jnp.stack(vs)
        return head, krows, vrows

    def _prefill_fn(self, params, ck, cv, tokens, lengths, slot_ids):
        import jax.numpy as jnp

        with region(regions.PREFILL):
            B = tokens.shape[0]
            head, krows, vrows = self._prefill_trunk(params, tokens, lengths)
            with region(regions.SAMPLE):
                next_tok = jnp.argmax(head, axis=-1).astype(jnp.int32)
            if B == 1:
                # interactive path: one dynamic_update_slice per buffer
                ck = kvc.write_prompt(ck, slot_ids[0], krows[:, 0])
                cv = kvc.write_prompt(cv, slot_ids[0], vrows[:, 0])
            else:
                ck = kvc.write_prompt_batch(ck, slot_ids, krows)
                cv = kvc.write_prompt_batch(cv, slot_ids, vrows)
            return ck, cv, next_tok

    def _decode_fn(self, params, ck, cv, tokens, slot_ids, positions):
        import jax
        import jax.numpy as jnp

        self.traces += 1
        with region(regions.DECODE):
            B = tokens.shape[0]
            eps = self._eps
            with region(regions.EMBED):
                x = params["wte"][tokens] + params["wpe"][positions]
            col = jnp.arange(self.pool.max_seq)
            for li, blk in enumerate(params["blocks"]):
                with region(regions.ATTN_QKV):
                    h = _ln(x, blk["ln1_w"], blk["ln1_b"], eps)
                    qkv = (h @ blk["qkv_w"] + blk["qkv_b"]).reshape(
                        B, self._heads, 3, self._head_dim)
                    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                # write this token's K/V at (layer, slot, position), then
                # attend over the slot's rows 0..position inclusive
                ck = kvc.append_token(ck, li, slot_ids, positions, k)
                cv = kvc.append_token(cv, li, slot_ids, positions, v)
                with region(regions.ATTN_KV_GATHER):
                    keys = ck[li, slot_ids]  # [B, max_seq, heads, head_dim]
                    vals = cv[li, slot_ids]
                with region(regions.ATTN_CORE):
                    logits = jnp.einsum("bhd,bthd->bht", q, keys) * self._scale
                    mask = col[None, None, :] <= positions[:, None, None]
                    logits = jnp.where(mask, logits, -1e30)
                    probs = jax.nn.softmax(logits.astype(jnp.float32),
                                           axis=-1).astype(x.dtype)
                    att = jnp.einsum("bht,bthd->bhd", probs, vals).reshape(
                        B, self._hidden)
                with region(regions.ATTN_OUT):
                    x = x + att @ blk["out_w"] + blk["out_b"]
                x = _mlp(x, blk, eps)
            head = self._logits_head(params, x)
            with region(regions.SAMPLE):
                next_tok = jnp.argmax(head, axis=-1).astype(jnp.int32)
            return ck, cv, next_tok

    def _carry_fn(self, prev, tokens):
        """A decode call's input tokens, for lanes whose newest token the
        host has not read: ``tokens`` ``[B]`` holds a token id where the
        host knows it and ``-1 - row`` where it is row ``row`` of ``prev``
        ``[P]``, the token output of the call before (any family's prefill
        or decode, still on the device). The scheduler reads a call's
        tokens one beat late; this is what lets it dispatch the next call
        first. One small program in front of the decode programs, whose
        bodies it leaves alone."""
        import jax.numpy as jnp

        self.traces += 1
        with region(regions.DECODE), region(regions.EMBED):
            rows = jnp.clip(-1 - tokens, 0, prev.shape[0] - 1)
            return jnp.where(tokens >= 0, tokens, prev[rows])

    # ------------------------------------------------------------- rungs
    @property
    def rungs(self) -> List[tuple]:
        """Every specialization warmup arms: ``("decode", b)`` per batch
        rung plus ``("prefill", b, s)`` over the (batch x seq) grid, and
        the token carry's (:attr:`carry_rungs`)."""
        out = [("decode", b) for b in self.decode_rungs]
        out += [("prefill", b, s) for b in self.prefill_batch_rungs
                for s in self.seq_ladder]
        return out + self.carry_rungs

    @property
    def carry_rungs(self) -> List[tuple]:
        """``("carry", p, b)``: a decode call of batch rung ``b`` fed from
        a call whose token output has ``p`` rows, which is any decode rung
        or any prefill batch rung: every pair the scheduler can meet."""
        rows = sorted(set(self.decode_rungs) | set(self.prefill_batch_rungs))
        return [("carry", p, b) for p in rows for b in self.decode_rungs]

    @staticmethod
    def _carry_zero_args(key):
        _, p, b = key
        return np.zeros(p, np.int32), np.zeros(b, np.int32)

    def _zero_args(self, key):
        pad = self.pool.pad_slot
        if key[0] == "decode":
            b = key[1]
            return (np.zeros(b, np.int32), np.full(b, pad, np.int32),
                    np.zeros(b, np.int32))
        _, b, s = key
        return (np.zeros((b, s), np.int32), np.ones(b, np.int32),
                np.full(b, pad, np.int32))

    def _jitted(self, key):
        return {"decode": self._jit_decode, "prefill": self._jit_prefill,
                "carry": self._jit_carry}[key[0]]

    def warmup(self) -> List[tuple]:
        """Arm every rung with one traced call. Idempotent per rung."""
        with self._lock:
            for key in self.rungs:
                if key in self.warmed:
                    continue
                self._warm(key)
                self.warmed.append(key)
        return list(self.warmed)

    def _warm(self, key) -> None:
        if key[0] == "carry":   # no pool, no parameters
            self._jit_carry(*self._carry_zero_args(key))
            return
        args = self._zero_args(key)
        # one traced call against the pad slot (harmless writes land in
        # the trash slot); outputs are committed so a donation backend
        # keeps the pool buffers alive
        held = self.pool.arrays()
        out = self._jitted(key)(self._call_params(key), *held, *args)
        self.pool.commit(*out[:len(held)])

    def _call_params(self, key) -> dict:
        """The parameter pytree rung ``key`` runs against. The base
        families serve everything from ``self.params``; the paged family
        routes its draft rungs through the truncated-layer view."""
        return self.params

    def _flip_params(self, staged) -> None:
        """The one reference assignment a hot swap commits (caller holds
        the programs lock). Subclasses with DERIVED parameter views — the
        paged family's truncated-layer draft tier — extend this so every
        tier flips under the same lock acquisition: a draft program can
        never observe pre-swap weights once ``swap_params`` returns."""
        self.params = staged

    def swap_params(self, model) -> int:
        """Zero-downtime weight hot-swap for the decode tier: re-extract
        ``model``'s parameters (zero-copy of its live device arrays) and
        flip the program-set's parameter reference. The model must share
        the serving model's structural identity (config + KV layout) —
        validated leaf by leaf (structure/shape/dtype), so every warmed
        prefill/decode executable keeps replaying: ``traces`` cannot
        move across a swap.

        The flip is one reference assignment; each prefill/decode call
        reads ``self.params`` once at its start, so the swap lands
        exactly BETWEEN decode steps — running lanes keep their KV slots
        and simply attend with the new weights from the next step on.
        Returns the number of parameter leaves swapped."""
        import jax

        new_params, _cfg = self._extract(model)
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_params)
        if old_def != new_def:
            raise ValueError(
                "swap_params: the new model's parameter tree differs "
                "structurally from the serving one — a decode hot swap "
                "must carry the same architecture")
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            if tuple(o.shape) != tuple(n.shape) or o.dtype != n.dtype:
                raise ValueError(
                    f"swap_params: leaf {i} is {tuple(n.shape)}/{n.dtype}, "
                    f"decode executables expect {tuple(o.shape)}/{o.dtype}")
        # stage the transfer BEFORE taking the lock (CX1002: a device
        # transfer under a held lock serializes every other swapper
        # behind device latency); the flip itself is one reference
        # assignment under the lock
        staged = jax.device_put(new_params)
        with self._lock:
            self._flip_params(staged)
        return len(new_leaves)

    # -------------------------------------------------------------- calls
    def prefill(self, ck, cv, tokens, lengths, slot_ids):
        return self._jit_prefill(self.params, ck, cv, tokens, lengths,
                                 slot_ids)

    def decode(self, ck, cv, tokens, slot_ids, positions):
        return self._jit_decode(self.params, ck, cv, tokens, slot_ids,
                                positions)

    def carry(self, prev, tokens):
        """:meth:`_carry_fn` of the unread call's tokens ``prev`` (on the
        device) and the next decode call's ``tokens`` (host)."""
        return self._jit_carry(prev, tokens)


class PagedDecodePrograms(DecodePrograms):
    """The decode program set over a :class:`~.kv_cache.KVPagePool`.

    Same warmup/compile-cache/donation/hot-swap machinery as the slot
    family; the cache layout and the rung key change:

    - K/V is indexed through a per-request *block table* — a traced
      ``[B, T]`` int32 array naming each lane's pages in order. The
      table is DATA: one compiled program serves any page map, so page
      churn (alloc on growth, reclaim on retire, reuse by the next
      request) costs zero retraces.
    - the pool is ``[layers, pages+1, page_size, heads*head_dim]``: the
      minor dimension merged, so (page_size, heads*head_dim) is whole
      TPU tiles where (heads, head_dim) = (12, 64) was padded 2.67x.
      The programs take and return it as it lies (no whole-pool
      relayout on entry and exit) and ``decode``/``draft``/``verify``
      never split the merged dimension on anything a page's size or
      larger: fresh k/v rows are merged before the write, and the
      attention contracts the merged dimension whole
      (:meth:`_attend_pages`): on one TPU the kernel of
      ``ops/pallas/paged_attention.py`` over the pool in place, elsewhere
      :func:`_attend_merged` over the gathered view.
    - decode rungs key on (batch rung × table rung): ``("decode", b,
      t)`` where ``t`` walks :func:`~..jit.bucketing.table_ladder`.
      With the kernel a lane pays for its live pages whatever the rung
      (a table entry past them costs a skipped grid step, no bytes);
      with the composition a short context pays a short gather, a 4k
      one a long gather. Both replay warm.
    - sampling rides as traced per-lane arguments (temperature / top-k
      / top-p / raw uint32 PRNG key pair): sampling is data too, never
      a retrace. ``temp == 0`` lanes take the argmax bit-exactly — the
      greedy audit mode the slot oracle is compared against — and a
      call in which no lane samples skips the vocabulary sort whole
      (one ``lax.cond`` in :meth:`_choose_tokens`); a call with one
      sampling lane sorts for every lane.

    With ``speculate_k > 0`` two more program families join the same
    (batch rung × table rung) grid — self-speculative decoding over the
    page pool (ISSUE 20):

    - ``draft``: ``speculate_k`` UNROLLED decode steps through a
      truncated-layer prefix of the SAME weights (``draft_layers``
      blocks, shared zero-copy — no second model, no extra weight
      memory). One dispatch proposes k tokens, writing the draft
      layers' K/V along the way.
    - ``verify``: one batched FULL-model pass over all ``k + 1``
      positions (last committed token + the k proposals), rewriting
      every layer's K/V at those positions with true-token inputs and
      choosing a token at each position with the request's canonical
      ``[seed, token_index]`` key. Committed tokens always come from
      the verify pass, so both the greedy and the sampled stream equal
      the non-speculative stream token for token; the draft only
      decides HOW MANY commit per round.

    Both families bake ``k`` and ``draft_layers`` in (compile-time
    constants) and warm with everything else, so flipping speculation on
    or off mid-flight never traces.
    """

    def __init__(self, model, pool: KVPagePool, *,
                 seq_ladder: Sequence[int],
                 prefill_batch_rungs: Sequence[int],
                 decode_rungs: Sequence[int],
                 max_seq: int,
                 speculate_k: int = 0,
                 draft_layers: Optional[int] = None):
        import jax

        from ..jit.bucketing import table_ladder

        self.max_seq = int(max_seq)
        self.speculate_k = max(int(speculate_k), 0)
        n_layers = int(model.config.num_hidden_layers)
        dl = int(get_flag("serving_spec_draft_layers")
                 if draft_layers is None else draft_layers)
        # clamp, never reject: a 1-layer demo model drafts with its one
        # block — a degenerate full-depth draft that accepts 100% and
        # still wins on dispatch count (2 calls commit up to k+1 tokens)
        self.draft_layers = max(1, min(dl, n_layers))
        # super() jits self._prefill_fn/_decode_fn — the overrides below,
        # bound through normal method resolution
        super().__init__(model, pool,
                         seq_ladder=seq_ladder,
                         prefill_batch_rungs=prefill_batch_rungs,
                         decode_rungs=decode_rungs)
        self.table_rungs = table_ladder(self.max_seq, pool.page_size)
        self.draft_params = (self._draft_view(self.params)
                             if self.speculate_k else None)
        if self.speculate_k:
            self._jit_draft = jax.jit(self._draft_fn,
                                      donate_argnums=self._donate)
            self._jit_verify = jax.jit(self._verify_fn,
                                       donate_argnums=self._donate)

    # -------------------------------------------------------- draft params
    def _draft_view(self, params: dict) -> dict:
        """The draft tier's parameter view: the first ``draft_layers``
        transformer blocks plus the shared embedding / final-LN / head
        leaves. Every leaf IS the full tree's leaf (no copy, no device
        memory) — truncation drops the TOP of the stack, so the draft's
        per-layer K/V is bitwise what the full model computes for those
        layers, and verify can overwrite it in place."""
        view = {k: v for k, v in params.items() if k != "blocks"}
        view["blocks"] = list(params["blocks"][:self.draft_layers])
        return view

    def _flip_params(self, staged) -> None:
        # one lock acquisition flips BOTH tiers: the draft view is
        # re-derived from the staged tree, so a mid-speculation hot swap
        # can never leave the draft proposing with stale weights
        super()._flip_params(staged)
        if self.speculate_k:
            self.draft_params = self._draft_view(staged)

    def _call_params(self, key) -> dict:
        return self.draft_params if key[0] == "draft" else self.params

    # ----------------------------------------------------------- sampling
    def _choose_tokens(self, head, temps, top_ks, top_ps, rkeys):
        """Per-lane next-token choice from head logits ``[B, V]``.

        All sampling parameters are traced data. A lane with ``temp ==
        0`` returns plain argmax — the SAME op the slot programs run,
        so greedy mode stays bit-exact. Otherwise: temperature-scale,
        keep the top-k / top-p prefix of the descending sort, and draw
        with ``jax.random.categorical`` from the lane's own raw uint32
        key pair — the key is ``[request_seed, token_index]`` on the
        host, so a request's stream never depends on batch composition.

        The sampled path sits under one ``lax.cond`` on ``any(temps >
        0)``: a call with no sampling lane runs the argmax and nothing
        else (no sort, softmax, cumulative sum or draw); a call with one
        runs the sort for EVERY lane and keeps the argmax wherever
        ``temp == 0``. The predicate is data, so both kinds of call
        replay the same compiled program.
        """
        import jax
        import jax.numpy as jnp

        with region(regions.SAMPLE):
            greedy = jnp.argmax(head, axis=-1).astype(jnp.int32)
            V = head.shape[-1]

            def lane(lg, temp, tk, tp, key):
                lg = lg.astype(jnp.float32)
                scaled = lg / jnp.where(temp > 0, temp, 1.0)
                srt = jnp.sort(scaled)[::-1]  # descending
                rank = jnp.arange(V)
                k_eff = jnp.clip(jnp.where(tk > 0, tk, V), 1, V)
                probs = jax.nn.softmax(srt)
                p_eff = jnp.where((tp > 0.0) & (tp < 1.0), tp, 1.0)
                # both filters are prefixes of the sort: kept set = prefix,
                # cutoff = the smallest kept value (rank 0 is always kept)
                keep = (rank < k_eff) & (jnp.cumsum(probs) - probs < p_eff)
                cutoff = jnp.min(jnp.where(keep, srt, jnp.inf))
                filtered = jnp.where(scaled >= cutoff, scaled, -jnp.inf)
                return jax.random.categorical(key, filtered).astype(jnp.int32)

            def sample():
                sampled = jax.vmap(lane)(head, temps, top_ks, top_ps, rkeys)
                return jnp.where(temps > 0, sampled, greedy)

            return jax.lax.cond(jnp.any(temps > 0), sample, lambda: greedy)

    # ----------------------------------------------------------- programs
    def _prefill_fn(self, params, ck, cv, tokens, lengths, tables,
                    temps, top_ks, top_ps, rkeys):
        import jax.numpy as jnp

        with region(regions.PREFILL):
            head, krows, vrows = self._prefill_trunk(params, tokens, lengths)
            next_tok = self._choose_tokens(head, temps, top_ks, top_ps, rkeys)
            # pad the prompt rows up to whole pages; the surplus rows route
            # through table entries past the lane's real pages (pad page 0)
            L, B, S = krows.shape[:3]
            want = tables.shape[1] * self.pool.page_size
            with region(regions.ATTN_KV_WRITE):
                # the pool's rows carry heads merged into the minor dim
                krows = krows.reshape(L, B, S, self._hidden)
                vrows = vrows.reshape(L, B, S, self._hidden)
                if want > S:
                    padw = ((0, 0), (0, 0), (0, want - S), (0, 0))
                    krows = jnp.pad(krows, padw)
                    vrows = jnp.pad(vrows, padw)
            ck = kvc.write_prompt_pages(ck, tables, krows)
            cv = kvc.write_prompt_pages(cv, tables, vrows)
            return ck, cv, next_tok

    def _attend_pages(self, q, ck, cv, li, tables, positions):
        """Layer ``li``'s attention of ``q`` ``[B, S, heads*dim]`` at
        ``positions`` ``[B, S]`` over the lanes' pages, the step's own
        rows already written: query ``s`` sees columns ``<=
        positions[b, s]``. The traced table maps token position -> page,
        so column j IS position j and the slot program's mask and softmax
        carry over. Where the gate says so (a TPU, one device) this is the
        kernel of ``ops/pallas/paged_attention.py``, which reads each
        lane's live pages from the pool as it lies; elsewhere
        :func:`~.kv_cache.gather_pages` builds the dense view and
        :func:`_attend_merged` reads it, the kernel's oracle."""
        if self._kernel():
            from ..ops.pallas import paged_attention as kernel

            with region(regions.ATTN_CORE):
                return kernel.paged_attention(
                    q, ck, cv, li, tables, positions, heads=self._heads,
                    scale=self._scale)
        keys = kvc.gather_pages(ck, li, tables)  # [B, T*ps, h*d]
        vals = kvc.gather_pages(cv, li, tables)
        with region(regions.ATTN_CORE):
            return _attend_merged(q, keys, vals, positions, self._heads,
                                  self._scale)

    def _paged_step_trunk(self, params, ck, cv, tokens, tables, positions,
                          *, bounded=False):
        """One paged decode step's transformer body: ``[B]`` tokens at
        ``[B]`` positions → (ck, cv, head logits ``[B, V]``), K/V
        appended through the block tables. Shared verbatim by the plain
        decode program (``bounded=False`` — the PR 18 trace, byte for
        byte) and the draft program's unrolled steps.

        ``bounded=True`` adds the speculative overflow clamps: a lane
        whose draft position runs past ``max_seq`` (or the model's
        position table) must not corrupt a LIVE page through index
        clamping, so out-of-range writes are redirected to the pool's
        pad page 0 and the wpe lookup is clamped. Such a lane's
        proposals are garbage, but its verify tokens past the boundary
        are never committed — the scheduler retires it at ``max_seq``.
        """
        import jax.numpy as jnp

        B, T = tables.shape
        ps = self.pool.page_size
        eps = self._eps
        with region(regions.EMBED):
            if bounded:
                x = (params["wte"][tokens]
                     + params["wpe"][jnp.minimum(positions,
                                                 self._max_pos - 1)])
            else:
                x = params["wte"][tokens] + params["wpe"][positions]
        with region(regions.ATTN_KV_WRITE):
            page_idx = (positions // ps).astype(jnp.int32)
            if bounded:
                page_idx = jnp.minimum(page_idx, T - 1)
            pages = jnp.take_along_axis(tables, page_idx[:, None],
                                        axis=1)[:, 0]
            if bounded:
                pages = jnp.where(positions < self.max_seq, pages, 0)
            offsets = (positions % ps).astype(jnp.int32)
        for li, blk in enumerate(params["blocks"]):
            with region(regions.ATTN_QKV):
                h = _ln(x, blk["ln1_w"], blk["ln1_b"], eps)
                qkv = (h @ blk["qkv_w"] + blk["qkv_b"]).reshape(
                    B, self._heads, 3, self._head_dim)
                # one token's q, k, v, heads merged: [B, heads*dim]
                q, k, v = (qkv[:, :, i].reshape(B, self._hidden)
                           for i in range(3))
            ck = kvc.append_token_paged(ck, li, pages, offsets, k)
            cv = kvc.append_token_paged(cv, li, pages, offsets, v)
            att = self._attend_pages(q[:, None], ck, cv, li, tables,
                                     positions[:, None])[:, 0]
            with region(regions.ATTN_OUT):
                x = x + att @ blk["out_w"] + blk["out_b"]
            x = _mlp(x, blk, eps)
        return ck, cv, self._logits_head(params, x)

    def _decode_fn(self, params, ck, cv, tokens, tables, positions,
                   temps, top_ks, top_ps, rkeys):
        self.traces += 1
        with region(regions.DECODE):
            ck, cv, head = self._paged_step_trunk(params, ck, cv, tokens,
                                                  tables, positions)
            next_tok = self._choose_tokens(head, temps, top_ks, top_ps,
                                           rkeys)
            return ck, cv, next_tok

    @staticmethod
    def _shift_keys(rkeys, j):
        """The request's canonical sampling key for the j-th token of a
        speculation round: host keys are ``[seed, len(generated)]`` at
        round start, so offsetting the counter lane by j reproduces
        EXACTLY the key the non-speculative stream would use for that
        token index — per-seed determinism survives speculation."""
        import jax.numpy as jnp

        if j == 0:
            return rkeys
        return rkeys + jnp.asarray([0, j], jnp.uint32)[None, :]

    def _draft_fn(self, params, ck, cv, tokens, tables, positions,
                  temps, top_ks, top_ps, rkeys):
        """``speculate_k`` decode steps through the truncated-layer
        params, unrolled into ONE program — a speculation round costs
        two dispatches (draft + verify) instead of k+1. Writes the
        draft layers' K/V (verify rewrites the accepted positions with
        full-model values anyway) and returns the proposals ``[B, k]``.
        """
        import jax.numpy as jnp

        self.traces += 1
        with region(regions.DRAFT):
            tok, pos, drafts = tokens, positions, []
            for j in range(self.speculate_k):
                ck, cv, head = self._paged_step_trunk(
                    params, ck, cv, tok, tables, pos, bounded=True)
                tok = self._choose_tokens(head, temps, top_ks, top_ps,
                                          self._shift_keys(rkeys, j))
                drafts.append(tok)
                pos = pos + 1
            return ck, cv, jnp.stack(drafts, axis=1)

    def _verify_fn(self, params, ck, cv, tokens, tables, positions,
                   temps, top_ks, top_ps, rkeys):
        """One batched full-model pass over all ``k + 1`` positions:
        ``tokens[:, 0]`` is each lane's last committed token at its
        write position p, ``tokens[:, 1:]`` the draft proposals at
        p+1..p+k. Every layer's K/V is appended at ALL k+1 positions
        before the gather, masked causally per query column, and a
        token is chosen at each position with the canonical shifted
        key — the j-th verify token is bitwise the token the plain
        decode program would emit after committing tokens 0..j-1, which
        is the whole bit-exactness contract."""
        import jax.numpy as jnp

        self.traces += 1
        with region(regions.VERIFY):
            B, K1 = tokens.shape
            T = tables.shape[1]
            ps = self.pool.page_size
            eps = self._eps
            pos = positions[:, None] + jnp.arange(K1, dtype=jnp.int32)[None, :]
            with region(regions.EMBED):
                x = (params["wte"][tokens]
                     + params["wpe"][jnp.minimum(pos, self._max_pos - 1)])
            with region(regions.ATTN_KV_WRITE):
                page_idx = jnp.minimum((pos // ps).astype(jnp.int32), T - 1)
                pages = jnp.take_along_axis(tables, page_idx, axis=1)
                pages = jnp.where(pos < self.max_seq, pages, 0)  # pad-page spill
                offsets = (pos % ps).astype(jnp.int32)
            for li, blk in enumerate(params["blocks"]):
                with region(regions.ATTN_QKV):
                    h = _ln(x, blk["ln1_w"], blk["ln1_b"], eps)
                    qkv = (h @ blk["qkv_w"] + blk["qkv_b"]).reshape(
                        B, K1, self._heads, 3, self._head_dim)
                    q, k, v = (qkv[..., i, :].reshape(B, K1, self._hidden)
                               for i in range(3))
                ck = kvc.append_token_paged(ck, li, pages, offsets, k)
                cv = kvc.append_token_paged(cv, li, pages, offsets, v)
                # query j sees cols <= p+j
                att = self._attend_pages(q, ck, cv, li, tables, pos)
                with region(regions.ATTN_OUT):
                    x = x + att @ blk["out_w"] + blk["out_b"]
                x = _mlp(x, blk, eps)
            head = self._logits_head(params, x)  # [B, K1, V]
            vtoks = [self._choose_tokens(head[:, j], temps, top_ks, top_ps,
                                         self._shift_keys(rkeys, j))
                     for j in range(K1)]
            return ck, cv, jnp.stack(vtoks, axis=1)

    # -------------------------------------------------------------- rungs
    def _one_table_rung(self) -> List[int]:
        """The table ladder of a family that prefills in chunks of whole
        pages and decodes over the whole table (the kernel passes by the
        entries a lane has no page for): ONE rung, whole blocks of the top
        seq rung, so that no chunk's pages and no block's run past it."""
        top, ps = self.seq_ladder[-1], self.pool.page_size
        if any(c % ps for c in self.seq_ladder):
            raise ValueError(f"a prefill chunk writes whole pages: every seq "
                             f"bucket {self.seq_ladder} must be a multiple of "
                             f"the page size {ps}")
        return [-(-self.max_seq // top) * (top // ps)]

    def _prefill_table_cols(self, seq_rung: int) -> int:
        return -(-int(seq_rung) // self.pool.page_size)

    @property
    def rungs(self) -> List[tuple]:
        """``("decode", b, t)`` over (batch × table) rungs plus
        ``("prefill", b, s)`` over the (batch × seq) grid — the prefill
        table width is a function of the seq rung, not a third axis.
        With speculation enabled, ``("draft", b, t)`` and ``("verify",
        b, t)`` join over the SAME (batch × table) grid — every batch
        shape a plain decode step can take, a speculation round can
        take too, so toggling speculation mid-flight never meets a cold
        rung (JX335 audits the parity)."""
        out = [("decode", b, t) for b in self.decode_rungs
               for t in self.table_rungs]
        if self.speculate_k:
            out += [("draft", b, t) for b in self.decode_rungs
                    for t in self.table_rungs]
            out += [("verify", b, t) for b in self.decode_rungs
                    for t in self.table_rungs]
        out += [("prefill", b, s) for b in self.prefill_batch_rungs
                for s in self.seq_ladder]
        return out + self.carry_rungs

    def _zero_args(self, key):
        def sample_args(b):
            return (np.zeros(b, np.float32), np.zeros(b, np.int32),
                    np.ones(b, np.float32), np.zeros((b, 2), np.uint32))

        if key[0] in ("decode", "draft"):
            _, b, t = key
            return (np.zeros(b, np.int32),          # tokens
                    np.zeros((b, t), np.int32),     # tables -> pad page
                    np.zeros(b, np.int32),          # positions
                    *sample_args(b))
        if key[0] == "verify":
            _, b, t = key
            return (np.zeros((b, self.speculate_k + 1), np.int32),
                    np.zeros((b, t), np.int32),
                    np.zeros(b, np.int32),
                    *sample_args(b))
        _, b, s = key
        t = self._prefill_table_cols(s)
        return (np.zeros((b, s), np.int32), np.ones(b, np.int32),
                np.zeros((b, t), np.int32), *sample_args(b))

    def _jitted(self, key):
        if key[0] == "draft":
            return self._jit_draft
        if key[0] == "verify":
            return self._jit_verify
        return super()._jitted(key)

    # -------------------------------------------------------------- calls
    def prefill(self, ck, cv, tokens, lengths, tables,
                temps, top_ks, top_ps, rkeys):
        return self._jit_prefill(self.params, ck, cv, tokens, lengths, tables,
                                 temps, top_ks, top_ps, rkeys)

    def decode(self, ck, cv, tokens, tables, positions,
               temps, top_ks, top_ps, rkeys):
        return self._jit_decode(self.params, ck, cv, tokens, tables, positions,
                                temps, top_ks, top_ps, rkeys)

    def draft(self, ck, cv, tokens, tables, positions,
              temps, top_ks, top_ps, rkeys):
        """One draft dispatch: k truncated-layer steps, proposals [B, k]."""
        return self._jit_draft(self.draft_params, ck, cv, tokens, tables,
                               positions, temps, top_ks, top_ps, rkeys)

    def verify(self, ck, cv, tokens, tables, positions,
               temps, top_ks, top_ps, rkeys):
        """One verify dispatch: full-model scores at all k+1 positions."""
        return self._jit_verify(self.params, ck, cv, tokens, tables, positions,
                                temps, top_ks, top_ps, rkeys)


def _extract_brumby(model):
    """A ``models.brumby.BrumbyForCausalLM``'s parameters as a plain
    pytree, zero-copy: the model already holds its layers stacked on a
    leading axis, which is what the programs scan over."""
    cfg = model.config
    stack = model.brumby.layers
    return {
        "embed": model.brumby.embed_tokens._value,
        "norm": model.brumby.norm._value,
        "head": model.lm_head._value,
        "layers": {name: p._value for name, p in stack._parameters.items()},
    }, cfg


def _rms(x, w, eps):
    """RMSNorm, the mean of squares in float32, the result in ``x``'s dtype."""
    import jax.numpy as jnp
    from jax import lax

    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotate-halves RoPE on all of the head's dimensions: ``x`` ``[N,
    heads, d]`` float32 at ``positions`` ``[N]``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


class RetentionPrograms(DecodePrograms):
    """The decode program set over a :class:`~.kv_cache.StateLanePool`,
    for a model whose layers are power-retention layers
    (``models/brumby.py``): no keys and values, a state of constant size
    a lane. The warm-up, compile-cache, donation and hot-swap machinery
    is the slot family's; the programs are not:

    - ``("prefill", 1, c)``: ONE CHUNK of one lane's prompt, ``c`` from
      the seq ladder (whole chunks run the top rung, a ragged last chunk
      the smallest rung that holds it). It takes the lane's state in and
      gives it back: inside the chunk the masked quadratic form, across
      chunks the state (``retention_chunk``). A prompt of 8k is four
      calls of one program; ``fresh`` tells the first that the lane's
      old state is to be ignored. Every call returns the token after its
      last valid position; the scheduler keeps the last chunk's.
    - ``("decode", b)``: one token a lane. The lanes' states are updated
      in place under donation and read once: on a TPU by the kernel of
      ``ops/pallas/retention.py``, which finds each lane's blocks through
      the prefetched slot ids, elsewhere by a gather, the jnp step and a
      scatter.

    The layers run under ONE ``lax.scan`` over the stacked weights, the
    pool riding the carry, so one layer is compiled once. Greedy only,
    as the slot family."""

    chunked = True
    _extract = staticmethod(_extract_brumby)

    def _bind_config(self, cfg) -> None:
        self._heads = int(cfg.num_attention_heads)
        self._kv_heads = int(cfg.num_key_value_heads)
        self._head_dim = int(cfg.head_dim)
        self._hidden = int(cfg.hidden_size)
        self._max_pos = int(cfg.max_position_embeddings)
        self._eps = float(cfg.rms_norm_eps)
        self._theta = float(cfg.rope_theta)

    # ------------------------------------------------------------ the block
    def _project(self, w, x, positions):
        """``x`` ``[N, hidden]`` at ``positions`` ``[N]`` -> q ``[N, Hq,
        d]`` and k ``[N, Hkv, d]`` in float32 (normed, rotated), v in the
        activations' dtype, ``log g`` ``[N, Hkv]`` in float32."""
        import jax
        import jax.numpy as jnp

        N, d = x.shape[0], self._head_dim
        with region(regions.NORM):
            a = _rms(x, w["input_norm"], self._eps)
        with region(regions.ATTN_QKV):
            qkv = a @ w["qkv_proj"]
            nq, nk = self._heads * d, self._kv_heads * d
            q = qkv[:, :nq].reshape(N, self._heads, d)
            k = qkv[:, nq:nq + nk].reshape(N, self._kv_heads, d)
            v = qkv[:, nq + nk:].reshape(N, self._kv_heads, d)
        with region(regions.RETN_GATE):
            log_g = jax.nn.log_sigmoid(
                jnp.dot(a, w["g_proj"], preferred_element_type=jnp.float32)
                + w["g_bias"])
        with region(regions.NORM):
            q = _rms(q.astype(jnp.float32), w["q_norm"], self._eps)
            k = _rms(k.astype(jnp.float32), w["k_norm"], self._eps)
        with region(regions.ROPE):
            q = _rope(q, positions, self._theta)
            k = _rope(k, positions, self._theta)
        return q, k, v, log_g

    def _finish(self, w, x, y):
        """The layer's second half: the output projection of the
        retention's ``y`` ``[N, Hq, d]``, then RMSNorm and SwiGLU."""
        import jax
        import jax.numpy as jnp

        with region(regions.ATTN_OUT):
            x = x + y.reshape(x.shape[0], -1).astype(x.dtype) @ w["o_proj"]
        with region(regions.NORM):
            b = _rms(x, w["post_norm"], self._eps)
        with region(regions.MLP):
            gate, up = jnp.split(b @ w["gate_up_proj"], 2, axis=-1)
            return x + (jax.nn.silu(gate) * up) @ w["down_proj"]

    def _logits_head(self, params, x):
        with region(regions.LM_HEAD):
            return _rms(x, params["norm"], self._eps) @ params["head"]

    # ------------------------------------------------------------ programs
    def _prefill_fn(self, params, state, tokens, lengths, slot_ids, starts,
                    fresh):
        import jax.numpy as jnp
        from jax import lax

        self.traces += 1
        with region(regions.PREFILL):
            C = tokens.shape[1]
            n, slot, start = lengths[0], slot_ids[0], starts[0]
            with region(regions.EMBED):
                x = params["embed"][tokens[0]]
            positions = start + jnp.arange(C, dtype=jnp.int32)
            valid = jnp.arange(C) < n

            def layer(carry, w):
                x, state, li = carry
                q, k, v, log_g = self._project(w, x, positions)
                y, state = self._state_chunk(state, li, slot, fresh[0],
                                             q, k, v, log_g, valid)
                return (self._finish(w, x, y), state, li + 1), None

            (x, state, _), _ = lax.scan(
                layer, (x, state, jnp.zeros((), jnp.int32)), params["layers"])
            # the token after the chunk's LAST VALID position
            with region(regions.LM_HEAD):
                x_last = lax.dynamic_slice_in_dim(x, n - 1, 1, axis=0)
            head = self._logits_head(params, x_last)
            with region(regions.SAMPLE):
                return state, jnp.argmax(head, axis=-1).astype(jnp.int32)

    def _state_chunk(self, state, li, slot, fresh, q, k, v, log_g, valid):
        """One prefill chunk of one layer against the pool: the lane's
        state taken out (ignored where the lane is ``fresh``), carried
        through the chunk, put back; ``y`` ``[C, Hq, d]`` read."""
        import jax.numpy as jnp
        from jax import lax

        from ..nn.functional.power_retention import retention_chunk

        with region(regions.RETN_CHUNK):
            zero = jnp.zeros((), jnp.int32)
            at = (li, slot, zero, zero, zero)
            held = lax.dynamic_slice(state, at, (1, 1) + state.shape[2:])[0, 0]
            keep = jnp.where(fresh != 0, 0.0, 1.0).astype(state.dtype)
        y, held = retention_chunk(q, k, v, log_g, held * keep, valid)
        with region(regions.RETN_CHUNK):
            return y, lax.dynamic_update_slice(state, held[None, None], at)

    def _state_step(self, state, li, slot_ids, q, k, v, log_g):
        """One decode step of one layer against the pool: the touched
        lanes' states updated in place, ``y`` ``[B, Hq, d]`` read."""
        from ..nn.functional import power_retention as pr

        if self._kernel():
            from ..ops.pallas import retention as kernel

            with region(regions.RETN_STATE):
                state, total = kernel.retention_step(
                    state, li, slot_ids, *pr.step_operands(q, k, v, log_g))
                return pr.finish_step(total), state
        with region(regions.RETN_STATE):
            held = state[li, slot_ids]
        y, held = pr.retention_step(q, k, v, log_g, held)
        with region(regions.RETN_STATE):
            return y, state.at[li, slot_ids].set(held)

    def _decode_fn(self, params, state, tokens, slot_ids, positions):
        import jax.numpy as jnp
        from jax import lax

        self.traces += 1
        with region(regions.DECODE):
            with region(regions.EMBED):
                x = params["embed"][tokens]

            def layer(carry, w):
                x, state, li = carry
                q, k, v, log_g = self._project(w, x, positions)
                y, state = self._state_step(state, li, slot_ids, q, k, v, log_g)
                return (self._finish(w, x, y), state, li + 1), None

            (x, state, _), _ = lax.scan(
                layer, (x, state, jnp.zeros((), jnp.int32)), params["layers"])
            head = self._logits_head(params, x)
            with region(regions.SAMPLE):
                return state, jnp.argmax(head, axis=-1).astype(jnp.int32)

    # -------------------------------------------------------------- rungs
    @property
    def rungs(self) -> List[tuple]:
        return ([("decode", b) for b in self.decode_rungs]
                + [("prefill", 1, c) for c in self.seq_ladder]
                + self.carry_rungs)

    def _zero_args(self, key):
        pad = self.pool.pad_slot
        if key[0] == "decode":
            return super()._zero_args(key)
        c = key[2]
        return (np.zeros((1, c), np.int32), np.ones(1, np.int32),
                np.full(1, pad, np.int32), np.zeros(1, np.int32),
                np.ones(1, np.int32))

    # -------------------------------------------------------------- calls
    def prefill(self, state, tokens, lengths, slot_ids, starts, fresh):
        return self._jit_prefill(self.params, state, tokens, lengths,
                                 slot_ids, starts, fresh)

    def decode(self, state, tokens, slot_ids, positions):
        return self._jit_decode(self.params, state, tokens, slot_ids,
                                positions)


def _extract_axk1(model):
    """A ``models.axk1.AXK1ForCausalLM``'s parameters as a plain pytree,
    zero-copy: the dense layers stacked in one tree, the sparse ones in
    another, as the model holds them."""
    def stack(layers):
        return {name: p._value for name, p in layers._parameters.items()}

    return {
        "embed": model.axk1.embed_tokens._value,
        "norm": model.axk1.norm._value,
        "head": model.lm_head._value,
        "dense": stack(model.axk1.dense),
        "sparse": stack(model.axk1.sparse),
    }, model.config


class LatentPrograms(PagedDecodePrograms):
    """The decode program set over a :class:`~.kv_cache.KVPagePool` of
    LATENT rows, for a model with multi-head latent attention and sparse
    experts (``models/axk1.py``). The pool is one array ``[layers, pages +
    1, page, W]``: a token's row in a layer is ``[c_kv | k_rope | zeros]``,
    K and V at once for every head (``W`` is the latent width rounded up to
    whole lanes of 128). Pages, block tables, admission by pages, the
    sampling arguments and :meth:`_choose_tokens` are the paged family's;
    the programs are not:

    - ``("prefill", 1, c)``: ONE CHUNK of one lane's prompt, ``c`` from the
      seq ladder (whole chunks run the top rung, a ragged last chunk the
      smallest rung that holds it), at ``start``, a multiple of the top
      rung. Each layer writes the chunk's latent rows to the lane's pages,
      then attends in the EXPANDED form over ``[the pages before the cursor
      | the chunk]``, a block of top-rung tokens at a time under a running
      softmax, keys and values formed from the stored rows a block and a
      group of heads at a time, so the temporaries stay a block's whatever
      the context. A 16k prompt is eight calls of one program.
    - ``("decode", b)``: one token a lane, ABSORBED: the key's
      up-projection moves onto the query, the scores and the weighted sum
      run over the latent rows themselves (on a TPU the kernel
      ``latent_paged_attn`` over the live pages where they lie, elsewhere
      ``gather_pages`` and ``attend_absorbed``), the value's up-projection
      comes after. There is ONE table rung, the whole table: the kernel
      skips the entries past a lane's last page, and with 64 lanes of
      mixed depths one is nearly always long.

    The dense layers run under one ``lax.scan`` and the sparse ones under
    another, the pool riding the carry. The routed experts' weights do NOT
    ride the scan: they are read as they lie, ``[layers x held, ...]``, and a
    layer's grouped product is told where its groups start
    (``sparse_experts.held_experts``); sliced out layer by layer they would
    be copied, 0.7 GB a layer a step. Each program returns, beside the
    tokens, the pairs each held expert computed, ``[sparse layers, held]``,
    which the scheduler reads in the same read (:meth:`note_step`)."""

    chunked = True
    _extract = staticmethod(_extract_axk1)
    HEAD_GROUP = 8   # heads whose scores exist at once in a prefill block

    def __init__(self, model, pool: KVPagePool, *, seq_ladder: Sequence[int],
                 decode_rungs: Sequence[int], max_seq: int):
        stack = model.axk1.sparse
        self._first, self._held = int(stack.first), int(stack.held)
        super().__init__(model, pool, seq_ladder=seq_ladder,
                         prefill_batch_rungs=[1], decode_rungs=decode_rungs,
                         max_seq=max_seq)
        self.table_rungs = self._one_table_rung()

    def _bind_config(self, cfg) -> None:
        from ..nn.functional import latent_attention as la

        self._cfg = cfg
        self._heads = int(cfg.num_attention_heads)
        self._nope = int(cfg.qk_nope_head_dim)
        self._rope_dim = int(cfg.qk_rope_head_dim)
        self._rank = int(cfg.kv_lora_rank)
        self._hidden = int(cfg.hidden_size)
        self._max_pos = int(cfg.max_position_embeddings)
        self._eps = float(cfg.rms_norm_eps)
        self._inv_freq = cfg.inv_freq()
        self._scale = float(la.softmax_scale(cfg.qk_head_dim, cfg.rope_scaling))
        self._dense_layers = int(cfg.first_k_dense_replace)
        if self.pool.row_width < cfg.latent_width:
            raise ValueError(f"the pool's rows are {self.pool.row_width} wide, a "
                             f"latent row needs {cfg.latent_width}")

    # ------------------------------------------------------------ the block
    def _latent_proj(self, w, x, positions):
        """``x`` ``[N, hidden]`` at ``positions`` ``[N]`` -> ``q_nope`` ``[N,
        H, dn]``, ``q_rope`` ``[N, H, dr]`` (rotated) and the tokens' cache
        rows ``[N, W]``: ``[c_kv (normed) | k_rope (rotated) | zeros]``."""
        from ..nn.functional import latent_attention as la

        N, rank = x.shape[0], self._rank
        with region(regions.NORM):
            a = _rms(x, w["input_norm"], self._eps)
        with region(regions.ATTN_LATENT_PROJ):
            c_q = _rms(a @ w["q_a_proj"], w["q_a_norm"], self._eps)
            q = (c_q @ w["q_b_proj"]).reshape(N, self._heads, -1)
            kv = a @ w["kv_a_proj"]
            c_kv = _rms(kv[:, :rank], w["kv_a_norm"], self._eps)
        with region(regions.ROPE):
            q_rope = la.rope(q[..., self._nope:], positions, self._inv_freq)
            k_rope = la.rope(kv[:, rank:], positions, self._inv_freq)
        return q[..., :self._nope], q_rope, self._cache_rows(c_kv, k_rope)

    def _cache_rows(self, c_kv, k_rope):
        """The tokens' rows as the pool holds them: ``[c_kv (normed) | k_rope
        (rotated) | zeros]``, ``[N, W]``."""
        import jax.numpy as jnp

        with region(regions.ATTN_KV_WRITE):
            pad = jnp.zeros((c_kv.shape[0], self.pool.row_width - c_kv.shape[1]
                             - k_rope.shape[1]), c_kv.dtype)
            return jnp.concatenate([c_kv, k_rope, pad], axis=-1)

    def _ffn(self, w, x, experts, group_offset, valid):
        """The layer's second half on ``x`` ``[N, hidden]``: a dense SwiGLU
        (``experts`` None), or the router, the held experts' part and the
        shared expert. ``valid`` ``[N]`` is False for a padding token, which
        picks no expert. Returns the new ``x`` and the held experts' pair
        counts (None for a dense layer)."""
        import jax.numpy as jnp

        from ..nn.functional import sparse_experts as se

        cfg = self._cfg
        with region(regions.NORM):
            b = _rms(x, w["post_norm"], self._eps)
        if experts is None:
            with region(regions.MLP):
                return x + se.swiglu(b, w["gate_up_proj"], w["down_proj"]), None
        idx, wt = se.route(b, w["router"], n_group=cfg.n_group,
                           topk_group=cfg.topk_group, top_k=cfg.num_experts_per_tok,
                           scaling=cfg.routed_scaling_factor,
                           norm_topk=cfg.norm_topk_prob)
        idx = jnp.where(valid[:, None], idx, -1)
        routed, counts = se.held_experts(
            b, idx, wt, *experts, first=self._first, held=self._held,
            group_offset=group_offset)
        with region(regions.MOE_SHARED):
            shared = se.swiglu(b, w["shared_gate_up"], w["shared_down"])
        return x + shared + routed, counts

    def _stacks(self, params, x, pool, attend, valid):
        """Every layer: the dense stack, then the sparse one, each under one
        scan with the pool on the carry. ``attend(w, x, pool, li)`` is the
        layer's first half (it writes the pool). Returns ``x``, the pool and
        the pair counts ``[sparse layers, held]``."""
        import jax.numpy as jnp
        from jax import lax

        def dense(carry, w):
            x, pool, li = carry
            x, pool = attend(w, x, pool, li)
            x, _ = self._ffn(w, x, None, None, valid)
            return (x, pool, li + 1), None

        sparse_w = dict(params["sparse"])
        # the routed experts as they lie, every layer's groups in one run
        experts = tuple(
            sparse_w.pop(name).reshape((-1,) + params["sparse"][name].shape[2:])
            for name in ("experts_gate_up", "experts_down"))

        def sparse(carry, w):
            x, pool, li = carry
            x, pool = attend(w, x, pool, li)
            x, counts = self._ffn(w, x, experts,
                                  (li - self._dense_layers) * self._held, valid)
            return (x, pool, li + 1), counts

        carry = (x, pool, jnp.zeros((), jnp.int32))
        carry, _ = lax.scan(dense, carry, params["dense"])
        (x, pool, _), counts = lax.scan(sparse, carry, sparse_w)
        return x, pool, counts

    def _logits_head(self, params, x):
        with region(regions.LM_HEAD):
            return _rms(x, params["norm"], self._eps) @ params["head"]

    # ------------------------------------------------------------ attention
    def _attend_chunk(self, w, q_nope, q_rope, pool, li, table, start):
        """Expanded attention of a chunk's ``C`` queries at ``start ..
        start + C - 1`` over the lane's pages, the chunk's own rows already
        written: blocks of ``top rung`` tokens (``start`` is a multiple of
        it, so the chunk lies in the last block), ``start // top + 1`` of
        them, each gathered from the pool and expanded for
        :attr:`HEAD_GROUP` heads at a time. ``table`` ``[T]``."""
        import jax.numpy as jnp
        from jax import lax

        from ..nn.functional import latent_attention as la

        C, H, dn = q_nope.shape
        rank, dr, W = self._rank, self._rope_dim, self.pool.row_width
        blk, ps = self.seq_ladder[-1], self.pool.page_size
        G = min(self.HEAD_GROUP, H)
        qpos = start + jnp.arange(C, dtype=jnp.int32)
        n_blocks = start // blk + 1

        def heads_first(a):     # [N, H, d] -> [H / G, N, G, d]
            return a.reshape(a.shape[0], H // G, G, -1).transpose(1, 0, 2, 3)

        def group(a):
            q, wk, wv = a

            def block(j, carry):
                with region(regions.ATTN_KV_GATHER):
                    pages = lax.dynamic_slice(table, (j * (blk // ps),), (blk // ps,))
                    rows = pool[li, pages].reshape(blk, W)
                col = j * blk + jnp.arange(blk, dtype=jnp.int32)
                return la.expanded_block(
                    carry, q, rows[:, :rank], rows[:, rank:rank + dr],
                    wk, wv, col[None, :] <= qpos[:, None], self._scale)

            carry = lax.fori_loop(0, n_blocks, block,
                                  la.start_blocks(C, G, wv.shape[-1]))
            return la.finish_blocks(carry, q_nope.dtype)

        out = lax.map(group, (heads_first(jnp.concatenate([q_nope, q_rope], -1)),
                              heads_first(w["k_b_proj"].reshape(rank, H, dn)),
                              heads_first(w["v_b_proj"].reshape(rank, H, -1))))
        return out.transpose(1, 0, 2, 3).reshape(C, -1)

    def _attend_step(self, w, q_nope, q_rope, pool, li, tables, positions):
        """Absorbed attention of one query a lane over its latent pages, the
        step's own rows already written -> ``[B, heads x dv]``."""
        import jax.numpy as jnp

        from ..nn.functional import latent_attention as la

        with region(regions.ATTN_LATENT_PROJ):
            q_lat = la.absorb_q(q_nope, w["k_b_proj"])
        if self._kernel():
            from ..ops.pallas import paged_attention as kernel

            B, H, _ = q_lat.shape
            with region(regions.ATTN_CORE):
                pad = jnp.zeros((B, H, pool.shape[-1] - self._rank - self._rope_dim),
                                q_lat.dtype)
                # q in the pool's dtype: the kernel's products take one
                q = jnp.concatenate([q_lat, q_rope, pad], axis=-1).astype(pool.dtype)
                o_lat = kernel.latent_paged_attention(
                    q, pool, li, tables, positions, v_cols=self._rank,
                    scale=self._scale)
        else:
            rows = kvc.gather_pages(pool, li, tables)
            with region(regions.ATTN_CORE):
                o_lat = la.attend_absorbed(q_lat, q_rope, rows, positions,
                                           self._rank, self._scale)
        with region(regions.ATTN_LATENT_PROJ):
            return la.unabsorb(o_lat, w["v_b_proj"])

    # ------------------------------------------------------------ programs
    def _prefill_fn(self, params, pool, tokens, lengths, tables, starts,
                    temps, top_ks, top_ps, rkeys):
        import jax.numpy as jnp
        from jax import lax

        self.traces += 1
        with region(regions.PREFILL):
            C, ps = tokens.shape[1], self.pool.page_size
            n, start, table = lengths[0], starts[0], tables[0]
            with region(regions.EMBED):
                x = params["embed"][tokens[0]]
            positions = start + jnp.arange(C, dtype=jnp.int32)
            with region(regions.ATTN_KV_WRITE):
                # the chunk's pages: whole ones, `start` and `C` being
                # multiples of the page size; entries past the lane's own
                # are 0, the pad page
                pages = lax.dynamic_slice(table, (start // ps,), (C // ps,))

            def attend(w, x, pool, li):
                q_nope, q_rope, rows = self._latent_proj(w, x, positions)
                pool = kvc.write_chunk_pages(pool, li, pages, rows)
                att = self._attend_chunk(w, q_nope, q_rope, pool, li, table, start)
                with region(regions.ATTN_OUT):
                    return x + att @ w["o_proj"], pool

            x, pool, counts = self._stacks(params, x, pool, attend,
                                           jnp.arange(C) < n)
            # the token after the chunk's LAST VALID position
            with region(regions.LM_HEAD):
                x_last = lax.dynamic_slice_in_dim(x, n - 1, 1, axis=0)
            head = self._logits_head(params, x_last)
            return pool, self._choose_tokens(head, temps, top_ks, top_ps,
                                             rkeys), counts

    def _decode_fn(self, params, pool, tokens, tables, positions,
                   temps, top_ks, top_ps, rkeys):
        import jax.numpy as jnp

        self.traces += 1
        with region(regions.DECODE):
            ps = self.pool.page_size
            with region(regions.EMBED):
                x = params["embed"][tokens]
            with region(regions.ATTN_KV_WRITE):
                pages = jnp.take_along_axis(
                    tables, (positions // ps).astype(jnp.int32)[:, None], axis=1)[:, 0]
                offsets = (positions % ps).astype(jnp.int32)

            def attend(w, x, pool, li):
                q_nope, q_rope, rows = self._latent_proj(w, x, positions)
                pool = kvc.append_token_paged(pool, li, pages, offsets, rows)
                att = self._attend_step(w, q_nope, q_rope, pool, li, tables,
                                        positions)
                with region(regions.ATTN_OUT):
                    return x + att @ w["o_proj"], pool

            # a padded batch lane's table names the pad page only
            x, pool, counts = self._stacks(params, x, pool, attend,
                                           tables[:, 0] != self.pool.pad_page)
            head = self._logits_head(params, x)
            return pool, self._choose_tokens(head, temps, top_ks, top_ps,
                                             rkeys), counts

    # -------------------------------------------------------------- rungs
    @property
    def rungs(self) -> List[tuple]:
        return ([("decode", b) for b in self.decode_rungs]
                + [("prefill", 1, c) for c in self.seq_ladder]
                + self.carry_rungs)

    def _zero_args(self, key):
        t = self.table_rungs[-1]
        if key[0] == "decode":
            return PagedDecodePrograms._zero_args(self, ("decode", key[1], t))
        c = key[2]
        tokens, lengths, tables, *sample = PagedDecodePrograms._zero_args(
            self, ("prefill", 1, c))
        return (tokens, lengths, np.zeros((1, t), np.int32),
                np.zeros(1, np.int32), *sample)

    # -------------------------------------------------------------- calls
    def prefill(self, pool, tokens, lengths, tables, starts,
                temps, top_ks, top_ps, rkeys):
        return self._jit_prefill(self.params, pool, tokens, lengths, tables,
                                 starts, temps, top_ks, top_ps, rkeys)

    def decode(self, pool, tokens, tables, positions,
               temps, top_ks, top_ps, rkeys):
        return self._jit_decode(self.params, pool, tokens, tables, positions,
                                temps, top_ks, top_ps, rkeys)

    def note_step(self, counts, tokens: int) -> dict:
        """What a step's second output says, for its span and the registry:
        ``counts`` ``[sparse layers, held]`` (already on the host) ->
        ``pairs`` (token-expert pairs the held experts computed) and
        ``experts_hit`` (held experts with at least one, over the layers);
        ``tokens`` latent rows were written in each layer."""
        from ..observability.metrics import registry

        registry.counter(
            "serving.latent.rows_written",
            "latent cache rows written (one a token a layer)").inc(
                int(tokens) * self.pool.num_layers)
        return _note_experts(counts)


def _note_experts(counts) -> dict:
    """``counts`` ``[sparse layers, held]`` (on the host), the pairs each
    held expert computed in one program call -> what the call's span says of
    them (``pairs``, ``experts_hit``), and the registry's two counters."""
    from ..observability.metrics import registry

    pairs, hit = int(counts.sum()), int((counts > 0).sum())
    registry.counter(
        "serving.moe.pairs",
        "token-expert pairs computed by the held experts").inc(pairs)
    registry.counter(
        "serving.moe.experts_hit",
        "held experts that computed at least one pair, summed over "
        "layers and steps").inc(hit)
    return {"pairs": pairs, "experts_hit": hit}


def _extract_cohere2(model):
    """A ``models.cohere2_moe.Cohere2MoEForCausalLM``'s parameters as a plain
    pytree, zero-copy: one tree a layer (nothing is stacked), the embedding
    (which is the head too) and the final norm."""
    return {
        "embed": model.model.embed_tokens._value,
        "norm": model.model.norm._value,
        "layers": [{name: p._value for name, p in block._parameters.items()}
                   for block in model.model.layers],
    }, model.config


def _ln_plain(x, w, eps):
    """LayerNorm without a bias, the statistics in float32, the result in
    ``x``'s dtype."""
    import jax.numpy as jnp
    from jax import lax

    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


class WindowedPrograms(PagedDecodePrograms):
    """The decode program set over a :class:`~.kv_cache.WindowedPagePools`,
    for a model whose layers are parallel blocks over sparse experts, some
    with a sliding window and rotary positions, some global and without
    positions (``models/cohere2_moe.py``). Rungs, warm-up, the sampling
    arguments, :meth:`_choose_tokens`, the token carry and the late read are
    the paged family's; the expert layer is ``LatentPrograms``' (``route`` and
    ``held_experts`` told the share, the pair counts returned beside the
    tokens). What differs:

    - FOUR pool arrays ride a call, the window pool's K and V and the global
      pool's, and ONE table argument ``[rows, 2, cols]`` names a lane's
      pages in each (``[:, 0]`` window, ``[:, 1]`` global; the same logical
      columns, a released window column reading 0, the pad page);
    - the layers are unrolled (they are of two kinds over two pools; a
      layer's weights are arrays of their own, nothing is sliced out of a
      stack) and each is a PARALLEL block: one LayerNorm feeds q, k, v, the
      router and the experts, and attention and experts are both added to
      the residual;
    - ``("decode", b)``: one token a lane, its K/V row appended to the
      layer's pool, then grouped-query attention over the lane's pages: on a
      TPU the kernel ``gqa_paged_attn`` (``ops/pallas/paged_attention.py``),
      told the layer's window or none, elsewhere ``gather_pages`` and
      ``window_attention.attend_grouped``. One table rung, the whole table;
    - ``("prefill", 1, c)``: ONE CHUNK of one lane's prompt at ``start``, a
      multiple of the top rung: each layer writes the chunk's rows to whole
      pages, then attends over the lane's pages. On a TPU that is the flash
      kernel ``gqa_chunk_attn`` (``ops/pallas/paged_attention.py``): K and V
      read through the table where they lie, a tile's scores kept in VMEM,
      only the table columns a tile of queries sees visited (a global layer's
      up to the tile's own, a window layer's from the window's first).
      Elsewhere (:meth:`_attend_chunk_blocks`, the kernel's oracle) blocks
      of top-rung keys are gathered and scored under a running softmax, a
      global layer over every block up to the chunk's own, a window layer
      over the blocks its first query still sees (the chunk's own and at
      most ``ceil((window - 1) / top)`` before it). Blocks of
      :attr:`ROPE_ROWS` rows of q are rotated one after another, so the
      float32 copies the rotation makes are a block's, not the chunk's."""

    chunked = True
    _extract = staticmethod(_extract_cohere2)
    HEAD_GROUP = 8   # off the TPU: query heads whose scores exist at once in a prefill block
    ROPE_ROWS = 256  # rows rotated at once: the float32 copies of a chunk's q are a block's, not the chunk's

    def __init__(self, model, pool: WindowedPagePools, *,
                 seq_ladder: Sequence[int], decode_rungs: Sequence[int],
                 max_seq: int):
        block = model.model.layers[0]
        self._first, self._held = int(block.first), int(block.held)
        super().__init__(model, pool, seq_ladder=seq_ladder,
                         prefill_batch_rungs=[1], decode_rungs=decode_rungs,
                         max_seq=max_seq)
        self.table_rungs = self._one_table_rung()

    def _bind_config(self, cfg) -> None:
        self._cfg = cfg
        self._heads = int(cfg.num_attention_heads)
        self._kv_heads = int(cfg.num_key_value_heads)
        self._head_dim = int(cfg.head_dim)
        self._hidden = int(cfg.hidden_size)
        self._max_pos = int(cfg.max_position_embeddings)
        self._eps = float(cfg.layer_norm_eps)
        self._theta = float(cfg.rope_theta)
        self._scale = 1.0 / math.sqrt(cfg.head_dim)
        self._logit_scale = float(cfg.logit_scale)
        # a layer's window (None: global) and its index in ITS pool
        self._windows = [cfg.window_of(i) for i in range(cfg.num_hidden_layers)]
        seen = {True: 0, False: 0}
        self._pool_index = []
        for w in self._windows:
            self._pool_index.append(seen[w is not None])
            seen[w is not None] += 1
        pool = self.pool
        if (seen[True] != pool.window.num_layers or seen[False] != pool.full.num_layers
                or pool.window.row_width != self._kv_heads * self._head_dim
                or any(w != pool.window_rows for w in self._windows if w)):
            raise ValueError(
                f"the pools hold {pool.window.num_layers} window and "
                f"{pool.full.num_layers} global layers of rows "
                f"{pool.window.row_width} wide under a window of "
                f"{pool.window_rows}; the model has {seen[True]} and "
                f"{seen[False]} of {self._kv_heads * self._head_dim} under "
                f"{cfg.sliding_window}")

    # ------------------------------------------------------------ the block
    def _qkv(self, w, x, positions, window):
        """``x`` ``[N, hidden]`` at ``positions`` ``[N]`` -> the block's norm
        ``n`` and q ``[N, heads x d]``, k, v ``[N, kv_heads x d]``; q and k
        rotated in a window layer, left alone in a global one."""
        with region(regions.LN):
            n = _ln_plain(x, w["ln"], self._eps)
        with region(regions.ATTN_QKV):
            q, k, v = n @ w["q_proj"], n @ w["k_proj"], n @ w["v_proj"]
        if window is not None:
            with region(regions.ROPE):
                q, k = (self._rotated(a, positions) for a in (q, k))
        return n, q, k, v

    def _rotated(self, a, positions):
        """``a`` ``[N, heads x d]`` turned by ``positions`` ``[N]``, blocks of
        :attr:`ROPE_ROWS` rows one after another."""
        from jax import lax

        from ..nn.functional import window_attention as wa

        def block(a, positions):
            return wa.rope_interleaved(
                a.reshape(a.shape[0], -1, self._head_dim), positions,
                self._theta).astype(a.dtype).reshape(a.shape)

        N, blocks = a.shape[0], a.shape[0] // self.ROPE_ROWS
        if blocks < 2 or N % self.ROPE_ROWS:
            return block(a, positions)
        return lax.map(lambda x: block(*x), (a.reshape(blocks, self.ROPE_ROWS, -1),
                                             positions.reshape(blocks, -1))).reshape(N, -1)

    def _ffn(self, w, n, valid):
        """The block's experts on its norm ``n`` ``[N, hidden]``: the held
        experts' part and the shared experts' mean. ``valid`` ``[N]`` is False
        for a padding token, which picks no expert. Returns the sum and the
        held experts' pair counts."""
        import jax.numpy as jnp

        from ..nn.functional import sparse_experts as se

        cfg = self._cfg
        idx, wt = se.route(n, w["router"], n_group=1, topk_group=1,
                           top_k=cfg.num_experts_per_tok, scaling=1.0,
                           norm_topk=cfg.norm_topk_prob, group_limited=False)
        idx = jnp.where(valid[:, None], idx, -1)
        routed, counts = se.held_experts(
            n, idx, wt, w["experts_gate_up"], w["experts_down"],
            first=self._first, held=self._held)
        with region(regions.MOE_SHARED):
            shared = se.swiglu(n, w["shared_gate_up"], w["shared_down"])
            shared = shared * (1.0 / cfg.num_shared_experts)
        return shared + routed, counts

    def _layers(self, params, x, positions, pools, attend, valid):
        """Every layer, unrolled, on ``x`` ``[N, hidden]`` at ``positions``
        ``[N]``: ``attend(li, window, q, k, v, kp, vp)`` writes the layer's
        pool and returns ``(attention [N, heads x d], kp, vp)``. ``pools``
        ``(wk, wv, fk, fv)``. Returns ``x``, the pools and the pair counts
        ``[layers, held]``."""
        import jax.numpy as jnp

        wk, wv, fk, fv = pools
        counts = []
        for li, w in enumerate(params["layers"]):
            window = self._windows[li]
            n, q, k, v = self._qkv(w, x, positions, window)
            if window is not None:
                att, wk, wv = attend(li, window, q, k, v, wk, wv)
            else:
                att, fk, fv = attend(li, window, q, k, v, fk, fv)
            with region(regions.ATTN_OUT):
                att = att @ w["o_proj"]
            ffn, c = self._ffn(w, n, valid)
            x = x + att + ffn
            counts.append(c)
        return x, (wk, wv, fk, fv), jnp.stack(counts)

    def _logits_head(self, params, x):
        with region(regions.LM_HEAD):
            h = _ln_plain(x, params["norm"], self._eps) @ params["embed"].T
            return h if self._logit_scale == 1 else h * self._logit_scale

    # ------------------------------------------------------------ attention
    def _attend_chunk(self, q, kp, vp, li, table, start, window):
        """A chunk's ``C`` queries at ``start .. start + C - 1`` (``q`` ``[C,
        heads x d]``; ``start`` a multiple of the page, the chunk inside one
        block of ``top rung`` rows) over the lane's pages of pool layer
        ``li``, the chunk's own rows already written. ``table`` ``[T]``. On a
        TPU the flash kernel ``gqa_chunk_attn``, which reads the pages where
        they lie, elsewhere :meth:`_attend_chunk_blocks`."""
        if self._kernel():
            from ..ops.pallas import paged_attention as kernel

            with region(regions.ATTN_CORE):
                return kernel.gqa_chunk_attention(
                    q, kp, vp, li, table, start, kv_heads=self._kv_heads,
                    scale=self._scale, window=window)
        return self._attend_chunk_blocks(q, kp, vp, li, table, start, window)

    def _attend_chunk_blocks(self, q, kp, vp, li, table, start, window):
        """:meth:`_attend_chunk` off the TPU, and the kernel's oracle: blocks
        of ``top rung`` keys under a running softmax, each gathered from the
        pool, the chunk in the last one; a window layer starts at the first
        block its first query can see."""
        import jax.numpy as jnp
        from jax import lax

        from ..nn.functional import window_attention as wa

        C = q.shape[0]
        G, d = self._kv_heads, self._head_dim
        blk, ps = self.seq_ladder[-1], self.pool.page_size
        qpos = start + jnp.arange(C, dtype=jnp.int32)
        last = start // blk
        first = (jnp.zeros((), jnp.int32) if window is None else
                 jnp.maximum(last - (-(-(window - 1) // blk)), 0))
        # a K/V head's query heads in `parts` runs of HEAD_GROUP: so many
        # heads' scores exist at once, [C x HEAD_GROUP, blk] in float32
        parts = max(self._heads // G // self.HEAD_GROUP, 1)
        qg = q.reshape(C, G * parts, -1, d).transpose(1, 0, 2, 3)

        def block(j, carry):
            with region(regions.ATTN_KV_GATHER):
                pages = lax.dynamic_slice(table, (j * (blk // ps),), (blk // ps,))
                k, v = (jnp.repeat(a[li, pages].reshape(blk, G, d).transpose(1, 0, 2),
                                   parts, axis=0) for a in (kp, vp))
            col = j * blk + jnp.arange(blk, dtype=jnp.int32)
            seen = col[None, :] <= qpos[:, None]
            if window is not None:
                seen &= qpos[:, None] - col[None, :] < window
            with region(regions.ATTN_CORE):
                return wa.grouped_block(carry, qg, k, v, seen, self._scale)

        carry = lax.fori_loop(first, last + 1, block,
                              wa.start_blocks(C, G * parts, qg.shape[2], d))
        with region(regions.ATTN_CORE):
            return wa.finish_blocks(carry, q.dtype)

    def _attend_step(self, q, kp, vp, li, tables, positions, window):
        """One query a lane (``q`` ``[B, heads x d]`` at ``positions``) over
        its pages of pool layer ``li``, the step's own rows already written."""
        from ..nn.functional import window_attention as wa

        if self._kernel():
            from ..ops.pallas import paged_attention as kernel

            with region(regions.ATTN_CORE):
                return kernel.gqa_paged_attention(
                    q, kp, vp, li, tables, positions, kv_heads=self._kv_heads,
                    scale=self._scale, window=window)
        keys = kvc.gather_pages(kp, li, tables)
        vals = kvc.gather_pages(vp, li, tables)
        with region(regions.ATTN_CORE):
            return wa.attend_grouped(q, keys, vals, positions, self._kv_heads,
                                     self._scale, window)

    # ------------------------------------------------------------ programs
    def _prefill_fn(self, params, wk, wv, fk, fv, tokens, lengths, tables,
                    starts, temps, top_ks, top_ps, rkeys):
        import jax.numpy as jnp
        from jax import lax

        self.traces += 1
        with region(regions.PREFILL):
            C, ps = tokens.shape[1], self.pool.page_size
            n, start = lengths[0], starts[0]
            with region(regions.EMBED):
                x = params["embed"][tokens[0]]
            with region(regions.ATTN_KV_WRITE):
                # the chunk's pages in each table: whole ones, `start` and
                # `C` being multiples of the page size; entries past the
                # lane's own are 0, the pad page
                pages = [lax.dynamic_slice(tables[0, kind], (start // ps,), (C // ps,))
                         for kind in (0, 1)]

            def attend(li, window, q, k, v, kp, vp):
                kind, pli = int(window is None), self._pool_index[li]
                kp = kvc.write_chunk_pages(kp, pli, pages[kind], k)
                vp = kvc.write_chunk_pages(vp, pli, pages[kind], v)
                return (self._attend_chunk(q, kp, vp, pli, tables[0, kind],
                                           start, window), kp, vp)

            x, pools, counts = self._layers(
                params, x, start + jnp.arange(C, dtype=jnp.int32),
                (wk, wv, fk, fv), attend, jnp.arange(C) < n)
            # the token after the chunk's LAST VALID position
            with region(regions.LM_HEAD):
                x_last = lax.dynamic_slice_in_dim(x, n - 1, 1, axis=0)
            head = self._logits_head(params, x_last)
            return (*pools, self._choose_tokens(head, temps, top_ks, top_ps,
                                                rkeys), counts)

    def _decode_fn(self, params, wk, wv, fk, fv, tokens, tables, positions,
                   temps, top_ks, top_ps, rkeys):
        import jax.numpy as jnp

        self.traces += 1
        with region(regions.DECODE):
            ps = self.pool.page_size
            with region(regions.EMBED):
                x = params["embed"][tokens]
            with region(regions.ATTN_KV_WRITE):
                col = (positions // ps).astype(jnp.int32)[:, None, None]
                pages = jnp.take_along_axis(tables, col, axis=2)[:, :, 0]   # [B, 2]
                offsets = (positions % ps).astype(jnp.int32)

            def attend(li, window, q, k, v, kp, vp):
                kind, pli = int(window is None), self._pool_index[li]
                kp = kvc.append_token_paged(kp, pli, pages[:, kind], offsets, k)
                vp = kvc.append_token_paged(vp, pli, pages[:, kind], offsets, v)
                return (self._attend_step(q, kp, vp, pli, tables[:, kind],
                                          positions, window), kp, vp)

            # a padded batch lane's tables name the pad page only
            x, pools, counts = self._layers(
                params, x, positions, (wk, wv, fk, fv), attend,
                tables[:, 1, 0] != self.pool.pad_page)
            head = self._logits_head(params, x)
            return (*pools, self._choose_tokens(head, temps, top_ks, top_ps,
                                                rkeys), counts)

    # -------------------------------------------------------------- rungs
    @property
    def rungs(self) -> List[tuple]:
        return ([("decode", b) for b in self.decode_rungs]
                + [("prefill", 1, c) for c in self.seq_ladder]
                + self.carry_rungs)

    def _zero_args(self, key):
        t = self.table_rungs[-1]
        if key[0] == "decode":
            tokens, _, *rest = PagedDecodePrograms._zero_args(
                self, ("decode", key[1], t))
            return (tokens, np.zeros((key[1], 2, t), np.int32), *rest)
        tokens, lengths, _, *sample = PagedDecodePrograms._zero_args(
            self, ("prefill", 1, key[2]))
        return (tokens, lengths, np.zeros((1, 2, t), np.int32),
                np.zeros(1, np.int32), *sample)

    # -------------------------------------------------------------- calls
    def prefill(self, wk, wv, fk, fv, tokens, lengths, tables, starts,
                temps, top_ks, top_ps, rkeys):
        return self._jit_prefill(self.params, wk, wv, fk, fv, tokens, lengths,
                                 tables, starts, temps, top_ks, top_ps, rkeys)

    def decode(self, wk, wv, fk, fv, tokens, tables, positions,
               temps, top_ks, top_ps, rkeys):
        return self._jit_decode(self.params, wk, wv, fk, fv, tokens, tables,
                                positions, temps, top_ks, top_ps, rkeys)

    def note_step(self, counts, tokens: int) -> dict:
        """``counts`` ``[layers, held]`` -> the span's ``pairs`` and
        ``experts_hit``, as the latent family says them."""
        return _note_experts(counts)


class DecodeEngine(EngineBase):
    """Decode serving with true continuous batching.

    ``model`` is a live ``models.gpt.GPTForCausalLM``,
    ``models.brumby.BrumbyForCausalLM``, ``models.axk1.AXK1ForCausalLM`` or
    ``models.cohere2_moe.Cohere2MoEForCausalLM``
    (eval mode; its device weights are shared zero-copy with
    training/export users).
    Requests (:meth:`submit`) join the running batch at the next step
    boundary and leave when their last token is read, one beat after the
    step that made it — the scheduler dispatches ONE prefill-or-decode
    program call per beat against the warmed rung set (and reads the call
    before: :class:`~.scheduler.DecodeScheduler`), so
    ``compiles_after_warmup == 0`` holds under any mix of prefill and
    decode traffic (JX330), the KV pool footprint never moves after
    warmup (JX332), and greedy tokens are bit-exact with a
    single-request decode of the same prompt.

    What a sequence holds on the device between steps follows from the
    model (its ``serving_residency``): keys and values (a GPT, in pages or
    slots as ``kv_mode`` says), a recurrent state of constant size in a
    lane (``"state"``: :class:`RetentionPrograms` over a
    :class:`~.kv_cache.StateLanePool`, chunked prefill carrying the
    state), or latent rows in pages, one a token a layer, K and V at once
    (``"latent"``: :class:`LatentPrograms` over a one-array
    :class:`~.kv_cache.KVPagePool`, chunked prefill over the pages before
    the cursor, page and pool sizes from ``page_size``/``pool_pages``), or
    K/V pages of two lifetimes (``"windowed"``: :class:`WindowedPrograms`
    over a :class:`~.kv_cache.WindowedPagePools`, the window layers' pages
    given back as the window passes them, ``window_pool_pages`` of them
    beside the global layers' ``pool_pages``).
    Five program families on one chassis; for a GPT, two KV residency
    modes (``kv_mode``):

    - ``"paged"`` (default, ISSUE 18): a :class:`~.kv_cache.KVPagePool`
      holds fixed-size pages; each request owns only the pages its live
      tokens fill, named by a per-request block table that rides the
      compiled programs as TRACED int32 data — one executable per
      (batch rung × table rung), any page map. Mixed 128–4k contexts
      stop stranding worst-case rows, admission waits for pages instead
      of shedding, and sampled decoding (``temperature``/``top_k``/
      ``top_p``/``seed`` on :meth:`submit`) draws from a per-request
      PRNG stream that is deterministic per seed and independent of
      batch composition.
    - ``"slots"`` (PR 13): one full ``max_seq`` row per request — the
      greedy bit-exact oracle the paged mode is audited against.
    """

    def __init__(self, model, *,
                 max_slots: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 prefill_max_batch: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 kv_dtype: str = "float32",
                 kv_mode: str = "paged",
                 page_size: Optional[int] = None,
                 pool_pages: Optional[int] = None,
                 window_pool_pages: Optional[int] = None,
                 speculate_k: Optional[int] = None,
                 spec_draft_layers: Optional[int] = None,
                 spec_min_accept: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 tenant_quota: Optional[int] = None,
                 request_ttl_ms: Optional[float] = None,
                 serve_telemetry_port: Optional[int] = None,
                 stats=serving_stats):
        from ..jit.bucketing import powers_of_two_buckets

        super().__init__(max_queue=max_queue, tenant_quota=tenant_quota,
                         request_ttl_ms=request_ttl_ms,
                         serve_telemetry_port=serve_telemetry_port,
                         stats=stats)
        if kv_mode not in ("paged", "slots"):
            raise ValueError(f"kv_mode must be 'paged' or 'slots', "
                             f"got {kv_mode!r}")
        cfg = model.config
        # the residency follows from the model: one whose layers keep a
        # recurrent state (``serving_residency = "state"``) has no keys
        # and values to page or slot, whatever ``kv_mode`` says
        residency = getattr(model, "serving_residency", "kv")
        if residency in ("state", "latent", "windowed"):
            kv_mode = residency
        max_slots = int(get_flag("serving_max_slots")
                        if max_slots is None else max_slots)
        flag_seq = int(get_flag("serving_max_seq"))
        max_seq = int(max_seq if max_seq is not None
                      else (flag_seq or cfg.max_position_embeddings))
        if max_seq > cfg.max_position_embeddings:
            raise ValueError(
                f"max_seq {max_seq} exceeds the model's position table "
                f"({cfg.max_position_embeddings})")
        prefill_max = int(get_flag("serving_prefill_max_batch")
                          if prefill_max_batch is None else prefill_max_batch)
        prefill_max = min(prefill_max, max_slots)
        if seq_buckets is None and kv_mode in ("state", "latent", "windowed"):
            seq_buckets = [min(2048, max_seq)]  # the prefill chunk
        if seq_buckets is None:
            seq_min = min(int(get_flag("serving_seq_bucket_min")), max_seq)
            # clamp the top rung: the power-of-two ladder rounds UP past a
            # non-power-of-two max_seq, but a slot can't hold more rows
            seq_buckets = sorted({min(s, max_seq) for s in
                                  powers_of_two_buckets(seq_min, max_seq)})
        seq_buckets = sorted(int(s) for s in seq_buckets)
        if seq_buckets[-1] > max_seq:
            raise ValueError(f"seq bucket {seq_buckets[-1]} exceeds "
                             f"max_seq {max_seq}")
        spec_k = int(get_flag("serving_spec_k")
                     if speculate_k is None else speculate_k)
        spec_k = max(spec_k, 0)
        if kv_mode != "paged" and spec_k > 0:
            raise ValueError(
                "self-speculative decoding rides the paged block tables; "
                "the slots-mode engine is the greedy oracle, a state "
                "lane has no rollback, and a latent-attention model's "
                "draft would be a truncated stack of the same weights "
                "whose layer 0 is another kind of layer (dense) than the "
                "rest, and a window table cannot be rolled back (a page "
                "released as the window passed it is another lane's by the "
                "time a draft is refused) — use kv_mode='paged' (a GPT) for "
                "speculate_k > 0")
        self.kv_mode = kv_mode
        #: the residencies that hold pages named by block tables
        self._pages = kv_mode in ("paged", "latent", "windowed")
        self.max_slots = max_slots  # max concurrent lanes in either mode
        self.eos_id = eos_id
        self.speculate_k = spec_k
        self._model = model  # the weight source swap_weights re-extracts
        from ..reliability.policy import RetryPolicy

        retry = RetryPolicy("serving.decode_step")

        def paged_sizes():
            """The page size and the count of pages held for a request's
            life: the arguments, else the flags, else (equal bytes) the
            token capacity a slot pool of ``max_slots`` full rows holds."""
            ps = int(get_flag("serving_page_size")
                     if page_size is None else page_size)
            n_pages = int(get_flag("serving_pool_pages")
                          if pool_pages is None else pool_pages)
            return ps, n_pages if n_pages > 0 else -(-max_slots * max_seq // ps)

        if kv_mode == "state":
            self.kv_pool = StateLanePool(
                cfg.num_hidden_layers, max_slots, cfg.num_key_value_heads,
                cfg.head_dim, max_seq)
            self.programs = RetentionPrograms(
                model, self.kv_pool, seq_ladder=seq_buckets,
                prefill_batch_rungs=[1],
                decode_rungs=powers_of_two_buckets(1, max_slots))
            self._scheduler = DecodeScheduler(
                self.queue, self.programs, self.kv_pool,
                prefill_max_batch=1, eos_id=eos_id, stats=stats,
                retry=retry, breakers=self.breakers)
        elif kv_mode == "latent":
            ps, n_pages = paged_sizes()
            # one array; a row is [c_kv | k_rope], padded to whole lanes
            self.kv_pool = KVPagePool(
                cfg.num_hidden_layers, n_pages, ps, dtype=kv_dtype,
                row_width=-(-cfg.latent_width // 128) * 128, arrays=1)
            self.programs = LatentPrograms(
                model, self.kv_pool, seq_ladder=seq_buckets,
                decode_rungs=powers_of_two_buckets(1, max_slots),
                max_seq=max_seq)
            self._scheduler = PagedDecodeScheduler(
                self.queue, self.programs, self.kv_pool,
                max_lanes=max_slots, prefill_max_batch=1, eos_id=eos_id,
                stats=stats, retry=retry, breakers=self.breakers)
        elif kv_mode == "windowed":
            ps, n_pages = paged_sizes()
            windows = [cfg.window_of(i) for i in range(cfg.num_hidden_layers)]
            n_window = sum(w is not None for w in windows)
            if window_pool_pages is None:
                # never what decides admission: every lane's window and one
                # prefill chunk's own pages on top
                window_pool_pages = (
                    max_slots * kvc.window_columns(cfg.sliding_window, ps)
                    + -(-seq_buckets[-1] // ps))
            self.kv_pool = WindowedPagePools(
                n_window, len(windows) - n_window, int(window_pool_pages),
                n_pages, ps, cfg.num_key_value_heads, cfg.head_dim,
                cfg.sliding_window, dtype=kv_dtype)
            self.programs = WindowedPrograms(
                model, self.kv_pool, seq_ladder=seq_buckets,
                decode_rungs=powers_of_two_buckets(1, max_slots),
                max_seq=max_seq)
            self._scheduler = WindowedDecodeScheduler(
                self.queue, self.programs, self.kv_pool,
                max_lanes=max_slots, prefill_max_batch=1, eos_id=eos_id,
                stats=stats, retry=retry, breakers=self.breakers)
        elif kv_mode == "slots":
            self.kv_pool = KVSlotPool(
                cfg.num_hidden_layers, max_slots, max_seq,
                cfg.num_attention_heads, cfg.head_dim, dtype=kv_dtype)
            self.programs = DecodePrograms(
                model, self.kv_pool,
                seq_ladder=seq_buckets,
                prefill_batch_rungs=powers_of_two_buckets(1, prefill_max),
                decode_rungs=powers_of_two_buckets(1, max_slots))
            self._scheduler = DecodeScheduler(
                self.queue, self.programs, self.kv_pool,
                prefill_max_batch=prefill_max, eos_id=eos_id, stats=stats,
                retry=retry, breakers=self.breakers)
        else:
            ps, n_pages = paged_sizes()
            self.kv_pool = KVPagePool(
                cfg.num_hidden_layers, n_pages, ps,
                cfg.num_attention_heads, cfg.head_dim, dtype=kv_dtype)
            self.programs = PagedDecodePrograms(
                model, self.kv_pool,
                seq_ladder=seq_buckets,
                prefill_batch_rungs=powers_of_two_buckets(1, prefill_max),
                decode_rungs=powers_of_two_buckets(1, max_slots),
                max_seq=max_seq,
                speculate_k=spec_k,
                draft_layers=spec_draft_layers)
            self._scheduler = PagedDecodeScheduler(
                self.queue, self.programs, self.kv_pool,
                max_lanes=max_slots, prefill_max_batch=prefill_max,
                eos_id=eos_id, stats=stats, retry=retry,
                breakers=self.breakers,
                speculate_k=spec_k,
                spec_min_accept=spec_min_accept)

    # ------------------------------------------------------------ lifecycle
    def warmup(self) -> "DecodeEngine":
        """Arm every prefill/decode rung (compile-cache restore or AOT
        compile), freeze the KV pool footprint baseline, start the decode
        loop."""
        self.programs.warmup()
        self.kv_pool.mark_warm()
        self._start_serving()
        return self

    # ------------------------------------------------------------- serving
    def submit(self, tenant: str, prompt, max_new_tokens: int = 16,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: int = 0,
               speculate: Optional[bool] = None) -> DecodeRequest:
        """Enqueue one generation request; returns the future. The prompt
        must fit the seq ladder; generation stops at ``max_new_tokens``,
        the engine's ``eos_id``, or the ``max_seq`` capacity — whichever
        comes first. The scheduler reads a step's tokens one beat late, so
        a lane may ride ONE program call past its ``eos``: nothing of that
        call is emitted (the stream ends with the ``eos`` token, exactly
        as a loop that read every step at once would end it), and what the
        call wrote lies past the sequence's last visible position in pages
        (or a slot, or a state lane) the request gives back at retirement.
        A stop by length is known before the token is read; such a lane
        rides no call past its last.

        ``temperature == 0`` (default) decodes greedily — the bit-exact
        audit mode. A positive temperature samples with optional top-k /
        top-p truncation from the request's own PRNG stream (``seed``):
        deterministic per seed, independent of batch composition. The
        sampling knobs ride the compiled programs as traced data (paged
        engines); a slots-mode engine serves greedy only.

        ``speculate`` opts the request in or out of self-speculative
        decoding (``None`` = the engine default: on iff the engine was
        built with ``speculate_k > 0``). Speculation never changes the
        token stream — committed tokens always come from the full-model
        verify pass — only how many commit per full-model call."""
        if not self._pages and temperature > 0:
            raise ValueError("sampled decoding needs kv_mode='paged'; "
                             "the slot-pool engine is the greedy oracle "
                             "and the state-lane programs are greedy")
        if speculate and not self.speculate_k:
            raise ValueError(
                "speculate=True needs an engine built with speculate_k > 0 "
                "(or FLAGS_serving_spec_k) — the draft/verify programs are "
                "compile-time families, not a per-request switch")
        if not self._started:
            raise RuntimeError("engine not started: call warmup() first")
        spec = bool(self.speculate_k) if speculate is None else bool(speculate)
        req = DecodeRequest(tenant, prompt, max_new_tokens,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p, seed=seed, speculate=spec)
        # chunked programs cut a prompt; the sequence's own limit bounds it
        limit = getattr(self.programs, "max_seq", None) or self.kv_pool.max_seq
        top = limit - 1 if self.programs.chunked else self.programs.seq_ladder[-1]
        if req.prompt.size > top:
            raise ValueError(
                f"prompt of {req.prompt.size} tokens exceeds the largest "
                f"seq bucket ({top}); raise FLAGS_serving_max_seq or the "
                "seq ladder")
        if self._pages:
            need = -(-int(req.prompt.size) // self.kv_pool.page_size)
            # pages held for a request's life: where the pool knows two
            # lifetimes, those of its global layers
            held = getattr(self.kv_pool, "full", self.kv_pool).num_pages
            if need > held:
                raise ValueError(
                    f"prompt needs {need} KV pages but the pool holds "
                    f"{held} total; it could never be "
                    "admitted — raise FLAGS_serving_pool_pages")
        self.tenant(tenant)
        return self.queue.submit(req)

    def generate(self, tenant: str, prompt, max_new_tokens: int = 16,
                 timeout: Optional[float] = 120.0) -> np.ndarray:
        """submit + block: returns the generated token ids."""
        return self.submit(tenant, prompt, max_new_tokens).result(timeout)

    def active_requests(self) -> int:
        """Sequences currently holding a slot (decoding, awaiting
        prefill, or waiting for their last token to be read) — the JX333
        slot-leak audit's liveness source."""
        return self._scheduler.active_count()

    def set_speculation(self, enabled: bool) -> bool:
        """Master toggle for self-speculative decoding, safe mid-flight:
        the scheduler picks the plain-decode or draft+verify path per
        step, and both program families were warmed together, so flipping
        this under live traffic costs zero retraces (the churn test's
        contract). Requires an engine built with ``speculate_k > 0``.
        Returns the previous setting."""
        if not self.speculate_k:
            raise ValueError("engine was built without speculation "
                             "(speculate_k == 0); nothing to toggle")
        prev = self._scheduler.spec_enabled
        self._scheduler.spec_enabled = bool(enabled)
        return prev

    # ------------------------------------------------------------ hot swap
    def swap_weights(self, source) -> dict:
        """Roll new weights into the live decode loop between two decode
        steps — KV slots intact, zero retraces, zero dropped requests
        (ISSUE 15). ``source`` is a sharded checkpoint directory (its
        tensor names must match the serving model's state_dict keys;
        values restore onto each parameter's current placement/dtype via
        the dtype-converting load, landing device-side NEXT TO the old
        weights) or a live ``GPTForCausalLM`` twin of the serving model.

        Running lanes keep their slots: tokens already cached attend
        unchanged, tokens emitted after the flip use the new weights —
        exactly the semantics of a served model picking up a mid-stream
        deploy. Requests wanting one-model generations should drain
        first; the engine itself never fails one over a swap.

        The source is never mutated: a checkpoint's values are staged
        through the serving model's tensors only long enough to
        re-extract the params pytree, then the original values are
        restored — the model object handed to the constructor keeps
        the weights its owner left in it. A live-model source becomes
        the engine's weight source for later dir-based swaps."""
        import os as _os
        import time as _time

        t0 = _time.perf_counter()
        if isinstance(source, (str, _os.PathLike)):
            from ..distributed.checkpoint.sharded import load_sharded_like

            model = self._model
            flat = dict(model.state_dict())
            new = load_sharded_like(str(source), flat)
            saved = {k: t._value for k, t in flat.items()}
            try:
                for k, t in flat.items():
                    t._value = new[k]
                n_leaves = self.programs.swap_params(model)
            finally:
                for k, t in flat.items():
                    t._value = saved[k]
        else:
            n_leaves = self.programs.swap_params(source)
            self._model = source
        try:
            from ..observability.metrics import registry

            registry.counter(
                "serving.weight_swaps",
                "zero-downtime weight hot-swaps committed into live "
                "predictors/engines").inc()
        except Exception:
            pass
        return {
            "n_leaves": n_leaves,
            "seconds": round(_time.perf_counter() - t0, 4),
            "compiles_after_warmup": self.compiles_after_warmup,
            "kv_slots_in_use": self.kv_pool.in_use(),
        }

    # ---------------------------------------------------------- accounting
    @property
    def compile_count(self) -> int:
        return self.programs.traces

    def telemetry_health(self) -> dict:
        health = super().telemetry_health()
        health.update(
            kv_slots=self.max_slots,
            active_requests=self.active_requests(),
        )
        if self._pages:
            health.update(
                kv_mode=self.kv_mode,
                kv_pages=self.kv_pool.num_pages,
                kv_page_size=self.kv_pool.page_size,
                kv_pages_in_use=self.kv_pool.in_use(),
            )
        else:
            health.update(kv_mode=self.kv_mode,
                          kv_slots_in_use=self.kv_pool.in_use())
        return health

    def serving_report(self) -> dict:
        """Stats summary + the decode tier's contractual proofs."""
        report = self.stats.summary()
        report.update(
            n_tenants=len(self._tenants),
            seq_buckets=list(self.programs.seq_ladder),
            decode_rungs=list(self.programs.decode_rungs),
            prefill_batch_rungs=list(self.programs.prefill_batch_rungs),
            compiled_rungs=len(self.programs.warmed),
            compiles_after_warmup=self.compiles_after_warmup,
            kv_pool_bytes=self.kv_pool.device_bytes(),
            kv_pool_bytes_constant=(
                self.kv_pool.bytes_at_warmup is None
                or self.kv_pool.device_bytes() == self.kv_pool.bytes_at_warmup),
            kv_slots=self.max_slots,
            kv_mode=self.kv_mode,
        )
        if self._pages:
            util = self.kv_pool.utilization_report()
            report.update(
                table_rungs=list(self.programs.table_rungs),
                kv_pages=self.kv_pool.num_pages,
                kv_page_size=self.kv_pool.page_size,
                kv_pages_in_use=self.kv_pool.in_use(),
                kv_pool_utilization=round(util["mean"], 4),
                kv_shed_requests=self._scheduler.shed_count,
            )
            if self.speculate_k:
                report.update(
                    speculate_k=self.speculate_k,
                    spec_draft_layers=self.programs.draft_layers,
                    spec_enabled=self._scheduler.spec_enabled,
                )
        return report


def executable_name(programs: DecodePrograms, kind: str) -> str:
    """The name the runtime gives the executable of ``programs``' program
    ``kind`` (``jit_<function>``): what a device trace's ``XLA Modules``
    line prints for each of its executions, less the parenthesis, so a
    call's span can be tied to the device's executions of it. (Down here,
    below every program body: a Pallas kernel's cache key holds the line
    numbers of its callers in this file.)"""
    return "jit_" + programs._jitted((kind,)).__name__
