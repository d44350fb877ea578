"""Decode attention over the paged K/V pool, as one Pallas kernel that reads
each lane's live pages where they lie.

The pool is ``[layers, pages + 1, page, heads x head_dim]`` (``serving/
kv_cache.py:KVPagePool``) and is passed whole. The layer index, the block
tables ``[B, T]`` and the per-query positions ``[B, S]`` are *prefetched
scalars*: the K and V block index maps name ``(layer, tables[b, t])``, so a
page's DMA comes straight from the pool, and no dense ``[B, T x page, heads
x head_dim]`` view of the cache is ever built. A grid step is one lane and
up to ``PAGES`` consecutive table entries, each an operand of its own. An
entry past the lane's last live page is *dead*: it names the page its
operand held in the step before (the pipeline refetches nothing when a block
index stands still) and its arithmetic is skipped, so the bytes a call moves
are the live pages', once, and a table wider than a lane's pages costs at
most a skipped grid step. A padded batch lane (position 0, table all 0)
reads the pad page and its output is thrown away by the caller.

The mathematics is ``serving/decode.py:_attend_merged``'s: K, V and q as
stored, logits times ``scale``, column ``j`` visible to query ``s`` where
``j <= positions[b, s]``, softmax in float32 (across pages its
running-maximum form, accumulated in float32), probabilities cast to the
activations' dtype before the product with V. ``q`` arrives spread
block-diagonally over the merged minor dimension (row ``s x heads + h`` holds
head ``h``'s ``head_dim`` columns of query ``s`` and zeros elsewhere), so both
products contract the whole ``heads x head_dim`` on the matrix unit and the
minor dimension is never split.

:func:`latent_paged_attention` is the same kernel for a pool whose row is a
token's LATENT (``models/axk1.py``): a page is K and, in its leading columns,
V, for every head at once, so it is one operand and not two, and q is dense
(64 heads are 64 rows, each ``[q_lat | q_rope]``) and not spread.

:func:`gqa_paged_attention` is the grouped-query form, for a model whose K/V
heads are fewer than its query heads (``models/cohere2_moe.py``: 128 on 8):
the query heads of one K/V head are the rows of one small product against
that head's ``head_dim`` columns of the page, a lane-aligned slice of the
merged minor dimension, so nothing is spread and no zero is multiplied. Given
a ``window`` a lane reads only the table entries that hold a row the window
still covers: the table is cut to those columns before the call, so entries
in front of the window are not even skipped grid steps.

:func:`gqa_chunk_attention` is the same model's PREFILL attention: a chunk
of one lane's queries over that lane's pages, the chunk's own among them, as
a flash kernel. The grid is (K/V head, tile of queries, table columns), the
K/V head's query heads share each page read, a tile's scores live in VMEM
only, and a (tile, column) pair in which no query sees a key (behind the
window, or past the diagonal) is a dead entry as above. It takes the place
of XLA fusions over gathered blocks of keys
(``serving/decode.py:WindowedPrograms._attend_chunk_blocks``), which wrote
each block's float32 scores to memory three times over.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...base import regions
from .flash_attention import _NN, _NT, NEG_INF, _dot

ROWS = 16    # a bf16 tile's sublanes: the spread q's rows are padded to whole tiles
PAGES = 4    # table entries a grid step: 8 double-buffered (256, 768) bf16 blocks are 6.3 MB of VMEM


def _kernel(layer_ref, pages_ref, last_ref, pos_ref, q_ref, *refs, scale,
            heads, queries, page, per_step, v_cols=None):
    """``v_cols`` None: K pages and V pages apart (``per_step`` operands
    each). ``v_cols`` n: a page is K and V at once, V its leading n columns
    (the latent caller), so a page is ONE operand and its bytes move once."""
    del layer_ref, pages_ref   # read by the index maps
    n_kv = per_step if v_cols else 2 * per_step
    kv_refs, (o_ref, m_scr, l_scr, acc_scr) = refs[:n_kv], refs[n_kv:]
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for j in range(per_step):
        entry = t * per_step + j

        @pl.when(entry <= last_ref[b])
        def _(entry=entry, k_ref=kv_refs[j], v_ref=kv_refs[j if v_cols else per_step + j]):
            v = v_ref[...]
            logits = _dot(q_ref[...], v if v_cols else k_ref[...], _NT) * scale   # [rows, page] f32
            if v_cols:
                v = v[:, :v_cols]
            row = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
            col = entry * page + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            # row s x heads + h asks for query s; padding rows keep the last one's
            at = jnp.full(logits.shape, pos_ref[b * queries], jnp.int32)
            for s in range(1, queries):
                at = jnp.where(row >= s * heads, pos_ref[b * queries + s], at)
            logits = jnp.where(col <= at, logits, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new)
            l_scr[...] = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
            acc_scr[...] = alpha * acc_scr[...] + _dot(p.astype(v.dtype), v, _NN)
            m_scr[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _held_pages(tables, last, per_step, first=None):
    """The page each table entry's operand holds in its grid step: the
    entry's own where it is live (``first <= t <= last``; ``first`` 0 unless
    given), else what that operand held in the step before (the pad page if
    none did), so a dead entry moves no bytes.
    Grid steps run lane-major; entry ``(b, t)`` is operand ``t % per_step``
    of step ``(b, t // per_step)``."""
    B, T = tables.shape
    steps = T // per_step
    live = jnp.arange(T)[None, :] <= last[:, None]
    if first is not None:
        live &= jnp.arange(T)[None, :] >= first[:, None]
    # [B, steps, per_step] -> one row a grid step, in the grid's order
    own = jnp.where(live, tables, -1).reshape(B * steps, per_step)
    at = jnp.where(own >= 0, jnp.arange(B * steps)[:, None], -1)
    at = jax.lax.cummax(at, axis=0)
    held = jnp.take_along_axis(own, jnp.maximum(at, 0), axis=0)
    return jnp.where(at >= 0, held, 0).reshape(B * T)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret"))
def paged_attention(q, k_pool, v_pool, layer, tables, positions, *, heads,
                    scale, interpret=False):
    """``q`` ``[B, S, heads x dim]``, the pools ``[L, N, page, heads x dim]``
    whole, ``layer`` a scalar, ``tables`` ``[B, T]`` int32 (page ids, 0 past
    a lane's pages), ``positions`` ``[B, S]`` int32. Returns ``[B, S, heads x
    dim]`` in ``q``'s dtype: query ``s`` of lane ``b`` attends over columns
    ``<= positions[b, s]`` of the lane's pages. Jitted, so the layers of a
    program share one trace of it (``layer`` is an argument, not a constant)."""
    B, S, HD = q.shape
    T = tables.shape[1]
    page = k_pool.shape[2]
    per_step = min(T, PAGES)   # the table ladder's rungs are powers of two
    rows = -(-S * heads // ROWS) * ROWS
    own = (jnp.arange(HD) // (HD // heads))[None, :] == jnp.arange(heads)[:, None]
    qbd = jnp.where(own, q[:, :, None, :], 0).reshape(B, S * heads, HD)
    qbd = jnp.pad(qbd, ((0, 0), (0, rows - S * heads), (0, 0)))
    positions = positions.astype(jnp.int32)
    # the last table entry that holds a column some query of the lane may see
    last = jnp.minimum(positions.max(axis=1) // page, T - 1)

    def lane(b, t, layer, pages, last, pos):
        return (b, 0, 0)

    def entry(j):
        return lambda b, t, layer, pages, last, pos: (
            layer[0], pages[b * T + t * per_step + j], 0, 0)

    q_spec = pl.BlockSpec((None, rows, HD), lane)
    kv_specs = [pl.BlockSpec((None, None, page, HD), entry(j))
                for j in range(per_step)]
    full = pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=heads, queries=S,
                          page=page, per_step=per_step),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, T // per_step),
            in_specs=[q_spec] + kv_specs + kv_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),    # m
                pltpu.VMEM((rows, 1), jnp.float32),    # l
                pltpu.VMEM((rows, HD), jnp.float32),   # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, rows, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=regions.PAGED_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      _held_pages(tables.astype(jnp.int32), last, per_step), last,
      positions.reshape(B * S), qbd,
      *[k_pool] * per_step, *[v_pool] * per_step)
    # head h keeps its own dim columns of row s x heads + h
    full = full[:, :S * heads].reshape(B, S, heads, HD)
    return jnp.where(own, full, 0).sum(axis=2)


LATENT_PAGES = 8   # table entries a grid step: 8 double-buffered (256, 640) bf16 blocks are 5.2 MB of VMEM


@functools.partial(jax.jit, static_argnames=("v_cols", "scale", "interpret"))
def latent_paged_attention(q, pool, layer, tables, positions, *, v_cols, scale,
                           interpret=False):
    """The absorbed latent attention of one query a lane over latent pages:
    ``q`` ``[B, H, W]`` (head ``h``'s ``[q_lat | q_rope | zeros]``, ``W`` the
    pool's row width), ``pool`` ``[L, N, page, W]`` whole, whose row is K and,
    in its leading ``v_cols`` columns, V; ``tables`` ``[B, T]``, ``positions``
    ``[B]``. Returns ``o_lat`` ``[B, H, v_cols]``: lane ``b`` attends over
    columns ``<= positions[b]`` of its pages. The page loop, the dead-entry
    rule and the softmax are :func:`paged_attention`'s (one ``_kernel``); the
    heads are the rows of one dense q, not a block-diagonal spread, and every
    head reads the same page."""
    B, H, W = q.shape
    T = tables.shape[1]
    page = pool.shape[2]
    per_step = next(n for n in range(min(T, LATENT_PAGES), 0, -1) if T % n == 0)
    rows = -(-H // ROWS) * ROWS
    q = jnp.pad(q, ((0, 0), (0, rows - H), (0, 0)))
    positions = positions.astype(jnp.int32)
    last = jnp.minimum(positions // page, T - 1)

    def lane(b, t, layer, pages, last, pos):
        return (b, 0, 0)

    def entry(j):
        return lambda b, t, layer, pages, last, pos: (
            layer[0], pages[b * T + t * per_step + j], 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=rows, queries=1,
                          page=page, per_step=per_step, v_cols=v_cols),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, T // per_step),
            in_specs=[pl.BlockSpec((None, rows, W), lane)]
            + [pl.BlockSpec((None, None, page, W), entry(j)) for j in range(per_step)],
            out_specs=pl.BlockSpec((None, rows, v_cols), lane),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),        # m
                pltpu.VMEM((rows, 1), jnp.float32),        # l
                pltpu.VMEM((rows, v_cols), jnp.float32),   # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, rows, v_cols), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=regions.LATENT_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      _held_pages(tables.astype(jnp.int32), last, per_step), last,
      positions, q, *[pool] * per_step)
    return out[:, :H]


GQA_PAGES = 4   # table entries a grid step: 16 double-buffered (256, 1024) bf16 blocks are 8.4 MB of VMEM


def _gqa_kernel(layer_ref, pages_ref, first_ref, last_ref, base_ref, pos_ref,
                q_ref, *refs, scale, kv_heads, dim, page, per_step, window):
    """One lane, ``per_step`` table entries: ``q_ref`` ``[kv_heads x group,
    dim]`` (a K/V head's query heads are consecutive rows), K and V pages
    ``[page, kv_heads x dim]``. Entry ``e`` of the call's table is logical
    column ``base + e`` of the lane's; it is live where ``first <= e <=
    last``. The running softmax is kept a row (query head) in float32."""
    del layer_ref, pages_ref   # read by the index maps
    k_refs, v_refs = refs[:per_step], refs[per_step:2 * per_step]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * per_step:]
    b, t = pl.program_id(0), pl.program_id(1)
    group = q_ref.shape[0] // kv_heads
    pos = pos_ref[b]

    @pl.when(t == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for j in range(per_step):
        entry = t * per_step + j

        @pl.when((entry >= first_ref[b]) & (entry <= last_ref[b]))
        def _(entry=entry, k_ref=k_refs[j], v_ref=v_refs[j]):
            col = ((base_ref[b] + entry) * page
                   + jax.lax.broadcasted_iota(jnp.int32, (group, page), 1))
            seen = col <= pos
            if window is not None:
                seen &= pos - col < window
            for g in range(kv_heads):
                rows, cols = pl.ds(g * group, group), pl.ds(g * dim, dim)
                logits = _dot(q_ref[rows, :], k_ref[:, cols], _NT) * scale   # [group, page] f32
                logits = jnp.where(seen, logits, NEG_INF)
                m_prev = m_scr[rows, :]
                m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # a live entry may hold no visible column for this lane (the
                # window's first page at its edge): its rows then count as 0
                p = jnp.where(seen, jnp.exp(logits - m_new), 0.0)
                l_scr[rows, :] = alpha * l_scr[rows, :] + p.sum(axis=1, keepdims=True)
                v = v_ref[:, cols]
                acc_scr[rows, :] = alpha * acc_scr[rows, :] + _dot(p.astype(v.dtype), v, _NN)
                m_scr[rows, :] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def window_columns(window: int, page: int) -> int:
    """The most table columns that hold a row some query can see through a
    window of ``window`` keys (its own among them): the query's page and the
    pages of the ``window - 1`` rows behind it."""
    return (window - 1 + page - 1) // page + 1


@functools.partial(jax.jit, static_argnames=("kv_heads", "scale", "window",
                                             "interpret"))
def gqa_paged_attention(q, k_pool, v_pool, layer, tables, positions, *,
                        kv_heads, scale, window=None, interpret=False):
    """Grouped-query attention of ONE query a lane over its pages: ``q``
    ``[B, heads x dim]`` (query head ``h`` uses K/V head ``h // (heads //
    kv_heads)``), the pools ``[L, N, page, kv_heads x dim]`` whole, ``layer``
    a scalar, ``tables`` ``[B, T]`` int32 (a lane's pages by logical column;
    0, the pad page, past its pages and, in a window layer, where a page was
    released), ``positions`` ``[B]``. Returns ``[B, heads x dim]`` in ``q``'s
    dtype: lane ``b`` attends over the columns ``j <= positions[b]`` and,
    given ``window``, ``positions[b] - j < window``.

    With a window the table is cut, lane by lane, to the
    :func:`window_columns` columns that end at the query's page (``base`` is
    the first of them), so the grid is that narrow whatever ``T`` is and the
    columns in front of the window are never visited; without one ``base`` is
    0. Either way an entry that holds no visible row is dead as in
    :func:`paged_attention`: it names the page its operand already holds and
    moves no bytes."""
    B, HD = q.shape
    L, N, page, KD = k_pool.shape
    dim = KD // kv_heads
    heads = HD // dim
    T = tables.shape[1]
    positions = positions.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    col_last = jnp.minimum(positions // page, T - 1)
    if window is None:
        base = jnp.zeros((B,), jnp.int32)
    else:
        width = min(T, window_columns(window, page))
        base = jnp.maximum(col_last - (width - 1), 0)
        tables = jnp.take_along_axis(
            tables, base[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :], axis=1)
        T = width
    per_step = min(T, GQA_PAGES)
    tables = jnp.pad(tables, ((0, 0), (0, -T % per_step)))
    T = tables.shape[1]
    last = col_last - base
    first = (jnp.zeros((B,), jnp.int32) if window is None else
             jnp.maximum(positions - (window - 1), 0) // page - base)

    def lane(b, t, layer, pages, first, last, base, pos):
        return (b, 0, 0)

    def entry(j):
        return lambda b, t, layer, pages, first, last, base, pos: (
            layer[0], pages[b * T + t * per_step + j], 0, 0)

    q_spec = pl.BlockSpec((None, heads, dim), lane)
    kv_specs = [pl.BlockSpec((None, None, page, KD), entry(j))
                for j in range(per_step)]
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, scale=scale, kv_heads=kv_heads, dim=dim,
                          page=page, per_step=per_step, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, T // per_step),
            in_specs=[q_spec] + kv_specs + kv_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),     # m
                pltpu.VMEM((heads, 1), jnp.float32),     # l
                pltpu.VMEM((heads, dim), jnp.float32),   # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, heads, dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=regions.GQA_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      _held_pages(tables, last, per_step, first), first, last, base, positions,
      q.reshape(B, heads, dim), *[k_pool] * per_step, *[v_pool] * per_step)
    return out.reshape(B, HD)


CHUNK_ROWS = 256    # queries a tile: q twice, out and the float32 accumulator of a K/V head's 16 query heads are 1 MB each and 2 MB
CHUNK_PAGES = 4     # table columns a grid step: one softmax update over their keys together
CHUNK_HEADS = 2     # query heads a body of the kernel's loop: all 16 unrolled run 3% more tokens a second in the cell and load a minute longer
CHUNK_VMEM = 48 << 20   # the tile's blocks, scratch and score temporaries pass the compiler's default 16 MiB


def chunk_columns(start: int, size: int, window, page: int) -> int:
    """The table columns that hold a key some query of a chunk at ``start ..
    start + size - 1`` sees (``start`` a multiple of ``page``): the chunk's
    own and, behind them, every column (``window`` None) or those of the
    ``window - 1`` keys before its first query. Never more than at a deep
    ``start``, which is the width of :func:`gqa_chunk_attention`'s grid."""
    last = (start + size - 1) // page
    if window is None:
        return last + 1
    return min(last + 1, -(-size // page) + -(-(window - 1) // page))


def _chunk_kernel(layer_ref, pages_ref, first_ref, last_ref, lo_ref, hi_ref,
                  at_ref, q_ref, *refs, scale, page, per_step, window, unroll):
    """One K/V head, one tile of ``rows`` queries, ``per_step`` table
    columns: ``q_ref`` ``[rows, group x dim]`` (the K/V head's query heads
    side by side, each a lane-aligned slice), K and V pages ``[page, dim]``
    (that head's columns of the pool's row). Column ``c`` of the call (its
    first is the table's ``at_ref[1]``) is live for tile ``i`` where
    ``first[i] <= c <= last[i]`` and needs no mask where ``lo[i] <= c <=
    hi[i]``: every query of the tile then sees it whole. A step of such
    columns only scores their keys together, one update of the running
    softmax a query head; any other step goes page by page, live pages only,
    under the mask. The query heads go one after another in a loop (its body
    is the kernel's whole code), their q, running softmax and accumulator
    head-major in scratch, in float32."""
    del layer_ref, pages_ref   # read by the index maps
    k_refs, v_refs = refs[:per_step], refs[per_step:2 * per_step]
    o_ref, q_scr, m_scr, l_scr, acc_scr = refs[2 * per_step:]
    i, t = pl.program_id(1), pl.program_id(2)
    group, rows, dim = q_scr.shape
    col = t * per_step

    @pl.when(t == 0)
    def _():
        for h in range(group):
            q_scr[h] = q_ref[:, h * dim:(h + 1) * dim]
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(k_refs, v_refs, seen=None):
        """The tile's query heads against these pages' keys, all visible
        (``seen`` None) or those of ``seen`` ``[rows, keys]``."""
        def head(h):
            logits = jnp.concatenate(
                [_dot(q_scr[h], k_ref[...], _NT) for k_ref in k_refs], axis=1) * scale   # [rows, keys] f32
            if seen is not None:
                logits = jnp.where(seen, logits, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new)
            if seen is not None:
                # a query may see no key of a live page (the window's first,
                # past its own edge): its row then counts as 0
                p = jnp.where(seen, p, 0.0)
            l_scr[h] = alpha * l_scr[h] + p.sum(axis=1, keepdims=True)
            p = p.astype(v_refs[0].dtype)
            weighted = _dot(p[:, :page], v_refs[0][...], _NN)
            for j in range(1, len(v_refs)):
                weighted += _dot(p[:, j * page:(j + 1) * page], v_refs[j][...], _NN)
            acc_scr[h] = alpha * acc_scr[h] + weighted
            m_scr[h] = m_new

        def heads(n, carry):
            for u in range(unroll):
                head(n * unroll + u)
            return carry

        jax.lax.fori_loop(0, group // unroll, heads, 0)

    clear = (col >= lo_ref[i]) & (col + per_step - 1 <= hi_ref[i])
    pl.when(clear)(lambda: attend(k_refs, v_refs))
    for j in range(per_step):
        @pl.when(jnp.logical_not(clear) & (col + j >= first_ref[i]) & (col + j <= last_ref[i]))
        def _(j=j):
            pos = (at_ref[0] + i * rows
                   + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0))
            key = ((at_ref[1] + col + j) * page
                   + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1))
            seen = key <= pos
            if window is not None:
                seen &= pos - key < window
            attend(k_refs[j:j + 1], v_refs[j:j + 1], seen)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        for h in range(group):
            o_ref[:, h * dim:(h + 1) * dim] = (acc_scr[h] / l_scr[h]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_heads", "scale", "window",
                                             "interpret", "rows", "per_step"))
def gqa_chunk_attention(q, k_pool, v_pool, layer, table, start, *, kv_heads,
                        scale, window=None, interpret=False, rows=CHUNK_ROWS,
                        per_step=CHUNK_PAGES):
    """Grouped-query attention of a prefill CHUNK over one lane's pages, the
    chunk's own rows already written: ``q`` ``[C, heads x dim]`` at positions
    ``start .. start + C - 1`` (``start`` a multiple of the page; query head
    ``h`` uses K/V head ``h // (heads // kv_heads)``), the pools ``[L, N,
    page, kv_heads x dim]`` whole, ``layer`` and ``start`` scalars, ``table``
    ``[T]`` int32 (the lane's pages by logical column; 0, the pad page, past
    its pages and where a window page was released). Returns ``[C, heads x
    dim]`` in ``q``'s dtype: query ``i`` attends over the keys ``j <= i``
    and, given ``window``, ``i - j < window``.

    A flash kernel: the grid is (K/V head, tile of ``rows`` queries,
    ``per_step`` table columns), the columns innermost under a running
    softmax, so a tile's scores never leave VMEM and a K/V head's query
    heads share each page read. With a window the column axis is cut to the
    :func:`chunk_columns` columns that end at the chunk's last; without one
    it is the table's width. Either way a column that holds no key the
    tile's queries see is dead as in :func:`paged_attention` (it names the
    page its operand already holds and moves no bytes), and only the pages
    on the diagonal or the window's edge are scored under a mask. ``rows``
    and ``per_step`` are the tile, picked on the chip (``.chip_scratch/
    pr37_chunk_bench.py``); tests shrink them to meet several tiles."""
    C, HD = q.shape
    L, N, page, KD = k_pool.shape
    dim = KD // kv_heads
    width = HD // kv_heads          # a K/V head's query heads, side by side
    group = width // dim
    T = table.shape[0]
    rows = min(rows, C)
    tiles = C // rows
    start = jnp.asarray(start, jnp.int32)
    table = table.astype(jnp.int32)
    cols = min(T, chunk_columns(T * page, C, window, page))
    per_step = min(per_step, cols)
    base = (jnp.zeros((), jnp.int32) if window is None else
            jnp.maximum((start + C - 1) // page - (cols - 1), 0))
    cols = -(-cols // per_step) * per_step
    # the call's columns, one row of them a tile (past the table: dead)
    pages = jnp.take(table, base + jnp.arange(cols, dtype=jnp.int32), mode="clip")
    head = start + rows * jnp.arange(tiles, dtype=jnp.int32)      # a tile's first query
    tail = head + rows - 1
    last = tail // page - base
    first = (jnp.zeros((tiles,), jnp.int32) if window is None else
             jnp.maximum(head - (window - 1), 0) // page - base)
    # the columns every query of a tile sees whole: behind the first query's
    # own, and from where the last query's window begins
    hi = (head + 1) // page - 1 - base
    lo = (jnp.zeros((tiles,), jnp.int32) if window is None else
          jnp.maximum((tail - window) // page + 1 - base, 0))
    held = _held_pages(jnp.broadcast_to(pages, (tiles, cols)), last, per_step, first)

    def tile(g, i, t, *_):
        return (i, g)

    def entry(j):
        return lambda g, i, t, layer, pages, *_: (
            layer[0], pages[i * cols + t * per_step + j], 0, g)

    q_spec = pl.BlockSpec((rows, width), tile)
    kv_specs = [pl.BlockSpec((None, None, page, dim), entry(j))
                for j in range(per_step)]
    return pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, page=page,
                          per_step=per_step, window=window,
                          unroll=math.gcd(CHUNK_HEADS, group)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(kv_heads, tiles, cols // per_step),
            in_specs=[q_spec] + kv_specs + kv_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((group, rows, dim), q.dtype),       # q, head-major
                pltpu.VMEM((group, rows, 1), jnp.float32),     # m
                pltpu.VMEM((group, rows, 1), jnp.float32),     # l
                pltpu.VMEM((group, rows, dim), jnp.float32),   # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((C, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM),
        interpret=interpret,
        name=regions.GQA_CHUNK_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), held, first, last, lo, hi,
      jnp.stack([start, base]), q, *[k_pool] * per_step, *[v_pool] * per_step)
