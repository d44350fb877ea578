"""Device-resident KV cache with slot-based alloc/release.

The memory discipline of true continuous batching: decode state lives in
ONE pair of device buffers shaped ``[layers, max_slots+1, max_seq, heads,
head_dim]``, allocated once at engine construction and never resized —
O(``FLAGS_serving_max_slots``) residency, not O(traffic) and not
O(max_batch x max_seq) per request (the O(shard)-residency discipline of
the redistribution work, PAPERS arxiv 2112.01075, applied to serving
state). Requests borrow a slot from the free list at admission, their
prompt/token K/V rows are written in place by the jitted prefill/decode
programs (functional ``lax.dynamic_update_slice`` / scatter updates under
buffer donation, so XLA aliases the output onto the input allocation —
no per-step reallocation), and the slot returns to the free list at
retirement for the next queued request.

Slot ``max_slots`` (the last one) is the *pad slot*: batch lanes that
only exist to fill a bucket rung write their garbage K/V there, so a
padded program call can scatter unconditionally without touching any
live sequence's state.

Host-side bookkeeping (free list, per-slot lengths, occupancy gauge)
stays in :class:`KVSlotPool`; the pure functions below run inside the
jitted programs and carry no python state.

:class:`KVPagePool` is the same discipline over fixed-size pages, shaped
``[layers, num_pages+1, page_size, heads*head_dim]``: its minor dimension
is heads and head_dim merged, which a TPU tiles without padding (the
class docstring has the two costs that removed). The slot pool keeps the
split ``(heads, head_dim)`` tail: it is the greedy oracle the paged
programs are tested against, on another layout and another contraction.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np

from ..base import regions
from ..base.regions import region
from ..observability.locks import named_lock

__all__ = ["LanePool", "KVSlotPool", "KVPagePool", "WindowedPagePools",
           "window_columns", "StateLanePool", "write_prompt",
           "write_prompt_batch", "append_token", "write_prompt_pages",
           "append_token_paged", "write_chunk_pages", "gather_pages"]


# ------------------------------------------------------ functional updates
def write_prompt(cache, slot, rows):
    """Write one prompt's K (or V) rows into one slot — the interactive
    single-request prefill path: ``rows`` is ``[layers, S, heads, dim]``,
    ``slot`` a scalar; one ``lax.dynamic_update_slice`` at (0, slot, 0,
    0, 0). Under donation XLA updates the pool buffer in place."""
    import jax.lax as lax
    import jax.numpy as jnp

    with region(regions.ATTN_KV_WRITE):
        return lax.dynamic_update_slice(
            cache, rows[:, None].astype(cache.dtype),
            (jnp.zeros((), jnp.int32), jnp.asarray(slot, jnp.int32),
             jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.int32)))


def write_prompt_batch(cache, slot_ids, rows):
    """Batched prefill write: ``rows`` is ``[layers, B, S, heads, dim]``,
    ``slot_ids`` ``[B]`` — one scatter over the slot axis covering every
    layer. Rows past a lane's real prompt length carry garbage, which is
    safe by construction: decode overwrites position ``len`` before any
    step attends to it."""
    S = rows.shape[2]
    with region(regions.ATTN_KV_WRITE):
        return cache.at[:, slot_ids, :S].set(rows.astype(cache.dtype))


def append_token(cache, layer, slot_ids, positions, rows):
    """One decode step's write for one layer: ``rows`` is ``[B, heads,
    dim]`` landing at ``(layer, slot_ids[b], positions[b])``. Pad lanes
    point at the pool's pad slot so the scatter needs no mask."""
    with region(regions.ATTN_KV_WRITE):
        return cache.at[layer, slot_ids, positions].set(
            rows.astype(cache.dtype))


# ------------------------------------------------- paged functional updates
def write_prompt_pages(cache, tables, rows):
    """Batched paged prefill write: ``rows`` is ``[layers, B, T*ps,
    heads*dim]`` (prompt K/V padded up to whole pages, heads merged into
    the pool's minor dimension), ``tables`` is the traced ``[B, T]``
    int32 block table — one scatter over the page axis covering every
    layer. Table entries past a lane's real pages are 0 (the pad page),
    so garbage rows land in the trash page and a padded program call
    never touches live state."""
    L, B, _, HD = rows.shape
    T = tables.shape[1]
    ps = cache.shape[2]
    with region(regions.ATTN_KV_WRITE):
        paged = rows.astype(cache.dtype).reshape(L, B, T, ps, HD)
        return cache.at[:, tables].set(paged)


def append_token_paged(cache, layer, pages, offsets, rows):
    """One decode step's paged write for one layer: ``rows`` is ``[B,
    heads*dim]`` landing at ``(layer, pages[b], offsets[b])`` where
    ``pages[b] = table[b, pos // page_size]`` and ``offsets[b] = pos %
    page_size`` — both traced. Pad lanes carry page 0."""
    with region(regions.ATTN_KV_WRITE):
        return cache.at[layer, pages, offsets].set(rows.astype(cache.dtype))


def write_chunk_pages(cache, layer, pages, rows):
    """One prefill chunk's write for one layer of one lane: ``rows`` ``[C,
    width]``, ``C`` a multiple of the page size, into the whole pages
    ``pages`` ``[C / page_size]`` (traced; entries past the lane's own are
    0, the pad page) of layer ``layer``. A chunk of ONE page goes row by
    row, as a decode step's write does: the compiler turns a scatter with
    one index into a ``dynamic-update-slice``, gives it the layout its
    update was computed in, and re-lays the whole pool out to match (3.7 GB
    in and out at the A.X-K1 cell's size, past the chip's memory)."""
    import jax.numpy as jnp

    ps = cache.shape[2]
    C = rows.shape[0]
    if C == ps:
        return append_token_paged(cache, layer, jnp.broadcast_to(pages[0], (C,)),
                                  jnp.arange(C, dtype=jnp.int32), rows)
    with region(regions.ATTN_KV_WRITE):
        return cache.at[layer, pages].set(
            rows.astype(cache.dtype).reshape(C // ps, ps, -1))


def gather_pages(cache, layer, tables):
    """Materialize a batch's contiguous K (or V) view from the page
    array: ``cache[layer, tables]`` gathers ``[B, T, ps, heads*dim]``
    along the page axis (ONE gather from the whole pool — slicing the
    layer out first is a 100 MB copy a layer on a TPU) and reshapes to
    ``[B, T*ps, heads*dim]`` — the traced-block-table read the decode
    attention indexes through. One compiled program serves ANY page map
    because the table is data.
    WHERE THIS RUNS: on a CPU and under a mesh of more than one device,
    and as the oracle of the kernel's tests. The view is as large as the
    table, live pages or not (at 64 lanes x 4 pages as large as the whole
    pool, written once and read twice a layer a step), so on one TPU the
    decode programs take ``ops/pallas/paged_attention.py`` instead, which
    reads each lane's live pages from the pool in place and gathers
    nothing (``decode.PagedDecodePrograms._attend_pages``).
    The view keeps the pool's merged minor dimension: the reader
    contracts against it whole (``decode._attend_merged``) — splitting
    it back into (heads, dim) here would have the TPU compiler pad and
    relay out the copy, the cost the merged pool exists to remove."""
    B, T = tables.shape
    ps, HD = cache.shape[2], cache.shape[3]
    with region(regions.ATTN_KV_GATHER):
        return cache[layer, tables].reshape(B, T * ps, HD)


# --------------------------------------------------------------- the pool
class LanePool:
    """The host side every lane-per-request pool shares: a free list of
    ``max_slots`` lanes plus the *pad lane* (the last one, never
    allocated), per-lane lengths, and the frozen footprint baseline.
    ``alloc()``/``release()`` run on the scheduler thread (a lock keeps
    them safe for engine shutdown paths). A subclass owns the device
    arrays: :meth:`arrays`, :meth:`commit`, :meth:`device_bytes` and the
    occupancy gauge."""

    def __init__(self, max_slots: int, max_seq: int):
        if max_slots < 1:
            raise ValueError(f"{type(self).__name__} needs at least one slot")
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        self.lengths = np.zeros(self.max_slots, np.int32)  # host-side
        self._free: List[int] = list(range(self.max_slots - 1, -1, -1))
        self._lock = named_lock("serving.kv_pool")
        self.bytes_at_warmup: Optional[int] = None

    # ------------------------------------------------------------ slots
    @property
    def pad_slot(self) -> int:
        """The trash slot padded batch lanes write to (never allocated)."""
        return self.max_slots

    def alloc(self) -> int:
        """Borrow a free slot (its length resets to 0); raises
        ``RuntimeError`` when the pool is exhausted — the scheduler must
        gate admission on :meth:`free_count`."""
        with self._lock:
            if not self._free:
                raise RuntimeError(
                    f"KV slot pool exhausted ({self.max_slots} slots in "
                    "use); admission must wait for a retirement")
            slot = self._free.pop()
            self.lengths[slot] = 0
        self._gauge_occupancy()
        return slot

    def release(self, slot: int) -> None:
        """Return a slot to the free list (idempotence guarded)."""
        with self._lock:
            slot = int(slot)
            if not 0 <= slot < self.max_slots:
                raise ValueError(f"slot {slot} out of range")
            if slot in self._free:
                raise ValueError(f"slot {slot} is already free")
            self.lengths[slot] = 0
            self._free.append(slot)
        self._gauge_occupancy()

    def in_use(self) -> int:
        with self._lock:
            return self.max_slots - len(self._free)

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def mark_warm(self) -> None:
        """Freeze the footprint baseline (end of engine warmup): any
        later :meth:`device_bytes` drift is a JX332 error."""
        self.bytes_at_warmup = self.device_bytes()

    def arrays(self) -> tuple:
        """The device arrays a program call takes and gives back."""
        raise NotImplementedError

    def device_bytes(self) -> int:
        return sum(int(a.nbytes) for a in self.arrays())


class KVSlotPool(LanePool):
    """Free-list slot allocator over one device-resident K/V buffer pair.

    The arrays themselves are replaced wholesale by :meth:`commit` after
    each program call — the functional update idiom, with donation making
    it in-place on accelerators. :meth:`device_bytes` must never change
    after :meth:`mark_warm` (the JX332 audit and the bench's
    ``kv_pool_bytes_constant`` proof)."""

    def __init__(self, num_layers: int, max_slots: int, max_seq: int,
                 num_heads: int, head_dim: int, dtype="float32"):
        import jax.numpy as jnp

        super().__init__(max_slots, max_seq)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        # +1: the pad slot — garbage writes from bucket-padding lanes
        shape = (self.num_layers, self.max_slots + 1, self.max_seq,
                 self.num_heads, self.head_dim)
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        self._gauge_occupancy()

    def arrays(self) -> tuple:
        return self.k, self.v

    # ------------------------------------------------------------ buffers
    def commit(self, new_k, new_v) -> None:
        """Swap in the post-step buffers (the jitted program's functional
        outputs). Shape and dtype are pinned — a program handing back a
        different footprint is a bug the JX332 audit would otherwise
        catch after the fact. An injected ``kv.commit`` fault rejects
        the swap BEFORE any assignment: the pool keeps the previous
        buffers and the decode fault wall releases the step's slots."""
        from ..reliability.faults import fault_point

        fault_point("kv.commit")
        if (new_k.shape != self.k.shape or new_v.shape != self.v.shape
                or new_k.dtype != self.k.dtype):
            raise ValueError(
                f"KV commit changed the pool footprint: "
                f"{self.k.shape}/{self.k.dtype} -> "
                f"{new_k.shape}/{new_k.dtype}")
        self.k = new_k
        self.v = new_v
        # NaN/Inf sentinel on the committed keys (one bool read when the
        # numerics witness is dark; a poisoned decode step shows up here
        # before it contaminates every later token)
        from ..observability import numerics

        numerics.watch("serving.kv_commit", new_k)

    # ------------------------------------------------------ observability
    def _gauge_occupancy(self) -> None:
        from ..observability.metrics import registry

        registry.gauge(
            "serving.kv_slots_in_use",
            "KV cache slots currently allocated to live decode sequences "
            "(capacity = FLAGS_serving_max_slots)").set(
                self.max_slots - len(self._free))


# --------------------------------------------------------- the state pool
class StateLanePool(LanePool):
    """The third residency: a *recurrent state* a lane, for a model whose
    layers remember the sequence in a state of constant size (power
    retention, ``nn/functional/power_retention.py``) and keep no keys or
    values. ONE device array ``[layers, max_slots + 1, kv_heads, head_dim
    + 8, D]`` in float32, allocated once, the pad lane last: a lane's
    state is as many bytes at token 100 as at token 30,000, so the pool
    sets no limit on a sequence's length (``max_seq`` is the model's
    position limit, kept for the scheduler's retirement test). Row
    ``head_dim`` of a head's state is the normaliser ``z``, so the update
    and the read are one pass over one array; ``D`` is the minor
    dimension, whole lanes of 128 at the published head size.

    A lane that JOINS is not cleared here: the first prefill chunk of a
    request ignores what the lane held (``fresh``), which is the zeroing.
    Programs take and return the array under donation; the decode step's
    kernel updates the touched lanes in place and reads each once."""

    def __init__(self, num_layers: int, max_slots: int, num_kv_heads: int,
                 head_dim: int, max_seq: int):
        import jax.numpy as jnp

        from ..nn.functional.power_retention import phi_dim, state_rows

        super().__init__(max_slots, max_seq)
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.state = jnp.zeros(
            (self.num_layers, self.max_slots + 1, self.num_kv_heads,
             state_rows(self.head_dim), phi_dim(self.head_dim)), jnp.float32)
        self._gauge_occupancy()
        from ..observability.metrics import registry

        registry.gauge(
            "serving.state_pool_bytes",
            "device bytes of the recurrent-state lane pool (allocated once)"
        ).set(self.device_bytes())

    def arrays(self) -> tuple:
        return (self.state,)

    def commit(self, new_state) -> None:
        """Swap in the post-step array; shape and dtype are pinned, and an
        injected ``kv.commit`` fault rejects the swap before it."""
        from ..reliability.faults import fault_point

        fault_point("kv.commit")
        if new_state.shape != self.state.shape or new_state.dtype != self.state.dtype:
            raise ValueError(
                f"state commit changed the pool footprint: {self.state.shape}/"
                f"{self.state.dtype} -> {new_state.shape}/{new_state.dtype}")
        self.state = new_state

    def _gauge_occupancy(self) -> None:
        from ..observability.metrics import registry

        registry.gauge(
            "serving.state_lanes_in_use",
            "recurrent-state lanes currently held by live sequences").set(
                self.max_slots - len(self._free))


# ---------------------------------------------------------- the page pool
class KVPagePool:
    """Free-list *page* allocator over one device-resident K/V buffer
    pair shaped ``[layers, num_pages+1, page_size, heads*head_dim]``, or,
    given ``row_width`` and ``arrays``, over that many buffers of rows
    that wide: a model with latent attention keeps ONE array whose row is
    a token's latent, K and V at once (``models/axk1.py``; 512 + 64
    columns padded to 640, whole lanes of 128). The free list, the pad
    page, ``commit`` and the footprint audit are the same.

    The minor dimension is heads and head_dim MERGED. A TPU holds an
    array in tiles of its two minor dimensions (16 sublanes x 128 lanes
    for bf16); a ``(heads, head_dim)`` tail such as (12, 64) fills
    neither, so the compiled decode step (a) copied both pool arrays
    whole into the padded form on entry and back on exit, every step,
    and (b) gathered each layer's pages into a view 2.67x the bytes of
    its data. ``(page_size, heads*head_dim)`` — (256, 768) for
    gpt2-small — is whole tiles: the runtime's layout and the program's
    are the same array, nothing is copied and nothing padded. Same
    bytes, same page ids; only the rows' shape differs, and the decode
    programs contract against the merged dimension without splitting
    it: on one TPU a Pallas kernel whose block index maps name ``(layer,
    table[b, t])``, so a step reads the live pages from this array as it
    lies (``ops/pallas/paged_attention.py``); elsewhere
    :func:`gather_pages` and ``decode._attend_merged``.

    The vLLM discipline applied to the slot pool above: instead of one
    full ``max_seq`` row per sequence, a request holds only the fixed-
    size pages its live tokens occupy, named by a per-request *block
    table* (a list of page ids, traced as an int32 array inside the
    decode programs). Page 0 is the pad page — bucket-padding lanes and
    table padding both point there, so scatters and gathers need no
    mask. Mixed 128–4k contexts share one pool whose residency tracks
    live tokens, not the per-request worst case.

    The host side mirrors :class:`KVSlotPool`: ``alloc``/``release`` on
    the scheduler thread under a lock, :meth:`commit` swapping in the
    jitted programs' functional outputs under donation, and
    :meth:`device_bytes` frozen after :meth:`mark_warm` (the JX332
    audit and the bench's ``kv_pool_bytes_constant`` proof duck-type
    both pools). :meth:`note_utilization` feeds the JX334
    page-fragmentation watermark."""

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_heads: Optional[int] = None, head_dim: Optional[int] = None,
                 dtype="float32", *, row_width: Optional[int] = None,
                 arrays: int = 2, kind: Optional[str] = None):
        import jax.numpy as jnp

        if num_pages < 1:
            raise ValueError("KVPagePool needs at least one page")
        if page_size < 1 or (page_size & (page_size - 1)):
            raise ValueError(
                f"page_size must be a power of two, got {page_size}")
        if row_width is None:
            if num_heads is None or head_dim is None:
                raise ValueError("KVPagePool needs num_heads and head_dim, "
                                 "or a row_width")
            row_width = int(num_heads) * int(head_dim)
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.num_heads = None if num_heads is None else int(num_heads)
        self.head_dim = None if head_dim is None else int(head_dim)
        self.row_width = int(row_width)
        self.kind = kind   # names the occupancy gauge where pools of two kinds live side by side
        # +1: page 0 is the pad page — never allocated, absorbs garbage
        shape = (self.num_layers, self.num_pages + 1, self.page_size,
                 self.row_width)
        self._held = [jnp.zeros(shape, dtype) for _ in range(int(arrays))]
        # low page ids hand out first: pop() from the tail
        self._free: List[int] = list(range(self.num_pages, 0, -1))
        self._lock = named_lock("serving.kv_pool")
        self.bytes_at_warmup: Optional[int] = None
        self._util_sum = 0.0
        self._util_min = 1.0
        self._util_samples = 0
        self._gauge_occupancy()

    @property
    def k(self):
        """The first array: the keys, where the pool holds K and V apart."""
        return self._held[0]

    @k.setter
    def k(self, array):
        self._held[0] = array

    @property
    def v(self):
        return self._held[1]

    @v.setter
    def v(self, array):
        self._held[1] = array

    # ------------------------------------------------------------ pages
    @property
    def pad_page(self) -> int:
        """The trash page padded lanes and table padding point at."""
        return 0

    def alloc(self, n: int = 1) -> List[int]:
        """Borrow ``n`` free pages; raises ``RuntimeError`` when the
        pool cannot cover the request — the caller (scheduler) sheds
        that ONE request and releases any pages it already holds, so an
        allocation failure never leaks and never touches other lanes.
        The ``kv.page_alloc`` fault site lives here: an injected
        failure exercises exactly that shed path."""
        from ..reliability.faults import fault_point

        fault_point("kv.page_alloc")
        with self._lock:
            if len(self._free) < n:
                raise RuntimeError(
                    f"KV page pool exhausted ({self.num_pages - len(self._free)}"
                    f"/{self.num_pages} pages in use, {n} requested); "
                    "admission must wait for a retirement")
            pages = [self._free.pop() for _ in range(n)]
        self._gauge_occupancy()
        return pages

    def release(self, pages: Iterable[int]) -> None:
        """Return a request's pages to the free list (idempotence and
        range guarded per page)."""
        with self._lock:
            for page in pages:
                page = int(page)
                if not 1 <= page <= self.num_pages:
                    raise ValueError(f"page {page} out of range")
                if page in self._free:
                    raise ValueError(f"page {page} is already free")
                self._free.append(page)
        self._gauge_occupancy()

    def in_use(self) -> int:
        with self._lock:
            return self.num_pages - len(self._free)

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    # ------------------------------------------------------------ buffers
    def arrays(self) -> tuple:
        """The device arrays a program call takes and gives back."""
        return tuple(self._held)

    def commit(self, *new) -> None:
        """Swap in the post-step buffers — same contract as
        :meth:`KVSlotPool.commit`: footprint pinned, ``kv.commit``
        fault rejects BEFORE assignment, numerics witness on the first
        array (the keys, or the rows that are K and V at once)."""
        from ..reliability.faults import fault_point

        fault_point("kv.commit")
        if len(new) != len(self._held) or any(
                n.shape != h.shape or n.dtype != h.dtype
                for n, h in zip(new, self._held)):
            raise ValueError(
                f"KV commit changed the pool footprint: "
                f"{[(h.shape, h.dtype) for h in self._held]} -> "
                f"{[(n.shape, n.dtype) for n in new]}")
        self._held = list(new)
        from ..observability import numerics

        numerics.watch("serving.kv_commit", new[0])

    def device_bytes(self) -> int:
        return sum(int(a.nbytes) for a in self._held)

    def mark_warm(self) -> None:
        """Freeze the footprint baseline (end of engine warmup): any
        later :meth:`device_bytes` drift is a JX332 error."""
        self.bytes_at_warmup = self.device_bytes()

    # ------------------------------------------------------ observability
    def note_utilization(self, live_tokens: int) -> None:
        """Record one page-utilization sample: live tokens over the
        token capacity of the pages currently in use. Sampled by the
        scheduler each decode step; the running mean/min feed the JX334
        fragmentation watermark and the utilization gauge."""
        with self._lock:
            used = self.num_pages - len(self._free)
        if used <= 0:
            return
        util = min(1.0, float(live_tokens) / float(used * self.page_size))
        self._util_sum += util
        self._util_min = min(self._util_min, util)
        self._util_samples += 1
        from ..observability.metrics import registry

        registry.gauge(
            "serving.kv_page_utilization",
            "live tokens / token capacity of in-use KV pages — low "
            "values mean fragmentation (JX334)").set(util)

    def utilization_report(self) -> dict:
        n = self._util_samples
        return {
            "samples": n,
            "mean": (self._util_sum / n) if n else 1.0,
            "min": self._util_min if n else 1.0,
        }

    def _gauge_occupancy(self) -> None:
        from ..observability.metrics import registry

        registry.gauge(
            "serving.kv_pages_in_use" + (f".{self.kind}" if self.kind else ""),
            "KV cache pages currently allocated to live decode "
            "sequences (capacity = pool num_pages)").set(
                self.num_pages - len(self._free))


# ----------------------------------------------------- two page lifetimes
def window_columns(window: int, page_size: int) -> int:
    """The most table columns that hold a row some query sees through a
    window of ``window`` keys (its own among them): the query's page and
    the pages of the ``window - 1`` rows behind it."""
    return (int(window) - 1 + page_size - 1) // page_size + 1


class WindowedPagePools:
    """The cache manager of a model whose layers are of two kinds
    (``models/cohere2_moe.py``): *global* layers attend over the whole
    sequence, so a token's K/V row lives as long as its request; *window*
    layers attend over the last ``window`` rows, so a page of theirs is dead
    once every row of it lies more than ``window - 1`` positions behind the
    next query. One table a request no longer describes every layer. Two
    :class:`KVPagePool` s of the same page size, ``window`` over the window
    layers and ``full`` over the global ones, and two tables a request
    (``DecodeRequest.window_pages`` beside ``.pages``, the same logical
    columns; a released column names the pad page, so a table walk stays a
    walk). At a 32k context a lane then holds ``window / page + 1`` window
    pages a window layer instead of 128.

    To the scheduler this IS a page pool of the request-long kind:
    ``alloc``/``release``/``free_count`` mean global pages, as
    :class:`~.scheduler.PagedDecodeScheduler` has always meant them, and the
    window pages are taken and given back through :attr:`window` by the
    scheduler that knows two lifetimes. What is asked of the manager WHOLE
    answers for both kinds together: :meth:`arrays` and :meth:`commit` (the
    window pool's arrays, then the global pool's: the programs take and
    return them in that order), :meth:`in_use`, :attr:`num_pages`,
    :meth:`device_bytes` and the warm-up baseline, so that a leak audit or
    a peak-occupancy reader sees one manager."""

    def __init__(self, window_layers: int, full_layers: int,
                 window_pages: int, full_pages: int, page_size: int,
                 num_heads: int, head_dim: int, window: int, dtype="float32"):
        if window < 1:
            raise ValueError("a window holds at least the query's own row")
        self.window_rows = int(window)
        self.window = KVPagePool(window_layers, window_pages, page_size,
                                 num_heads, head_dim, dtype, kind="window")
        self.full = KVPagePool(full_layers, full_pages, page_size,
                               num_heads, head_dim, dtype, kind="full")
        self.page_size = int(page_size)
        self.bytes_at_warmup: Optional[int] = None

    @property
    def window_columns(self) -> int:
        """The most window pages a lane holds between two program calls
        (:func:`window_columns`)."""
        return window_columns(self.window_rows, self.page_size)

    def first_live_column(self, position: int) -> int:
        """The first table column whose page holds a row that a query at
        ``position`` (or any later one) can still see."""
        return max(int(position) - (self.window_rows - 1), 0) // self.page_size

    # ------------------------------------------- global pages, as one pool
    @property
    def pad_page(self) -> int:
        return self.full.pad_page

    def alloc(self, n: int = 1) -> List[int]:
        return self.full.alloc(n)

    def release(self, pages: Iterable[int]) -> None:
        self.full.release(pages)

    def free_count(self) -> int:
        return self.full.free_count()

    def note_utilization(self, live_tokens: int) -> None:
        self.full.note_utilization(live_tokens)

    def utilization_report(self) -> dict:
        return self.full.utilization_report()

    # ------------------------------------------------ both kinds together
    @property
    def num_pages(self) -> int:
        return self.window.num_pages + self.full.num_pages

    def in_use(self) -> int:
        return self.window.in_use() + self.full.in_use()

    def arrays(self) -> tuple:
        return self.window.arrays() + self.full.arrays()

    def commit(self, *new) -> None:
        held = len(self.window.arrays())
        self.window.commit(*new[:held])
        self.full.commit(*new[held:])

    def device_bytes(self) -> int:
        return self.window.device_bytes() + self.full.device_bytes()

    def mark_warm(self) -> None:
        self.window.mark_warm()
        self.full.mark_warm()
        self.bytes_at_warmup = self.device_bytes()
