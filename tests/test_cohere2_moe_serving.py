"""Command A+ through the decode engine on the CPU at a small size: two page
lifetimes under one cache manager, chunked prefill and decode through both
pools against the reference's full forward pass, the grouped-query kernel in
the engine (interpret mode), the allocator's invariants, and the benchmark's
own two comparisons with each way of computing less refused by one of them.
The reference is benchmark/reference_cohere2_moe.py; the stand-in
configuration and the tolerances are the benchmark's own files."""
import dataclasses
import functools
import os
import re
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark import models_cohere2_moe as lm  # noqa: E402
from benchmark import reference_cohere2_moe as ref  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.base import regions  # noqa: E402
from paddle_tpu.models import Cohere2MoEForCausalLM, cohere2_moe_tiny  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as kernel  # noqa: E402
from paddle_tpu.serving import decode as decode_mod  # noqa: E402
from paddle_tpu.serving.kv_cache import WindowedPagePools  # noqa: E402

STAND_IN = harness.load_json(os.path.join(ROOT, "benchmark", "tests", "tiny-cohere2-moe.json"))
TRAFFIC = harness.load_json(os.path.join(ROOT, "benchmark", "traffic", "mixedlen-closed.json"))
TRAFFIC["attention_check"] = dict(TRAFFIC["attention_check"], chunks=3, ragged=10,
                                  deep=[32, 64], others=4, queries=8, steps=8, step_group=4)
SHARE = (1, 2)
ENGINE = dict(max_slots=4, max_seq=128, seq_buckets=[8, 16, 32], page_size=8, pool_pages=48)


@pytest.fixture(scope="module")
def model():
    paddle.seed(5)
    m = Cohere2MoEForCausalLM(cohere2_moe_tiny(initializer_range=0.16), expert_share=SHARE)
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(model):
    eng = serving.DecodeEngine(model, **ENGINE)
    eng.warmup()
    yield eng
    eng.shutdown()


def _gaps(model, prompt, out):
    """For each returned token, how far the reference's logit for it lies
    under the reference's largest, given the engine's own earlier tokens."""
    ids = jnp.asarray(np.concatenate([prompt, out]))
    logits = np.asarray(ref.forward_logits(decode_mod._extract_cohere2(model)[0], ids,
                                           dataclasses.asdict(model.config), SHARE))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
    return rows.max(-1) - rows[np.arange(len(out)), out]


# ------------------------------------------------ against the full forward
@pytest.mark.parametrize("length", [5, 16, 17, 20, 40, 70, 100])
def test_prefill_in_chunks_then_decode_agrees_with_the_references_full_forward(
        engine, model, length):
    """Window 16, page 8, chunk 32: 16 and 17 sit on the window's edge, 20
    has its first window page released during decode (at position 24), 40
    and 70 during prefill, 100 is four chunks."""
    prompt = np.random.default_rng(length).integers(0, 256, length).astype(np.int32)
    out = engine.generate("t", prompt, max_new_tokens=24)
    assert len(out) == 24 and _gaps(model, prompt, out).max() < 1e-4
    assert engine.kv_pool.in_use() == 0 and engine.compiles_after_warmup == 0


def test_a_batch_of_lanes_at_mixed_depths(engine, model):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (3, 90, 17, 33, 64, 9)]
    sent = [engine.submit("t", p, max_new_tokens=20) for p in prompts]
    for p, r in zip(prompts, sent):
        out = r.result(timeout=120)
        assert len(out) == 20 and _gaps(model, p, out).max() < 1e-4


def _force_kernel(monkeypatch):
    """Both kernels of the family, decode's and the prefill chunk's, interpreted."""
    monkeypatch.setattr(decode_mod.WindowedPrograms, "_kernel", staticmethod(lambda: True))
    for name in ("gqa_paged_attention", "gqa_chunk_attention"):
        monkeypatch.setattr(kernel, name, functools.partial(getattr(kernel, name),
                                                            interpret=True))


def test_with_the_kernel_the_engine_returns_the_same_tokens(monkeypatch, model):
    def streams():
        eng = serving.DecodeEngine(model, **ENGINE)
        eng.warmup()
        try:
            rng = np.random.default_rng(2)
            sent = [eng.submit("t", rng.integers(0, 256, n).astype(np.int32),
                               max_new_tokens=12) for n in (5, 17, 40, 70)]
            return [r.result(timeout=300) for r in sent]
        finally:
            eng.shutdown()

    want = streams()
    _force_kernel(monkeypatch)
    for got, expected in zip(streams(), want):
        assert np.array_equal(got, expected)


def test_with_both_kernels_a_mixed_load_returns_the_same_tokens(monkeypatch, model):
    """Short and long prompts in one queue (window 16, chunk 32): one-page
    prompts on the smallest rung, prompts on the window's edge, and prompts
    of several chunks whose window pages go back during prefill, decoding in
    one batch; the chunk kernel and the decode kernel against the XLA path."""
    def streams():
        eng = serving.DecodeEngine(model, **ENGINE)
        eng.warmup()
        try:
            rng = np.random.default_rng(6)
            sent = [eng.submit("t", rng.integers(0, 256, n).astype(np.int32),
                               max_new_tokens=m)
                    for n, m in ((3, 9), (100, 6), (16, 12), (33, 5), (8, 10), (64, 7), (90, 8))]
            out = [r.result(timeout=600) for r in sent]
            assert eng.kv_pool.in_use() == 0 and eng.compiles_after_warmup == 0
            return out
        finally:
            eng.shutdown()

    want = streams()
    _force_kernel(monkeypatch)
    for got, expected in zip(streams(), want):
        assert np.array_equal(got, expected)


# ------------------------------------------ the prefill chunk's flash kernel
PAGE, KV_HEADS, DIM, TOP, COLS = 8, 2, 16, 64, 64   # a stand-in pool: 64 columns of 8 rows, blocks of 64


@functools.lru_cache(maxsize=None)
def _block_path(group, window):
    """`WindowedPrograms._attend_chunk_blocks` over the stand-in pool, jitted:
    the path off the TPU, which is the kernel's oracle."""
    import jax
    from types import SimpleNamespace

    P = object.__new__(decode_mod.WindowedPrograms)
    P.seq_ladder, P.pool = [16, TOP], SimpleNamespace(page_size=PAGE)
    P._kv_heads, P._heads, P._head_dim, P._scale = KV_HEADS, KV_HEADS * group, DIM, DIM ** -0.5
    return jax.jit(lambda *a: P._attend_chunk_blocks(*a, window))


def _chunk_case(seed, group, size, start, window, dtype):
    """A lane `start + size` rows deep in a pool of random rows, its table
    as the scheduler leaves it before this chunk (the columns behind the
    window of the chunk's first query released, reading 0), q for the chunk's
    `size` positions. Every page the lane does not hold, the pad page among
    them, is NaN."""
    rng = np.random.default_rng(seed)
    held = (start + size) // PAGE
    table = np.zeros(COLS, np.int32)
    table[:held] = rng.permutation(np.arange(1, 2 * COLS))[:held]
    if window is not None:
        table[:max(start - (window - 1), 0) // PAGE] = 0
    pools = []
    for _ in range(2):
        rows = rng.standard_normal((2, 2 * COLS, PAGE, KV_HEADS * DIM)).astype(np.float32)
        dead = np.ones(2 * COLS, bool)
        dead[table[table > 0]] = False
        rows[:, dead] = np.nan
        pools.append(jnp.asarray(rows, dtype))
    q = jnp.asarray(rng.standard_normal((size, KV_HEADS * group * DIM)), dtype)
    return q, pools[0], pools[1], jnp.asarray(table)


@functools.lru_cache(maxsize=None)
def _chunk_kernel(group, window, rows=16, per_step=2):
    """The kernel, interpreted, `layer` and `start` traced: one trace serves
    every `start`."""
    import jax

    return jax.jit(lambda q, kp, vp, li, table, start: kernel.gqa_chunk_attention(
        q, kp, vp, li, table, start, kv_heads=KV_HEADS, scale=DIM ** -0.5, window=window,
        interpret=True, rows=rows, per_step=per_step))


# start: 0; a page short of a block's end (the smaller chunk: the block path
# wants a chunk inside one block); a block; past the window; deep
@pytest.mark.parametrize("size,start", [(8, 0), (8, 56), (8, 64), (8, 128), (8, 384),
                                        (64, 0), (64, 64), (64, 128), (64, 384)])
@pytest.mark.parametrize("window", [None, 32, 33])
@pytest.mark.parametrize("dtype,group,tolerance", [("float32", 16, 2e-6), ("float32", 2, 2e-6),
                                                   ("bfloat16", 16, 2e-2), ("bfloat16", 2, 2e-2)])
def test_chunk_kernel_agrees_with_the_block_path(dtype, group, tolerance, size, window, start):
    """Tiles of 16 queries, two table columns a grid step, `layer` and
    `start` traced: the same keys by the same rule as the blocks of 64 under
    `grouped_block`, the released columns and the pad page never read (they
    are NaN here and the block path is given zeros there)."""
    q, kp, vp, table = _chunk_case(start + size, group, size, start, window, dtype)
    li, at = jnp.asarray(1, jnp.int32), jnp.asarray(start, jnp.int32)
    got = _chunk_kernel(group, window)(q, kp, vp, li, table, at)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _block_path(group, window)(q, jnp.nan_to_num(kp), jnp.nan_to_num(vp), li, table, at)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tolerance * np.abs(want).max()


@pytest.mark.parametrize("rows,per_step", [(8, 1), (16, 4), (32, 3), (64, 2)])
def test_chunk_kernel_gives_the_same_at_every_tile_size(rows, per_step):
    """Tiles narrower than a page and wider, a column count the step does
    not divide: what a step holds changes, the keys a query sees do not."""
    q, kp, vp, table = _chunk_case(11, 4, 64, 192, 40, "float32")
    args = (q, kp, vp, jnp.asarray(0, jnp.int32), table, jnp.asarray(192, jnp.int32))
    got = _chunk_kernel(4, 40, rows, per_step)(*args)
    want = _chunk_kernel(4, 40)(*args)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6


def test_chunk_kernel_without_a_window_reads_the_released_columns():
    """The `released` control's premise: told no window over a table with
    released columns, the kernel READS the pad page there (a released column
    is live to a global layer), so what it returns is the pad page's, here
    NaN; told the window it reads none of them."""
    q, kp, vp, table = _chunk_case(12, 2, 16, 128, 32, "float32")
    assert (np.asarray(table[:12]) == 0).all() and np.isnan(np.asarray(kp[0, 0])).all()
    args = (q, kp, vp, jnp.asarray(0, jnp.int32), table, jnp.asarray(128, jnp.int32))
    assert np.isnan(np.asarray(_chunk_kernel(2, None)(*args))).all()
    assert np.isfinite(np.asarray(_chunk_kernel(2, 32)(*args))).all()


@pytest.mark.parametrize("start,size,window,live", [
    (0, 2048, 4096, 8), (0, 256, None, 1), (2048, 2048, 4096, 16), (6144, 2048, 4096, 24),
    (30720, 2048, 4096, 24), (30720, 2048, None, 128), (4096, 256, 4096, 17), (4096, 256, 4097, 17),
    (4352, 256, 4097, 17), (1792, 256, 4096, 8)])
def test_chunk_columns_are_the_columns_some_query_of_the_chunk_sees(start, size, window, live):
    first = 0 if window is None else max(start - (window - 1), 0) // 256
    assert kernel.chunk_columns(start, size, window, 256) == live \
        == (start + size - 1) // 256 - first + 1


# ------------------------------------------------------ the cache manager
def test_manager_answers_for_both_kinds_together():
    pool = WindowedPagePools(3, 1, 10, 20, 8, 2, 16, window=16, dtype="float32")
    wk, wv, fk, fv = pool.arrays()
    assert wk.shape == wv.shape == (3, 11, 8, 32) and fk.shape == fv.shape == (1, 21, 8, 32)
    assert pool.num_pages == 30 and pool.in_use() == 0 and pool.free_count() == 20
    assert pool.device_bytes() == sum(a.nbytes for a in pool.arrays())
    mine, theirs = pool.alloc(3), pool.window.alloc(2)
    assert pool.in_use() == 5 and pool.full.in_use() == 3 and pool.window.in_use() == 2
    pool.commit(wk + 1, wv, fk, fv + 1)
    assert float(pool.window.k[0, 0, 0, 0]) == 1 and float(pool.full.v[0, 0, 0, 0]) == 1
    with pytest.raises(ValueError, match="footprint"):
        pool.commit(fk, fv, wk, wv)
    pool.release(mine)
    pool.window.release(theirs)
    pool.mark_warm()
    assert pool.in_use() == 0 and pool.bytes_at_warmup == pool.device_bytes()
    assert pool.pad_page == 0 and pool.page_size == 8


@pytest.mark.parametrize("window,page,columns", [(4096, 256, 17), (16, 8, 3), (1, 8, 1)])
def test_window_columns_a_lane_holds_between_calls(window, page, columns):
    pool = WindowedPagePools(1, 1, 4, 4, page, 1, 8, window=window)
    assert pool.window_columns == columns == kernel.window_columns(window, page)


@pytest.mark.parametrize("position,first", [(0, 0), (4095, 0), (4096, 0), (4351, 1),
                                            (4352, 1), (4607, 2), (32768, 112)])
def test_first_live_column_follows_the_windows_edge(position, first):
    """Row j is visible to a query at i iff i - j < 4096: at 4351 the first
    visible row is 256, so page 0 has gone; at 4350 row 255 still shows."""
    pool = WindowedPagePools(1, 1, 4, 4, 256, 1, 8, window=4096)
    assert pool.first_live_column(position) == first
    assert pool.first_live_column(4350) == 0


def test_residency_follows_the_model_and_speculation_is_refused(model, engine):
    assert engine.kv_mode == "windowed" and engine.programs.chunked
    assert isinstance(engine.programs, decode_mod.WindowedPrograms)
    assert isinstance(engine.kv_pool, WindowedPagePools)
    assert isinstance(engine._scheduler, serving.scheduler.WindowedDecodeScheduler)
    pool = engine.kv_pool
    # never what decides admission: 4 lanes x 3 columns and one chunk's 4 pages
    assert pool.window.num_pages == 16 and pool.full.num_pages == 48
    assert pool.window.num_layers == 3 and pool.full.num_layers == 1
    assert engine.programs.table_rungs == [16]
    assert sorted(engine.programs.warmed) == sorted(
        [("decode", b) for b in (1, 2, 4)] + [("prefill", 1, c) for c in (8, 16, 32)]
        + [("carry", p, b) for p in (1, 2, 4) for b in (1, 2, 4)])
    with pytest.raises(ValueError, match="a window table cannot be rolled back"):
        serving.DecodeEngine(model, max_slots=2, max_seq=64, speculate_k=2)
    with pytest.raises(ValueError, match="multiple of the page size"):
        serving.DecodeEngine(model, max_slots=2, max_seq=64, seq_buckets=[12], page_size=8)
    report = engine.serving_report()
    assert report["kv_mode"] == "windowed" and report["kv_pages"] == 64


# ------------------------------------------------ the allocator's invariants
def test_a_lane_never_holds_more_window_pages_than_its_window(engine):
    """Sampled where every call's tables are built, which is where a lane
    holds most: a decode step's lane the window's columns (3), a prefill
    chunk's the chunk's own pages on top of the window behind its first
    query (2 + 4). Nothing is held once every request has retired."""
    sched, pool = engine._scheduler, engine.kv_pool
    seen = {"decode": 0, "prefill": 0, "global": 0}
    tables = sched._tables

    def watched(lanes, rows, cols):
        kind = "prefill" if rows == 1 and lanes[0].sent == 0 else "decode"
        for r in lanes:
            seen[kind] = max(seen[kind], len(r.window_pages) - r.window_from)
            seen["global"] = max(seen["global"], len(r.pages))
            assert all(p == 0 for p in r.window_pages[:r.window_from])
            assert all(p > 0 for p in r.window_pages[r.window_from:])
        return tables(lanes, rows, cols)

    sched._tables = watched
    try:
        rng = np.random.default_rng(4)
        sent = [engine.submit("t", rng.integers(0, 256, n).astype(np.int32), max_new_tokens=20)
                for n in (100, 5, 70, 33, 90, 64, 17, 100)]
        assert all(len(r.result(timeout=120)) == 20 for r in sent)
    finally:
        sched._tables = tables
    assert seen["decode"] == pool.window_columns == 3
    assert seen["prefill"] == 2 + 4 and seen["global"] == 15      # 119 rows of 8
    assert pool.window.in_use() == 0 and pool.full.in_use() == 0
    assert all(r.window_pages == [] and r.pages == [] and r.window_from == 0 for r in sent)


def test_window_pages_released_are_counted_and_the_gauges_are_by_kind(engine):
    from paddle_tpu.observability.metrics import registry

    released = registry.counter("serving.kv_window_pages_released")
    before = released.value()
    engine.generate("t", np.arange(70, dtype=np.int32), max_new_tokens=10)
    # the last query, at 78 (the tenth token is not fed), still sees row 63:
    # columns 0..6 went
    assert released.value() - before == 7
    assert registry.gauge("serving.kv_pages_in_use.window").value() == 0
    assert registry.gauge("serving.kv_pages_in_use.full").value() == 0


def test_admission_counts_both_kinds_and_makes_prompts_wait_not_fail(model):
    """A window pool of one prompt's worth: the second prompt waits for the
    first one's chunk to give its pages back; a global pool of two prompts'
    worth: the third waits for a retirement. Nothing is shed."""
    eng = serving.DecodeEngine(model, max_slots=4, max_seq=128, seq_buckets=[8, 16, 32],
                               page_size=8, pool_pages=22, window_pool_pages=9)
    eng.warmup()
    try:
        rng = np.random.default_rng(3)
        sent = [eng.submit("t", rng.integers(0, 256, 70).astype(np.int32), max_new_tokens=6)
                for _ in range(6)]                     # 9 global pages each, 22 in all
        assert all(len(r.result(timeout=120)) == 6 for r in sent)
        assert eng.kv_pool.in_use() == 0 and eng._scheduler.shed_count == 0
    finally:
        eng.shutdown()


def test_a_shed_request_gives_back_both_kinds(engine, monkeypatch):
    """The window pool refuses the SECOND chunk's pages (an injected
    `kv.page_alloc` fault): the request is shed holding two chunks' global
    pages and one chunk's window pages, and both kinds come back."""
    from paddle_tpu.reliability.faults import FaultInjection
    from paddle_tpu.serving import AdmissionError

    calls, alloc = {"n": 0}, engine.kv_pool.window.alloc

    def flaky(n=1):
        calls["n"] += 1
        if calls["n"] == 2:
            raise FaultInjection("kv.page_alloc")
        return alloc(n)

    monkeypatch.setattr(engine.kv_pool.window, "alloc", flaky)
    bad = engine.submit("t", np.arange(70, dtype=np.int32), max_new_tokens=4)
    with pytest.raises(AdmissionError):
        bad.result(timeout=120)
    monkeypatch.undo()
    assert calls["n"] == 2 and engine._scheduler.shed_count >= 1
    assert engine.kv_pool.window.in_use() == 0 and engine.kv_pool.full.in_use() == 0
    assert bad.pages == [] and bad.window_pages == []
    assert len(engine.generate("t", np.arange(70, dtype=np.int32), max_new_tokens=4)) == 4


def test_a_crashed_chunk_fails_only_its_request_and_frees_both_kinds(engine, monkeypatch):
    calls = {"n": 0}
    prefill = engine.programs.prefill

    def boom(*args):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("chunk crashed")
        return prefill(*args)

    monkeypatch.setattr(engine.programs, "prefill", boom)
    bad = engine.submit("t", np.arange(70, dtype=np.int32), max_new_tokens=4)
    with pytest.raises(RuntimeError, match="chunk crashed"):
        bad.result(timeout=120)
    monkeypatch.undo()
    assert len(engine.generate("t", np.arange(70, dtype=np.int32), max_new_tokens=4)) == 4
    assert engine.kv_pool.in_use() == 0


# ------------------------------------------------- spans, counters, regions
def test_steps_say_their_pages_of_both_kinds_and_their_pairs(engine):
    from paddle_tpu.observability.metrics import registry
    from paddle_tpu.observability.tracing import tracer

    names = ("serving.moe.pairs", "serving.moe.experts_hit", "serving.prefill_chunks")
    before = {n: registry.counter(n).value() for n in names}
    tracer.reset()
    tracer.enable()
    try:
        time.sleep(0.2)   # the idle beat under way was not recording: let it end
        engine.generate("t", np.arange(70, dtype=np.int32), max_new_tokens=5)
    finally:
        tracer.disable()
    steps = [e["args"] for e in tracer.to_chrome_trace()["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "serving.decode"]
    assert [a["lanes"] for a in steps] == [1] * 7 + [0]
    steps = steps[:-1]
    chunks = [a for a in steps if a["kind"] == "prefill"]
    decodes = [a for a in steps if a["kind"] == "decode"]
    assert [(a["chunk"], a["chunks"], a["tokens"]) for a in chunks] == [(0, 3, 32), (1, 3, 32), (2, 3, 6)]
    # a chunk reads every global page up to its end and the window behind
    # its first query: rows 0-31, 17-63 (from column 2), 49-69 (from column 6)
    assert [(a["pages_live"], a["window_pages_live"]) for a in chunks] == [(4, 4), (8, 6), (9, 3)]
    # the columns the chunk kernel's calls visit, three window layers and a
    # global one: rungs 32, 32, 8 at 0, 32, 64 under a window of 16 (the
    # chunk's own and two behind); whole blocks of 32 rows would visit 1, 2, 2
    # blocks of 4 columns a window layer and 1, 2, 3 the global one
    assert [(a["attn_columns_live"], a["attn_columns_dense"]) for a in chunks] \
        == [(3 * 4 + 4, 3 * 4 + 4), (3 * 6 + 8, 3 * 8 + 8), (3 * 3 + 9, 3 * 8 + 12)]
    assert all("attn_columns_live" not in a for a in decodes)
    # decode at 70..73: global columns 0..8 (0..9 from 72), window from (p - 15) // 8
    assert [(a["pages_live"], a["window_pages_live"]) for a in decodes] \
        == [(9, 3), (9, 2), (10, 3), (10, 3)]
    assert all(a["pages_table"] == 16 for a in decodes)
    for a in steps:
        tokens = a.get("tokens", a["lanes"])
        # 4 layers, 8 held experts, 4 chosen of 16 a token
        assert 0 < a["pairs"] <= tokens * 4 * 4 and 0 < a["experts_hit"] <= 32
    for n in names[:2]:
        key = "pairs" if n.endswith("pairs") else "experts_hit"
        assert registry.counter(n).value() - before[n] == sum(a[key] for a in steps)
    assert registry.counter(names[2]).value() - before[names[2]] == 3


@pytest.mark.parametrize("key", [("decode", 2), ("prefill", 1, 8)], ids=lambda k: k[0])
def test_lowered_program_names_its_regions(engine, key):
    """Each program body under its own root, every region of the windowed
    vocabulary in some operation's name (off the TPU decode gathers its
    pages too, `attn/kv_gather`, which the kernel replaces)."""
    P = engine.programs
    text = P._jitted(key).lower(P.params, *P.pool.arrays(), *P._zero_args(key)).as_text(
        debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    assert any(f"/{key[0]}/" in f"/{n}" for n in names)
    for name in regions.WINDOWED_MOE:
        assert any(f"/{name}/" in f"/{n}/" for n in names), name
    assert regions.GQA_ATTN in regions.KERNELS


def test_with_the_kernel_decode_gathers_nothing_and_names_the_kernel(monkeypatch, engine):
    import jax

    from paddle_tpu.analysis.drift_check import _walk

    _force_kernel(monkeypatch)
    P = engine.programs
    # a function of its own: the warmed program's trace is cached by function
    closed = jax.make_jaxpr(lambda *a: P._decode_fn(*a))(
        P.params, *P.pool.arrays(), *P._zero_args(("decode", 4)))
    calls = [e for e in closed.jaxpr.eqns if e.params.get("name") == "gqa_paged_attention"]
    assert len(calls) == 4                                       # one a layer
    windows = []
    for e in calls:
        assert str(e.source_info.name_stack).endswith(regions.ATTN_CORE)
        (call,) = [x for x in _walk(e.params["jaxpr"].jaxpr) if x.primitive.name == "pallas_call"]
        assert str(call.source_info.name_stack) == regions.GQA_ATTN
        windows.append(tuple(call.params["grid_mapping"].grid))
    # three window layers over 3 table columns, the global one over all 16
    assert windows == [(4, 1)] * 3 + [(4, 4)]
    for e in _walk(closed.jaxpr):
        assert regions.ATTN_KV_GATHER not in str(e.source_info.name_stack)


def test_with_the_kernel_a_prefill_chunk_scores_no_block_in_memory(monkeypatch, engine):
    """One `gqa_chunk_attention` a layer under `attn/core`, the kernel under
    its own name, nothing gathered, and no float32 tensor with a block of
    keys (32 here) in its last dimension: a tile's scores stay in the kernel.
    Off the TPU the same program holds them."""
    import jax

    from paddle_tpu.analysis.drift_check import _walk

    P = engine.programs
    key = ("prefill", 1, 32)

    def scores(closed):
        """Float32 `[chunk, query heads of a K/V head, block]` tensors."""
        return [v.aval.shape for e in _walk(closed.jaxpr) if e.primitive.name != "pallas_call"
                for v in e.outvars if getattr(v.aval, "dtype", None) == jnp.float32
                and len(v.aval.shape) >= 3 and v.aval.shape[-1] == 32 == v.aval.shape[-3]]

    args = (P.params, *P.pool.arrays(), *P._zero_args(key))
    assert scores(jax.make_jaxpr(lambda *a: P._prefill_fn(*a))(*args))
    _force_kernel(monkeypatch)
    closed = jax.make_jaxpr(lambda *a: P._prefill_fn(*a))(*args)
    calls = [e for e in closed.jaxpr.eqns if e.params.get("name") == "gqa_chunk_attention"]
    assert len(calls) == 4                                       # one a layer
    grids = []
    for e in calls:
        assert str(e.source_info.name_stack).endswith(regions.ATTN_CORE)
        (call,) = [x for x in _walk(e.params["jaxpr"].jaxpr) if x.primitive.name == "pallas_call"]
        assert str(call.source_info.name_stack) == regions.GQA_CHUNK_ATTN
        grids.append(tuple(call.params["grid_mapping"].grid))
    # (K/V heads, tiles, steps of so many columns): a window layer over the
    # chunk's 4 columns and the 2 behind them, the global one over all 16
    per = kernel.CHUNK_PAGES
    assert grids == [(2, 1, -(-6 // per))] * 3 + [(2, 1, -(-16 // per))]
    assert not scores(closed)
    for e in _walk(closed.jaxpr):
        assert regions.ATTN_KV_GATHER not in str(e.source_info.name_stack)
    assert regions.GQA_CHUNK_ATTN in regions.KERNELS and regions.GQA_CHUNK_ATTN != regions.GQA_ATTN


# ------------------------------------------------- the tolerances, and faults
@pytest.fixture(scope="module")
def stand_in():
    _, eng = lm.build_engine(STAND_IN, 2147483900)
    yield eng
    eng.shutdown(drain=False)


def test_attention_check_of_the_sound_program_is_far_inside_its_tolerance(stand_in):
    assert lm.latent_error(stand_in, STAND_IN, TRAFFIC, 2147483900) \
        < TRAFFIC["attention_check"]["tolerance"] / 10
    assert stand_in.kv_pool.in_use() == 0


@pytest.mark.parametrize("fault", lm.FAULTS)
def test_each_attention_fault_fails_the_attention_check(stand_in, fault):
    """The window's edge off by one, a window layer left unrotated, a global
    layer rotated, a released page read: each read past the tolerance."""
    assert lm.latent_error(stand_in, STAND_IN, TRAFFIC, 2147483900, fault=fault) \
        > 2 * TRAFFIC["attention_check"]["tolerance"]


def _judged(engine, monkeypatch=None, patch=None, **how):
    traffic = dict(TRAFFIC, check_prompts=[5, 16, 17, 20, 70, 100], check_answer=24,
                   check_widths=[32, 128], check_block=32)
    answered = lm.collect_check(lm.send_check(engine, STAND_IN, traffic, 2147483900), traffic)
    return lm.judge_check(engine.programs.params, STAND_IN, traffic, answered, **how), traffic


def test_logit_check_of_the_sound_program_is_exact_in_float32(stand_in):
    check, traffic = _judged(stand_in)
    assert check["complete"] and check["tokens"] == 6 * 24
    assert check["worst_gap"] < 1e-4 and check["exact"] == check["tokens"]


def test_shared_experts_not_divided_by_their_number_fail_the_logit_check(stand_in):
    check, traffic = _judged(stand_in, average=False)
    assert (check["exact"] < traffic["exact_floor"] * check["tokens"]
            or check["worst_gap"] > traffic["logit_tolerance"])


@pytest.mark.parametrize("name", ["shared_not_averaged", "weights_not_normalised",
                                  "norm_not_shared"])
def test_each_block_fault_fails_the_logit_check(monkeypatch, name):
    """Faults of the PROGRAM's block, each in a fresh engine (its programs
    trace the faulted body): the shared experts summed, the routing weights
    left unnormalised, the experts fed another input than the block's one
    norm."""
    from paddle_tpu.nn.functional import sparse_experts as se

    P = decode_mod.WindowedPrograms
    if name == "shared_not_averaged":
        swiglu = se.swiglu
        monkeypatch.setattr(se, "swiglu", lambda n, g, d: swiglu(n, g, d) * 2.0)
    elif name == "weights_not_normalised":
        route = se.route
        monkeypatch.setattr(se, "route", lambda *a, **k: route(*a, **dict(k, norm_topk=False)))
    else:
        ffn = P._ffn
        monkeypatch.setattr(P, "_ffn", lambda self, w, n, valid: ffn(self, w, n * 1.5, valid))
    _, eng = lm.build_engine(STAND_IN, 2147483900)
    try:
        check, traffic = _judged(eng)
    finally:
        eng.shutdown(drain=False)
    assert check["complete"]
    assert (check["exact"] < traffic["exact_floor"] * check["tokens"]
            or check["worst_gap"] > traffic["logit_tolerance"]), check
