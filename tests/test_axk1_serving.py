"""A.X-K1 through the decode engine on the CPU at a small size: the latent
page pool, chunked prefill over pages, the absorbed decode step, the expert
layer's share, and the benchmark's own two comparisons with each way of
computing less refused by one of them. The reference is
benchmark/reference_axk1.py, the stand-in configuration and the tolerances
are the benchmark's own files."""
import dataclasses
import functools
import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmark import harness, models_axk1  # noqa: E402
from benchmark import reference_axk1 as ref  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.base import regions  # noqa: E402
from paddle_tpu.models import AXK1ForCausalLM, axk1_tiny  # noqa: E402
from paddle_tpu.nn.functional import latent_attention as la  # noqa: E402
from paddle_tpu.nn.functional import sparse_experts as se  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as kernel  # noqa: E402
from paddle_tpu.serving import decode as decode_mod  # noqa: E402
from paddle_tpu.serving.kv_cache import KVPagePool  # noqa: E402

STAND_IN = harness.load_json(os.path.join(ROOT, "benchmark", "tests", "tiny-axk1.json"))
TRAFFIC = harness.load_json(os.path.join(ROOT, "benchmark", "traffic", "docqa-closed.json"))
SHARE = (1, 4)


def weights_of(model):
    return decode_mod._extract_axk1(model)[0]


@pytest.fixture(scope="module")
def model():
    paddle.seed(5)
    m = AXK1ForCausalLM(axk1_tiny(), expert_share=SHARE)
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(model):
    eng = serving.DecodeEngine(model, max_slots=4, max_seq=128, seq_buckets=[8, 16],
                               page_size=8, pool_pages=48)
    eng.warmup()
    yield eng
    eng.shutdown()


def _gaps(model, prompt, out):
    """For each returned token, how far the reference's logit for it lies
    under the reference's best at that position."""
    ids = jnp.asarray(np.concatenate([prompt, out]), jnp.int32)
    logits = np.asarray(ref.forward_logits(weights_of(model), ids,
                                           dataclasses.asdict(model.config), SHARE))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
    return rows.max(-1) - rows[np.arange(len(out)), out]


# ------------------------------------------------------------------ the pool
def test_page_pool_takes_a_row_width_for_both_callers():
    gpt = KVPagePool(2, 6, 8, 3, 4)
    assert gpt.k.shape == gpt.v.shape == (2, 7, 8, 12) and len(gpt.arrays()) == 2
    assert gpt.row_width == 12 and gpt.device_bytes() == 2 * gpt.k.nbytes
    latent = KVPagePool(3, 6, 8, dtype="bfloat16", row_width=128, arrays=1)
    assert latent.arrays()[0].shape == (3, 7, 8, 128) and latent.k is latent.arrays()[0]
    assert latent.device_bytes() == latent.k.nbytes and latent.num_heads is None
    for pool in (gpt, latent):
        pages = pool.alloc(4)
        assert pool.in_use() == 4 and pool.pad_page == 0 and 0 not in pages
        pool.release(pages)
        pool.commit(*pool.arrays())
        pool.mark_warm()
        assert pool.bytes_at_warmup == pool.device_bytes()
    with pytest.raises(ValueError):
        latent.commit(latent.k, latent.k)            # one array, not two
    with pytest.raises(ValueError):
        latent.commit(latent.k.astype(jnp.float32))  # the footprint is pinned
    with pytest.raises(ValueError):
        KVPagePool(1, 4, 8)                          # neither heads nor a width


# ---------------------------------------------------------------- the kernel
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_latent_kernel_agrees_with_the_dense_oracle_in_interpret_mode(dtype, tol):
    """Lanes at position 0, at a page's last row, past a page's edge, at the
    table's end and a padded one; 5 heads (padded to 16 rows); the table 5
    wide, so a grid step is 5 entries."""
    rng = np.random.default_rng(0)
    L, N, page, W, rank, dr, H = 2, 12, 8, 128, 32, 8, 5
    pool = jnp.asarray(rng.standard_normal((L, N, page, W)), dtype)
    tables = jnp.asarray([[1, 0, 0, 0, 0], [2, 0, 0, 0, 0], [3, 4, 5, 0, 0],
                          [6, 7, 8, 9, 10], [0, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([0, 7, 17, 39, 0], jnp.int32)
    q = jnp.asarray(rng.standard_normal((5, H, W)), dtype).at[:, :, rank + dr:].set(0)
    got = kernel.latent_paged_attention(q, pool, 1, tables, pos, v_cols=rank, scale=0.3,
                                        interpret=True)
    rows = pool[1][tables].reshape(5, 5 * page, W)
    want = la.attend_absorbed(q[..., :rank], q[..., rank:rank + dr], rows, pos, rank, 0.3)
    assert got.shape == (5, H, rank) and got.dtype == q.dtype
    assert float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))[:4].max()) < tol


def test_with_the_kernel_the_engine_returns_the_same_tokens(monkeypatch, model):
    def tokens():
        eng = serving.DecodeEngine(model, max_slots=2, max_seq=64, seq_buckets=[8],
                                   page_size=8, pool_pages=16)
        eng.warmup()
        try:
            return eng.generate("t", np.arange(3, 22, dtype=np.int32), max_new_tokens=6)
        finally:
            eng.shutdown()

    want = tokens()
    monkeypatch.setattr(decode_mod.LatentPrograms, "_kernel", staticmethod(lambda: True))
    monkeypatch.setattr(kernel, "latent_paged_attention", functools.partial(
        kernel.latent_paged_attention, interpret=True))
    assert np.array_equal(tokens(), want)


# ---------------------------------------------------------------- the engine
@pytest.mark.parametrize("length", [3, 8, 16, 17, 21, 37, 48])
def test_prefill_in_chunks_then_decode_agrees_with_the_references_full_forward(
        engine, model, length):
    """Chunk edges at the tiny rungs (a chunk is 16): exactly one chunk, a
    chunk and a token, a ragged last chunk of 5 on the 8 rung after one and
    after two whole ones, three whole chunks. Float32 weights: the engine's
    tokens are the reference's own argmax up to 1e-3 of a logit."""
    prompt = np.random.default_rng(length).integers(0, 256, length).astype(np.int32)
    out = engine.generate("t", prompt, max_new_tokens=10)
    assert len(out) == 10 and float(_gaps(model, prompt, out).max()) < 1e-3
    assert engine.kv_pool.in_use() == 0 and engine.compiles_after_warmup == 0


def test_a_batch_of_lanes_at_mixed_depths(engine, model):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (50, 4, 23, 16, 9, 33)]
    alone = [engine.generate("t", p, max_new_tokens=12) for p in prompts]
    sent = [engine.submit("t", p, max_new_tokens=12) for p in prompts]   # six on four lanes
    for p, req, solo in zip(prompts, sent, alone):
        out = req.result(timeout=120)
        assert np.array_equal(out, solo)
        assert float(_gaps(model, p, out).max()) < 1e-3
    assert engine.kv_pool.in_use() == 0 and engine.active_requests() == 0


def test_pages_are_freed_at_retirement_and_reused(engine):
    """Twelve requests of 5 pages each through a pool of 48: the pages of the
    first come back for the last; the pool's bytes never move and nothing
    compiles."""
    pool, rng = engine.kv_pool, np.random.default_rng(2)
    before = pool.device_bytes()
    sent = [engine.submit("t", rng.integers(0, 256, 30).astype(np.int32), max_new_tokens=8)
            for _ in range(12)]
    assert all(len(r.result(timeout=120)) == 8 for r in sent)
    assert pool.in_use() == 0 and pool.free_count() == 48
    assert pool.device_bytes() == before == pool.bytes_at_warmup
    assert engine.compiles_after_warmup == 0
    assert engine.serving_report()["kv_mode"] == "latent"


def test_a_pool_too_small_for_every_lane_makes_prompts_wait_not_fail(model):
    eng = serving.DecodeEngine(model, max_slots=4, max_seq=64, seq_buckets=[8, 16],
                               page_size=8, pool_pages=14)
    eng.warmup()
    try:
        rng = np.random.default_rng(3)
        sent = [eng.submit("t", rng.integers(0, 256, 40).astype(np.int32), max_new_tokens=6)
                for _ in range(6)]                     # 6 pages each, 14 in all
        assert all(len(r.result(timeout=120)) == 6 for r in sent)
        assert eng.kv_pool.in_use() == 0 and eng._scheduler.shed_count == 0
    finally:
        eng.shutdown()


def test_residency_follows_the_model_and_speculation_is_refused(model, engine):
    assert engine.kv_mode == "latent" and engine.programs.chunked
    assert isinstance(engine.programs, decode_mod.LatentPrograms)
    assert engine.kv_pool.row_width == 128 and len(engine.kv_pool.arrays()) == 1
    assert engine.programs.table_rungs == [16]
    assert sorted(engine.programs.warmed) == sorted(
        [("decode", b) for b in (1, 2, 4)] + [("prefill", 1, c) for c in (8, 16)]
        + [("carry", p, b) for p in (1, 2, 4) for b in (1, 2, 4)])
    with pytest.raises(ValueError, match="layer 0 is another kind of layer"):
        serving.DecodeEngine(model, max_slots=2, max_seq=64, speculate_k=2)
    with pytest.raises(ValueError, match="multiple of the page size"):
        serving.DecodeEngine(model, max_slots=2, max_seq=64, seq_buckets=[12], page_size=8)


def test_sampled_decoding_is_deterministic_per_seed(engine):
    prompt = np.arange(20, dtype=np.int32)
    a, b, c = (engine.submit("t", prompt, max_new_tokens=8, temperature=0.9, top_k=50,
                             seed=s).result(timeout=120) for s in (7, 7, 8))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_a_crashed_chunk_fails_only_its_request_and_frees_its_pages(engine, monkeypatch):
    calls = {"n": 0}
    prefill = engine.programs.prefill

    def boom(*args):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("chunk crashed")
        return prefill(*args)

    monkeypatch.setattr(engine.programs, "prefill", boom)
    bad = engine.submit("t", np.arange(40, dtype=np.int32), max_new_tokens=4)
    with pytest.raises(RuntimeError, match="chunk crashed"):
        bad.result(timeout=120)
    monkeypatch.undo()
    assert len(engine.generate("t", np.arange(40, dtype=np.int32), max_new_tokens=4)) == 4
    assert engine.kv_pool.in_use() == 0


# ------------------------------------------------- spans, counters, regions
def test_steps_say_their_chunk_their_pairs_and_the_experts_hit(engine):
    from paddle_tpu.observability.metrics import registry
    from paddle_tpu.observability.tracing import tracer

    def counter(name):
        return registry.counter(name).value()

    names = ("serving.moe.pairs", "serving.moe.experts_hit", "serving.latent.rows_written",
             "serving.prefill_chunks")
    before = {n: counter(n) for n in names}
    tracer.reset()
    tracer.enable()
    try:
        time.sleep(0.2)   # the idle beat under way was not recording: let it end
        engine.generate("t", np.arange(37, dtype=np.int32), max_new_tokens=5)
    finally:
        tracer.disable()
    steps = [e["args"] for e in tracer.to_chrome_trace()["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "serving.decode"]
    # a call's counts reach its own span with its read, a beat later; the last
    # beat only reads (nothing to dispatch): its span has no lanes and no counts
    assert [a["lanes"] for a in steps] == [1] * 7 + [0] and steps[-1]["kind"] == "decode"
    steps = steps[:-1]
    chunks = [a for a in steps if a["kind"] == "prefill"]
    decodes = [a for a in steps if a["kind"] == "decode"]
    assert [(a["chunk"], a["chunks"], a["tokens"]) for a in chunks] == [(0, 3, 16), (1, 3, 16), (2, 3, 5)]
    assert len(decodes) == 4 and all({"pages_live", "pages_table", "lanes"} <= set(a) for a in decodes)
    for a in steps:
        tokens = a.get("tokens", a["lanes"])
        # 2 sparse layers, 4 held experts, 4 chosen of 16 a token
        assert 0 <= a["pairs"] <= tokens * 4 * 2 and 0 <= a["experts_hit"] <= 8
        assert (a["pairs"] == 0) == (a["experts_hit"] == 0)
    assert counter("serving.moe.pairs") - before["serving.moe.pairs"] == sum(a["pairs"] for a in steps)
    assert counter("serving.moe.experts_hit") - before["serving.moe.experts_hit"] \
        == sum(a["experts_hit"] for a in steps)
    assert counter("serving.latent.rows_written") - before["serving.latent.rows_written"] \
        == (37 + 4) * 3                                # a row a token a layer
    assert counter("serving.prefill_chunks") - before["serving.prefill_chunks"] == 3


@pytest.mark.parametrize("key", [("decode", 2), ("prefill", 1, 8)], ids=lambda k: k[0])
def test_lowered_program_names_its_regions(engine, key):
    """Each program body under its own root, every region of the latent
    vocabulary in some operation's name; `attn/expand` is prefill's alone
    (decode never forms a key or value of the context), and off the TPU
    decode gathers its pages (`attn/kv_gather`, which the kernel replaces)."""
    import re

    P = engine.programs
    text = P._jitted(key).lower(P.params, *P.pool.arrays(), *P._zero_args(key)).as_text(
        debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    assert any(f"/{key[0]}/" in f"/{n}" for n in names)
    other = regions.ATTN_EXPAND if key[0] == "decode" else None
    for name in regions.LATENT_MOE:
        assert any(f"/{name}/" in f"/{n}/" for n in names) == (name != other), name
    assert regions.LATENT_ATTN in regions.KERNELS and regions.RAGGED_DOT == "ragged-dot"


# ------------------------------------------------- the tolerances, and faults
def _stand_in_check(monkeypatch=None, fault=None):
    """The benchmark's own two comparisons (models_axk1: the engine's tokens
    judged by the reference over the engine's bfloat16 weights and share, and
    the attention path alone through the engine's pool) on the CPU stand-in,
    with prompts of one to five chunks of 32."""
    if fault is not None:
        fault(monkeypatch)
    traffic = dict(TRAFFIC, check_prompts=[150, 100, 68, 32, 33, 5], check_answer=48,
                   check_widths=[96, 224], latent_check=dict(TRAFFIC["latent_check"], ragged=5))
    model = models_axk1.build(STAND_IN, 2147483900)
    engine = serving.DecodeEngine(model, **STAND_IN["engine"])
    engine.warmup()
    try:
        answered = models_axk1.collect_check(
            models_axk1.send_check(engine, STAND_IN, traffic, 2147483900), traffic)
    finally:
        engine.shutdown()
    latent = models_axk1.latent_error(engine, STAND_IN, traffic, 2147483900)
    assert engine.kv_pool.in_use() == 0
    if monkeypatch is not None:
        monkeypatch.undo()          # the judge runs the sound reference, and only it
    check = models_axk1.judge_check(weights_of(model), STAND_IN, traffic, answered)
    return check, latent, models_axk1.verdict(check, latent, traffic, True, 0, 0, True)


def _scale_without_yarn(mp):
    mp.setattr(la, "softmax_scale", lambda qk, scaling: qk ** -0.5)


def _k_rope_not_rotated(mp):
    rope = la.rope
    mp.setattr(la, "rope", lambda x, pos, f: x if x.ndim == 2 else rope(x, pos, f))


def _pages_before_the_cursor_ignored(mp):
    attend = decode_mod.LatentPrograms._attend_chunk

    def forgetful(self, w, qn, qr, pool, li, table, start):
        before = jnp.arange(table.shape[0]) < start // self.pool.page_size
        return attend(self, w, qn, qr, pool, li, jnp.where(before, 0, table), start)
    mp.setattr(decode_mod.LatentPrograms, "_attend_chunk", forgetful)


def _one_held_expert_dropped(mp):
    held = se.held_experts
    mp.setattr(se, "held_experts", lambda x, idx, w, *a, first, **kw: held(
        x, idx, jnp.where(idx == first, 0.0, w), *a, first=first, **kw))


def _route_with(**changes):
    def fault(mp):
        route = se.route
        mp.setattr(se, "route", lambda x, w, **kw: route(x, w, **dict(kw, **changes)))
    return fault


def _shared_expert_dropped(mp):
    swiglu, shared = se.swiglu, 2 * STAND_IN["moe_intermediate_size"]
    mp.setattr(se, "swiglu", lambda x, gu, dn: swiglu(x, gu, dn) * (gu.shape[-1] != shared))


def _latent_kept_unnormalised(mp):
    rms, rank = decode_mod._rms, STAND_IN["kv_lora_rank"]
    mp.setattr(decode_mod, "_rms",
               lambda x, w, eps: x if w.shape[-1] == rank else rms(x, w, eps))


FAULTS = {
    "scale-without-yarn": (_scale_without_yarn, "latent"),
    "k-rope-not-rotated": (_k_rope_not_rotated, "latent"),
    "pages-before-the-cursor-ignored": (_pages_before_the_cursor_ignored, "both"),
    "one-held-expert-dropped": (_one_held_expert_dropped, "logit"),
    "weights-not-normalised": (_route_with(norm_topk=False), "logit"),
    "weights-not-times-2.5": (_route_with(scaling=1.0), "logit"),
    "group-limit-ignored": (_route_with(group_limited=False), "logit"),
    "shared-expert-dropped": (_shared_expert_dropped, "logit"),
    "latent-kept-unnormalised": (_latent_kept_unnormalised, "logit"),
}


def test_sound_program_is_well_inside_both_tolerances():
    check, latent, correct = _stand_in_check()
    print("READING sound", check["worst_gap"], latent, check["exact"], check["tokens"])
    assert correct and check["complete"] and check["tokens"] == 6 * 48
    # a router's choice is discrete: the worst of 288 tokens has a long tail
    # (1.2 here), and nearly every token is the reference's own all the same
    assert check["worst_gap"] < TRAFFIC["logit_tolerance"]
    assert check["exact"] >= 0.95 * check["tokens"]
    assert latent < TRAFFIC["latent_check"]["tolerance"] / 2


@pytest.mark.parametrize("name", list(FAULTS))
def test_each_fault_fails_a_tolerance(monkeypatch, name):
    """The ways of computing something else than the configuration states,
    each refused by one of the cell's limits: the logit check (what the
    engine's tokens say: the worst gap for a gross fault, the share that is
    the reference's own choice for one that moves every token a little) or
    the attention path's (its scale, its rotation,
    the pages before a chunk's cursor). With seeded weights the model's own
    attention is nearly uniform, so a wrong scale or an unrotated k_rope
    passes the first and fails the second; the expert layer and the
    latent's norm live outside the second."""
    fault, limit = FAULTS[name]
    check, latent, correct = _stand_in_check(monkeypatch, fault)
    print("READING", name, check["worst_gap"], latent, check["exact"], check["tokens"])
    assert not correct
    by_logit = (check["worst_gap"] > TRAFFIC["logit_tolerance"]
                or check["exact"] < TRAFFIC["exact_floor"] * check["tokens"])
    by_latent = latent > TRAFFIC["latent_check"]["tolerance"]
    # on the chip, at the published widths, a fault of the attention's scale
    # or rotation hides under the first limit (the model's own attention is
    # nearly uniform there); the stand-in's is not, and it fails that too
    assert by_latent == (limit != "logit") and (by_logit or limit == "latent")


@pytest.mark.parametrize("stale", [0.0, 3.0], ids=["empty-pool", "stale-pool"])
def test_latent_check_drives_the_engines_own_pool(stale):
    """The check writes the ENGINE'S pool array (its dtype, its last layer,
    pages it allocates and gives back); what the pages held before does not
    reach the reading."""
    traffic = dict(TRAFFIC, latent_check=dict(TRAFFIC["latent_check"], ragged=5))
    engine = serving.DecodeEngine(models_axk1.build(STAND_IN, 7), **STAND_IN["engine"])
    try:
        pool = engine.kv_pool
        pool.commit(jnp.full_like(pool.k, stale))
        error = models_axk1.latent_error(engine, STAND_IN, traffic, 7)
        assert error < traffic["latent_check"]["tolerance"] / 2
        rows = np.asarray(pool.k.astype(jnp.float32))
        assert pool.k.dtype == jnp.bfloat16 and pool.in_use() == 0
        assert (rows[:-1] == stale).all() and (rows[-1] != stale).any()
    finally:
        engine.shutdown()
