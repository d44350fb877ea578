"""Paged KV decode tests (ISSUE 18): the vLLM-style page pool behind
the decode serving tier — block-table paging, the mixed-context decode
matrix (the bench runs the real 128–4k spread; these tests scale the
same four-bucket shape down to fit the tier-1 budget), mid-flight page
growth, page reclaim, greedy bit-exactness vs the slot-pool oracle,
sampled decoding determinism, pool-pressure wait/shed semantics, the
JX334 fragmentation watermark and the page-pressure chaos scenario."""
import contextlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.profiler.pipeline import ServingStats
from paddle_tpu.serving import AdmissionError
from paddle_tpu.serving.kv_cache import KVPagePool, KVSlotPool


def _tiny_model(**overrides):
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    base = dict(vocab_size=128, num_hidden_layers=1, hidden_size=8,
                num_attention_heads=1, max_position_embeddings=512)
    base.update(overrides)
    model = GPTForCausalLM(gpt_tiny(**base))
    model.eval()
    return model


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


def _paged(model, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 512)
    kw.setdefault("seq_buckets", [64, 128, 256, 512])
    kw.setdefault("prefill_max_batch", 2)
    kw.setdefault("page_size", 32)
    kw.setdefault("kv_mode", "paged")
    kw.setdefault("stats", ServingStats())
    return serving.DecodeEngine(model, **kw)


@pytest.fixture(scope="module")
def engine(model):
    eng = _paged(model).warmup()
    yield eng
    eng.shutdown(drain=True)


@pytest.fixture(scope="module")
def programs():
    """A speculating engine's four program families, not warmed: for
    the tests that walk a traced program."""
    model = _tiny_model(num_hidden_layers=2, num_attention_heads=2,
                        max_position_embeddings=64)
    eng = _paged(model, max_seq=64, seq_buckets=[32, 64], page_size=16,
                 speculate_k=2, spec_draft_layers=1)
    yield eng.programs
    eng.shutdown(drain=False)


@pytest.fixture(scope="module")
def oracle(model):
    """The PR 13 slot-pool engine: greedy decode ground truth."""
    eng = serving.DecodeEngine(
        model, max_slots=4, max_seq=512, seq_buckets=[64, 128, 256, 512],
        prefill_max_batch=2, kv_mode="slots", stats=ServingStats()).warmup()
    yield eng
    eng.shutdown(drain=True)


# the four-bucket interleaved matrix: every seq rung, two mid-flight
# page growers (32+8 and 63+8 both cross a 32-token page boundary)
MATRIX = [50, 100, 240, 500, 32, 63, 200, 120]


def _prompts(sizes, seed=3):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 128, size=int(n)).astype(np.int32)
            for n in sizes]


# ------------------------------------------------------------ KVPagePool
class TestKVPagePool:
    def _pool(self, pages=6, ps=8):
        return KVPagePool(1, pages, ps, 1, 4)

    def test_alloc_low_ids_first_pad_reserved(self):
        pool = self._pool()
        assert pool.pad_page == 0
        assert pool.alloc(3) == [1, 2, 3]  # low ids hand out first
        pool.release([2])
        assert pool.alloc(2) == [2, 4]  # freed page reused before fresh
        assert pool.in_use() == 4

    def test_release_guards_double_free_and_range(self):
        pool = self._pool()
        pages = pool.alloc(2)
        pool.release(pages)
        with pytest.raises(ValueError, match="already free"):
            pool.release([pages[0]])
        with pytest.raises(ValueError, match="out of range"):
            pool.release([0])  # the pad page is never allocatable

    def test_exhaustion_names_occupancy(self):
        pool = self._pool(pages=2)
        pool.alloc(2)
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.alloc(1)
        # a failed alloc must not leak partial state
        assert pool.in_use() == 2 and pool.free_count() == 0

    def test_commit_footprint_guard(self):
        import jax.numpy as jnp

        pool = self._pool()
        with pytest.raises(ValueError, match="footprint"):
            pool.commit(jnp.zeros((1, 3, 8, 4)), pool.v)
        # the same bytes with the minor dimension split back into
        # (heads, head_dim) is another footprint too: the layout is pinned
        with pytest.raises(ValueError, match="footprint"):
            pool.commit(pool.k.reshape(1, 7, 8, 1, 4), pool.v)
        pool.commit(pool.k + 0, pool.v + 0)  # same footprint: fine

    @pytest.mark.parametrize("layers,pages,ps,heads,dim",
                             [(1, 6, 8, 1, 4), (2, 5, 16, 2, 4),
                              (3, 4, 32, 12, 64)])
    def test_device_bytes_and_shape(self, layers, pages, ps, heads, dim):
        """The merged layout moves no byte: K and V are ``[layers,
        pages+1, page_size, heads*head_dim]``, the footprint what the
        split ``(heads, head_dim)`` tail held, and heads / head_dim
        stay attributes of the pool."""
        pool = KVPagePool(layers, pages, ps, heads, dim, dtype="bfloat16")
        assert pool.k.shape == pool.v.shape == (layers, pages + 1, ps,
                                                heads * dim)
        assert (pool.num_heads, pool.head_dim) == (heads, dim)
        assert pool.device_bytes() == 2 * 2 * layers * (pages + 1) * ps * heads * dim
        pool.mark_warm()
        assert pool.bytes_at_warmup == pool.device_bytes()

    def test_equal_bytes_vs_slot_pool(self):
        """The bench's sizing identity: a page pool with
        ``(slots+1)*max_seq/ps - 1`` pages holds EXACTLY the slot
        pool's bytes — the pad page stands in for the pad slot row."""
        slots, max_seq, ps = 4, 64, 8
        slot_pool = KVSlotPool(1, slots, max_seq, 1, 4)
        page_pool = KVPagePool(1, (slots + 1) * max_seq // ps - 1, ps, 1, 4)
        assert page_pool.device_bytes() == slot_pool.device_bytes()

    def test_utilization_watermark(self):
        pool = self._pool(pages=4, ps=8)
        pool.alloc(4)  # 32-token capacity in use
        pool.note_utilization(8)   # quarter full
        pool.note_utilization(32)  # full
        rep = pool.utilization_report()
        assert rep["samples"] == 2
        assert rep["mean"] == pytest.approx(0.625)
        assert rep["min"] == pytest.approx(0.25)


# ------------------------------------------------- merged-heads layout
class TestMergedHeadsLayout:
    """ISSUE 28: the pool's minor dimension is heads*head_dim, and the
    decode programs read it without splitting it."""

    @pytest.mark.parametrize("queries", [1, 3])
    @pytest.mark.parametrize("width", [1, 4])
    @pytest.mark.parametrize("heads,dim", [(2, 4), (12, 64)])
    def test_merged_read_matches_split_heads(self, heads, dim, width,
                                             queries):
        """Write through ``append_token_paged``, read through
        ``gather_pages`` + ``_attend_merged``; the oracle is the plain
        split-heads einsum over the same bytes viewed ``[..., heads,
        dim]``. Lanes: position 0, the last row of the first page, the
        first row of the next (or the row before, at width 1), the
        table's last position, and a pad lane whose table is all page 0
        (its rows are the trash page's; it must still read itself)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.serving import kv_cache as kvc
        from paddle_tpu.serving.decode import _attend_merged

        ps, B, HD = 8, 5, heads * dim
        cols = width * ps
        rs = np.random.RandomState(heads * 100 + width * 10 + queries)
        pool = rs.randn(1, B * width + 1, ps, HD).astype(np.float32)
        tables = 1 + np.arange(B * width, dtype=np.int32).reshape(B, width)
        tables[-1] = 0                                   # the pad lane
        last = cols - queries
        first = np.array([0, ps - 1, min(ps, last), last, 0], np.int32)
        first = np.minimum(first, last)
        pos = first[:, None] + np.arange(queries, dtype=np.int32)[None, :]
        q, k, v = (rs.randn(B, queries, HD).astype(np.float32)
                   for _ in range(3))
        pages = np.take_along_axis(tables, pos // ps, axis=1)
        scale = 1.0 / np.sqrt(dim)

        ck = kvc.append_token_paged(jnp.asarray(pool), 0, pages, pos % ps, k)
        cv = kvc.append_token_paged(jnp.asarray(pool[:, ::-1]), 0, pages,
                                    pos % ps, v)
        keys = kvc.gather_pages(ck, 0, tables)
        vals = kvc.gather_pages(cv, 0, tables)
        assert keys.shape == vals.shape == (B, cols, HD)
        got = _attend_merged(jnp.asarray(q), keys, vals, jnp.asarray(pos),
                             heads, scale)
        assert got.shape == (B, queries, HD)

        keys4 = np.asarray(keys).reshape(B, cols, heads, dim)
        vals4 = np.asarray(vals).reshape(B, cols, heads, dim)
        # the token just written is what each query's own column holds
        for b in range(B - 1):
            np.testing.assert_array_equal(
                keys4[b, pos[b]].reshape(queries, HD), k[b])
        logits = jnp.einsum("bshd,bthd->bhst",
                            q.reshape(B, queries, heads, dim), keys4,
                            precision="highest") * scale
        mask = np.arange(cols)[None, None, None, :] <= pos[:, None, :, None]
        probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        want = jnp.einsum("bhst,bthd->bshd", probs, vals4,
                          precision="highest").reshape(B, queries, HD)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("kind", ["decode", "draft", "verify"])
    def test_no_program_splits_a_page_or_more(self, programs, kind):
        """The half of the layout a reshape would silently undo: walk
        the program's jaxpr and find no intermediate with a ``(heads,
        head_dim)`` tail as large as one page (only one token's fresh
        q, k, v may carry it), and the pool going in and out rank 4."""
        import jax

        from paddle_tpu.analysis.drift_check import _walk

        P = programs
        heads, dim, ps = P.pool.num_heads, P.pool.head_dim, P.pool.page_size
        key = (kind, 4, 2)
        assert key in P.rungs
        fn = {"decode": P._decode_fn, "draft": P._draft_fn,
              "verify": P._verify_fn}[kind]
        closed = jax.make_jaxpr(fn)(P._call_params(key), P.pool.k, P.pool.v,
                                    *P._zero_args(key))
        n_params = len(jax.tree_util.tree_leaves(P._call_params(key)))
        pool_in = closed.in_avals[n_params:n_params + 2]
        pool_out = closed.out_avals[:2]
        want = (P.pool.num_layers, P.pool.num_pages + 1, ps, heads * dim)
        assert [a.shape for a in pool_in + pool_out] == [want] * 4
        seen_small = 0
        for eqn in _walk(closed.jaxpr):
            for var in eqn.outvars:
                shape = tuple(getattr(var.aval, "shape", ()))
                if shape[-2:] != (heads, dim):
                    continue
                seen_small += 1
                assert int(np.prod(shape)) < ps * heads * dim, (
                    kind, eqn.primitive.name, shape)
        assert seen_small  # the fresh token's q/k/v: the walk sees tails

    def test_multi_head_streams_equal_slot_oracle_and_speculation(self):
        """The contracts the one-head fixtures cannot see through the
        merged read: with two heads, greedy paged tokens equal the slot
        oracle's (split layout, split einsum), and the speculative
        stream equals the plain one token for token."""
        model = _tiny_model(num_hidden_layers=2, num_attention_heads=2,
                            max_position_embeddings=64)
        common = dict(max_slots=2, max_seq=64, seq_buckets=[32, 64],
                      prefill_max_batch=2, stats=ServingStats())
        prompts = _prompts([5, 16, 31, 40], seed=11)
        streams = {}
        for name, kw in [("slots", dict(kv_mode="slots")),
                         ("paged", dict(kv_mode="paged", page_size=16)),
                         ("spec", dict(kv_mode="paged", page_size=16,
                                       speculate_k=2, spec_draft_layers=1,
                                       spec_min_accept=0.0))]:
            eng = serving.DecodeEngine(model, **dict(common, **kw)).warmup()
            try:
                futs = [eng.submit("t", p, max_new_tokens=10)
                        for p in prompts]
                streams[name] = [np.asarray(f.result(60)) for f in futs]
                assert eng.serving_report()["compiles_after_warmup"] == 0
            finally:
                eng.shutdown(drain=True)
        for want, paged, spec in zip(*(streams[n] for n in
                                       ("slots", "paged", "spec"))):
            assert np.array_equal(paged, want)
            assert np.array_equal(spec, want)


# ------------------------------------------- the paged-attention kernel
def _force_kernel(monkeypatch):
    """Make the paged programs take ``ops/pallas/paged_attention.py`` in
    interpret mode, as the gate would make them take it compiled on a
    TPU: steered here, in the test, not by an option of the program."""
    import functools

    from paddle_tpu.ops.pallas import paged_attention as kernel
    from paddle_tpu.serving import decode

    monkeypatch.setattr(decode.PagedDecodePrograms, "_kernel",
                        staticmethod(lambda: True))
    monkeypatch.setattr(kernel, "paged_attention", functools.partial(
        kernel.paged_attention, interpret=True))


class TestPagedAttentionKernel:
    """ISSUE 33: on a TPU the paged programs' attention is one Pallas
    kernel over the block table. Its oracle is what every other backend
    runs: ``gather_pages`` + ``_attend_merged`` on the same pool."""

    TOL = {"float32": 1e-5, "bfloat16": 2e-2}

    @staticmethod
    def _case(ps, heads, dim, queries, dtype, seed=0):
        """One pool after a step's write, and the step's q, tables and
        positions. Lanes: position 0; the last row of the first page
        (255 at the cell's page size); the first row of the second (256);
        a lane into its third page; a short lane; a padded batch lane
        (table all page 0, position 0). The table rung (4) is wider than
        any lane's pages, and lane 3's pages are not in order."""
        import jax.numpy as jnp

        from paddle_tpu.serving import kv_cache as kvc

        rs = np.random.RandomState(seed)
        HD, T, L, N = heads * dim, 4, 2, 12
        tables = np.zeros((6, T), np.int32)
        first = np.array([0, ps - 1, ps, 2 * ps + 5, ps // 2, 0], np.int32)
        owned = iter([3, 1, 7, 4, 9, 2, 8, 5, 6, 10])
        pos = first[:, None] + np.arange(queries, dtype=np.int32)[None, :]
        for b in range(5):
            for t in range(pos[b, -1] // ps + 1):
                tables[b, t] = next(owned)
        dt = jnp.dtype(dtype)
        kpool, vpool = (jnp.asarray(rs.randn(L, N, ps, HD), dt)
                        for _ in range(2))
        q, k, v = (jnp.asarray(rs.randn(6, queries, HD), dt)
                   for _ in range(3))
        pages = np.take_along_axis(tables, pos // ps, axis=1)
        kpool = kvc.append_token_paged(kpool, 1, pages, pos % ps, k)
        vpool = kvc.append_token_paged(vpool, 1, pages, pos % ps, v)
        return kpool, vpool, q, tables, pos

    @staticmethod
    def _both(kpool, vpool, q, tables, pos, heads, scale):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.paged_attention import paged_attention
        from paddle_tpu.serving import kv_cache as kvc
        from paddle_tpu.serving.decode import _attend_merged

        got = paged_attention(q, kpool, vpool, jnp.int32(1),
                              jnp.asarray(tables), jnp.asarray(pos),
                              heads=heads, scale=scale, interpret=True)
        want = _attend_merged(q, kvc.gather_pages(kpool, 1, tables),
                              kvc.gather_pages(vpool, 1, tables),
                              jnp.asarray(pos), heads, scale)
        assert got.shape == want.shape and got.dtype == want.dtype
        return (np.asarray(got.astype(jnp.float32)),
                np.asarray(want.astype(jnp.float32)))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("queries", [1, 3], ids=["S1", "Sk1"])
    @pytest.mark.parametrize("ps,heads,dim", [(256, 2, 8), (16, 12, 64)],
                             ids=["page256", "heads12x64"])
    def test_kernel_agrees_with_gather_and_attend(self, ps, heads, dim,
                                                  queries, dtype):
        got, want = self._both(*self._case(ps, heads, dim, queries, dtype),
                               heads, 1.0 / np.sqrt(dim))
        tol = self.TOL[dtype]
        np.testing.assert_allclose(got, want, rtol=tol,
                                   atol=tol * np.abs(want).max())

    @pytest.mark.parametrize("queries", [1, 3], ids=["S1", "Sk1"])
    def test_a_dead_page_is_never_read(self, queries):
        """NaN in the pad page and in every page no lane owns changes
        nothing and reaches no real lane: a table entry past a lane's
        last live page costs no read. (The padded batch lane reads the
        pad page; its output is thrown away, here as in the programs.)"""
        import jax.numpy as jnp

        kpool, vpool, q, tables, pos = self._case(16, 4, 8, queries,
                                                  "float32", seed=1)
        clean, _ = self._both(kpool, vpool, q, tables, pos, 4, 0.35)
        dead = np.setdiff1d(np.arange(kpool.shape[1]), tables[tables > 0])
        assert 0 in dead and len(dead) >= 3
        kpool, vpool = (p.at[:, dead].set(jnp.nan) for p in (kpool, vpool))
        got, want = self._both(kpool, vpool, q, tables, pos, 4, 0.35)
        assert np.isfinite(got[:5]).all()
        np.testing.assert_array_equal(got[:5], clean[:5])
        # the oracle does read them: probabilities of 0 times NaN
        assert not np.isfinite(want[0]).all()

    @pytest.mark.parametrize("speculate_k", [0, 2], ids=["plain", "speculate"])
    def test_programs_with_the_kernel_return_the_compositions_tokens(
            self, monkeypatch, speculate_k):
        """Four lanes, a prefill and eight decode steps (or speculation
        rounds: draft and verify take the kernel too): the greedy
        streams with the kernel forced are the composition's."""
        model = _tiny_model(num_hidden_layers=2, num_attention_heads=2,
                            max_position_embeddings=64)
        prompts = _prompts([5, 15, 16, 30], seed=33)
        kw = dict(max_seq=64, seq_buckets=[32], page_size=16,
                  prefill_max_batch=4, speculate_k=speculate_k,
                  spec_draft_layers=1, spec_min_accept=0.0)

        def streams():
            eng = _paged(model, **kw).warmup()
            try:
                futs = [eng.submit("t", p, max_new_tokens=9) for p in prompts]
                out = [np.asarray(f.result(120)) for f in futs]
                assert eng.serving_report()["compiles_after_warmup"] == 0
                return out
            finally:
                eng.shutdown(drain=True)

        want = streams()
        _force_kernel(monkeypatch)
        for got, ref in zip(streams(), want):
            assert len(got) == 9 and np.array_equal(got, ref)

    @pytest.mark.parametrize("kind", ["decode", "draft", "verify"])
    def test_with_the_kernel_no_program_gathers_a_dense_view(
            self, monkeypatch, programs, kind):
        """Traced with the kernel, a program holds one ``pallas_call`` a
        layer and attention step, named ``paged_attn`` under ``attn/core``,
        nothing under ``attn/kv_gather``, and no intermediate of the
        gathered view's shape."""
        import jax

        from paddle_tpu.analysis.drift_check import _walk
        from paddle_tpu.base import regions

        _force_kernel(monkeypatch)
        P = programs
        key = (kind, 4, 2)
        fn = {"decode": P._decode_fn, "draft": P._draft_fn,
              "verify": P._verify_fn}[kind]
        closed = jax.make_jaxpr(fn)(P._call_params(key), P.pool.k, P.pool.v,
                                    *P._zero_args(key))
        layers = {"decode": 2, "draft": 1 * P.speculate_k, "verify": 2}[kind]
        # one jitted call a layer and attention step, sharing one trace
        calls = [e for e in closed.jaxpr.eqns
                 if e.params.get("name") == "paged_attention"]
        assert len(calls) == layers
        assert len({id(e.params["jaxpr"]) for e in calls}) == 1
        for e in calls:
            assert str(e.source_info.name_stack).endswith(regions.ATTN_CORE)
            (kernel,) = [x for x in _walk(e.params["jaxpr"].jaxpr)
                         if x.primitive.name == "pallas_call"]
            assert str(kernel.source_info.name_stack) == regions.PAGED_ATTN
        ps, HD = P.pool.page_size, P.pool.num_heads * P.pool.head_dim
        for e in _walk(closed.jaxpr):
            assert regions.ATTN_KV_GATHER not in str(e.source_info.name_stack)
            for var in e.outvars:
                shape = tuple(getattr(var.aval, "shape", ()))
                assert shape not in ((4, 2 * ps, HD), (4, 2, ps, HD)), (
                    e.primitive.name, shape)


# ------------------------------------------------- mixed-context matrix
class TestMixedContextMatrix:
    def test_greedy_bit_exact_vs_slot_oracle(self, engine, oracle):
        """The contractual proof: continuous paged decode over the
        interleaved four-bucket mix emits the same tokens as the
        slot-pool engine — page indirection is invisible to the math."""
        prompts = _prompts(MATRIX)
        paged = [engine.submit("a" if i % 2 else "b", p, max_new_tokens=8)
                 for i, p in enumerate(prompts)]
        slot = [oracle.submit("a" if i % 2 else "b", p, max_new_tokens=8)
                for i, p in enumerate(prompts)]
        for pr, sr in zip(paged, slot):
            assert np.array_equal(pr.result(60), sr.result(60))

    def test_zero_retrace_and_constant_footprint(self, engine):
        before = engine.kv_pool.device_bytes()
        reqs = [engine.submit("mix", p, max_new_tokens=6)
                for p in _prompts(MATRIX, seed=5)]
        for r in reqs:
            r.result(60)
        report = engine.serving_report()
        assert report["compiles_after_warmup"] == 0
        assert report["kv_pool_bytes_constant"] is True
        assert engine.kv_pool.device_bytes() == before

    def test_pages_reclaimed_after_drain(self, engine):
        outs = [engine.generate("r", p, max_new_tokens=6)
                for p in _prompts([63, 32, 500], seed=9)]
        assert all(len(o) == 6 for o in outs)
        assert engine.kv_pool.in_use() == 0  # every page came home

    def test_requests_join_and_leave_midflight(self, engine):
        first = [engine.submit("j", p, max_new_tokens=10)
                 for p in _prompts([240, 500], seed=11)]
        # second wave joins while the first is decoding
        second = [engine.submit("j", p, max_new_tokens=4)
                  for p in _prompts([50, 100, 63], seed=12)]
        outs = [r.result(60) for r in first + second]
        assert [len(o) for o in outs] == [10, 10, 4, 4, 4]
        assert engine.kv_pool.in_use() == 0

    def test_report_surfaces_paged_keys(self, engine):
        engine.generate("rep", _prompts([100])[0], max_new_tokens=4)
        report = engine.serving_report()
        assert report["kv_mode"] == "paged"
        assert report["kv_page_size"] == 32
        assert report["kv_pages"] == 64  # equal-bytes default sizing
        assert report["table_rungs"] == [1, 2, 4, 8, 16]
        assert 0.0 < report["kv_pool_utilization"] <= 1.0
        assert report["kv_shed_requests"] == 0

    def test_audit_clean_on_live_engine(self, engine):
        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        engine.generate("audit", _prompts([120])[0], max_new_tokens=4)
        assert audit_serving(engine) == []


# ---------------------------------------------------- sampled decoding
class TestSampledDecoding:
    PROMPT = _prompts([40], seed=21)[0]

    def test_same_seed_same_stream(self, engine):
        a = engine.submit("s", self.PROMPT, max_new_tokens=12,
                          temperature=1.5, seed=7).result(60)
        b = engine.submit("s", self.PROMPT, max_new_tokens=12,
                          temperature=1.5, seed=7).result(60)
        assert np.array_equal(a, b)

    def test_seeds_decorrelate(self, engine):
        a = engine.submit("s", self.PROMPT, max_new_tokens=12,
                          temperature=1.5, seed=7).result(60)
        b = engine.submit("s", self.PROMPT, max_new_tokens=12,
                          temperature=1.5, seed=8).result(60)
        assert not np.array_equal(a, b)

    def test_sampling_independent_of_batch_composition(self, engine):
        solo = engine.submit("s", self.PROMPT, max_new_tokens=10,
                             temperature=1.5, seed=7).result(60)
        reqs = [engine.submit("s", self.PROMPT, max_new_tokens=10,
                              temperature=1.5, seed=7)]
        reqs += [engine.submit("noise", p, max_new_tokens=10)
                 for p in _prompts([500, 63, 240], seed=23)]
        batched = reqs[0].result(60)
        for r in reqs[1:]:
            r.result(60)
        assert np.array_equal(solo, batched)

    def test_topk_topp_deterministic_per_seed(self, engine):
        kw = dict(max_new_tokens=10, temperature=0.9, top_k=16,
                  top_p=0.9, seed=3)
        a = engine.submit("s", self.PROMPT, **kw).result(60)
        b = engine.submit("s", self.PROMPT, **kw).result(60)
        assert np.array_equal(a, b)
        assert all(0 <= int(t) < 128 for t in a)

    def test_slots_engine_refuses_sampling(self, oracle):
        with pytest.raises(ValueError, match="greedy oracle"):
            oracle.submit("s", self.PROMPT, max_new_tokens=4,
                          temperature=0.9)


# ------------------------------------- the sort runs only when a lane samples
def _parent_choose_tokens(head, temps, top_ks, top_ps, rkeys):
    """``PagedDecodePrograms._choose_tokens`` as it stood before the
    ``lax.cond`` (PR 30's body, without its region): every lane sorted,
    ``temp == 0`` lanes overwritten by the argmax at the end."""
    import jax
    import jax.numpy as jnp

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
        keep = (rank < k_eff) & (jnp.cumsum(probs) - probs < p_eff)
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf))
        filtered = jnp.where(scaled >= cutoff, scaled, -jnp.inf)
        return jax.random.categorical(key, filtered).astype(jnp.int32)

    sampled = jax.vmap(lane)(head, temps, top_ks, top_ps, rkeys)
    return jnp.where(temps > 0, sampled, greedy)


@contextlib.contextmanager
def _decode_spans():
    """The program's tracer on for the block; the yielded list holds the
    block's ``serving.decode`` events once it has ended."""
    from paddle_tpu.observability import tracer

    tracer.reset()
    was = tracer.enabled
    tracer.enable()
    events = []
    try:
        yield events
        events += [e for e in tracer.to_chrome_trace()["traceEvents"]
                   if e["ph"] == "X" and e["name"] == "serving.decode"]
    finally:
        tracer.enabled = was
        tracer.reset()


def _steps(stats) -> int:
    """Program calls the stats counted, of every kind."""
    cell = stats.summary()["decode"]
    return sum(cell.get(f"{kind}_steps", 0)
               for kind in ("prefill", "decode", "draft", "verify"))


class TestSortOnlyWhenSampling:
    PROMPTS = _prompts([40, 50, 63, 100], seed=41)

    # one token choice a program, or one a draft step / verify position
    @pytest.mark.parametrize("kind,key,choices",
                             [("prefill", ("prefill", 2, 32), 1),
                              ("decode", ("decode", 4, 2), 1),
                              ("draft", ("draft", 4, 2), 2),
                              ("verify", ("verify", 4, 2), 3)])
    def test_sort_sits_inside_a_cond_branch(self, programs, kind, key,
                                            choices):
        """Walk the traced program: outside a ``cond`` it holds no
        ``sort`` (nor the cumulative sum or the draw's bits), the
        ``sample`` region holds one ``cond`` a token choice whose
        predicate is computed in the program, the argmax stays outside
        it, and the sort is in one branch of each."""
        import jax

        from paddle_tpu.analysis.drift_check import _sub_jaxprs, _walk

        P = programs
        assert key in P.rungs
        fn = {"prefill": P._prefill_fn, "decode": P._decode_fn,
              "draft": P._draft_fn, "verify": P._verify_fn}[kind]
        closed = jax.make_jaxpr(fn)(P._call_params(key), P.pool.k, P.pool.v,
                                    *P._zero_args(key))

        def outside_conds(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                if eqn.primitive.name != "cond":
                    for sub in _sub_jaxprs(eqn):
                        yield from outside_conds(sub)

        top = list(outside_conds(closed.jaxpr))
        names = [e.primitive.name for e in top]
        for banned in ("sort", "cumsum", "random_bits", "threefry2x32"):
            assert banned not in names, (kind, banned)
        conds = [e for e in top if e.primitive.name == "cond"]
        assert len(conds) == choices
        assert names.count("argmax") == choices
        for e in conds + [e for e in top if e.primitive.name == "argmax"]:
            assert str(e.source_info.name_stack).endswith("sample")
        for e in conds:
            sorts = [sum(x.primitive.name == "sort" for x in _walk(b.jaxpr))
                     for b in e.params["branches"]]
            assert sorted(sorts) == [0, 1]
            # the predicate is the program's own, not a host constant
            assert not hasattr(e.invars[0], "val")  # a Literal has one

    def test_greedy_batch_equals_slot_oracle_and_sorts_nothing(self, engine,
                                                               oracle):
        before = engine.stats.summary()["decode"]["sample_sort_steps"]
        paged = [engine.submit("g", p, max_new_tokens=8)
                 for p in self.PROMPTS]
        slot = [oracle.submit("g", p, max_new_tokens=8)
                for p in self.PROMPTS]
        for pr, sr in zip(paged, slot):
            assert np.array_equal(pr.result(60), sr.result(60))
        after = engine.stats.summary()["decode"]["sample_sort_steps"]
        assert after == before

    def test_mixed_batch_keeps_every_lanes_stream(self, engine):
        """One sampling lane among three greedy ones: the batch sorts,
        the sampled lane draws what it draws alone, and the greedy
        lanes keep the tokens of an all-greedy batch."""
        sampled_kw = dict(max_new_tokens=10, temperature=1.5, seed=7)
        solo = engine.submit("s", self.PROMPTS[0], **sampled_kw).result(60)
        greedy = [engine.submit("g", p, max_new_tokens=10)
                  for p in self.PROMPTS[1:]]
        greedy = [r.result(60) for r in greedy]
        before = engine.stats.summary()["decode"]["sample_sort_steps"]
        mixed = [engine.submit("s", self.PROMPTS[0], **sampled_kw)]
        mixed += [engine.submit("g", p, max_new_tokens=10)
                  for p in self.PROMPTS[1:]]
        mixed = [r.result(60) for r in mixed]
        assert np.array_equal(mixed[0], solo)
        for got, want in zip(mixed[1:], greedy):
            assert np.array_equal(got, want)
        after = engine.stats.summary()["decode"]["sample_sort_steps"]
        assert after - before >= 10  # each call the sampled lane rode

    @pytest.mark.parametrize("temps,top_ks,top_ps", [
        ([0.0, 0.0, 0.0, 0.0], [0, 0, 0, 0], [1.0, 1.0, 1.0, 1.0]),
        ([1.5, 0.0, 0.0, 0.0], [0, 0, 0, 0], [1.0, 1.0, 1.0, 1.0]),
        ([0.9, 0.9, 0.9, 0.9], [16, 16, 16, 16], [0.9, 0.9, 0.9, 0.9]),
        ([0.7, 0.0, 1.3, 2.0], [0, 5, 1, 40], [0.5, 0.9, 1.0, 0.95]),
    ], ids=["all-greedy", "one-sampling", "topk-topp", "mixed"])
    def test_streams_are_the_parents_per_seed(self, engine, temps, top_ks,
                                              top_ps):
        """On the same logits and keys, eager and jitted, the tokens
        are those of the parent's body for every lane and seed."""
        import jax

        rs = np.random.RandomState(43)
        head = (3.0 * rs.randn(4, 128)).astype(np.float32)
        args = (np.asarray(temps, np.float32), np.asarray(top_ks, np.int32),
                np.asarray(top_ps, np.float32))
        choose = engine.programs._choose_tokens
        for seed in (3, 7, 2**31 + 5):
            for index in range(4):
                rkeys = np.tile(np.asarray([seed, index], np.uint32), (4, 1))
                want = np.asarray(_parent_choose_tokens(head, *args, rkeys))
                assert np.array_equal(choose(head, *args, rkeys), want)
                assert np.array_equal(jax.jit(choose)(head, *args, rkeys),
                                      want)
                greedy = np.asarray(temps) == 0
                assert np.array_equal(want[greedy],
                                      head.argmax(-1)[greedy])

    @pytest.mark.parametrize("speculate_k", [0, 2], ids=["plain", "speculate"])
    def test_span_carries_sampling_and_stats_count_sort_calls(
            self, speculate_k):
        """``serving.decode`` carries ``sampling``; the stats count no
        call of a greedy run and every call of a sampled request's."""
        model = _tiny_model(num_hidden_layers=2, max_position_embeddings=64)
        stats = ServingStats()
        with _decode_spans() as steps:
            eng = _paged(model, max_seq=64, seq_buckets=[32, 64],
                         page_size=16, speculate_k=speculate_k,
                         spec_draft_layers=1, spec_min_accept=0.0,
                         stats=stats).warmup()
            try:
                greedy = [eng.submit("g", p, max_new_tokens=6)
                          for p in _prompts([20, 30, 40], seed=45)]
                for r in greedy:
                    r.result(60)
                assert _steps(stats) > 0
                assert stats.summary()["decode"]["sample_sort_steps"] == 0
                n_greedy = _steps(stats)
                one = eng.submit("s", _prompts([25], seed=46)[0],
                                 max_new_tokens=6, temperature=1.5, seed=7)
                one.result(60)
                assert (stats.summary()["decode"]["sample_sort_steps"]
                        == _steps(stats) - n_greedy > 0)
            finally:
                eng.shutdown(drain=True)
        # the tracer is the process's: keep this engine's steps
        mine = {r.id for r in greedy} | {one.id}
        steps = [e for e in steps if set(e["args"]["requests"]) <= mine]
        by_sampling = {0: 0, 1: 0}
        for e in steps:
            want = int(one.id in e["args"]["requests"])
            assert e["args"]["sampling"] == want
            by_sampling[want] += 1
        assert by_sampling[0] > 0 and by_sampling[1] > 0

    def test_alternating_greedy_and_sampled_batches_never_retrace(self,
                                                                  engine):
        warmed = engine.programs.traces
        for i in range(3):
            kw = dict(temperature=0.8, top_k=8, seed=i) if i % 2 else {}
            reqs = [engine.submit("alt", p, max_new_tokens=4, **kw)
                    for p in self.PROMPTS]
            for r in reqs:
                r.result(60)
        assert engine.programs.traces == warmed
        assert engine.serving_report()["compiles_after_warmup"] == 0


class TestPagesLiveCounter:
    """ISSUE 33: a paged decode span says how much of its block table
    was live, and the stats keep the running share."""

    @staticmethod
    def _serve(speculate_k, sizes, new_tokens, stats):
        """Serve ``sizes`` prompts on a traced engine of 16-token pages;
        return this engine's ``serving.decode`` events of a decode kind."""
        model = _tiny_model(num_hidden_layers=2, max_position_embeddings=64)
        with _decode_spans() as events:
            eng = _paged(model, max_seq=64, seq_buckets=[32, 64],
                         page_size=16, speculate_k=speculate_k,
                         spec_draft_layers=1, spec_min_accept=0.0,
                         stats=stats).warmup()
            try:
                reqs = [eng.submit("g", p, max_new_tokens=new_tokens)
                        for p in _prompts(sizes, seed=47)]
                for r in reqs:
                    r.result(60)
            finally:
                eng.shutdown(drain=True)
        mine = {r.id for r in reqs}
        # (a beat that only reads the call before it dispatches nothing:
        # its span has no lanes and says nothing of pages)
        return [e["args"] for e in sorted(events, key=lambda e: e["ts"])
                if e["args"]["lanes"] and set(e["args"]["requests"]) <= mine
                and e["args"]["kind"] != "prefill"]

    @pytest.mark.parametrize("speculate_k", [0, 2], ids=["plain", "speculate"])
    def test_span_carries_pages_live_and_pages_table(self, speculate_k):
        stats = ServingStats()
        steps = self._serve(speculate_k, [5, 20, 30, 40], 8, stats)
        assert steps
        for a in steps:
            assert a["kind"] == ("speculate" if speculate_k else "decode")
            assert a["pages_table"] == a["rung"][0] * a["rung"][1]
            assert a["lanes"] <= a["pages_live"] <= a["pages_table"]
        cell = stats.summary()["decode"]
        assert cell["pages_live"] == sum(a["pages_live"] for a in steps)
        assert cell["pages_table"] == sum(a["pages_table"] for a in steps)
        assert 0 < cell["pages_live_share"] <= 1

    def test_crossing_a_page_boundary_raises_pages_live_by_one(self):
        """One lane, prompt 14, pages of 16: the steps write positions
        14, 15, 16, 17 ...; the step that writes 16 is the first to see
        a second page."""
        steps = self._serve(0, [14], 6, ServingStats())
        assert [a["pages_live"] for a in steps] == [1, 1, 2, 2, 2]
        assert [a["pages_table"] for a in steps] == [1, 1, 2, 2, 2]

    def test_metrics_page_shows_the_share(self):
        from paddle_tpu.observability.export import prometheus_text
        from paddle_tpu.observability.metrics import MetricsRegistry

        stats = ServingStats()
        reg = MetricsRegistry()
        reg.register_collector("serving", stats.summary)
        assert "pages_live" not in prometheus_text(reg.snapshot())
        stats.record_decode_step("decode", 0.001, 3, 3, t_end=1.0,
                                 dispatch_s=0.0004, read_wait_s=0.0002)
        stats.record_pages(5, 8)
        stats.record_pages(6, 8)
        lines = prometheus_text(reg.snapshot()).splitlines()
        assert "paddle_serving_decode_pages_live 11" in lines
        assert "paddle_serving_decode_pages_table 16" in lines
        assert "paddle_serving_decode_pages_live_share 0.6875" in lines


# ------------------------------------------------------- pool pressure
class TestPagePressure:
    def _small(self, model16, **kw):
        kw.setdefault("max_slots", 4)
        kw.setdefault("max_seq", 16)
        kw.setdefault("seq_buckets", [8, 16])
        kw.setdefault("prefill_max_batch", 1)
        kw.setdefault("page_size", 8)
        kw.setdefault("kv_mode", "paged")
        kw.setdefault("stats", ServingStats())
        return serving.DecodeEngine(model16, **kw)

    @pytest.fixture(scope="class")
    def model16(self):
        return _tiny_model(max_position_embeddings=16)

    def test_admission_waits_for_pages_not_sheds(self, model16):
        """6 one-page requests over a 3-page pool: admission staggers
        behind retirements — every request completes, zero sheds."""
        eng = self._small(model16, pool_pages=3).warmup()
        try:
            reqs = [eng.submit("w", p, max_new_tokens=2)
                    for p in _prompts([6] * 6, seed=31)]
            outs = [r.result(60) for r in reqs]
            assert all(len(o) == 2 for o in outs)
            report = eng.serving_report()
            assert report["kv_shed_requests"] == 0
            assert eng.kv_pool.in_use() == 0
        finally:
            eng.shutdown(drain=True)

    def test_starved_lane_waits_and_resumes_bit_exact(self, model16):
        """Natural exhaustion mid-decode: the growing lane sits out
        steps until a retirement frees a page, then finishes with the
        same tokens it would have produced unobstructed."""
        eng = self._small(model16, pool_pages=2, max_slots=2).warmup()
        try:
            grower, quick = _prompts([6, 6], seed=33)
            solo = eng.generate("solo", grower, max_new_tokens=8)
            # both lanes hold the pool's 2 pages; the grower needs a
            # third at position 8 and must wait for quick to retire
            a = eng.submit("p", grower, max_new_tokens=8)
            b = eng.submit("p", quick, max_new_tokens=2)
            assert np.array_equal(a.result(60), solo)
            assert len(b.result(60)) == 2
            assert eng.serving_report()["kv_shed_requests"] == 0
        finally:
            eng.shutdown(drain=True)

    def test_never_fits_refused_at_submit(self, model16):
        eng = self._small(model16, pool_pages=1).warmup()
        try:
            with pytest.raises(ValueError, match="never be admitted"):
                eng.submit("n", _prompts([9], seed=35)[0],
                           max_new_tokens=2)
        finally:
            eng.shutdown(drain=True)

    def test_deadlock_breaker_sheds_youngest(self, model16):
        """Both lanes starve with nothing pending: the youngest sheds
        (AdmissionError, pages released), the oldest completes."""
        eng = self._small(model16, pool_pages=2, max_slots=2).warmup()
        try:
            old = eng.submit("d", _prompts([6], seed=37)[0],
                             max_new_tokens=8)
            young = eng.submit("d", _prompts([6], seed=38)[0],
                               max_new_tokens=8)
            assert len(old.result(60)) == 8
            with pytest.raises(AdmissionError) as ei:
                young.result(60)
            assert ei.value.reason == "kv_pages"
            assert eng.serving_report()["kv_shed_requests"] == 1
            assert eng.kv_pool.in_use() == 0  # the shed leaked nothing
        finally:
            eng.shutdown(drain=True)


# ------------------------------------------------- JX334 fragmentation
class TestJX334Fragmentation:
    class _Duck:
        """audit_serving duck-type: counters + a pool."""
        compiles_after_warmup = 0

        def __init__(self, pool):
            self.kv_pool = pool
            self.kv_pool.mark_warm()
            self._held = pool.alloc(4)

        def active_requests(self):
            return 1

    def test_seeded_low_utilization_warns(self):
        duck = self._Duck(KVPagePool(1, 8, 64, 1, 4))
        for _ in range(8):  # 4 pages held, ~3% of their tokens live
            duck.kv_pool.note_utilization(8)
        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        findings = [f for f in audit_serving(duck) if f.code == "JX334"]
        assert len(findings) == 1
        assert findings[0].severity == "warning"
        assert "page_size" in findings[0].message

    def test_healthy_utilization_clean(self):
        duck = self._Duck(KVPagePool(1, 8, 64, 1, 4))
        for _ in range(8):
            duck.kv_pool.note_utilization(4 * 64)  # pages brim-full
        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        assert [f for f in audit_serving(duck) if f.code == "JX334"] == []


# ------------------------------------------------- chaos regression
class TestChaosPagePressure:
    def test_scenario_page_pressure_green(self):
        from tools.chaos import scenario_page_pressure

        out = scenario_page_pressure(0)
        assert out["ok"] is True, out
        assert out["shed_admission_error"] > 0
        assert out["kv_pages_leaked"] == 0
        assert out["compiles_after_warmup"] == 0
