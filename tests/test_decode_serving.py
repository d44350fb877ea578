"""Decode serving tests (ISSUE 13): true continuous batching for GPT
decode — KV slot pool residency, slot join/leave, bit-exact greedy
decode vs single-request and the eager reference, the zero-retrace
contract under mixed prefill/decode traffic, request TTL, priority
tiers, tenant churn mid-traffic, the two-axis (batch x seq) bucket
ladder, and the JX332/JX333 seeded negatives."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.profiler.pipeline import ServingStats
from paddle_tpu.serving.kv_cache import KVSlotPool
from paddle_tpu.serving.request_queue import (AdmissionController,
                                              AdmissionError, DecodeRequest,
                                              Request, RequestQueue)


def _tiny_model(**overrides):
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    base = dict(num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
                max_position_embeddings=64)
    base.update(overrides)
    model = GPTForCausalLM(gpt_tiny(**base))
    model.eval()
    return model


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


def _engine(model, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 32)
    kw.setdefault("seq_buckets", [8, 16])
    kw.setdefault("prefill_max_batch", 2)
    kw.setdefault("stats", ServingStats())
    return serving.DecodeEngine(model, **kw)


@pytest.fixture(scope="module")
def engine(model):
    eng = _engine(model).warmup()
    yield eng
    eng.shutdown(drain=True)


def _prompts(n, lo=3, hi=14, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 512, size=int(k)).astype(np.int32)
            for k in rs.randint(lo, hi, size=n)]


def _ref_decode(model, prompt, m):
    """Greedy decode through the model's own eager forward — the oracle
    the KV-cache programs must match."""
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(m):
        logits = model(np.asarray(toks, np.int64)[None])
        nxt = int(np.argmax(np.asarray(logits._value)[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


# ------------------------------------------------------------- KV slot pool
class TestKVSlotPool:
    def _pool(self, slots=3):
        return KVSlotPool(2, slots, 8, 2, 4)

    def test_alloc_release_free_list(self):
        pool = self._pool()
        a, b = pool.alloc(), pool.alloc()
        assert a != b and pool.in_use() == 2 and pool.free_count() == 1
        pool.release(a)
        assert pool.in_use() == 1
        c = pool.alloc()  # LIFO reuse of the freed slot
        assert c == a

    def test_exhaustion_raises(self):
        pool = self._pool(slots=1)
        pool.alloc()
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.alloc()

    def test_double_release_rejected(self):
        pool = self._pool()
        s = pool.alloc()
        pool.release(s)
        with pytest.raises(ValueError, match="already free"):
            pool.release(s)

    def test_pad_slot_never_allocated(self):
        pool = self._pool(slots=2)
        assert pool.pad_slot == 2
        assert sorted([pool.alloc(), pool.alloc()]) == [0, 1]

    def test_device_bytes_and_footprint_guard(self):
        import jax.numpy as jnp

        pool = self._pool()
        assert pool.device_bytes() == pool.k.nbytes + pool.v.nbytes
        pool.commit(pool.k + 0, pool.v + 0)  # same footprint: fine
        with pytest.raises(ValueError, match="footprint"):
            pool.commit(jnp.zeros((1,)), pool.v)

    def test_occupancy_gauge_tracks_slots(self):
        from paddle_tpu.observability.metrics import registry

        pool = self._pool()
        s = pool.alloc()
        assert registry.gauge("serving.kv_slots_in_use").value() == 1
        pool.release(s)
        assert registry.gauge("serving.kv_slots_in_use").value() == 0


# --------------------------------------------------------------- decoding
class TestContinuousDecode:
    def test_bit_exact_vs_sequential_and_reference(self, engine, model):
        prompts = _prompts(6)
        reqs = [engine.submit(f"t{i % 2}", p, max_new_tokens=5)
                for i, p in enumerate(prompts)]
        cont = [r.result(60) for r in reqs]
        seq = [engine.generate("solo", p, max_new_tokens=5) for p in prompts]
        for a, b in zip(cont, seq):
            np.testing.assert_array_equal(a, b)
        for a, p in zip(cont, prompts):
            assert list(a) == _ref_decode(model, p, 5)

    def test_zero_retrace_under_mixed_traffic(self, engine):
        before = engine.compiles_after_warmup
        assert before == 0
        reqs = [engine.submit("t0", p, max_new_tokens=4)
                for p in _prompts(8, seed=3)]
        for r in reqs:
            r.result(60)
        assert engine.compiles_after_warmup == 0

    def test_pool_bytes_constant_and_slots_reused(self, engine):
        bytes0 = engine.kv_pool.device_bytes()
        assert bytes0 == engine.kv_pool.bytes_at_warmup
        # oversubscribe: 10 requests through 4 slots
        reqs = [engine.submit("t1", p, max_new_tokens=6)
                for p in _prompts(10, seed=5)]
        for r in reqs:
            r.result(60)
        assert engine.kv_pool.device_bytes() == bytes0
        assert engine.kv_pool.in_use() == 0      # every slot came back
        dec = engine.stats.summary()["decode"]
        assert dec["slot_occupancy_peak"] == engine.max_slots

    def test_requests_join_and_leave_midflight(self, engine):
        """Staggered arrivals ride the running batch: a request submitted
        while others decode completes without waiting for them all."""
        long_reqs = [engine.submit("t0", p, max_new_tokens=24)
                     for p in _prompts(3, seed=7)]
        time.sleep(0.02)  # the long batch is mid-decode now
        quick = engine.submit("t1", _prompts(1, seed=8)[0], max_new_tokens=1)
        quick_toks = quick.result(30)
        assert quick_toks.shape == (1,)
        # the long requests were NOT failed or restarted by the join
        # (slot capacity may cap them: max_seq - len(prompt) + 1 tokens)
        outs = [r.result(60) for r in long_reqs]
        for o, p in zip(outs, _prompts(3, seed=7)):
            assert len(o) == min(24, 32 - len(p) + 1)

    def test_eos_stops_generation_early(self, model):
        # discover the greedy continuation, then make its first token EOS
        probe = _engine(model).warmup()
        prompt = _prompts(1, seed=11)[0]
        toks = probe.generate("a", prompt, max_new_tokens=4)
        probe.shutdown()
        eng = _engine(model, eos_id=int(toks[0])).warmup()
        try:
            out = eng.generate("a", prompt, max_new_tokens=4)
            assert list(out) == [int(toks[0])]
        finally:
            eng.shutdown()

    def test_slot_capacity_caps_generation(self, model):
        eng = _engine(model, max_seq=16, seq_buckets=[8, 16]).warmup()
        try:
            prompt = _prompts(1, lo=8, hi=9, seed=2)[0]  # 8 tokens
            out = eng.generate("a", prompt, max_new_tokens=50)
            # positions 8..15 hold generated-token KV: 8 prompt rows + the
            # first token from prefill + 8 more until the slot is full
            assert len(out) == 16 - 8 + 1
        finally:
            eng.shutdown()

    def test_oversized_prompt_refused_at_submit(self, engine):
        with pytest.raises(ValueError, match="largest"):
            engine.submit("t0", np.arange(17, dtype=np.int32))

    def test_submit_before_warmup_raises(self, model):
        eng = _engine(model)
        with pytest.raises(RuntimeError, match="warmup"):
            eng.submit("t0", np.arange(4, dtype=np.int32))

    def test_health_and_report_surfaces(self, engine):
        health = engine.telemetry_health()
        assert health["kv_slots"] == 4 and health["active_requests"] == 0
        report = engine.serving_report()
        assert report["kv_pool_bytes_constant"] is True
        assert report["compiles_after_warmup"] == 0
        assert report["decode"]["tokens"] > 0
        assert report["decode"]["prefill_steps"] > 0
        assert report["decode"]["decode_steps"] > 0


class TestFaultWall:
    def test_crashed_prefill_fails_only_its_group(self, model):
        """A program-call crash fails exactly the lanes riding it: their
        slots release and futures raise; the loop keeps serving."""
        eng = _engine(model, max_slots=4).warmup()
        try:
            real_prefill = eng.programs.prefill
            crashes = {"n": 0}

            def boom(*a, **k):
                crashes["n"] += 1
                raise RuntimeError("seeded prefill crash")

            eng.programs.prefill = boom
            doomed = eng.submit("t0", _prompts(1, seed=21)[0],
                                max_new_tokens=4)
            with pytest.raises(RuntimeError, match="seeded prefill crash"):
                doomed.result(30)
            eng.programs.prefill = real_prefill
            assert crashes["n"] == 1
            assert eng.kv_pool.in_use() == 0          # the slot came back
            assert eng.active_requests() == 0
            # quota released, loop alive: the next request serves normally
            out = eng.generate("t0", _prompts(1, seed=22)[0],
                               max_new_tokens=3)
            assert len(out) == 3
        finally:
            eng.programs.prefill = real_prefill
            eng.shutdown(drain=True)


def test_default_seq_ladder_clamps_to_non_power_of_two_max_seq(model):
    eng = _engine(model, max_seq=24, seq_buckets=None)
    assert eng.programs.seq_ladder[-1] == 24
    assert all(s <= 24 for s in eng.programs.seq_ladder)


def test_static_output_axis_matching_seq_rung_survives(tmp_path):
    """Out-slicing is driven by the export's symbolic out_avals, not
    shape coincidence: an output whose STATIC axis equals the seq rung
    keeps every column."""
    from paddle_tpu.inference import Config, Predictor
    from paddle_tpu.nn.layer.layers import Layer
    from paddle_tpu.static import InputSpec

    class PooledHead(Layer):
        def __init__(self):
            super().__init__()
            self.emb = paddle.nn.Embedding(64, 16)  # hidden == a seq rung

        def forward(self, x):
            return paddle.mean(self.emb(x), axis=1)  # [B, 16]: seq dropped

    paddle.seed(0)
    net = PooledHead()
    net.eval()
    prefix = str(tmp_path / "pooled")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, None], "int64")])
    p = Predictor(Config(prefix))
    p.set_batch_ladder([1, 2])
    p.set_seq_ladder([8, 16])
    p.warmup_ladder()
    prog = p._ensure_batch_program()
    assert prog.out_seq_axes == {}  # no output carries the seq symbol
    x = np.random.RandomState(0).randint(0, 64, size=(1, 9)).astype(np.int64)
    out, = p.run_many([x])          # rung (1, 16): 16 == hidden size
    assert out.shape == (1, 16)     # all 16 real columns intact


# ---------------------------------------------------------------- TTL gate
class TestRequestTTL:
    def _queue(self, ttl_ms, stats=None):
        return RequestQueue(AdmissionController(max_queue=64,
                                                tenant_quota=0,
                                                request_ttl_ms=ttl_ms),
                            stats=stats or ServingStats())

    def test_overdue_requests_expire_with_ttl_reason(self):
        q = self._queue(ttl_ms=60.0)
        r1 = q.submit(Request("a", [np.zeros((1, 4))], 1))
        time.sleep(0.09)
        r2 = q.submit(Request("a", [np.zeros((1, 4))], 1))  # fresh
        taken, bucket = q.take_batch([1, 2, 4], timeout=0.01)
        assert [t.id for t in taken] == [r2.id]
        with pytest.raises(AdmissionError) as ei:
            r1.result(0.1)
        assert ei.value.reason == "ttl"
        assert q.stats.summary()["expired"] == 1
        assert q.stats.summary()["tenants"]["a"]["expired"] == 1

    def test_expiry_ticks_the_counter(self):
        from paddle_tpu.observability.metrics import registry

        before = registry.counter("serving.expired").value(tenant="tick") or 0
        q = self._queue(ttl_ms=1.0)
        q.submit(Request("tick", [np.zeros((1, 4))], 1))
        time.sleep(0.01)
        assert q.take_slots(4) == []
        assert registry.counter("serving.expired").value(
            tenant="tick") == before + 1

    def test_admission_charge_released_on_expiry(self):
        q = self._queue(ttl_ms=1.0)
        q.submit(Request("a", [np.zeros((1, 4))], 1))
        time.sleep(0.01)
        q.take_slots(4)
        assert q.admission._queued == 0
        assert q.admission.inflight("a") == 0

    def test_zero_ttl_disables_expiry(self):
        q = self._queue(ttl_ms=0.0)
        r = q.submit(Request("a", [np.zeros((1, 4))], 1))
        time.sleep(0.01)
        taken = q.take_slots(4)
        assert [t.id for t in taken] == [r.id]


# ----------------------------------------------------------- priority tiers
class TestPriorityTiers:
    def test_bulk_tier_blocked_past_its_queue_share(self):
        ctl = AdmissionController(max_queue=10, tenant_quota=0)
        ctl.set_tier("batch", "bulk")
        # FLAGS_serving_bulk_queue_share = 0.5 -> bulk may fill 5
        assert ctl.try_admit("batch", 5) is None
        assert ctl.try_admit("batch", 1) == "priority"
        # interactive headroom above the bulk share stays open
        assert ctl.try_admit("chat", 5) is None
        assert ctl.try_admit("chat", 1) == "queue"

    def test_interactive_preempts_bulk_at_slot_admission(self):
        q = RequestQueue(AdmissionController(max_queue=64, tenant_quota=0),
                         stats=ServingStats())
        q.admission.set_tier("bulk", "bulk")
        bulk = [q.submit(Request("bulk", [np.zeros((1, 4))], 1))
                for _ in range(3)]
        chat = q.submit(Request("chat", [np.zeros((1, 4))], 1))
        taken = q.take_slots(2)
        # the interactive request jumped the three older bulk ones;
        # within the bulk tier FIFO order holds
        assert [t.id for t in taken] == [chat.id, bulk[0].id]
        rest = q.take_slots(4)
        assert [t.id for t in rest] == [b.id for b in bulk[1:]]

    def test_engine_exposes_tier_api(self, engine):
        engine.set_tenant_tier("bulky", "bulk")
        assert engine.queue.admission.tier_of("bulky") == 1
        assert engine.queue.admission.tier_of("other") == 0


# ------------------------------------------------------------ tenant churn
class TestTenantChurn:
    def test_add_and_drop_tenants_while_decoding(self, model):
        """Tenants appear and retire mid-traffic under the running decode
        loop: no dropped futures, stats lanes created and retired
        cleanly."""
        eng = _engine(model, max_slots=2).warmup()
        try:
            results = {}
            errors = []

            def client(tenant, seed):
                try:
                    reqs = [eng.submit(tenant, p, max_new_tokens=8)
                            for p in _prompts(4, seed=seed)]
                    results[tenant] = [r.result(60) for r in reqs]
                except Exception as e:  # pragma: no cover - failure detail
                    errors.append((tenant, e))

            t0 = threading.Thread(target=client, args=("t0", 1))
            t1 = threading.Thread(target=client, args=("t1", 2))
            t0.start()
            t1.start()
            time.sleep(0.01)
            # a NEW tenant joins mid-traffic...
            late = threading.Thread(target=client, args=("late", 3))
            late.start()
            for t in (t0, t1, late):
                t.join(60)
            assert not errors
            assert {k: len(v) for k, v in results.items()} == {
                "t0": 4, "t1": 4, "late": 4}
            lanes = eng.stats.summary()["tenants"]
            assert {"t0", "t1", "late"} <= set(lanes)
            # ... and one retires: lane dropped, everyone else intact
            assert eng.drop_tenant("t0") is True
            assert eng.drop_tenant("t0") is False
            assert "t0" not in eng.tenants
            lanes = eng.stats.summary()["tenants"]
            assert "t0" not in lanes and {"t1", "late"} <= set(lanes)
            # dropped tenants may come back as a fresh lane
            out = eng.generate("t0", _prompts(1, seed=9)[0],
                               max_new_tokens=2)
            assert len(out) == 2
            assert "t0" in eng.stats.summary()["tenants"]
        finally:
            eng.shutdown(drain=True)

    def test_batch_engine_drop_tenant_retires_clone_and_lane(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu.static import InputSpec

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 4))
        net.eval()
        prefix = str(tmp_path / "m")
        paddle.jit.save(net, prefix,
                        input_spec=[InputSpec([None, 8], "float32")])
        eng = serving.ServingEngine(prefix, buckets=[1, 2],
                                    stats=ServingStats()).warmup()
        try:
            eng.run("a", np.zeros((1, 8), np.float32))
            eng.run("b", np.zeros((1, 8), np.float32))
            assert eng.drop_tenant("a") is True
            assert eng.tenants == ["b"]
            assert "a" not in eng.stats.summary()["tenants"]
            # a's admitted work was already served; new submits re-clone
            out, = eng.run("a", np.ones((1, 8), np.float32))
            assert out.shape == (1, 4)
        finally:
            eng.shutdown(drain=True)


# ----------------------------------------------------- two-axis bucket grid
class TestTwoAxisLadder:
    @pytest.fixture(scope="class")
    def served_gpt(self, tmp_path_factory):
        from paddle_tpu.static import InputSpec

        model = _tiny_model()
        prefix = str(tmp_path_factory.mktemp("twoaxis") / "gpt")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([None, None], "int64")])
        return prefix

    def test_save_records_per_rank_symbols(self, served_gpt):
        from paddle_tpu.inference import Config, Predictor

        p = Predictor(Config(served_gpt))
        assert p.dynamic_batch and p.dynamic_seq
        assert (0, 0, 0) in p._dynamic_ranks
        assert (0, 1, 1) in p._dynamic_ranks

    def test_grid_warmup_and_zero_retrace_run_many(self, served_gpt):
        from paddle_tpu.inference import Config, Predictor

        p = Predictor(Config(served_gpt))
        p.set_batch_ladder([1, 2])
        p.set_seq_ladder([8, 16])
        prog = p._ensure_batch_program()
        assert prog.rungs == [(1, 8), (1, 16), (2, 8), (2, 16)]
        p.warmup_ladder()
        assert p.compile_count == 4
        x = np.random.RandomState(0).randint(
            0, 512, size=(2, 11)).astype(np.int64)
        out, = p.run_many([x])
        assert out.shape == (2, 11, 512)   # seq pad sliced back off
        assert p.compile_count == 4        # replayed the (2, 16) rung

    @staticmethod
    def _gpt_float64(w, ids, heads=2, eps=1e-5):
        """The served GPT's logits for ``ids`` ``[S]``, on float64 copies
        ``w`` of its exported weights, in plain numpy."""
        def ln(x, name):
            mu, var = x.mean(-1, keepdims=True), x.var(-1, keepdims=True)
            return ((x - mu) / np.sqrt(var + eps) * w[f"{name}.weight"]
                    + w[f"{name}.bias"])

        def linear(x, name):
            return x @ w[f"{name}.weight"] + w[f"{name}.bias"]

        wte = w["gpt.embeddings.word_embeddings.weight"]
        S = len(ids)
        x = wte[ids] + w["gpt.embeddings.position_embeddings.weight"][:S]
        causal = np.tril(np.ones((S, S), bool))
        li = 0
        while f"gpt.h.{li}.ln_1.weight" in w:
            blk = f"gpt.h.{li}"
            qkv = linear(ln(x, f"{blk}.ln_1"), f"{blk}.attn.qkv_proj")
            q, k, v = np.moveaxis(qkv.reshape(S, heads, 3, -1), 2, 0)
            logits = np.einsum("shd,thd->hst", q, k) / np.sqrt(q.shape[-1])
            logits = np.where(causal[None], logits, -np.inf)
            probs = np.exp(logits - logits.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            att = np.einsum("hst,thd->shd", probs, v).reshape(S, -1)
            x = x + linear(att, f"{blk}.attn.out_proj")
            h = linear(ln(x, f"{blk}.ln_2"), f"{blk}.mlp.fc1")
            h = 0.5 * h * (1 + np.tanh(np.sqrt(2 / np.pi)
                                       * (h + 0.044715 * h ** 3)))
            x = x + linear(h, f"{blk}.mlp.fc2")
            li += 1
        return ln(x, "gpt.ln_f") @ wte.T

    def test_engine_serves_mixed_seq_lengths_bit_exact(self, served_gpt):
        """Every mixed-length batched result and the single-request
        ``Predictor.run`` on the same row agree with ONE float64
        evaluation of the served model within 2e-6 (the float32 programs
        of two batch shapes differ from each other by 9e-8 here, in
        whatever order XLA reduces; a half-precision result is off by
        1e-4 and more). Bit equality between programs of two batch
        shapes is not a property XLA gives; PR 32 withdrew that claim
        for this test's sibling in ``tests/test_serving.py``."""
        from paddle_tpu.inference import Config, Predictor

        eng = serving.ServingEngine(served_gpt, buckets=[1, 2, 4],
                                    seq_buckets=[8, 16],
                                    stats=ServingStats()).warmup()
        try:
            rs = np.random.RandomState(1)
            xs = [rs.randint(0, 512, size=(1, n)).astype(np.int64)
                  for n in (5, 11, 8, 16, 3)]
            weights = {k: np.asarray(v.numpy(), np.float64) for k, v in
                       paddle.jit.load(served_gpt).state_dict().items()}
            reqs = [eng.submit("a", x) for x in xs]
            outs = [r.result(60) for r in reqs]
            single = Predictor(Config(served_gpt))
            for x, (out,) in zip(xs, outs):
                assert out.shape == (1, x.shape[1], 512)
                want = single.run([x])[0]
                assert out.dtype == want.dtype == np.float32
                ref = self._gpt_float64(weights, x[0])[None]
                np.testing.assert_allclose(out, ref, rtol=0, atol=2e-6)
                np.testing.assert_allclose(want, ref, rtol=0, atol=2e-6)
                # the tolerance resolves a half-precision result
                low = ref.astype(np.float16).astype(np.float64)
                assert np.abs(low - ref).max() > 2e-6
            assert eng.compiles_after_warmup == 0
        finally:
            eng.shutdown(drain=True)

    def test_oversized_seq_refused_at_submit(self, served_gpt):
        eng = serving.ServingEngine(served_gpt, buckets=[1, 2],
                                    seq_buckets=[8, 16],
                                    stats=ServingStats()).warmup()
        try:
            with pytest.raises(ValueError, match="seq"):
                eng.submit("a", np.zeros((1, 17), np.int64))
        finally:
            eng.shutdown(drain=True)


# ------------------------------------------------------------ serving audit
class TestDecodeAudit:
    def test_green_on_demo_decode_engine(self):
        from paddle_tpu.analysis.jaxpr_audit import (
            audit_serving, record_demo_decode_engine)

        eng = record_demo_decode_engine()
        assert [str(f) for f in audit_serving(eng)] == []
        assert eng.compiles_after_warmup == 0
        assert eng.serving_report()["kv_pool_bytes_constant"] is True

    def test_jx332_seeded_pool_growth(self, engine):
        import jax.numpy as jnp

        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        pool = engine.kv_pool
        saved = pool.k
        pool.k = jnp.zeros(saved.shape[:-1] + (saved.shape[-1] + 1,),
                           saved.dtype)  # grown buffer, bypassing commit
        try:
            findings = audit_serving(engine)
            assert any(f.code == "JX332" and f.severity == "error"
                       for f in findings)
        finally:
            pool.k = saved
        assert not any(f.code == "JX332" for f in audit_serving(engine))

    def test_jx333_seeded_slot_leak(self, engine):
        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        slot = engine.kv_pool.alloc()  # a slot nobody owns: the leak
        try:
            findings = audit_serving(engine)
            assert any(f.code == "JX333" and f.severity == "warning"
                       for f in findings)
        finally:
            engine.kv_pool.release(slot)
        assert not any(f.code == "JX333" for f in audit_serving(engine))


# ------------------------------------------------- beats, phases and stamps
_SCHEDULER_SPANS = ("serving.admit", "serving.build", "serving.decode",
                    "serving.absorb")


def _traced_run(model, n=7, **engine_kw):
    """A tiny paged engine serving `n` requests with the tracer on from
    before its first beat. Returns (requests, complete events by name)."""
    from paddle_tpu.observability import tracer

    tracer.reset()
    was = tracer.enabled
    tracer.enable()
    try:
        eng = _engine(model, page_size=8, **engine_kw).warmup()
        try:
            rs = np.random.RandomState(3)
            reqs = [eng.submit("t", p, max_new_tokens=int(m))
                    for p, m in zip(_prompts(n, seed=5), rs.randint(2, 9, size=n))]
            for r in reqs:
                r.result(60)
        finally:
            eng.shutdown(drain=True)
        events = [e for e in tracer.to_chrome_trace()["traceEvents"]
                  if e["ph"] == "X"]
    finally:
        tracer.enabled = was
        tracer.reset()
    # the tracer is the process's: another engine of this module, idling on
    # its own thread, records its beats too. This engine's are those of its
    # own short count; everything else is kept by descent from them.
    own = {e["id"] for e in events if e["name"] == "serving.beat"
           and e["args"]["beat"] <= eng._scheduler._beat}
    assert len(own) == eng._scheduler._beat
    for e in sorted(events, key=lambda e: e["id"]):  # a parent's id is lower
        if e["parent"] in own:
            own.add(e["id"])
    return reqs, [e for e in events if e["id"] in own]


@pytest.fixture(scope="module", params=[0, 2], ids=["plain", "speculate"])
def traced(request):
    model = _tiny_model(num_hidden_layers=2)
    return _traced_run(model, speculate_k=request.param, spec_draft_layers=1)


class TestSchedulerTracing:
    def test_every_scheduler_span_has_an_id_and_a_parent_field(self, traced):
        _, events = traced
        ids = [e["id"] for e in events]
        assert len(set(ids)) == len(ids)
        assert all("parent" in e for e in events)
        by_id = {e["id"]: e for e in events}
        for e in events:
            if e["name"] in _SCHEDULER_SPANS:
                assert by_id[e["parent"]]["name"] == "serving.beat"
            elif e["name"] in ("serving.dispatch", "serving.read"):
                assert by_id[e["parent"]]["name"] == "serving.decode"
            elif e["name"] == "serving.beat":
                assert e["parent"] is None

    def test_children_tile_each_beat_without_overlap(self, traced):
        _, events = traced
        beats = [e for e in events if e["name"] == "serving.beat"]
        assert [b["args"]["beat"] for b in beats] == sorted(
            b["args"]["beat"] for b in beats)
        kinds = {b["args"]["kind"] for b in beats}
        assert {"prefill", "idle"} <= kinds
        assert kinds - {"idle", "prefill"} <= {"decode", "speculate"}
        in_flight = None    # the beat whose call is dispatched and unread
        for b in beats:
            kids = sorted((e for e in events if e["parent"] == b["id"]
                           and e["name"] in _SCHEDULER_SPANS),
                          key=lambda e: e["ts"])
            names = [k["name"] for k in kids]
            if b["args"]["kind"] == "idle":
                assert names == ["serving.admit"]
                continue
            assert names == list(_SCHEDULER_SPANS)
            assert kids[0]["ts"] >= b["ts"]
            assert kids[-1]["ts"] + kids[-1]["dur"] <= b["ts"] + b["dur"] + 1e-3
            for a, c in zip(kids, kids[1:]):
                assert a["ts"] + a["dur"] <= c["ts"] + 1e-3    # microseconds
            step = kids[2]
            assert step["args"]["kind"] == b["args"]["kind"]
            inner = sorted((e for e in events if e["parent"] == step["id"]),
                           key=lambda e: e["ts"])
            want = (["serving.dispatch", "serving.read"]
                    * (2 if step["args"]["kind"] == "speculate" else 1))
            assert [e["name"] for e in inner] == want
            assert sum(e["dur"] for e in inner) <= step["dur"]
            # a read is of the call of the beat BEFORE (none in flight: of
            # nothing); only a speculation round reads its own calls
            reads = [e["args"]["of_beat"] for e in inner
                     if e["name"] == "serving.read"]
            if step["args"]["kind"] == "speculate":
                assert in_flight is None
                assert reads == [b["args"]["beat"]] * 2
            else:
                assert reads == [in_flight]
                assert in_flight is None or in_flight < b["args"]["beat"]
                in_flight = b["args"]["beat"] if step["args"]["lanes"] else None
        assert in_flight is None    # the drain read the last call

    def test_decode_span_names_the_lanes_it_carried(self, traced):
        reqs, events = traced
        known = {r.id for r in reqs}
        steps = [e for e in events if e["name"] == "serving.decode"]
        assert steps
        for e in steps:
            a = e["args"]
            assert len(a["requests"]) == a["lanes"] and set(a["requests"]) <= known
            assert a["kind"] in ("prefill", "decode", "speculate") and "rung" in a
        # every token but a speculation round's extras is one ride
        rides = {r.id: sum(r.id in e["args"]["requests"] for e in steps)
                 for r in reqs}
        for r in reqs:
            assert 1 <= rides[r.id] <= len(r.generated)

    def test_request_phases_share_the_id_and_meet_end_to_end(self, traced):
        reqs, events = traced
        phases = {}
        for e in events:
            if e["name"].startswith("serving.request."):
                phases.setdefault(e["args"]["request"], {})[e["name"][16:]] = e
        assert set(phases) == {r.id for r in reqs}
        for r in reqs:
            p = phases[r.id]
            assert set(p) == {"queue", "prefill"}
            assert p["queue"]["ts"] == pytest.approx(r.t_enqueue * 1e6)
            assert p["queue"]["ts"] + p["queue"]["dur"] == pytest.approx(
                p["prefill"]["ts"]) == pytest.approx(r.t_dispatch * 1e6)
            assert p["prefill"]["ts"] + p["prefill"]["dur"] == pytest.approx(
                r.t_first_token * 1e6)
            assert p["prefill"]["args"]["prompt"] == r.prompt.size

    def test_first_token_stamp_holds_its_invariants(self, traced):
        reqs, events = traced
        # the stamp is the end of the prefill step the request rode
        ends = {e["args"]["request"]: e["ts"] + e["dur"] for e in events
                if e["name"] == "serving.request.prefill"}
        steps = [e for e in events if e["name"] == "serving.decode"
                 and e["args"]["kind"] == "prefill"]
        for r in reqs:
            assert len(r.generated) == len(r.result(0))
            assert (r.t_enqueue <= r.t_dispatch <= r.t_first_token
                    <= r.t_complete)
            rode = [e for e in steps if r.id in e["args"]["requests"]]
            assert len(rode) == 1
            assert rode[0]["ts"] + rode[0]["dur"] <= ends[r.id] + 1e-3

    def test_stamps_are_taken_with_the_tracer_off(self, engine):
        from paddle_tpu.observability import tracer

        assert not tracer.enabled
        r = engine.submit("t0", _prompts(1, seed=31)[0], max_new_tokens=5)
        r.result(30)
        assert len(r.generated) == 5
        assert r.t_dispatch <= r.t_first_token <= r.t_complete
        # (a beat that was open when an earlier test switched the tracer off
        # may still close into the ring; nothing of this request does)
        assert not any((e.get("args") or {}).get("request") == r.id
                       for e in tracer.tail_chrome_events(64))


def test_sampling_arguments_are_built_inside_the_decode_span(monkeypatch):
    """What `serving.decode` covers is part of `decode_step_ms`' yardstick:
    a plain step has always assembled its sampling arguments inside the
    span (in the program call, so inside `serving.dispatch`)."""
    from paddle_tpu.observability import tracer
    from paddle_tpu.serving.scheduler import PagedDecodeScheduler

    plain = PagedDecodeScheduler._sample_args

    def marked(self, lanes, b_rung):
        with tracer.span("test.sample_args"):
            return plain(self, lanes, b_rung)

    monkeypatch.setattr(PagedDecodeScheduler, "_sample_args", marked)
    _, events = _traced_run(_tiny_model(num_hidden_layers=2), n=3)
    by_id = {e["id"]: e for e in events}
    marks = [e for e in events if e["name"] == "test.sample_args"]
    kinds = set()
    for m in marks:
        call = by_id[m["parent"]]
        step = by_id[call["parent"]]
        assert (call["name"], step["name"]) == ("serving.dispatch",
                                                "serving.decode")
        kinds.add(step["args"]["kind"])
    assert kinds == {"prefill", "decode"}


def test_failed_request_closes_with_its_reason(model):
    from paddle_tpu.observability import tracer

    tracer.reset()
    was = tracer.enabled
    tracer.enable()
    eng = _engine(model, max_slots=4).warmup()
    try:
        def boom(*a, **k):
            raise RuntimeError("seeded prefill crash")

        eng.programs.prefill = boom
        doomed = eng.submit("t0", _prompts(1, seed=21)[0], max_new_tokens=4)
        with pytest.raises(RuntimeError):
            doomed.result(30)
        events = {e["name"]: e for e in tracer.to_chrome_trace()["traceEvents"]
                  if e["ph"] == "X" and (e.get("args") or {}).get("request") == doomed.id}
    finally:
        tracer.enabled = was
        tracer.reset()
        eng.shutdown(drain=False)
    assert set(events) == {"serving.request.queue", "serving.request.failed"}
    failed = events["serving.request.failed"]
    assert failed["args"]["reason"] == "RuntimeError"
    assert failed["ts"] == pytest.approx(doomed.t_dispatch * 1e6)
    assert doomed.t_first_token is None


# ------------------------------------------------------- program regions
SERVING_REGIONS = ("embed", "attn/qkv", "attn/kv_write", "attn/kv_gather",
                   "attn/core", "attn/out", "mlp", "lm_head", "sample")


def test_serving_vocabulary_is_the_module_s():
    from paddle_tpu.base import regions

    assert regions.SERVING == SERVING_REGIONS


@pytest.fixture(scope="module")
def spec_programs():
    model = _tiny_model(num_hidden_layers=2)
    eng = _engine(model, page_size=8, speculate_k=2, spec_draft_layers=1)
    return eng.programs


@pytest.mark.parametrize("key", [("decode", 4, 2), ("prefill", 2, 8),
                                 ("draft", 4, 2), ("verify", 4, 2)],
                         ids=lambda k: k[0])
def test_lowered_serving_program_names_every_region(spec_programs, key):
    """The scope vocabulary is a contract (PERF.md): each program body under
    its own root, every region inside it in some operation's name."""
    import re

    P = spec_programs
    text = P._jitted(key).lower(P._call_params(key), P.pool.k, P.pool.v,
                                *P._zero_args(key)).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    root = key[0]
    for region in SERVING_REGIONS:
        if region == "attn/kv_gather" and root == "prefill":
            continue                      # a prompt attends to its own rows
        assert any(f"/{root}/{region}/" in f"/{n}" for n in names), (root, region)
