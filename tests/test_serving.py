"""Serving tier tests (ISSUE 6): continuous bucketed batching over
warm-compiled predictors — batch assembly, admission control, tenant
isolation under clone, drain-on-shutdown, the zero-retrace contract, and
the JX33x serving audit (seeded negatives included)."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import serving
from paddle_tpu.profiler.pipeline import ServingStats
from paddle_tpu.static import InputSpec


@pytest.fixture(scope="module")
def served_model(tmp_path_factory):
    """One exported dynamic-batch MLP shared by the module's engines."""
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    net.eval()
    prefix = str(tmp_path_factory.mktemp("serving") / "model")
    paddle.jit.save(net, prefix, input_spec=[InputSpec([None, 16], "float32")])
    return prefix


def _engine(served_model, **kw):
    kw.setdefault("buckets", [1, 2, 4, 8])
    kw.setdefault("stats", ServingStats())
    return serving.ServingEngine(served_model, **kw)


# ---------------------------------------------------------------- assembly

class TestAssembleBucket:
    def _assemble(self, counts, buckets=(1, 2, 4, 8), max_total=None):
        from paddle_tpu.jit.bucketing import assemble_bucket

        return assemble_bucket(list(counts), list(buckets), max_total)

    def test_single_request_exact_rung(self):
        assert self._assemble([4]) == (1, 4)

    def test_mixed_sizes_greedy_fifo(self):
        # 3+2 = 5 -> rung 8; the free top-up then pulls the 3-sample tail in
        assert self._assemble([3, 2, 3]) == (3, 8)

    def test_fifo_never_reordered(self):
        # 5+4 > 8 stops the greedy fill; the 1 after the 4 is NOT pulled
        # ahead of it past the rung (4 then 1 both fit the pad: taken in order)
        k, bucket = self._assemble([5, 4, 1])
        assert (k, bucket) == (1, 8)

    def test_free_pad_topup(self):
        # greedy stops at 8 = cap; 6 -> rung 8, then 2 rides the pad free
        assert self._assemble([6, 2]) == (2, 8)

    def test_max_total_caps_assembly(self):
        assert self._assemble([3, 3, 3], max_total=4) == (1, 4)

    def test_topup_respects_max_total(self):
        # greedy lands at 5 -> rung 8; the 2-sample tail fits the pad but
        # would put 7 real samples past the caller's cap of 5: not taken
        assert self._assemble([5, 2], max_total=5) == (1, 8)

    def test_oversized_head_raises(self):
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            self._assemble([9])

    def test_empty_queue(self):
        assert self._assemble([]) == (0, None)


class TestStackScatter:
    def test_roundtrip_mixed_sizes(self):
        from paddle_tpu.serving import scatter_outputs, stack_requests
        from paddle_tpu.serving.request_queue import Request

        rs = np.random.RandomState(0)
        reqs = [Request("t", [rs.randn(n, 3).astype(np.float32)], n)
                for n in (2, 1, 3)]
        stacked = stack_requests(reqs, bucket=8, dynamic_axes={0: 0},
                                 n_inputs=1)
        assert stacked[0].shape == (8, 3)
        # rows land FIFO; the pad tail is zeros
        np.testing.assert_array_equal(stacked[0][:2], reqs[0].inputs[0])
        np.testing.assert_array_equal(stacked[0][6:], 0.0)
        rows = scatter_outputs([stacked[0]], reqs)
        for r, out in zip(reqs, rows):
            np.testing.assert_array_equal(out[0], r.inputs[0])

    def test_static_side_input_mismatch_fails_loud(self):
        """Per-batch side inputs must match bit-wise across the batch —
        serving request 1's rows with request 0's side value would be a
        silent cross-tenant data leak."""
        from paddle_tpu.serving import stack_requests
        from paddle_tpu.serving.request_queue import Request

        scale_a, scale_b = np.ones(4, np.float32), np.zeros(4, np.float32)
        reqs = [Request("a", [np.ones((2, 3), np.float32), scale_a], 2),
                Request("b", [np.ones((1, 3), np.float32), scale_b], 1)]
        with pytest.raises(ValueError, match="static input 1 differs"):
            stack_requests(reqs, bucket=4, dynamic_axes={0: 0}, n_inputs=2)
        # identical side inputs assemble fine
        reqs[1].inputs = [reqs[1].inputs[0], scale_a.copy()]
        stacked = stack_requests(reqs, bucket=4, dynamic_axes={0: 0},
                                 n_inputs=2)
        assert stacked[0].shape == (4, 3) and stacked[1].shape == (4,)


# ---------------------------------------------------------------- parity

def _mlp_float64(w, x):
    """The served MLP on float64 copies ``w`` of its exported weights."""
    h = np.maximum(x.astype(np.float64) @ w["0.weight"] + w["0.bias"], 0.0)
    return h @ w["2.weight"] + w["2.bias"]


# float32 sums of 16 and 32 products of O(1) terms (outputs up to 2.3) land
# within 2.4e-7 of the float64 value on the CPU, in whatever order XLA
# reduces them (the batched and the single-request program differ from each
# other by 6e-8); an output rounded to float16 is off by at least 1.6e-4 on
# every request's rows and one rounded to bfloat16 by more: 2e-6 is nine
# times the one and eighty times under the other
_PARITY_ATOL = 2e-6


def test_batched_and_sequential_match_float64_reference(served_model):
    """Every mixed-size batched result and the single-request
    Predictor.run on the same rows agree with one float64 evaluation of
    the served model, to a tolerance a half-precision result fails; dtype,
    shape and the request each row came back to are checked exactly."""
    eng = _engine(served_model).warmup()
    try:
        rs = np.random.RandomState(1)
        feeds = [rs.randn(n, 16).astype(np.float32)
                 for n in (1, 3, 2, 5, 8, 4, 7, 1)]
        weights = {k: np.asarray(v.numpy(), np.float64) for k, v in
                   paddle.jit.load(served_model).state_dict().items()}
        refs = [_mlp_float64(weights, x) for x in feeds]
        # submit everything first so the scheduler really assembles
        # multi-request batches, then run the sequential path
        reqs = [eng.submit("t0", x) for x in feeds]
        got = [r.result(30.0)[0] for r in reqs]
        single = eng.tenant("t0")
        for i, (x, out) in enumerate(zip(feeds, got)):
            want = single.run([x])[0]
            assert out.dtype == want.dtype == np.float32
            assert out.shape == want.shape == refs[i].shape
            np.testing.assert_allclose(out, refs[i], rtol=0,
                                       atol=_PARITY_ATOL)
            np.testing.assert_allclose(want, refs[i], rtol=0,
                                       atol=_PARITY_ATOL)
            # the rows are this request's own, not a neighbour's
            for j, other in enumerate(refs):
                if j != i and other.shape == out.shape:
                    assert np.abs(out - other).max() > 1e3 * _PARITY_ATOL
            # the tolerance resolves a half-precision result on these rows
            for low in (np.float16, jnp.bfloat16):
                rounded = refs[i].astype(low).astype(np.float64)
                assert np.abs(rounded - refs[i]).max() > _PARITY_ATOL
    finally:
        eng.shutdown(drain=True)


def test_tenant_isolation_under_clone(served_model):
    """Clones share weights/executable zero-copy (one layer, one batch
    program) while every tenant's rows route back to its own request."""
    eng = _engine(served_model).warmup()
    try:
        preds = [eng.tenant(f"t{i}") for i in range(3)]
        base = eng.predictor
        assert all(p._layer is base._layer for p in preds)
        assert all(p._batch_program is base._batch_program for p in preds)

        # distinctive per-tenant payloads served concurrently
        results = {}
        def client(i):
            x = np.full((2, 16), float(i + 1), np.float32)
            out, = eng.run(f"t{i}", x, timeout=30.0)
            results[i] = (x, out)
        threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert set(results) == {0, 1, 2}
        for i, (x, out) in results.items():
            want = preds[i].run([x])[0]
            np.testing.assert_array_equal(out, want)
    finally:
        eng.shutdown(drain=True)


# ------------------------------------------------------------- admission

def test_admission_rejects_over_queue_cap(served_model):
    eng = _engine(served_model, max_queue=4, tenant_quota=0).warmup()
    try:
        eng.shutdown(drain=True)  # stop the consumer so the queue backs up
        eng.queue.closed = False  # re-open the front door: no scheduler
        x = np.zeros((2, 16), np.float32)
        eng._started = True
        eng.submit("a", x)
        eng.submit("a", x)
        with pytest.raises(serving.AdmissionError) as ei:
            eng.submit("a", x)
        assert ei.value.reason == "queue"
        assert eng.stats.rejected == 1
    finally:
        eng.queue.fail_pending(serving.RejectedError("test over"))


def test_admission_tenant_quota_isolates_and_releases(served_model):
    """One tenant at quota is refused while another still serves; quota
    frees at completion, after which the refused tenant serves again."""
    eng = _engine(served_model, max_queue=0, tenant_quota=4).warmup()
    try:
        # stall the scheduler with a lock held inside execute? simpler:
        # fill tenant-a's quota with requests the live engine will serve,
        # measured via direct controller state
        ctrl = eng.queue.admission
        assert ctrl.try_admit("a", 4) is None          # a at quota
        assert ctrl.try_admit("a", 1) == "tenant"      # refused
        assert ctrl.try_admit("b", 4) is None          # b unaffected
        ctrl.on_dispatch("a", 4)
        ctrl.on_complete("a", 4)                       # completion frees
        assert ctrl.try_admit("a", 1) is None

        # end-to-end: a live submit beyond quota raises AdmissionError
        with pytest.raises(serving.AdmissionError):
            eng.submit("c", np.zeros((5, 16), np.float32))
        eng.queue.admission.tenant_quota = 256
        out, = eng.run("c", np.zeros((5, 16), np.float32), timeout=30.0)
        assert out.shape == (5, 8)
    finally:
        eng.shutdown(drain=True)


def test_oversized_request_refused_at_submit(served_model):
    eng = _engine(served_model).warmup()
    try:
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            eng.submit("t", np.zeros((9, 16), np.float32))
    finally:
        eng.shutdown(drain=True)


# -------------------------------------------------------------- shutdown

def test_queue_drains_on_shutdown(served_model):
    """Everything admitted before close() is served before the scheduler
    exits; submits after close are refused."""
    eng = _engine(served_model, linger_ms=0.0).warmup()
    rs = np.random.RandomState(2)
    reqs = [eng.submit("t", rs.randn(n, 16).astype(np.float32))
            for n in (3, 1, 2, 4, 2, 1)]
    eng.shutdown(drain=True)
    assert all(r.done() for r in reqs)
    for r in reqs:
        out, = r.result(0.0)
        assert out.shape == (r.n, 8)
    with pytest.raises(RuntimeError, match="closed"):
        eng.queue.submit(serving.Request("t", [np.zeros((1, 16), np.float32)], 1))


def test_non_drain_shutdown_fails_pending(served_model):
    eng = _engine(served_model).warmup()
    eng.shutdown(drain=True)       # scheduler gone
    eng.queue.closed = False
    req = eng.queue.submit(
        serving.Request("t", [np.zeros((1, 16), np.float32)], 1))
    eng.queue.close()
    eng.queue.fail_pending(serving.RejectedError("shutdown"))
    with pytest.raises(serving.RejectedError):
        req.result(0.0)


# ---------------------------------------------------------- zero retrace

def test_zero_retraces_after_warmup(served_model):
    """The tentpole contract: warmup compiles exactly the ladder; a
    steady-state mixed-size stream adds ZERO compiled specializations."""
    eng = _engine(served_model, buckets=[1, 2, 4, 8]).warmup()
    try:
        assert eng.compile_count == 4          # one per rung
        assert eng.compiles_after_warmup == 0
        rs = np.random.RandomState(3)
        for i in range(30):
            n = int(rs.randint(1, 9))
            eng.run(f"t{i % 3}", rs.randn(n, 16).astype(np.float32),
                    timeout=30.0)
        assert eng.compiles_after_warmup == 0
    finally:
        eng.shutdown(drain=True)


def test_fixed_shape_export_single_rung(served_model, tmp_path):
    """A concrete-batch export serves through the same surface: ladder
    pinned to the exported batch, smaller requests pad up to it."""
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    net.eval()
    prefix = str(tmp_path / "fixed")
    paddle.jit.save(net, prefix, input_spec=[InputSpec([4, 16], "float32")])

    from paddle_tpu.inference import Config, Predictor

    pred = Predictor(Config(prefix))
    assert not pred.dynamic_batch
    assert pred.batch_ladder == [4]
    with pytest.raises(ValueError, match="pinned"):
        pred.set_batch_ladder([1, 2, 4])
    rs = np.random.RandomState(4)
    x = rs.randn(3, 16).astype(np.float32)
    out, = pred.run_many([x], n=3)
    want = pred.run([np.pad(x, [(0, 1), (0, 0)])])[0][:3]
    np.testing.assert_array_equal(out, want)


# ------------------------------------------------------------ accounting

def test_serving_stats_percentiles_and_slo():
    stats = ServingStats()
    t0 = 100.0
    # 98 requests at 10ms end-to-end, two 100ms stragglers: p50 stays at
    # the fast mass, p99 lands on the tail
    for i in range(98):
        stats.record_request(t0 + i, t0 + i, t0 + i + 0.004, t0 + i + 0.010)
    for i in (98, 99):
        stats.record_request(t0 + i, t0 + i, t0 + i + 0.05, t0 + i + 0.1)
    stats.record_batch(3, 4)
    stats.record_queue_depth(2)
    stats.record_queue_depth(6)
    s = stats.summary(slo_ms=50.0)
    assert s["requests"] == 100
    assert s["p50_ms"] == 10.0
    assert s["p99_ms"] == 100.0
    assert s["in_slo_fraction"] == 0.98
    assert s["batch_fill"] == 0.75
    assert s["queue_depth_peak"] == 6
    assert s["requests_per_sec"] is not None
    # the SLO-gated rate is the headline: raw rate scaled by in-SLO mass
    # (both fields round to 0.1 rps, hence the absolute tolerance)
    assert s["requests_per_sec_in_slo"] == pytest.approx(
        s["requests_per_sec"] * 0.98, abs=0.1)


def test_request_phase_timestamps_recorded(served_model):
    eng = _engine(served_model).warmup()
    try:
        req = eng.submit("t", np.zeros((2, 16), np.float32))
        req.result(30.0)
        assert (req.t_enqueue <= req.t_admit <= req.t_dispatch
                <= req.t_complete)
        s = eng.stats.summary()
        assert s["requests"] == 1 and s["batches"] == 1
        assert s["p50_ms"] is not None and s["p50_ms"] >= 0
    finally:
        eng.shutdown(drain=True)


# ------------------------------------------------------------- JX33x audit

class TestServingAudit:
    def _codes(self, findings):
        return [f.code for f in findings]

    def test_green_on_warm_engine(self, served_model):
        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        eng = _engine(served_model).warmup()
        try:
            eng.run("t", np.zeros((3, 16), np.float32), timeout=30.0)
            assert self._codes(audit_serving(eng)) == []
        finally:
            eng.shutdown(drain=True)

    def test_jx330_seeded_steady_state_recompile(self, served_model):
        """Seeded negative: serving a rung outside the warmed ladder is
        exactly the per-request-retrace defect JX330 exists to catch."""
        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        eng = _engine(served_model, buckets=[1, 2, 4, 8]).warmup()
        try:
            prog = eng.predictor._batch_program
            prog.ladder = [1, 2, 4, 8, 16]      # rung 16 never warmed
            eng.run("t", np.zeros((16, 16), np.float32), timeout=30.0)
            assert eng.compiles_after_warmup == 1
            findings = audit_serving(eng)
            assert "JX330" in self._codes(findings)
            assert any(f.severity == "error" for f in findings)
        finally:
            eng.shutdown(drain=True)

    def test_jx331_seeded_cold_engine(self, served_model):
        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        eng = _engine(served_model)  # no warmup()
        assert "JX331" in self._codes(audit_serving(eng))

    def test_jx331_seeded_unwarmed_rung(self, served_model):
        from paddle_tpu.analysis.jaxpr_audit import audit_serving

        eng = _engine(served_model, buckets=[1, 2]).warmup()
        try:
            eng.predictor._batch_program.ladder = [1, 2, 4]  # 4 cold
            findings = audit_serving(eng)
            assert "JX331" in self._codes(findings)
            assert all(f.severity == "warning" for f in findings)
        finally:
            eng.shutdown(drain=True)

    def test_lint_family_green(self, tmp_path):
        """The tools.lint serving family over the repo's own demo engine:
        zero findings (the tier-1 gate in test_lint_clean runs the full
        CLI; this pins the family in isolation)."""
        from tools.lint import run_analyzers

        findings, crashed, timings = run_analyzers(("serving",))
        assert crashed == []
        assert [str(f) for f in findings] == []
        assert "serving" in timings
