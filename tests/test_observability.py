"""Unified runtime telemetry tests (ISSUE 7): metrics registry, span
tracer + chrome-trace export, device-memory sampler, OB6xx telemetry
lint (seeded negatives per code), batched serving D2H, per-tenant
latency breakdowns, and the end-to-end acceptance demo (one trace file
with dispatch + train-loop + serving tracks)."""
import json
import logging

import numpy as np
import pytest

import paddle_tpu as paddle


@pytest.fixture
def fresh_tracer():
    """The GLOBAL tracer, reset and guaranteed disabled afterwards —
    instrumented hot paths read it, so tests must not leak enabled=True."""
    from paddle_tpu.observability import tracer

    tracer.reset()
    was = tracer.enabled
    yield tracer
    tracer.enabled = was
    tracer.reset()


# --------------------------------------------------------------- registry
class TestMetricsRegistry:
    def _registry(self):
        from paddle_tpu.observability.metrics import MetricsRegistry

        return MetricsRegistry()

    def test_counter_gauge_histogram_roundtrip(self):
        reg = self._registry()
        reg.counter("c").inc()
        reg.counter("c").inc(2)
        assert reg.counter("c").value() == 3
        reg.gauge("g").set(7.5)
        assert reg.gauge("g").value() == 7.5
        h = reg.histogram("h")
        for v in range(100):
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 100 and s["min"] == 0.0 and s["max"] == 99.0
        assert s["p50"] == pytest.approx(50.0, abs=2)
        assert s["p99"] == pytest.approx(99.0, abs=2)

    def test_labels_key_distinct_cells(self):
        reg = self._registry()
        c = reg.counter("req")
        c.inc(tenant="a")
        c.inc(2, tenant="b")
        assert c.value(tenant="a") == 1
        assert c.value(tenant="b") == 2
        values = reg.snapshot()["metrics"]["req"]["values"]
        assert {frozenset(v["labels"].items()) for v in values} == {
            frozenset({("tenant", "a")}), frozenset({("tenant", "b")})}

    def test_snapshot_schema_and_collectors(self):
        reg = self._registry()
        reg.counter("a.count").inc(4)
        reg.register_collector("silo", lambda: {"hits": 9})
        snap = reg.snapshot()
        assert "ts_unix" in snap
        assert snap["metrics"]["a.count"]["type"] == "counter"
        assert snap["metrics"]["a.count"]["values"] == [{"value": 4}]
        assert snap["metrics"]["silo"] == {"type": "collected", "hits": 9}
        json.dumps(snap)  # the JSON surface must actually be JSON-able

    def test_broken_collector_degrades_not_raises(self):
        reg = self._registry()

        def boom():
            raise RuntimeError("silo down")

        reg.register_collector("bad", boom)
        payload = reg.snapshot()["metrics"]["bad"]
        assert "silo down" in payload["error"]

    def test_same_kind_reregistration_is_idempotent(self):
        reg = self._registry()
        assert reg.counter("x") is reg.counter("x")
        assert reg.collisions == []

    def test_kind_collision_recorded_and_detached(self):
        reg = self._registry()
        c = reg.counter("dup")
        g = reg.gauge("dup")   # schema collision
        assert reg.collisions == [("dup", "gauge", "counter")]
        g.set(1)               # detached instrument still works
        assert c.value() == 0  # and never corrupts the original

    def test_global_snapshot_rehomes_the_silos(self):
        """The migrated namespaces are present in one schema: kernel
        cache, pipeline, serving and the compile counters."""
        from paddle_tpu.observability import snapshot

        a = paddle.ones([3])
        paddle.add(a, a)
        snap = snapshot()
        m = snap["metrics"]
        assert set(m) >= {"dispatch.kernel_cache", "pipeline", "serving",
                          "jit.compile"}
        assert "totals" in m["dispatch.kernel_cache"]
        assert "host_syncs_per_step" in m["pipeline"]
        assert "tenants" in m["serving"]
        assert m["jit.compile"]["program_builds"] >= 0


# ---------------------------------------------------------------- tracer
class TestSpanTracer:
    def _tracer(self, **kw):
        from paddle_tpu.observability.tracing import SpanTracer

        kw.setdefault("enabled", True)
        kw.setdefault("max_events", 128)
        return SpanTracer(**kw)

    def test_disabled_tracer_records_nothing(self):
        t = self._tracer(enabled=False)
        with t.span("s", track="x"):
            pass
        t.instant("i")
        t.emit("e", 0.0, 1.0)
        assert len(t) == 0 and t.open_spans() == []

    def test_span_emit_instant_land_with_tracks(self):
        t = self._tracer()
        with t.span("step", track="train_loop", idx=3):
            pass
        t.emit("request", 1.0, 0.5, track="serving.requests.a", n=2)
        t.instant("hit", track="dispatch", op="add")
        trace = t.to_chrome_trace()
        by_name = {e["name"]: e for e in trace["traceEvents"]
                   if e["ph"] != "M"}
        assert by_name["step"]["ph"] == "X"
        assert by_name["step"]["args"] == {"idx": 3}
        assert by_name["request"]["ts"] == pytest.approx(1.0e6)
        assert by_name["request"]["dur"] == pytest.approx(0.5e6)
        assert by_name["hit"]["ph"] == "i"
        # correlated track ids: one metadata row per track, distinct tids
        meta = {e["args"]["name"]: e["tid"] for e in trace["traceEvents"]
                if e["ph"] == "M"}
        assert set(meta) == {"train_loop", "serving.requests.a", "dispatch"}
        assert len(set(meta.values())) == 3
        assert by_name["step"]["tid"] == meta["train_loop"]

    def test_ring_bound_drops_oldest(self):
        t = self._tracer(max_events=10)
        for i in range(25):
            t.instant(f"e{i}")
        assert len(t) == 10
        names = [e[1] for e in t._events]
        assert names[0] == "e15" and names[-1] == "e24"
        assert t.to_chrome_trace()["otherData"]["dropped_events"] == 15

    def test_export_writes_loadable_json(self, tmp_path):
        t = self._tracer()
        with t.span("s", track="host"):
            pass
        path = t.export(str(tmp_path / "sub" / "out.trace.json"))
        loaded = json.load(open(path))
        assert any(e["name"] == "s" for e in loaded["traceEvents"])

    def test_open_span_tracked_until_closed(self):
        t = self._tracer()
        s = t.span("leaky", track="x")
        assert t.open_spans() == ["leaky"]
        s.end()
        assert t.open_spans() == []
        assert len(t) == 1

    def test_ids_are_unique_and_parents_follow_the_thread(self):
        """Every event has an id of its own; a span's parent is the
        innermost span open ON ITS THREAD when it started, whatever other
        threads had open at that moment."""
        import threading

        t = self._tracer(max_events=4096)
        inner_started, outer_may_close = threading.Event(), threading.Event()

        def worker(k):
            with t.span(f"outer{k}", track="w"):
                with t.span(f"mid{k}", track="w"):
                    with t.span(f"leaf{k}", track="w"):
                        inner_started.set()
                        outer_may_close.wait(5)
                    t.instant(f"tick{k}", track="w")

        threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
        with t.span("main", track="m"):
            for th in threads:
                th.start()
            inner_started.wait(5)
            with t.span("main.child", track="m"):
                pass
            outer_may_close.set()
            for th in threads:
                th.join()
        events = [e for e in t.to_chrome_trace()["traceEvents"]
                  if e["ph"] != "M"]
        by_name = {e["name"]: e for e in events}
        assert len({e["id"] for e in events}) == len(events) == 4 * 4 + 2
        assert by_name["main"]["parent"] is None
        assert by_name["main.child"]["parent"] == by_name["main"]["id"]
        for k in range(4):
            assert by_name[f"outer{k}"]["parent"] is None  # not main's child
            assert by_name[f"mid{k}"]["parent"] == by_name[f"outer{k}"]["id"]
            assert by_name[f"leaf{k}"]["parent"] == by_name[f"mid{k}"]["id"]
            assert by_name[f"tick{k}"]["parent"] == by_name[f"mid{k}"]["id"]
        assert t.open_spans() == []
        with t.span("after") as after:
            assert after.parent is None      # every stack unwound

    def test_emit_takes_the_parent_it_is_given_and_infers_none(self):
        t = self._tracer()
        with t.span("beat", track="s") as beat:
            given = t.emit("phase", 1.0, 0.5, track="r", parent=beat.id, request=7)
            orphan = t.emit("late", 2.0, 0.1, track="r")
        by_id = {e["id"]: e for e in t.tail_chrome_events(10)}
        assert by_id[given]["parent"] == beat.id
        assert by_id[given]["args"] == {"request": 7}   # identity stays out of args
        assert by_id[orphan]["parent"] is None
        assert given != orphan != beat.id
        assert self._tracer(enabled=False).emit("off", 0.0, 1.0) is None

    def test_a_span_ended_out_of_order_leaves_the_stack_sound(self):
        t = self._tracer()
        a = t.span("a")
        b = t.span("b")
        a.end()                      # the outer one first
        with t.span("c") as c:
            assert c.parent == b.id  # b is still open, a is gone
        b.end()
        with t.span("d") as d:
            assert d.parent is None

    def test_ring_bound_is_read_once_per_enable(self, monkeypatch):
        """_cap() used to call get_flag on every append."""
        from paddle_tpu.base import flags
        from paddle_tpu.observability.tracing import SpanTracer

        reads = []
        real = flags.get_flag
        monkeypatch.setattr(flags, "get_flag",
                            lambda name: (reads.append(name), real(name))[1])
        t = SpanTracer(enabled=False)
        t.enable()
        for i in range(50):
            t.instant(f"e{i}")
        assert reads.count("telemetry_trace_max_events") == 1
        t.disable().enable()
        t.instant("again")
        assert reads.count("telemetry_trace_max_events") == 2

    def test_set_flags_toggles_the_global_tracer(self, fresh_tracer):
        """paddle.set_flags({'telemetry_trace': ...}) must actually flip
        recording at runtime (the flag is mirrored into the hot-path
        attribute via the on_flag_change hook)."""
        import paddle_tpu as paddle

        prev = bool(paddle.get_flags("telemetry_trace")["telemetry_trace"])
        try:
            paddle.set_flags({"telemetry_trace": True})
            assert fresh_tracer.enabled
            fresh_tracer.instant("on")
            paddle.set_flags({"telemetry_trace": False})
            assert not fresh_tracer.enabled
            fresh_tracer.instant("off")
            assert [e[1] for e in fresh_tracer._events] == ["on"]
        finally:
            paddle.set_flags({"telemetry_trace": prev})


# ------------------------------------------------------- instrumentation
class TestInstrumentation:
    def test_kernel_cache_compile_and_hit_events(self, fresh_tracer):
        fresh_tracer.enable()
        a = paddle.Tensor(np.full((3, 5), 2.0, np.float32),
                          stop_gradient=True)
        for _ in range(3):
            paddle.multiply(a, a)
        events = [(e[0], e[1], e[5]) for e in fresh_tracer._events
                  if e[1].startswith("kernel_cache.")]
        compiles = [e for e in events if e[1] == "kernel_cache.compile"]
        hits = [e for e in events if e[1] == "kernel_cache.hit"]
        assert len(compiles) == 1 and len(hits) == 2
        args = compiles[0][2]
        assert args["op"] == "multiply"
        assert args["signature"] == "float32[3,5],float32[3,5]"
        assert args["reason"] == "new_signature"

    def test_record_event_joins_unified_timeline(self, fresh_tracer):
        from paddle_tpu.profiler.profiler import RecordEvent

        fresh_tracer.enable()
        with RecordEvent("user_phase"):
            pass
        names = [e[1] for e in fresh_tracer._events]
        assert "user_phase" in names
        tracks = [e[2] for e in fresh_tracer._events if e[1] == "user_phase"]
        assert tracks == ["host"]

    def test_train_step_span_on_train_loop_track(self, fresh_tracer):
        from paddle_tpu.analysis.jaxpr_audit import record_demo_step

        fresh_tracer.enable()
        record_demo_step()
        spans = [e for e in fresh_tracer._events if e[1] == "train.step"]
        assert len(spans) == 2 and all(e[2] == "train_loop" for e in spans)
        builds = [e for e in fresh_tracer._events if e[1] == "jit.build"]
        assert len(builds) == 1  # two steps, one program build

    def test_d2h_fetch_is_batched_one_counter_tick_per_batch(self):
        """ROADMAP serving leftover: one device fetch per assembled batch
        instead of one per output leaf, proven by serving.d2h_fetches."""
        import jax.numpy as jnp

        from paddle_tpu.observability import registry
        from paddle_tpu.serving.scheduler import fetch_outputs

        counter = registry.counter("serving.d2h_fetches")
        before = counter.value()
        leaves = [jnp.ones((4, 2)), jnp.zeros((4,)),
                  jnp.full((4, 3), 7.0)]
        out = fetch_outputs(leaves)
        assert counter.value() - before == 1  # 3 leaves, ONE fetch round
        assert all(isinstance(a, np.ndarray) for a in out)
        np.testing.assert_array_equal(out[2], np.full((4, 3), 7.0))

    def test_memory_sampler_sets_gauges_and_throttles(self):
        from paddle_tpu.observability import registry
        from paddle_tpu.observability.memory import DeviceMemorySampler

        s = DeviceMemorySampler(sample_every=3)
        assert [s.maybe_sample() is not None for _ in range(6)] == [
            False, False, True, False, False, True]
        assert s.samples == 2
        assert s.last["live_bytes"] >= 0
        assert registry.gauge("memory.live_bytes").value() is not None
        # 0 disables entirely
        off = DeviceMemorySampler(sample_every=0)
        assert off.maybe_sample() is None and off.samples == 0

    def test_per_tenant_latency_breakdowns(self):
        """ROADMAP serving leftover: ServingStats.summary() carries
        per-tenant p50/p99, queue wait and request rate."""
        from paddle_tpu.profiler.pipeline import ServingStats

        st = ServingStats()
        t = 100.0
        for i in range(10):
            # tenant a: 5ms requests; tenant b: 20ms with 10ms queue wait
            st.record_request(t, t + 0.001, t + 0.002, t + 0.005, n=1,
                              tenant="a")
            st.record_request(t, t + 0.001, t + 0.011, t + 0.020, n=2,
                              tenant="b")
            t += 0.05
        st.record_rejected(tenant="b")
        s = st.summary(slo_ms=50.0)
        assert set(s["tenants"]) == {"a", "b"}
        a, b = s["tenants"]["a"], s["tenants"]["b"]
        assert a["requests"] == 10 and a["samples"] == 10
        assert b["requests"] == 10 and b["samples"] == 20
        assert a["p50_ms"] == pytest.approx(5.0, abs=0.5)
        assert b["p50_ms"] == pytest.approx(20.0, abs=0.5)
        assert b["queue_wait_p50_ms"] == pytest.approx(10.0, abs=0.5)
        assert b["rejected"] == 1 and a["rejected"] == 0
        assert a["requests_per_sec"] == pytest.approx(
            s["requests_per_sec"] / 2, rel=0.1)
        # untagged recording still works (back-compat path)
        ServingStats().record_request(0.0, 0.0, 0.0, 0.001)


# ------------------------------------------------------------ OB6xx lint
class TestTelemetryLint:
    def test_ob600_unclosed_span_at_export(self):
        from paddle_tpu.analysis.telemetry_check import audit_telemetry
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.observability.tracing import SpanTracer

        t = SpanTracer(enabled=True, max_events=16)
        reg = MetricsRegistry()
        leak = t.span("leaky.region", track="dispatch")
        findings = audit_telemetry(t, reg)
        assert [f.code for f in findings] == ["OB600"]
        assert "leaky.region" in findings[0].message
        leak.end()
        assert audit_telemetry(t, reg) == []

    def test_ob600_audits_the_supplied_tracer_not_the_global(self):
        """A tracer whose ONLY content is a leaked open span is falsy via
        __len__ — the audit must still inspect IT, not silently fall back
        to the global tracer."""
        from paddle_tpu.analysis.telemetry_check import audit_telemetry
        from paddle_tpu.observability.tracing import SpanTracer

        t = SpanTracer(enabled=True, max_events=16)
        t.span("only.open.span", track="x")   # zero CLOSED events
        assert len(t) == 0
        findings = audit_telemetry(t)         # registry defaults to global
        assert [f.code for f in findings] == ["OB600"]
        assert "only.open.span" in findings[0].message

    def test_ob601_duplicate_metric_registration(self):
        from paddle_tpu.analysis.telemetry_check import audit_telemetry
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.observability.tracing import SpanTracer

        reg = MetricsRegistry()
        reg.counter("serving.depth")
        reg.gauge("serving.depth")
        findings = audit_telemetry(SpanTracer(enabled=False), reg)
        assert [f.code for f in findings] == ["OB601"]
        assert "serving.depth" in findings[0].message

    def test_ob602_device_sync_inside_sampler(self):
        from paddle_tpu.analysis.telemetry_check import check_source

        src = (
            "import numpy as np\n"
            "def sample_memory(arrs):\n"
            "    total = 0\n"
            "    for a in arrs:\n"
            "        a.block_until_ready()\n"
            "        total += np.asarray(a).nbytes\n"
            "    return total\n")
        codes = [f.code for f in check_source(src, "seeded.py")]
        assert codes == ["OB602", "OB602"]

    def test_ob602_scoped_to_samplers_and_noqa(self):
        from paddle_tpu.analysis.telemetry_check import check_source

        # a non-sampler function may sync (that's TS1xx territory)
        clean = "def fetch(a):\n    return a.numpy()\n"
        assert check_source(clean, "x.py") == []
        # noqa suppression shares the trace-safety grammar
        suppressed = ("def maybe_sample(a):\n"
                      "    return a.item()  # noqa: OB602 — test fixture\n")
        assert check_source(suppressed, "x.py") == []

    def test_observability_tree_is_ob602_clean(self):
        import os

        from paddle_tpu.analysis.telemetry_check import check_paths

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        findings = check_paths(
            [os.path.join(repo, "paddle_tpu", "observability")])
        assert [str(f) for f in findings] == []

    def test_demo_telemetry_session_audits_clean(self):
        from paddle_tpu.analysis.telemetry_check import (
            audit_telemetry, record_demo_telemetry)

        tracer, registry = record_demo_telemetry()
        assert [str(f) for f in audit_telemetry(tracer, registry)] == []
        assert len(tracer) >= 4  # spans on every runtime track actually landed
        assert registry.counter("demo.requests").value(tenant="a") == 3


# -------------------------------------------------------- CLI + helpers
def test_capture_logs_helper_captures_nonpropagating_logger():
    from helpers import capture_logs
    from paddle_tpu.base.log import get_logger

    logger = get_logger()
    prev = logger.level
    with capture_logs() as buf:
        logger.info("telemetry helper smoke %d", 42)
    assert "telemetry helper smoke 42" in buf.getvalue()
    assert logger.level == prev  # level restored


def test_telemetry_cli_dumps_snapshot_and_trace(tmp_path, capsys):
    """`python -m tools.telemetry` (in-process): demo step + demo engine,
    one snapshot JSON + one Perfetto-loadable trace, exit 0, and the
    ISSUE 7 acceptance shape — dispatch, train-loop AND serving spans on
    correlated tracks of a SINGLE timeline."""
    import tools.telemetry as telemetry_cli

    rc = telemetry_cli.main(["--out", str(tmp_path), "--json"])
    out = capsys.readouterr().out
    assert rc == 0, out
    summary = json.loads(out)
    assert summary["telemetry_findings"] == []
    assert summary["compiles_after_warmup"] == 0

    snap = json.load(open(summary["snapshot_path"]))
    assert {"dispatch.kernel_cache", "pipeline", "serving",
            "jit.compile"} <= set(snap["metrics"])

    trace = json.load(open(summary["trace_path"]))
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e["ph"] == "M"}
    assert "train_loop" in tracks
    assert "dispatch" in tracks
    assert any(t.startswith("serving.") for t in tracks)
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert "train.step" in names
    assert "serving.request" in names and "serving.batch" in names
    # every X event carries ts+dur and a tid that maps to a named track
    tids = {e["tid"] for e in trace["traceEvents"] if e["ph"] == "M"}
    for e in trace["traceEvents"]:
        if e["ph"] == "X":
            assert e["tid"] in tids and "dur" in e


def test_lint_telemetry_family_green(capsys):
    import tools.lint as lint_cli

    rc = lint_cli.main(["--json", "--analyzer", "telemetry"])
    out = capsys.readouterr().out
    assert rc == 0, out
    payload = json.loads(out)
    assert payload["analyzers"] == ["telemetry"]
    assert "telemetry" in payload["timings_s"]


def test_lint_timings_rehomed_into_registry():
    """run_analyzers publishes per-family wall-time as a labeled gauge —
    the lint silo joins the snapshot schema."""
    from paddle_tpu.observability import registry
    from tools.lint import run_analyzers

    _, _, timings = run_analyzers(("telemetry",))
    g = registry.gauge("lint.family_seconds")
    assert g.value(family="telemetry") == timings["telemetry"]


# -------------------------------------------------------- program regions
TRAIN_REGIONS = ("embed", "ln", "attn/qkv", "attn/core", "attn/out", "mlp",
                 "lm_head", "loss")


def _op_names(lowered):
    import re

    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


class TestProgramRegions:
    """The training scope vocabulary (PERF.md lists it as a contract): every
    region names some forward AND some backward operation of the lowered
    step — the tape re-enters the forward op's scope around its pullback —
    and the optimizer's update has its own."""

    @pytest.fixture(scope="class")
    def step_names(self):
        from paddle_tpu.jit.api import TrainStep
        from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
        from paddle_tpu.models.gpt import gpt_tiny

        paddle.seed(0)
        model = GPTForCausalLM(gpt_tiny())
        criterion = GPTPretrainingCriterion(model.config)
        optimizer = paddle.optimizer.AdamW(learning_rate=1e-3,
                                           parameters=model.parameters())
        step = TrainStep(model=model, optimizer=optimizer,
                         loss_fn=lambda ids: criterion(model(ids), ids))
        ids = paddle.Tensor(
            np.random.RandomState(0).randint(0, 512, (2, 16)).astype(np.int64),
            stop_gradient=True)
        for _ in range(2):              # discovery, then the one program
            step(ids)
        assert step.fallback_reason is None
        compiled = step._compiled
        entry = compiled.last_entry
        if entry.get("guarded"):
            entry = entry["entries"][entry["last"]]
        args, kwargs = compiled._last_call
        return _op_names(entry["jitted"].lower(
            [c._value for c in entry["cells"]], args, kwargs))

    @pytest.mark.parametrize("region", TRAIN_REGIONS)
    def test_region_names_forward_and_backward_operations(self, step_names,
                                                          region):
        inside = [n for n in step_names
                  if f"/{region}/" in f"/{n}" or f"({region})" in n]
        assert any("transpose(" not in n for n in inside), region
        assert any("transpose(jvp(" in n for n in inside), region

    def test_optimizer_region_and_nearly_nothing_unscoped(self, step_names):
        ops = [n for n in step_names if n.startswith("jit(pure)/")]
        assert any(n.startswith("jit(pure)/optimizer/") for n in ops)
        bare = [n for n in ops if n.count("/") == 1 or
                n.startswith(("jit(pure)/transpose(jvp())", "jit(pure)/jvp()"))]
        # the LR cell's read and the tied embedding's second gradient
        assert len(bare) <= 2, bare

    def test_flash_path_names_layout_core_and_the_three_kernels(self):
        """The Pallas path (interpret mode here; compiled for the chip in
        tests/test_chip_compile.py) names its transposes and its kernels."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import flash_attention as fa

        q = jnp.ones((1, 128, 2, 64), jnp.float32)

        def loss(q, k, v):
            return fa.flash_attention_value(q, k, v, causal=True, scale=0.125,
                                            interpret=True).sum()

        names = _op_names(jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, q))
        for want in ("jvp(attn/layout)", "transpose(jvp(attn/layout))",
                     "jvp(attn/core)", "transpose(jvp(attn/core))"):
            assert any(want in n for n in names), want
        for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            assert f"{kernel}/pallas_call" in names, kernel

    def test_the_vocabulary_lives_in_one_module(self):
        """`base/regions.py` is what the program and the benchmark's readers
        both import; the names themselves are pinned here."""
        from paddle_tpu.base import regions

        assert set(regions.TRAINING) == set(TRAIN_REGIONS) | {
            "attn/layout", "optimizer"}
        assert regions.ROOTS == ("prefill", "decode", "draft", "verify")
        assert regions.KERNELS == ("flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv", "retn_step", "paged_attn",
                                   "latent_paged_attn", "gqa_paged_attn",
                                   "gqa_chunk_attn")
        assert {"attn/latent_proj", "attn/expand", "moe/route", "moe/experts",
                "moe/shared"} <= set(regions.LATENT_MOE)
        assert {"retn/gate", "retn/chunk", "retn/state", "norm",
                "rope"} <= set(regions.RETENTION)
        with regions.region(regions.MLP):
            from paddle_tpu.core.autograd import _active_scope

            assert _active_scope() == "mlp"

    def test_grad_node_records_the_scope_it_was_made_in(self):
        import jax

        x = paddle.Tensor(np.ones((2, 2), np.float32), stop_gradient=False)
        with jax.named_scope("outer"), jax.named_scope("attn/qkv"):
            y = paddle.multiply(x, x)
        z = paddle.multiply(y, y)
        assert y._grad_node.scope == "outer/attn/qkv"
        assert z._grad_node.scope is None
        paddle.sum(z).backward()        # re-entering a scope changes no value
        np.testing.assert_allclose(x.grad.numpy(), 4.0 * np.ones((2, 2)))
