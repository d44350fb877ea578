"""Telemetry egress + anomaly flight recorder tests (ISSUE 8).

Covers: Prometheus text exposition (line validity, empty-ring quantile
omission, label escaping, process metadata), the TelemetryServer
endpoints (/metrics /healthz /snapshot.json /trace.json) standalone and
engine-owned, concurrent scraping while a train step and a serving batch
run, the anomaly detectors and the flight recorder's bounded/rate-limited
bundles (slow step through the REAL TrainStep path, serving SLO breach,
clean-run silence), device-trace fusion (real jax.profiler capture on
CPU + synthetic ingest + degrade paths), and the OB603/OB604 audits with
seeded negatives.
"""
import glob
import gzip
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle

# ------------------------------------------------------------------ helpers
# one Prometheus text-exposition sample line: name{labels} value
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*"(,[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*")*\})?'
    r" \S+$")


def assert_valid_prometheus(text):
    """Every line is a comment or a parseable sample; no NaN ever."""
    lines = [ln for ln in text.splitlines() if ln]
    assert lines, "empty exposition"
    for ln in lines:
        if ln.startswith("#"):
            continue
        assert _PROM_LINE.match(ln), f"bad exposition line: {ln!r}"
        value = ln.rsplit(" ", 1)[1]
        v = float(value)  # raises on garbage
        assert v == v, f"NaN sample leaked: {ln!r}"
    return lines


@pytest.fixture
def fresh_tracer():
    from paddle_tpu.observability import tracer

    tracer.reset()
    was = tracer.enabled
    yield tracer
    tracer.enabled = was
    tracer.reset()


@pytest.fixture
def armed_monitor(tmp_path):
    """The GLOBAL monitor armed with a per-test dump dir and fresh
    detector state (rings, cooldown stamps), restored afterwards — the
    instrumented sites (TrainStep, engine, queue) read this object."""
    from paddle_tpu.observability.anomaly import (
        MemoryWatermarkDetector, RejectBurstDetector, ServingSLODetector,
        StepTimeRegressionDetector, monitor)

    dump_dir = str(tmp_path / "anomaly_dump")
    prev_flags = paddle.get_flags(["telemetry_anomaly", "telemetry_dump_dir",
                                   "anomaly_dump_cooldown_s"])
    prev_bundles = list(monitor.bundles)
    prev_flags.update(paddle.get_flags(["anomaly_step_mad"]))
    # pin the step gate high (same discipline as bench._telemetry_bench):
    # on a loaded CI box a 20ms sleep pad overshoots to ~31ms, past the
    # default 8-MAD gate (~29ms) — the injected anomalies here are 10x+,
    # so 50 MAD keeps them triggering while scheduler jitter never does
    paddle.set_flags({"telemetry_anomaly": True,
                      "telemetry_dump_dir": dump_dir,
                      "anomaly_dump_cooldown_s": 60.0,
                      "anomaly_step_mad": 50.0})
    monitor._last_dump.clear()
    for det in (StepTimeRegressionDetector(), ServingSLODetector(),
                RejectBurstDetector(), MemoryWatermarkDetector()):
        monitor.register(det)  # fresh rings + observed counters
    yield monitor, dump_dir
    paddle.set_flags(prev_flags)
    monitor._last_dump.clear()
    monitor.bundles[:] = prev_bundles


def _bundles(dump_dir):
    return sorted(glob.glob(os.path.join(dump_dir, "anomaly_*.json")))


def _demo_train_step():
    from paddle_tpu.jit.api import TrainStep

    paddle.seed(0)
    model = paddle.nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    crit = paddle.nn.MSELoss()
    step = TrainStep(model=model, optimizer=opt,
                     loss_fn=lambda x, y: crit(model(x), y))
    x = paddle.Tensor(np.ones((2, 8), np.float32), stop_gradient=True)
    y = paddle.Tensor(np.zeros((2, 4), np.float32), stop_gradient=True)
    return step, x, y


def _demo_engine(tmp_path, **kwargs):
    import paddle_tpu.nn as nn
    from paddle_tpu.profiler.pipeline import ServingStats
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    prefix = str(tmp_path / "served")
    paddle.jit.save(net, prefix,
                    input_spec=[paddle.static.InputSpec([None, 8],
                                                        "float32")])
    kwargs.setdefault("stats", ServingStats())
    return ServingEngine(prefix, buckets=[1, 2, 4], **kwargs)


# --------------------------------------------------------------- exposition
class TestPrometheusText:
    def _registry(self):
        from paddle_tpu.observability.metrics import MetricsRegistry

        return MetricsRegistry()

    def test_counter_gauge_histogram_render(self):
        from paddle_tpu.observability.export import prometheus_text

        reg = self._registry()
        reg.counter("req.count").inc(3, tenant="a")
        reg.counter("req.count").inc(1, tenant="b")
        reg.gauge("queue.depth").set(7)
        h = reg.histogram("latency.ms")
        for v in (1.0, 2.0, 5.0):
            h.observe(v)
        text = prometheus_text(reg.snapshot())
        lines = assert_valid_prometheus(text)
        assert "# TYPE paddle_req_count_total counter" in lines
        assert 'paddle_req_count_total{tenant="a"} 3' in lines
        assert "paddle_queue_depth 7" in lines
        assert "# TYPE paddle_latency_ms summary" in lines
        assert 'paddle_latency_ms{quantile="0.5"} 2.0' in lines
        assert "paddle_latency_ms_sum 8.0" in lines
        assert "paddle_latency_ms_count 3" in lines

    def test_process_metadata_lines(self):
        from paddle_tpu.observability.export import prometheus_text

        text = prometheus_text(self._registry().snapshot())
        lines = assert_valid_prometheus(text)
        info = [ln for ln in lines if ln.startswith("paddle_process_info")]
        assert len(info) == 1
        assert f'pid="{os.getpid()}"' in info[0]
        import jax

        assert f'jax_version="{jax.__version__}"' in info[0]
        assert 'backend="cpu"' in info[0]
        assert any(ln.startswith("paddle_process_uptime_seconds ")
                   for ln in lines)

    def test_label_escaping(self):
        from paddle_tpu.observability.export import prometheus_text

        reg = self._registry()
        reg.counter("esc").inc(tenant='we"ird\\te\nnant')
        text = prometheus_text(reg.snapshot())
        assert_valid_prometheus(text)
        assert r'tenant="we\"ird\\te\nnant"' in text

    def test_collected_namespace_flattens_numeric_leaves_only(self):
        from paddle_tpu.observability.export import prometheus_text

        reg = self._registry()
        reg.register_collector("silo", lambda: {
            "requests": 4, "p50_ms": None, "note": "cpu_fallback",
            "nested": {"ok": True, "ratio": 0.5}})
        text = prometheus_text(reg.snapshot())
        lines = assert_valid_prometheus(text)
        assert "paddle_silo_requests 4" in lines
        assert "paddle_silo_nested_ratio 0.5" in lines
        assert "paddle_silo_nested_ok 1" in lines  # bools export as 0/1
        # None and str leaves carry NO sample — never a NaN placeholder
        assert not any("p50_ms" in ln or "note" in ln for ln in lines)


class TestEmptyRingContract:
    """ONE contract for a percentile with no data: ``None`` in summaries,
    the line OMITTED from Prometheus exposition — never NaN. Histogram
    and ServingStats agree (the satellite fix)."""

    def test_histogram_summary_none_when_never_observed(self):
        from paddle_tpu.observability.metrics import Histogram

        h = Histogram("h")
        assert h.summary() is None
        h.observe(1.0, tenant="a")
        assert h.summary(tenant="b") is None        # other cell untouched
        assert h.summary(tenant="a")["p50"] == 1.0

    def test_empty_histogram_emits_no_lines(self):
        from paddle_tpu.observability.export import prometheus_text
        from paddle_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.histogram("never.observed")
        text = prometheus_text(reg.snapshot())
        assert "never_observed" not in text
        assert "NaN" not in text and "None" not in text

    def test_nan_observation_never_reaches_exposition(self):
        from paddle_tpu.observability.export import prometheus_text
        from paddle_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.histogram("odd").observe(float("nan"))
        text = prometheus_text(reg.snapshot())
        lines = assert_valid_prometheus(text)      # float-parses every sample
        # the poisoned quantiles/sum are OMITTED; the count still reports
        assert "paddle_odd_count 1" in lines
        assert not any(ln.startswith("paddle_odd{") or
                       ln.startswith("paddle_odd_sum") for ln in lines)

    def test_serving_stats_shares_the_contract(self):
        from paddle_tpu.observability.export import prometheus_text
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.profiler.pipeline import ServingStats

        stats = ServingStats()
        s = stats.summary(slo_ms=50.0)
        assert s["p50_ms"] is None and s["p99_ms"] is None
        assert s["queue_wait_p50_ms"] is None
        assert s["requests"] == 0
        reg = MetricsRegistry()
        reg.register_collector("serving", lambda: stats.summary(slo_ms=50.0))
        lines = assert_valid_prometheus(prometheus_text(reg.snapshot()))
        assert "paddle_serving_requests 0" in lines
        assert not any("p50_ms" in ln for ln in lines)  # omitted, not NaN
        # ... and once data exists the quantile leaves appear
        t0 = time.perf_counter()
        stats.record_request(t0, t0 + 0.001, t0 + 0.002, t0 + 0.004,
                             tenant="a")
        lines = assert_valid_prometheus(prometheus_text(reg.snapshot()))
        assert any(ln.startswith("paddle_serving_p50_ms ") for ln in lines)


# ------------------------------------------------------------------- server
class TestTelemetryServer:
    def test_endpoints_roundtrip(self, fresh_tracer):
        from paddle_tpu.observability.export import TelemetryServer

        fresh_tracer.enable()
        with fresh_tracer.span("demo.span", track="host"):
            pass
        with TelemetryServer(port=0) as srv:
            assert srv.running and srv.port > 0
            status, body = srv.scrape("/metrics")
            assert status == 200
            assert_valid_prometheus(body)
            status, body = srv.scrape("/snapshot.json")
            assert status == 200 and "metrics" in json.loads(body)
            status, body = srv.scrape("/trace.json")
            assert status == 200
            names = [e["name"] for e in json.loads(body)["traceEvents"]]
            assert "demo.span" in names
            status, body = srv.scrape("/healthz")
            assert status == 200 and json.loads(body)["ok"] is True
            status, body = srv.scrape("/nope")
            assert status == 404
        assert not srv.running

    def test_health_fn_merges_and_gates_status(self):
        from paddle_tpu.observability.export import TelemetryServer

        with TelemetryServer(port=0, health_fn=lambda: {
                "ok": False, "worker_alive": False}) as srv:
            status, body = srv.scrape("/healthz")
            assert status == 503
            payload = json.loads(body)
            assert payload["ok"] is False and payload["worker_alive"] is False

    def test_health_fn_exception_degrades_to_503(self):
        from paddle_tpu.observability.export import TelemetryServer

        def broken():
            raise RuntimeError("dead engine")

        with TelemetryServer(port=0, health_fn=broken) as srv:
            status, body = srv.scrape("/healthz")
            assert status == 503
            assert "dead engine" in json.loads(body)["health_error"]

    def test_active_servers_tracks_lifecycle(self):
        from paddle_tpu.observability.export import (TelemetryServer,
                                                     active_servers)

        srv = TelemetryServer(port=0)
        assert srv not in active_servers()
        srv.start()
        try:
            assert srv in active_servers()
        finally:
            srv.stop()
        assert srv not in active_servers()


class TestEngineOwnedExporter:
    def test_engine_serves_health_and_stops_with_engine(self, tmp_path):
        engine = _demo_engine(tmp_path, serve_telemetry_port=0)
        engine.warmup()
        try:
            url = engine.telemetry_url
            assert url is not None
            srv = engine._telemetry_server
            engine.run("a", np.ones((2, 8), np.float32))
            status, body = srv.scrape("/healthz")
            payload = json.loads(body)
            assert status == 200
            assert payload["worker_alive"] is True
            assert payload["compiles_after_warmup"] == 0
            assert payload["queue_depth_requests"] == 0
            status, body = srv.scrape("/metrics")
            assert_valid_prometheus(body)
        finally:
            engine.shutdown(drain=True)
        assert engine.telemetry_url is None
        assert not srv.running

    def test_no_exporter_by_default(self, tmp_path):
        assert int(paddle.get_flags(["telemetry_port"])["telemetry_port"]) == 0
        engine = _demo_engine(tmp_path)
        engine.warmup()
        try:
            assert engine.telemetry_url is None
        finally:
            engine.shutdown(drain=True)

    def test_flag_port_collision_degrades_not_fails(self, tmp_path):
        """Telemetry must never take down serving: with FLAGS_telemetry_port
        set, the SECOND engine in the process loses the bind race and must
        warm up exporter-less with a warning — only an explicit
        serve_telemetry_port= collision is a hard error."""
        from helpers import capture_logs
        from paddle_tpu.observability.export import TelemetryServer

        squatter = TelemetryServer(port=0).start()
        prev = paddle.get_flags(["telemetry_port"])
        paddle.set_flags({"telemetry_port": squatter.port})
        try:
            engine = _demo_engine(tmp_path)
            with capture_logs() as buf:
                engine.warmup()
            try:
                assert engine.telemetry_url is None
                assert "serving continues without egress" in buf.getvalue()
                engine.run("a", np.ones((2, 8), np.float32))  # still serves
            finally:
                engine.shutdown(drain=True)
            with pytest.raises(OSError):
                _demo_engine(tmp_path,
                             serve_telemetry_port=squatter.port).warmup()
        finally:
            paddle.set_flags(prev)
            squatter.stop()


class TestConcurrentExposition:
    def test_scrapes_race_train_and_serving_without_blocking(
            self, tmp_path, fresh_tracer):
        """The satellite contract: /metrics and /trace.json hammered from
        threads WHILE train steps and serving batches run — every scrape
        valid, no exceptions anywhere, and the scheduler keeps completing
        requests (export never blocks it)."""
        from paddle_tpu.observability.export import TelemetryServer

        fresh_tracer.enable()
        step, x, y = _demo_train_step()
        engine = _demo_engine(tmp_path).warmup()
        errors = []
        stop = threading.Event()

        def train_loop():
            try:
                while not stop.is_set():
                    step(x, y)
            except Exception as e:  # pragma: no cover - failure path
                errors.append(("train", e))

        def serve_loop():
            try:
                rs = np.random.RandomState(0)
                while not stop.is_set():
                    n = int(rs.randint(1, 5))
                    out = engine.run("t", rs.randn(n, 8).astype(np.float32),
                                     timeout=30.0)
                    assert len(out[0]) == n
            except Exception as e:  # pragma: no cover - failure path
                errors.append(("serve", e))

        scrapes = {"n": 0}

        def scrape_loop(srv):
            try:
                while not stop.is_set():
                    status, body = srv.scrape("/metrics")
                    assert status == 200
                    assert_valid_prometheus(body)
                    status, body = srv.scrape("/trace.json")
                    assert status == 200
                    json.loads(body)
                    scrapes["n"] += 1
            except Exception as e:  # pragma: no cover - failure path
                errors.append(("scrape", e))

        with TelemetryServer(port=0) as srv:
            threads = [threading.Thread(target=train_loop),
                       threading.Thread(target=serve_loop)]
            threads += [threading.Thread(target=scrape_loop, args=(srv,))
                        for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(1.2)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
        try:
            assert errors == []
            assert scrapes["n"] >= 3
            # the scheduler thread kept serving while being scraped
            assert engine.stats.summary()["requests"] >= 2
        finally:
            engine.shutdown(drain=True)


# ---------------------------------------------------------------- detectors
class TestDetectors:
    def test_step_time_median_mad_gate(self):
        from paddle_tpu.observability.anomaly import StepTimeRegressionDetector

        det = StepTimeRegressionDetector(mad_threshold=8.0)
        for _ in range(16):
            assert det.observe(0.010) is None
        # MAD floor = 5% of median -> gate = 10ms * 1.4; 13ms passes
        assert det.observe(0.013) is None
        verdict = det.observe(0.050)
        assert verdict["kind"] == "step_time"
        assert verdict["median_s"] == pytest.approx(0.010, abs=1e-3)
        assert 0.050 > verdict["gate_s"]
        assert det.triggered == 1

    def test_step_time_needs_history_and_flag(self):
        from paddle_tpu.observability.anomaly import StepTimeRegressionDetector

        det = StepTimeRegressionDetector(mad_threshold=8.0, min_history=8)
        for _ in range(7):
            det.observe(0.01)
        assert det.observe(10.0) is None      # history too short
        det2 = StepTimeRegressionDetector(mad_threshold=0.0)
        for _ in range(16):
            det2.observe(0.01)
        assert det2.observe(10.0) is None     # threshold <= 0: disabled

    def test_serving_slo_verdict_carries_queue_share(self):
        from paddle_tpu.observability.anomaly import ServingSLODetector

        det = ServingSLODetector(slo_ms=50.0)
        assert det.observe(0.020, 0.010, tenant="a") is None
        verdict = det.observe(0.080, 0.060, tenant="a")
        assert verdict["kind"] == "serving_slo"
        assert verdict["latency_ms"] == 80.0
        assert verdict["queue_wait_share"] == 0.75
        assert verdict["tenant"] == "a"

    def test_reject_burst_one_verdict_per_burst(self):
        from paddle_tpu.observability.anomaly import RejectBurstDetector

        det = RejectBurstDetector(burst=4)
        assert [det.observe() for _ in range(3)] == [None, None, None]
        verdict = det.observe()
        assert verdict["rejections"] == 4
        # the window cleared: the next rejection starts a NEW count
        assert det.observe() is None

    def test_memory_watermark_vs_budget(self):
        from paddle_tpu.observability.anomaly import MemoryWatermarkDetector

        det = MemoryWatermarkDetector(budget_bytes=1000)
        assert det.observe(None) is None
        assert det.observe({"live_bytes": 900, "devices": {}}) is None
        verdict = det.observe({"live_bytes": 500, "devices": {
            "cpu:0": {"peak_bytes_in_use": 2500}}})
        assert verdict["kind"] == "memory_watermark"
        assert verdict["peak_bytes"] == 2500
        assert verdict["over_budget_x"] == 2.5


# ---------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_slow_step_through_real_train_step_dumps_once(
            self, armed_monitor):
        """The acceptance path: a deliberately injected slow step (the
        compiled callable sleeps once) produces EXACTLY one rate-limited
        bundle with the span window and metrics snapshot; the clean steps
        around it write nothing."""
        from helpers import capture_logs

        monitor, dump_dir = armed_monitor
        step, x, y = _demo_train_step()
        compiled = step._compiled

        # pad every step to a fixed ~20ms so the raw dispatch jitter of a
        # loaded CI box (microsecond-scale steps swing 2-3x) stays far
        # inside the median+MAD gate; the REAL TrainStep close still
        # times and feeds the monitor
        def steady(*batch):
            time.sleep(0.02)
            return compiled(*batch)

        def slow(*batch):
            time.sleep(0.25)
            return compiled(*batch)

        step._compiled = steady
        for _ in range(12):
            step(x, y)
        assert _bundles(dump_dir) == []          # clean run: no bundle
        step._compiled = slow
        with capture_logs() as buf:
            step(x, y)                            # the injected slow step
        step._compiled = steady
        bundles = _bundles(dump_dir)
        assert len(bundles) == 1
        assert "anomaly flight recorder: step_time" in buf.getvalue()
        with open(bundles[0]) as f:
            bundle = json.load(f)
        assert bundle["kind"] == "step_time"
        assert bundle["verdict"]["step_s"] >= 0.25
        assert bundle["verdict"]["gate_s"] < bundle["verdict"]["step_s"]
        assert len(bundle["step_window_s"]) >= 12
        assert "metrics" in bundle and "spans" in bundle
        assert bundle["process"]["pid"] == os.getpid()
        # more steps, fast again: still exactly one bundle
        for _ in range(6):
            step(x, y)
        assert len(_bundles(dump_dir)) == 1

    def test_repeat_triggers_suppressed_inside_cooldown(self, armed_monitor):
        monitor, dump_dir = armed_monitor
        det = monitor.detectors["step_time"]
        for _ in range(16):
            det.observe(0.01)  # history only; feeds outside monitor.on_step
        monitor.on_step(5.0)
        monitor.on_step(5.0)   # same kind, inside the 60s cooldown
        assert len(_bundles(dump_dir)) == 1
        from paddle_tpu.observability.metrics import registry

        assert registry.counter("anomaly.suppressed").value(
            kind="step_time") >= 1
        assert registry.counter("anomaly.triggered").value(
            kind="step_time") >= 2

    def test_serving_slo_breach_dumps_once(self, armed_monitor, tmp_path):
        monitor, dump_dir = armed_monitor
        prev = paddle.get_flags(["serving_slo_ms"])
        paddle.set_flags({"serving_slo_ms": 0.001})  # everything breaches
        try:
            engine = _demo_engine(tmp_path).warmup()
            try:
                for n in (1, 2, 3):
                    engine.run("a", np.ones((n, 8), np.float32))
            finally:
                engine.shutdown(drain=True)
        finally:
            paddle.set_flags(prev)
        bundles = _bundles(dump_dir)
        assert len(bundles) == 1                 # rate-limited dedup
        with open(bundles[0]) as f:
            bundle = json.load(f)
        assert bundle["kind"] == "serving_slo"
        assert bundle["verdict"]["tenant"] == "a"
        assert bundle["verdict"]["latency_ms"] > 0.001

    def test_serving_clean_run_writes_nothing(self, armed_monitor, tmp_path):
        monitor, dump_dir = armed_monitor
        prev = paddle.get_flags(["serving_slo_ms"])
        paddle.set_flags({"serving_slo_ms": 60000.0})
        try:
            engine = _demo_engine(tmp_path).warmup()
            try:
                engine.run("a", np.ones((2, 8), np.float32))
            finally:
                engine.shutdown(drain=True)
        finally:
            paddle.set_flags(prev)
        assert _bundles(dump_dir) == []

    def test_train_loop_exception_dumps_postmortem(self, armed_monitor):
        """An uncaught exception escaping the fit loop (here: the input
        pipeline dying mid-epoch) leaves ONE post-mortem bundle behind."""
        from helpers import capture_logs
        from paddle_tpu.hapi import Model

        monitor, dump_dir = armed_monitor
        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        m = Model(net)
        m.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                       parameters=net.parameters()),
                  paddle.nn.MSELoss())

        def dying_loader():
            batch = (np.ones((2, 4), np.float32), np.zeros((2, 2),
                                                           np.float32))
            yield batch
            yield batch
            raise RuntimeError("input pipeline fell over")

        with capture_logs():
            with pytest.raises(RuntimeError, match="pipeline fell over"):
                m.fit(dying_loader(), epochs=1, verbose=0)
        bundles = _bundles(dump_dir)
        assert len(bundles) == 1
        with open(bundles[0]) as f:
            bundle = json.load(f)
        assert bundle["kind"] == "exception.train.fit"
        assert "input pipeline fell over" in bundle["verdict"]["exception"]

    def test_no_dump_dir_counts_but_never_writes(self, tmp_path):
        from helpers import capture_logs
        from paddle_tpu.observability.anomaly import AnomalyMonitor
        from paddle_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        mon = AnomalyMonitor(enabled=True, dump_dir="", cooldown_s=60,
                             registry=reg)
        det = mon.detectors["step_time"]
        for _ in range(16):
            det.observe(0.01)
        with capture_logs(level=10) as buf:
            assert mon.on_step(5.0) is None
        assert "counted, not dumped" in buf.getvalue()
        assert reg.counter("anomaly.triggered").value(kind="step_time") == 1
        assert mon.bundles == []

    def test_dump_dir_bounded_oldest_pruned(self, tmp_path):
        from paddle_tpu.observability.anomaly import AnomalyMonitor
        from paddle_tpu.observability.metrics import MetricsRegistry

        dump_dir = str(tmp_path / "dumps")
        mon = AnomalyMonitor(enabled=True, dump_dir=dump_dir, cooldown_s=0.0,
                             max_bundles=2, registry=MetricsRegistry())
        paths = []
        for i in range(4):  # distinct kinds dodge the per-kind cooldown
            p = mon.on_exception(f"worker{i}", ValueError(str(i)))
            paths.append(p)
            time.sleep(0.02)  # distinct mtimes for the prune ordering
        remaining = _bundles(dump_dir)
        assert len(remaining) == 2
        assert set(remaining) == set(paths[-2:])  # newest two survive

    def test_interrupt_is_not_an_anomaly(self, tmp_path):
        """Ctrl-C / SystemExit with the monitor armed must propagate with
        no snapshot/disk work and no bundle slot consumed."""
        from paddle_tpu.observability.anomaly import AnomalyMonitor
        from paddle_tpu.observability.metrics import MetricsRegistry

        dump_dir = str(tmp_path / "dumps")
        mon = AnomalyMonitor(enabled=True, dump_dir=dump_dir,
                             cooldown_s=0.0, registry=MetricsRegistry())
        for exc in (KeyboardInterrupt(), SystemExit(1), GeneratorExit()):
            assert mon.on_exception("train.fit", exc) is None
        assert _bundles(dump_dir) == []
        assert mon.on_exception("train.fit", ValueError("real")) is not None

    def test_counted_not_dumped_log_is_rate_limited(self):
        """Dir-unset mode leaves the dump cooldown unburned, so the info
        log must rate-limit itself — a sustained storm logs once per
        cooldown, while every trigger still ticks the counter."""
        from helpers import capture_logs
        from paddle_tpu.observability.anomaly import AnomalyMonitor
        from paddle_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        mon = AnomalyMonitor(enabled=True, dump_dir="", cooldown_s=60.0,
                             registry=reg)
        with capture_logs(level=10) as buf:
            for _ in range(5):
                mon.on_exception("worker", ValueError("storm"))
        assert buf.getvalue().count("counted, not dumped") == 1
        cells = reg.snapshot()["metrics"]["anomaly.triggered"]["values"]
        assert sum(c["value"] for c in cells) == 5

    def test_failed_write_still_burns_the_cooldown(self, tmp_path):
        """Persistent dump failure (ENOSPC, lost perms) must not repeat
        the expensive bundle build on every trigger: the write fails once,
        then the per-kind cooldown suppresses the storm. Only the
        dir-UNSET path leaves the cooldown unburned."""
        from helpers import capture_logs
        from paddle_tpu.observability.anomaly import AnomalyMonitor
        from paddle_tpu.observability.metrics import MetricsRegistry

        blocker = tmp_path / "not_a_dir"
        blocker.write_text("")  # makedirs under a FILE always fails
        mon = AnomalyMonitor(enabled=True,
                             dump_dir=str(blocker / "dumps"),
                             cooldown_s=60.0, registry=MetricsRegistry())
        with capture_logs() as buf:
            assert mon.on_exception("train.fit", ValueError("boom")) is None
            assert mon.on_exception("train.fit", ValueError("boom")) is None
        assert buf.getvalue().count("bundle write failed") == 1

    def test_restart_into_same_dump_dir_never_overwrites(self, tmp_path):
        """A persistent dump dir outlives the process: run 2's monitor
        restarts its sequence at 0, so its first bundle of a kind must not
        recreate (and truncate) run 1's path for that kind."""
        from paddle_tpu.observability.anomaly import AnomalyMonitor
        from paddle_tpu.observability.metrics import MetricsRegistry

        dump_dir = str(tmp_path / "dumps")
        paths, run_ids = [], set()
        for _ in range(2):  # two monitor instances = two process runs
            mon = AnomalyMonitor(enabled=True, dump_dir=dump_dir,
                                 cooldown_s=0.0, registry=MetricsRegistry())
            run_ids.add(mon._run_id)  # distinct even same-pid same-second
            paths.append(mon.on_exception("train.fit", ValueError("boom")))
        assert len(run_ids) == 2
        assert None not in paths and len(set(paths)) == 2
        assert len(_bundles(dump_dir)) == 2  # run 1's post-mortem survives

    def test_serving_worker_exception_feeds_recorder(
            self, armed_monitor, tmp_path):
        """The scheduler's fault wall feeds on_exception BEFORE failing
        the batch — the bundle is the post-mortem."""
        monitor, dump_dir = armed_monitor
        engine = _demo_engine(tmp_path).warmup()
        try:
            def boom(requests, bucket):
                raise RuntimeError("device fell over")

            engine._scheduler.execute = boom
            req = engine.submit("a", np.ones((1, 8), np.float32))
            with pytest.raises(RuntimeError, match="device fell over"):
                req.result(timeout=30.0)
        finally:
            engine.shutdown(drain=False)
        bundles = _bundles(dump_dir)
        assert len(bundles) == 1
        with open(bundles[0]) as f:
            assert json.load(f)["kind"] == "exception.serving.worker"

    def test_flag_hook_mirrors_monitor_enabled(self):
        from paddle_tpu.observability.anomaly import monitor

        prev = monitor.enabled
        prev_flag = paddle.get_flags(["telemetry_anomaly"])
        try:
            paddle.set_flags({"telemetry_anomaly": True})
            assert monitor.enabled is True
            paddle.set_flags({"telemetry_anomaly": False})
            assert monitor.enabled is False
        finally:
            paddle.set_flags(prev_flag)
            monitor.enabled = prev


# ------------------------------------------------------------ device fusion
def _write_fake_xla_trace(log_dir, events):
    run_dir = os.path.join(log_dir, "plugins", "profile", "run1")
    os.makedirs(run_dir)
    payload = {"traceEvents": events}
    with gzip.open(os.path.join(run_dir, "host.trace.json.gz"), "wt") as f:
        json.dump(payload, f)


class TestDeviceTraceFusion:
    def _fake_events(self):
        return [
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 10,
             "args": {"name": "TPU:0 XLA Ops"}},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 11,
             "args": {"name": "python"}},
            {"ph": "X", "name": "fusion.1", "pid": 1, "tid": 10,
             "ts": 5000.0, "dur": 10.0, "args": {"bytes": 64}},
            {"ph": "X", "name": "copy.2", "pid": 1, "tid": 10,
             "ts": 5020.0, "dur": 4.0},
            {"ph": "X", "name": "py_frame", "pid": 1, "tid": 11,
             "ts": 5000.0, "dur": 30.0},
            # capture_device's annotation: on the capture's clock it sits
            # 7 us after the earliest event
            {"ph": "X", "name": "paddle_tpu.sync", "pid": 1, "tid": 11,
             "ts": 5007.0, "dur": 1.0},
        ]

    def test_synthetic_ingest_clock_aligned_under_device_tracks(
            self, tmp_path, fresh_tracer):
        fresh_tracer.enable()
        with fresh_tracer.span("host.work", track="host"):
            pass
        _write_fake_xla_trace(str(tmp_path), self._fake_events())
        # the host read perf_counter = 1007 us inside the annotation
        n = fresh_tracer.ingest_device_trace_dir(str(tmp_path), 1007.0)
        assert n == 2                                # python lane dropped
        assert fresh_tracer.device_event_count() == 2
        trace = fresh_tracer.to_chrome_trace()
        tracks = {e["args"]["name"] for e in trace["traceEvents"]
                  if e["ph"] == "M"}
        assert "host" in tracks
        assert "device.TPU:0 XLA Ops" in tracks      # ONE fused export
        dev = [e for e in trace["traceEvents"]
               if e.get("cat", "").startswith("device.")]
        # aligned on the annotation, not on the earliest event: the event
        # 7 us before the annotation lands 7 us before the host's stamp
        assert min(e["ts"] for e in dev) == 1000.0
        assert {e["name"] for e in dev} == {"fusion.1", "copy.2"}
        gap = [e for e in dev if e["name"] == "copy.2"][0]
        assert gap["ts"] == 1020.0                   # relative offsets kept
        assert all("id" not in e for e in dev)       # no host identity

    def test_sync_annotation_later_in_capture_moves_every_event(
            self, tmp_path, fresh_tracer):
        """The profiler takes a while to start; the annotation is emitted
        after it has. Pinning the earliest event to a stamp taken before
        start_trace (the old alignment) put device lanes early by that
        long; the annotation's own timestamp does not."""
        events = self._fake_events()
        events[-1]["ts"] = 5300.0                    # 300 us after the first
        _write_fake_xla_trace(str(tmp_path), events)
        fresh_tracer.ingest_device_trace_dir(str(tmp_path), 2000.0)
        dev = {e["name"]: e["ts"]
               for e in fresh_tracer.to_chrome_trace()["traceEvents"]
               if e.get("cat", "").startswith("device.")}
        assert dev == {"fusion.1": 1700.0, "copy.2": 1720.0}

    def test_device_lanes_move_later_by_the_causality_bound(
            self, tmp_path, fresh_tracer):
        """The chip's clock lags the host's inside one capture: a run's
        completion callback (host) cannot start before its program's end
        (device), so the device's lanes move later by the least such gap
        over the runs both sides name; the host's lanes stay."""
        events = self._fake_events() + [
            {"ph": "M", "name": "thread_name", "pid": 2, "tid": 20,
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "name": "thread_name", "pid": 2, "tid": 21,
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 12,
             "args": {"name": "tpu-callbacks"}},
            {"ph": "X", "name": "jit_step(1)", "pid": 2, "tid": 20,
             "ts": 5100.0, "dur": 50.0, "args": {"run_id": 6}},
            {"ph": "X", "name": "jit_step(1)", "pid": 2, "tid": 20,
             "ts": 5200.0, "dur": 50.0, "args": {"run_id": "7"}},
            {"ph": "X", "name": "fusion.9", "pid": 2, "tid": 21,
             "ts": 5105.0, "dur": 40.0},
            # run 6's callback 1,100 us after its program's end as the
            # capture has it, run 7's 1,140: the device is 1,100 us early
            {"ph": "X", "name": "CompleteCallbacks", "pid": 1, "tid": 12,
             "ts": 6250.0, "dur": 5.0, "args": {"run_id": "6"}},
            {"ph": "X", "name": "CompleteCallbacks", "pid": 1, "tid": 12,
             "ts": 6390.0, "dur": 5.0, "args": {"run_id": 7}},
            # a callback of a run the device lane does not name: no bound
            {"ph": "X", "name": "CompleteCallbacks", "pid": 1, "tid": 12,
             "ts": 5000.0, "dur": 5.0, "args": {"run_id": 99}},
        ]
        _write_fake_xla_trace(str(tmp_path), events)
        fresh_tracer.ingest_device_trace_dir(str(tmp_path), 1007.0)
        dev = {(e["name"], e["ts"])
               for e in fresh_tracer.to_chrome_trace()["traceEvents"]
               if e.get("cat", "").startswith("device.")}
        shift, skew = 1007.0 - 5007.0, 1100.0
        assert ("jit_step(1)", 5100.0 + shift + skew) in dev
        assert ("jit_step(1)", 5200.0 + shift + skew) in dev
        assert ("fusion.9", 5105.0 + shift + skew) in dev
        # run 6's program now ends exactly where its callback starts
        assert ("CompleteCallbacks", 6250.0 + shift) in dev
        assert 5100.0 + 50.0 + skew == 6250.0
        # a process without an XLA Modules lane keeps the sync shift alone
        assert ("fusion.1", 1000.0) in dev and ("copy.2", 1020.0) in dev

    def test_capture_without_the_annotation_is_not_ingested(
            self, tmp_path, fresh_tracer):
        events = [e for e in self._fake_events()
                  if e["name"] != "paddle_tpu.sync"]
        _write_fake_xla_trace(str(tmp_path), events)
        import logging

        from paddle_tpu.base.log import get_logger

        said, handler = [], logging.Handler()
        handler.emit = lambda record: said.append(record.getMessage())
        get_logger().addHandler(handler)
        try:
            assert fresh_tracer.ingest_device_trace_dir(str(tmp_path), 0.0) == 0
        finally:
            get_logger().removeHandler(handler)
        assert fresh_tracer.device_event_count() == 0
        # not silently: the operator is told what was dropped, and why
        assert any("no 'paddle_tpu.sync' annotation: 3 event(s)" in m
                   for m in said), said

    def test_argsless_metadata_event_does_not_abort_ingest(
            self, tmp_path, fresh_tracer):
        """One malformed thread_name record without "args" must not cost
        the whole device timeline — the other lanes still fuse."""
        events = [{"ph": "M", "name": "thread_name", "pid": 1, "tid": 9}]
        events += self._fake_events()
        _write_fake_xla_trace(str(tmp_path), events)
        n = fresh_tracer.ingest_device_trace_dir(str(tmp_path), 1000.0)
        assert n == 2
        assert fresh_tracer.device_event_count() == 2

    def test_include_python_keeps_the_callstack_lane(self, tmp_path,
                                                     fresh_tracer):
        _write_fake_xla_trace(str(tmp_path), self._fake_events())
        n = fresh_tracer.ingest_device_trace_dir(str(tmp_path), 0.0,
                                                 include_python=True)
        assert n == 3                                # less the annotation

    def test_device_events_excluded_from_host_tail(self, tmp_path,
                                                   fresh_tracer):
        """The flight recorder's span window is the HOST tail; fused
        device events stay in the full export only."""
        fresh_tracer.enable()
        with fresh_tracer.span("host.only", track="host"):
            pass
        _write_fake_xla_trace(str(tmp_path), self._fake_events())
        fresh_tracer.ingest_device_trace_dir(str(tmp_path), 0.0)
        tail = fresh_tracer.tail_chrome_events(100)
        assert [e["name"] for e in tail] == ["host.only"]

    def test_device_ring_bounded_by_flag(self, tmp_path, fresh_tracer):
        events = [{"ph": "M", "name": "thread_name", "pid": 1, "tid": 10,
                   "args": {"name": "dev"}}]
        events += [{"ph": "X", "name": f"op.{i}", "pid": 1, "tid": 10,
                    "ts": 100.0 + i, "dur": 1.0} for i in range(6)]
        events.append({"ph": "X", "name": "paddle_tpu.sync", "pid": 1,
                       "tid": 10, "ts": 99.0, "dur": 1.0})
        _write_fake_xla_trace(str(tmp_path), events)
        prev = paddle.get_flags(["telemetry_device_trace_max_events"])
        paddle.set_flags({"telemetry_device_trace_max_events": 4})
        try:
            fresh_tracer.ingest_device_trace_dir(str(tmp_path), 0.0)
        finally:
            paddle.set_flags(prev)
        assert fresh_tracer.device_event_count() == 4
        trace = fresh_tracer.to_chrome_trace()
        assert trace["otherData"]["dropped_events"] == 2
        kept = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
        assert kept == {"op.2", "op.3", "op.4", "op.5"}  # newest kept

    def test_missing_or_empty_dir_degrades_to_zero(self, tmp_path,
                                                   fresh_tracer):
        assert fresh_tracer.ingest_device_trace_dir(
            str(tmp_path / "nowhere"), 0.0) == 0
        os.makedirs(str(tmp_path / "plugins" / "profile" / "r"))
        assert fresh_tracer.ingest_device_trace_dir(str(tmp_path), 0.0) == 0

    @pytest.mark.slow
    def test_capture_device_fuses_real_cpu_profile(self, fresh_tracer):
        """jax.profiler works on the CPU backend here: a real capture
        window lands device tracks in the same export as host spans. If
        the profiler is unavailable the capture degrades to a no-op —
        both outcomes are in-contract; an exception is not."""
        import jax.numpy as jnp

        fresh_tracer.enable()
        with fresh_tracer.span("host.around", track="host"):
            with fresh_tracer.capture_device():
                (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
        trace = fresh_tracer.to_chrome_trace()
        tracks = {e["args"]["name"] for e in trace["traceEvents"]
                  if e["ph"] == "M"}
        assert "host" in tracks
        if fresh_tracer.device_event_count():        # profiler was usable
            assert any(t.startswith("device.") for t in tracks)

    def test_nested_capture_degrades_not_raises(self, fresh_tracer):
        import jax.numpy as jnp

        fresh_tracer.enable()
        with fresh_tracer.capture_device():
            with fresh_tracer.capture_device():      # already active
                jnp.ones(4).block_until_ready()


# ------------------------------------------------------------- OB603/OB604
class TestTelemetryAuditCodes:
    def _clean_fixtures(self):
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.observability.tracing import SpanTracer

        return SpanTracer(enabled=False), MetricsRegistry()

    def test_ob603_dead_monitor_seeded(self):
        from paddle_tpu.analysis.telemetry_check import audit_telemetry
        from paddle_tpu.observability.anomaly import AnomalyMonitor

        t, r = self._clean_fixtures()
        mon = AnomalyMonitor(enabled=True)           # lit, never fed
        findings = audit_telemetry(t, r, monitor=mon, servers=[])
        assert [f.code for f in findings] == ["OB603"]
        assert "dead monitor" in str(findings[0])
        mon.on_step(0.01)                            # ONE feed clears it
        assert audit_telemetry(t, r, monitor=mon, servers=[]) == []

    def test_ob603_silent_when_disabled(self):
        from paddle_tpu.analysis.telemetry_check import audit_telemetry
        from paddle_tpu.observability.anomaly import AnomalyMonitor

        t, r = self._clean_fixtures()
        mon = AnomalyMonitor(enabled=False)
        assert audit_telemetry(t, r, monitor=mon, servers=[]) == []

    def test_ob604_unbounded_ring_behind_exporter_seeded(self):
        from paddle_tpu.analysis.telemetry_check import audit_telemetry
        from paddle_tpu.observability.anomaly import AnomalyMonitor
        from paddle_tpu.observability.export import TelemetryServer
        from paddle_tpu.observability.tracing import SpanTracer

        t, r = self._clean_fixtures()
        mon = AnomalyMonitor(enabled=False)
        unbounded = SpanTracer(enabled=True, max_events=0)
        srv = TelemetryServer(port=0, tracer=unbounded, registry=r)
        findings = audit_telemetry(t, r, monitor=mon, servers=[srv])
        assert [f.code for f in findings] == ["OB604"]
        assert "UNBOUNDED host span ring" in str(findings[0])
        # a bounded tracer behind the same exporter is clean
        srv.tracer = SpanTracer(enabled=True, max_events=128)
        assert audit_telemetry(t, r, monitor=mon, servers=[srv]) == []

    def test_ob604_unbounded_dump_dir_seeded(self, tmp_path):
        from paddle_tpu.analysis.telemetry_check import audit_telemetry
        from paddle_tpu.observability.anomaly import AnomalyMonitor

        t, r = self._clean_fixtures()
        mon = AnomalyMonitor(enabled=True, dump_dir=str(tmp_path),
                             max_bundles=0)
        mon.on_step(0.01)                            # fed: OB603 quiet
        findings = audit_telemetry(t, r, monitor=mon, servers=[])
        assert [f.code for f in findings] == ["OB604"]
        assert "max_bundles" in str(findings[0])

    def test_live_process_and_demo_monitor_audit_clean(self):
        from paddle_tpu.analysis.telemetry_check import (
            audit_telemetry, record_demo_monitor, record_demo_telemetry)

        t, r = record_demo_telemetry()
        mon = record_demo_monitor(t, r)
        assert mon.enabled and sum(
            d.observed for d in mon.detectors.values()) > 0
        assert [str(f) for f in audit_telemetry(t, r, monitor=mon)] == []


# ------------------------------------------------------------------ CLI
class TestTelemetryCLI:
    @pytest.mark.slow
    def test_serve_once_returns_prometheus_and_health(self, tmp_path):
        """The ISSUE 8 acceptance line: ``--serve --once`` answers with
        valid Prometheus text carrying kernel-cache, pipeline and serving
        series plus process metadata, and /healthz reflects the live
        engine's worker."""
        from tools.telemetry import run_serve

        summary = run_serve(port=0, once=True)
        assert summary["metrics_status"] == 200
        lines = assert_valid_prometheus(summary["metrics_body"])
        text = summary["metrics_body"]
        assert "paddle_dispatch_kernel_cache" in text     # kernel-cache silo
        assert "paddle_pipeline_" in text                 # pipeline silo
        assert "paddle_serving_requests" in text          # serving silo
        assert any(ln.startswith("paddle_process_info{") for ln in lines)
        assert summary["healthz_status"] == 200
        health = summary["healthz"]
        assert health["ok"] is True and health["worker_alive"] is True
        assert health["compiles_after_warmup"] == 0
        assert summary["trace_events"] > 0
        assert summary["telemetry_findings"] == []

    @pytest.mark.slow
    def test_serve_once_dump_on_anomaly_arms_recorder(self, tmp_path):
        from paddle_tpu.observability.anomaly import monitor
        from tools.telemetry import run_serve

        prev = paddle.get_flags(["telemetry_anomaly", "telemetry_dump_dir"])
        try:
            dump = str(tmp_path / "dumps")
            summary = run_serve(port=0, once=True, dump_dir=dump)
            assert summary["anomaly_armed"] is True
            assert os.path.isdir(dump)
            # the demo traffic is healthy: armed, but nothing dumped
            assert _bundles(dump) == []
        finally:
            paddle.set_flags(prev)
            monitor.enabled = bool(prev["telemetry_anomaly"])
