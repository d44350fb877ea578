"""The two helpers behind the per-layer metrics of PR 27 and each reader
built on them: `scopes` on the recorded v5e capture and on captures encoded
by hand, `program_spans` and the span readers on spans put into the program's
tracer by hand."""
import json
import os

import pytest

from benchmark import harness, program_spans, run, scopes, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny.xplane.pb")


# ---------------------------------------------- a capture encoded by hand
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def encode_capture(ops, with_tf_op=True, intern=False):
    """An XSpace with one `/device:TPU:0` plane whose `XLA Ops` line holds
    `ops`: (start_us, dur_us, hlo text, tf_op). `intern` stores the path as a
    reference to a stat's metadata, as the profiler does for repeated strings."""
    stat_names = {1: "tf_op", 2: "flops"}
    metadata, events = {}, b""
    for start_us, dur_us, text, tf_op in ops:
        key = (text, tf_op)
        if key not in metadata:
            stats = _field(5, _field(1, 2) + _field(3, 7))          # flops = 7
            if with_tf_op and tf_op is not None:
                if intern:
                    ref = max(stat_names) + 1
                    stat_names[ref] = tf_op
                    stats += _field(5, _field(1, 1) + _field(7, ref))
                else:
                    stats += _field(5, _field(1, 1) + _field(5, tf_op))
            metadata[key] = (len(metadata) + 1, stats)
        mid = metadata[key][0]
        events += _field(4, _field(1, mid) + _field(2, int(start_us * 1e6))
                         + _field(3, int(dur_us * 1e6)))
    plane = _field(2, "/device:TPU:0")
    plane += _field(3, _field(2, "XLA Modules"))
    plane += _field(3, _field(2, "XLA Ops") + _field(3, 0) + events)
    for (text, _), (mid, stats) in metadata.items():
        plane += _field(4, _field(1, mid) + _field(2, _field(1, mid) + _field(2, text) + stats))
    for sid, name in stat_names.items():
        plane += _field(5, _field(1, sid) + _field(2, _field(1, sid) + _field(2, name)))
    host = _field(2, "/host:CPU") + _field(3, _field(2, "python3"))
    return _field(1, host) + _field(1, plane)


class FakeTrace:
    """What the readers use of trace_reduce.Trace, on the capture's own clock."""

    def __init__(self, ops, t0, t1):
        self.devices = [tr.DeviceTrace(ops=[(s / 1e6, (s + d) / 1e6, tr.category(text))
                                            for s, d, text, _ in ops])]
        self.t0, self.t1, self.clock_shift_s = t0, t1, 0.0
        self.window_s = t1 - t0


OPS = [  # microseconds: a decode step's regions, twice
    (100.0, 40.0, "%copy.1 = bf16[8] copy(%x)", "jit(_decode_fn)/decode/attn/kv_gather/gather:"),
    (140.0, 10.0, "%fusion.2 = bf16[8] fusion(%y), kind=kLoop", "jit(_decode_fn)/decode/attn/kv_write/scatter:"),
    (150.0, 5.0, "%sort.3 = f32[8] sort(%z)", "jit(_decode_fn)/decode/sample/vmap()/while/body/sort:"),
    (155.0, 5.0, "%fusion.4 = bf16[8] fusion(%w), kind=kOutput", "jit(_decode_fn)/decode/mlp/dot_general:"),
    (200.0, 40.0, "%copy.1 = bf16[8] copy(%x)", "jit(_decode_fn)/decode/attn/kv_gather/gather:"),
    (240.0, 20.0, "%copy.9 = bf16[8] copy(%q)", None),
]


@pytest.fixture()
def capture(tmp_path):
    def write(**how):
        path = tmp_path / f"c{len(os.listdir(tmp_path))}.xplane.pb"
        path.write_bytes(encode_capture(OPS, **how))
        return str(path)
    return write


# ------------------------------------------------------------------ scopes
def test_region_of_opens_wrappers_drops_jit_frames_and_the_primitive():
    cases = {
        "jit(pure)/transpose(jvp(attn/qkv))/dot_general:": "attn/qkv",
        "jit(pure)/attn/out/transpose(jvp())/dot_general": "attn/out",
        "jit(pure)/jvp(attn/core)/jit(_flash_fwd_hm)/flash_fwd/pallas_call:": "attn/core/flash_fwd",
        "jit(_decode_fn)/decode/sample/vmap()/while/body/sort": "decode/sample/while/body",
        "jit(pure)/embed/transpose(jvp(jit(_take)))/scatter-add": "embed",
        "jit(<lambda>)/dot_general:": scopes.UNSCOPED,
        "": scopes.UNSCOPED,
    }
    assert {k: scopes.region_of(k) for k in cases} == cases
    assert scopes.holds("decode/attn/kv_gather", "attn/kv_gather")
    assert scopes.holds("attn/core/flash_fwd", "flash_fwd")
    assert not scopes.holds("attn/core/flash_fwd_x", "flash_fwd")
    assert not scopes.holds("decode/attn/kv_gather", "attn/kv")


def test_recorded_capture_gives_each_operation_its_tf_op_and_the_same_times():
    from jax.profiler import ProfileData

    (ops,) = scopes.device_ops(TINY)
    assert len(ops) == 9
    fusions = [op for op in ops if op[3].startswith("%fusion")]
    assert [op[2] for op in fusions] == ["jit(<lambda>)/dot_general:"] * 3
    assert {op[2] for op in ops if not op[3].startswith("%fusion")} == {None}
    plane = next(p for p in ProfileData.from_file(TINY).planes
                 if p.name.startswith(tr.DEVICE_PLANE))
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    for (start, dur, _, text), e in zip(ops, line.events):
        assert text == e.name
        assert start == pytest.approx(e.start_ns, abs=1.0)       # it rounds to whole ns
        assert dur == pytest.approx(e.duration_ns, abs=1.0)


def test_recorded_capture_reduces_to_one_unscoped_region_that_is_the_busy_time():
    with open(os.path.join(HERE, "tiny.xplane.json")) as f:
        meta = json.load(f)
    trace = tr.load(TINY, meta["t_sync"], meta["t0"], meta["t1"])
    regions = scopes.region_seconds(trace, path=TINY)
    assert set(regions) == {scopes.UNSCOPED}       # the program named nothing
    assert sum(regions.values()) == pytest.approx(tr.busy_seconds(trace), rel=2e-3)


@pytest.mark.parametrize("intern", [False, True])
def test_regions_sum_to_the_operations_seconds(capture, intern):
    trace = FakeTrace(OPS, 0.0, 1.0)
    regions = scopes.region_seconds(trace, path=capture(intern=intern))
    assert regions == pytest.approx({
        "decode/attn/kv_gather": 80e-6, "decode/attn/kv_write": 10e-6,
        "decode/sample/while/body": 5e-6, "decode/mlp": 5e-6, scopes.UNSCOPED: 20e-6})
    assert sum(regions.values()) == pytest.approx(tr.busy_seconds(trace))
    assert scopes.seconds_in(regions, "attn/kv_gather") == pytest.approx(80e-6)
    assert scopes.seconds_in(regions, "sample", "mlp") == pytest.approx(10e-6)
    # the window clips: only the second gather's first half and nothing after
    late = scopes.region_seconds(trace, 200e-6, 220e-6, path=capture(intern=intern))
    assert late == pytest.approx({"decode/attn/kv_gather": 20e-6})


def test_a_capture_without_tf_op_gives_none_never_zero(capture, tmp_path):
    trace = FakeTrace(OPS, 0.0, 1.0)
    assert scopes.region_seconds(trace, path=capture(with_tf_op=False)) is None
    assert scopes.region_seconds(trace, path=str(tmp_path / "nowhere.pb")) is None


# ------------------------------------------------- readers on the capture
@pytest.fixture()
def reader_on(capture, monkeypatch):
    def read(name, facts=None, **how):
        monkeypatch.setattr(scopes, "capture_path", lambda: capture(**how))
        trace = FakeTrace(OPS, 0.0, 1.0)
        return run.load_module("layers", name).read(trace, [], facts or {})
    return read


def test_share_readers_read_their_region_over_busy(reader_on):
    assert reader_on("kv_gather_share") == pytest.approx(100 * 80 / 120)
    assert reader_on("sample_share") == pytest.approx(100 * 5 / 120)
    # a program that names no such region: the metric is left out, not 0
    assert reader_on("attn_layout_share") is None
    assert reader_on("optimizer_share") is None
    assert reader_on("kv_gather_share", with_tf_op=False) is None
    # what no region names: the one copy the compiler made on its own
    assert reader_on("unscoped_share") == pytest.approx(100 * 20 / 120)
    assert reader_on("unscoped_share", with_tf_op=False) is None


def test_readers_name_regions_by_the_programs_vocabulary(reader_on, monkeypatch):
    from paddle_tpu.base import regions

    assert scopes.term("ATTN_KV_GATHER") == regions.ATTN_KV_GATHER == "attn/kv_gather"
    assert scopes.term("NO_SUCH_REGION") is None
    # a program without the vocabulary (a commit before PR 27): nothing is read
    monkeypatch.setattr(scopes, "term", lambda name: None)
    for reader in ("kv_gather_share", "sample_share", "unscoped_share"):
        assert reader_on(reader) is None


def test_unscoped_operations_are_listed_by_instruction(capture, monkeypatch):
    monkeypatch.setattr(scopes, "capture_path", lambda: capture())
    rows = scopes.unscoped_ops(FakeTrace(OPS, 0.0, 1.0))
    assert [(round(sec * 1e6), count, cat) for sec, count, cat, _ in rows] == [(20, 1, "copy")]
    assert rows[0][3].startswith("%copy.9 = ")


def test_kernel_readers_average_over_layers_and_whole_steps(tmp_path, monkeypatch):
    ops = []
    for step in range(3):                       # three steps of two layers
        base = 1000.0 * step
        for layer in range(2):
            at = base + 100.0 * layer
            ops += [(at, 30.0, "%flash_fwd.1 = custom-call()",
                     "jit(pure)/jvp(attn/core)/jit(_flash_fwd_hm)/flash_fwd/pallas_call:"),
                    (at + 40, 20.0, "%flash_bwd_dq.1 = custom-call()",
                     "jit(pure)/transpose(jvp(attn/core))/jit(_flash_bwd_hm)/flash_bwd_dq/pallas_call:"),
                    (at + 60, 25.0, "%flash_bwd_dkv.1 = custom-call()",
                     "jit(pure)/transpose(jvp(attn/core))/jit(_flash_bwd_hm)/flash_bwd_dkv/pallas_call:")]
    path = tmp_path / "train.xplane.pb"
    path.write_bytes(encode_capture(ops))
    monkeypatch.setattr(scopes, "capture_path", lambda: str(path))
    trace = FakeTrace(ops, 0.0, 2500e-6)        # the third step is cut
    trace.devices[0].modules = [(1000e-6 * s, 1000e-6 * s + 300e-6, "jit_pure") for s in range(3)]
    facts = {"layers": 2}
    assert run.load_module("layers", "flash_fwd_ms").read(trace, [], facts) \
        == pytest.approx(0.030)
    assert run.load_module("layers", "flash_bwd_ms").read(trace, [], facts) \
        == pytest.approx(0.045)
    assert run.load_module("layers", "flash_fwd_ms").read(trace, [], {}) is None


# ------------------------------------------------------------ span readers
@pytest.fixture()
def ring():
    from paddle_tpu.observability.tracing import tracer

    tracer.reset()
    was = tracer.enabled
    tracer.enable()
    yield tracer
    tracer.enabled = was
    tracer.reset()


def _beat(tracer, t0, kind, requests, step_s, host_s=0.002):
    """One beat as the scheduler records it, `host_s` around a `step_s` step."""
    beat = tracer.emit("serving.beat", t0, step_s + host_s, track="serving.scheduler",
                       beat=int(t0 * 1e3), kind=kind)
    step = tracer.emit("serving.decode", t0 + host_s / 2, step_s, track="serving.scheduler",
                       parent=beat, kind=kind, lanes=len(requests), requests=list(requests))
    tracer.emit("serving.dispatch", t0 + host_s / 2, 0.001, track="serving.scheduler",
                parent=step, program=kind)
    tracer.emit("serving.read", t0 + host_s / 2 + 0.001, step_s - 0.001,
                track="serving.scheduler", parent=step, program=kind)
    return t0 + step_s + host_s


def test_program_spans_gives_identity_and_keeps_to_the_window(ring):
    a = ring.emit("serving.beat", 1.0, 0.5, track="serving.scheduler", kind="decode")
    ring.emit("serving.decode", 1.1, 0.3, track="serving.scheduler", parent=a, kind="decode")
    ring.emit("serving.beat", 9.0, 0.5, track="serving.scheduler", kind="decode")
    ring.ingest_device_trace_dir("/nowhere", 0.0)
    trace = FakeTrace([], 0.9, 1.2)
    got = program_spans.spans(trace)
    assert [(s[0], s[3], s[4]) for s in got] == [("serving.beat", a, None),
                                                 ("serving.decode", a + 1, a)]
    assert got[0][5] == {"kind": "decode"}
    assert program_spans.inside(trace, {"serving.beat", "serving.decode"}) == []
    assert len(program_spans.spans()) == 3
    assert program_spans.busy_inside([(0.0, 1.0), (2.0, 3.0), (5.0, 6.0)], 0.5, 5.5) \
        == pytest.approx(0.5 + 1.0 + 0.5)


def test_ttft_reads_submit_to_first_token_of_the_windows_requests(ring, capsys):
    for request, (submit, dispatch, first) in enumerate(
            [(0.10, 0.15, 0.20), (0.30, 0.31, 0.40), (0.50, 0.58, 0.80), (0.2, 0.3, 1.5)]):
        ring.emit("serving.request.queue", submit, dispatch - submit,
                  track="serving.requests", request=request)
        ring.emit("serving.request.prefill", dispatch, first - dispatch,
                  track="serving.requests", request=request)
    reader = run.load_module("layers", "ttft_p50_ms")
    # the first request's first token fell before the window, the last one's after
    assert reader.read(FakeTrace([], 0.25, 1.0), [], {}) == pytest.approx(1e3 * (0.10 + 0.30) / 2)
    assert "2 first tokens" in capsys.readouterr().out
    assert reader.read(FakeTrace([], 2.0, 3.0), [], {}) is None
    # a request shed before its first token is named beside the times it is missing from
    ring.emit("serving.request.failed", 0.6, 0.1, track="serving.requests", request=9,
              reason="kv_pages")
    reader.read(FakeTrace([], 0.25, 1.0), [], {})
    assert "failed in the window: {'kv_pages': 1}" in capsys.readouterr().out


def test_token_gap_reads_consecutive_steps_of_one_request(ring):
    t = 0.0
    t = _beat(ring, t, "prefill", [1], 0.010)
    t = _beat(ring, t, "decode", [1, 2], 0.080)
    t = _beat(ring, t, "prefill", [3], 0.010)          # 1 and 2 sit this one out
    t = _beat(ring, t, "decode", [1, 2, 3], 0.080)
    t = _beat(ring, t, "decode", [2, 3], 0.080)
    reader = run.load_module("layers", "token_gap_p95_ms")
    # request 1: 82 ms from its prefill to its first decode step, then 94 over
    # the prefill beat it sat out; request 2: 94, 82; request 3: 82, 82
    want = harness.percentile([82.0, 94.0, 94.0, 82.0, 82.0, 82.0], 95.0)
    assert reader.read(FakeTrace([], 0.0, 1.0), [], {}) == pytest.approx(want)
    assert reader.read(FakeTrace([], 5.0, 6.0), [], {}) is None


def test_sched_host_is_the_beat_less_the_devices_busy_time_inside_it(ring, capsys):
    t, ops = 0.0, []
    for kind, step in (("prefill", 0.010), ("decode", 0.080), ("decode", 0.080), ("decode", 0.080)):
        host = 0.004 if kind == "decode" else 0.006
        ops.append(((t + host / 2) * 1e6, step * 1e6, "%copy.1 = bf16[8] copy(%x)", None))
        t = _beat(ring, t, kind, [1], step, host_s=host)
    reader = run.load_module("layers", "sched_host_ms")
    assert reader.read(FakeTrace(ops, 0.0, 1.0), [], {}) == pytest.approx(4.0)
    out = capsys.readouterr().out
    assert "their children cover" in out
    assert "decode 3 x median 4.00 ms" in out and "prefill 1 x median 6.00 ms" in out
    # where a decode beat's time lies: the call returns in 1 ms, the read waits out the step
    assert "a decode beat, medians: decode 80.000 ms, dispatch 1.000 ms, read 79.000 ms" in out
    assert reader.read(FakeTrace(ops, 5.0, 6.0), [], {}) is None
