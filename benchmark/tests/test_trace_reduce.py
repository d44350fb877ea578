"""The reduction from a capture to numbers: on a recorded v5e capture
(tiny.xplane.pb: three runs of one small jitted program, with the host's
spans and clock readings beside it in tiny.xplane.json) and on hand-made
intervals."""
import json
import os

import pytest

from benchmark import harness, run, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "tiny.xplane.json")) as f:
        meta = json.load(f)
    trace = tr.load(os.path.join(HERE, "tiny.xplane.pb"),
                    meta["t_sync"], meta["t0"], meta["t1"])
    return trace, [(n, a, b, {}) for n, a, b in meta["spans"]]


def test_recorded_capture_has_one_chip_three_runs_nine_ops(tiny):
    trace, _ = tiny
    assert len(trace.devices) == 1
    assert [m[2] for m in trace.devices[0].modules] == ["jit__lambda"] * 3
    assert len(trace.devices[0].ops) == 9
    assert {c for _, _, c in trace.devices[0].ops} == {"copy-start", "copy-done", "fusion.kOutput"}


def test_device_times_land_inside_the_host_spans_that_caused_them(tiny):
    # causality, which the raw capture breaks by a millisecond: each run starts
    # after its dispatch began and ends before the read that waited for it ended
    trace, spans = tiny
    dispatches = [s for s in spans if s[0] == "step_dispatch"]
    reads = [s for s in spans if s[0] == "loss_read"]
    for (m0, m1, _), d, r in zip(trace.devices[0].modules, dispatches, reads):
        assert d[1] <= m0 and m1 <= r[2]


def test_busy_idle_and_gaps_add_up_to_the_window(tiny):
    trace, spans = tiny
    busy = tr.busy_seconds(trace)
    assert busy == pytest.approx(sum(tr.op_seconds(trace).values()), rel=1e-6)
    assert 5e-6 < busy < 1e-5            # three runs of about 2.9 us, less the overlaps
    gaps = tr.idle_gaps(trace, spans)
    assert sum(gaps.values()) == pytest.approx(trace.window_s - busy, rel=1e-9)
    assert set(gaps) == {"step_dispatch", "loss_read", "unattributed"}
    # the device sits idle longest while the host sleeps between the steps
    assert gaps["unattributed"] > gaps["loss_read"] > gaps["step_dispatch"]


def test_whole_modules_leaves_out_what_the_window_cuts(tiny):
    trace, _ = tiny
    d = trace.devices[0]
    assert len(tr.whole_modules(d, trace.t0, trace.t1)) == 3
    cut = (d.modules[0][0] + d.modules[0][1]) / 2
    assert len(tr.whole_modules(d, cut, trace.t1)) == 2
    assert tr.whole_modules(d, trace.t0, trace.t1, name="no_such_program") == []


def _reader(name):
    return run.load_module("layers", name)


def test_readers_on_the_recorded_capture(tiny):
    trace, spans = tiny
    assert _reader("step_device_ms").read(trace, spans, {}) == pytest.approx(2.95e-3, rel=0.05)
    idle = _reader("idle_share").read(trace, spans, {})
    assert 99.9 < idle < 100.0
    # nothing to read: no Pallas kernel ran, no engine spans, no facts
    for name in ("flash_roofline", "flash_share", "decode_step_ms", "prefill_share",
                 "batch_occupancy", "queue_wait_p95_ms", "pool_peak_share",
                 "discovery_s", "cache_misses", "mfu"):
        assert _reader(name).read(trace, spans, {}) is None


@pytest.mark.parametrize("text, want", [
    ("%fusion.7 = bf16[4,8]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[4,8]{1,0:T(8,128)(2,1)} %p), "
     "kind=kOutput, calls=%fused_computation.7", "fusion.kOutput"),
    ("%iota_compare_fusion.2 = pred[1024]{0:T(1024)(128)(4,1)S(1)} fusion(), kind=kLoop, "
     "calls=%fused_computation.4072", "fusion.kLoop"),
    ('%custom-call.220 = bf16[1024,1024]{1,0} custom-call(bf16[256,1024]{1,0} %a), '
     'custom_call_target="ConcatBitcast"', "custom-call.ConcatBitcast"),
    ('%custom-call.3 = (bf16[4,1024,16,64]{3,2,1,0}, f32[4,16,1024]{2,1,0}) custom-call('
     'bf16[4,1024,16,64]{3,2,1,0} %q), custom_call_target="tpu_custom_call"',
     "custom-call.tpu_custom_call"),
    ("%slice-start.844 = ((bf16[1024,1024]{1,0:T(8,128)(2,1)}), bf16[256,1024]{1,0}, s32[]{:S(2)}) "
     "async-start(bf16[1024,1024]{1,0} %cell_vals_90_.1), calls=%async_computation.844",
     "async-start"),
    ("%copy-done = bf16[512,512]{1,0:T(8,128)(2,1)S(1)} copy-done((bf16[512,512]{1,0}, "
     "bf16[512,512]{1,0}, u32[]{:S(2)}) %copy-start)", "copy-done"),
])
def test_category_of_an_hlo_instruction(text, want):
    assert tr.category(text) == want


def test_union_and_clip_by_hand():
    ivs = [(0.0, 1.0, "a"), (0.5, 2.0, "b"), (3.0, 4.0, "c"), (3.2, 3.4, "d")]
    assert tr.union(ivs) == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.total(tr.union(ivs)) == pytest.approx(3.0)
    assert tr.clip(ivs, 0.75, 3.1) == [(0.75, 1.0, "a"), (0.75, 2.0, "b"), (3.0, 3.1, "c")]
    assert tr.union([]) == [] and tr.total([]) == 0.0


def test_busy_is_averaged_over_chips_and_gaps_follow_the_innermost_span():
    a = tr.DeviceTrace(ops=[(1.0, 2.0, "x"), (1.5, 3.0, "y")])     # busy 2 of 4
    b = tr.DeviceTrace(ops=[(0.0, 1.0, "x")])                       # busy 1 of 4
    trace = tr.Trace(devices=[a, b], t0=0.0, t1=4.0, clock_shift_s=0.0)
    assert tr.busy_seconds(trace) == pytest.approx(1.5)
    assert tr.op_seconds(trace) == pytest.approx({"x": 1.0, "y": 0.75})
    spans = [("outer", 0.0, 3.5, {}), ("inner", 0.25, 0.75, {})]
    # first chip idle in [0, 1) and [3, 4): inner 0.5, outer 0.5 + 0.5, none 0.5
    assert tr.idle_gaps(trace, spans) == pytest.approx(
        {"inner": 0.5, "outer": 1.0, "unattributed": 0.5})


def test_flatten_gives_each_moment_to_the_span_that_started_last():
    spans = [("a", 0.0, 10.0), ("b", 2.0, 4.0), ("c", 3.0, 6.0)]
    assert tr.flatten(spans) == [(0.0, 2.0, "a"), (2.0, 3.0, "b"), (3.0, 6.0, "c"),
                                 (6.0, 10.0, "a")]


@pytest.mark.parametrize("values, q, want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5), ([10], 95, 10.0),
    (list(range(101)), 95, 95.0), ([0, 10], 95, 9.5), ([5, 1, 3], 0, 1.0), ([5, 1, 3], 100, 5.0),
])
def test_percentile_by_hand(values, q, want):
    assert harness.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        harness.percentile([], 95)
