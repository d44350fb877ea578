"""The join of program calls to device executions (`benchmark/call_device.py`)
and the four readers built on the call's spans, on a synthetic timeline: a
scheduler that dispatches call N and then reads call N-1, and a device that
runs what it is handed in order."""
import pytest

from benchmark import call_device, run, trace_reduce as tr

OP = "%fusion.1 = bf16[8] fusion(%x), kind=kLoop"
DECODE, PREFILL, CARRY = "jit__decode_fn", "jit__prefill_fn", "jit__carry_fn"
TRACK = "serving.scheduler"


@pytest.fixture()
def ring():
    from paddle_tpu.observability.tracing import tracer

    tracer.reset()
    was = tracer.enabled
    tracer.enable()
    _forget()
    yield tracer
    tracer.enabled = was
    tracer.reset()
    _forget()


def _forget():
    call_device._memo.clear()
    call_device._dropped.clear()


class Timeline:
    """Beats as `DecodeScheduler._run` records them and the device beside
    them. A beat: admit 0.09 ms from 0.01 on, build 0.19, the dispatch 1.0 from 0.3 on (the
    step's span opens 0.01 before it; of which the
    carry 0.2 where lanes are carried, the sampling arguments 0.3), commit
    0.1, the read of the call before (until the device is done with it),
    absorb 0.1; 0.05 ms between beats. All in milliseconds here."""

    def __init__(self, ring, start=0.0, with_calls=True):
        self.ring, self.t, self.with_calls = ring, start, with_calls
        self.beat = self.seq = 0
        self.flight = None          # (seq, kind, rung, beat, dispatch id, t_dispatch, carried, done)
        self.device_free = start
        self.modules, self.ops = [], []

    def _emit(self, name, t0, t1, **args):
        return self.ring.emit(name, t0 / 1e3, (t1 - t0) / 1e3, track=TRACK, **args)

    def foreign(self, name="jit_somebody_else", ms=0.4):
        """An execution nobody's call explains, next on the device."""
        s = max(self.device_free, self.t)
        self.modules.append((s / 1e3, (s + ms) / 1e3, name))
        self.device_free = s + ms

    def step(self, kind=None, device_ms=2.0, rung=4, carried=0, carry_ms=0.05):
        """One beat: dispatch a call of `kind` (None: nothing to dispatch),
        then read what was in flight."""
        self.beat += 1
        b0 = self.t
        d0 = t = b0 + 0.3                  # admit 0.1, build 0.2
        sent = None
        if kind is not None:
            self.seq += 1
            s = max(self.device_free, d0 + 0.7)
            if carried:
                self.modules.append((s / 1e3, (s + carry_ms) / 1e3, CARRY))
                self.ops.append((s / 1e3, (s + carry_ms) / 1e3, tr.category(OP)))
                s += carry_ms
            name = DECODE if kind == "decode" else PREFILL
            self.modules.append((s / 1e3, (s + device_ms) / 1e3, name))
            self.ops.append((s / 1e3, (s + device_ms) / 1e3, tr.category(OP)))
            self.device_free = s + device_ms
            sent = dict(seq=self.seq, kind=kind, rung=rung, beat=self.beat, t0=d0,
                        executions=2 if carried else 1, program=name,
                        done=self.device_free)
            t = d0 + 1.1                   # the dispatch 1.0, the commit 0.1
        prev, self.flight = self.flight, sent
        r0 = t
        if prev is not None:
            t = max(t, prev["done"]) + 0.02
        end = t + 0.1                      # absorb
        beat = self._emit("serving.beat", b0, end, beat=self.beat,
                          kind=kind or (prev or {}).get("kind", "idle"))
        self._emit("serving.admit", b0 + 0.01, b0 + 0.1, parent=beat)
        self._emit("serving.build", b0 + 0.1, b0 + 0.29, parent=beat)
        step = self._emit("serving.decode", b0 + 0.29, t, parent=beat,
                          kind=kind or (prev or {}).get("kind"))
        if kind is not None:
            sent["parent"] = disp = self._emit("serving.dispatch", d0, d0 + 1.0,
                                               parent=step, program=kind)
            self._emit("serving.dispatch.sample_args", d0 + 0.05, d0 + 0.35, parent=disp)
            if carried:
                self._emit("serving.dispatch.carry", d0 + 0.4, d0 + 0.6, parent=disp)
        if prev is not None:
            self._emit("serving.read", r0, t, parent=step, program=prev["kind"],
                       of_beat=prev["beat"])
            if self.with_calls:
                self.ring.emit("serving.call", prev["t0"] / 1e3, (t - prev["t0"]) / 1e3,
                               track="serving.calls", parent=prev["parent"], seq=prev["seq"],
                               beat=prev["beat"], read_beat=self.beat, kind=prev["kind"],
                               rung=prev["rung"], lanes=3, overlapped=kind is not None,
                               program=prev["program"], executions=prev["executions"],
                               enqueue_ms=1.0, read_wait_ms=t - r0)
        self._emit("serving.absorb", t, end, parent=beat)
        self.t = end + 0.05

    def trace(self, t0_ms, t1_ms):
        return tr.Trace(devices=[tr.DeviceTrace(ops=sorted(self.ops), modules=sorted(self.modules))],
                        t0=t0_ms / 1e3, t1=t1_ms / 1e3, clock_shift_s=0.0)


def _reader(name):
    return run.load_module("layers", name)


def test_a_carry_in_front_of_a_decode_call_is_the_calls_own(ring, capsys):
    line = Timeline(ring)
    line.step("prefill", device_ms=5.0, rung=(1, 8))
    line.step("decode", carried=1)
    line.step("decode", carried=3, rung=8, device_ms=3.0)
    line.step("decode", carried=3, rung=8, device_ms=3.0)
    line.step()                                   # the flush
    trace = line.trace(-1.0, 100.0)
    joined = call_device.join(trace)
    assert (joined.seen, joined.matched, joined.foreign) == (4, 4, 0)
    assert [[m[2] for m in c.runs] for c in joined.calls] == [
        [PREFILL], [CARRY, DECODE], [CARRY, DECODE], [CARRY, DECODE]]
    assert joined.matched_s == pytest.approx(joined.busy_s) == pytest.approx(13.15e-3)
    assert "4 of 4 in the window" in capsys.readouterr().out
    # a decode step is its program's execution and the carry's in front
    assert _reader("decode_device_ms").read(trace, [], {}) == pytest.approx(3.05)
    out = capsys.readouterr().out
    assert "the carry in front of 3 of them, median 0.0500 ms" in out
    assert "8 2 x 3.050 ms" in out and "4 1 x 2.050 ms" in out
    assert _reader("prefill_device_share").read(trace, [], {}) == pytest.approx(
        100.0 * 5.0 / 13.15)
    assert "a prefill call on the device: 1 x median 5.000 ms" in capsys.readouterr().out


def test_dispatch_ms_reads_the_decode_dispatches_and_says_where_their_time_lies(ring, capsys):
    line = Timeline(ring)
    line.step("prefill")
    for _ in range(3):
        line.step("decode", carried=2)
    line.step()
    trace = line.trace(-1.0, 100.0)
    assert _reader("dispatch_ms").read(trace, [], {}) == pytest.approx(1.0)
    out = capsys.readouterr().out
    assert ("a decode dispatch, medians: carry 0.200 ms (in 3 of 3), "
            "sample_args 0.300 ms (in 3 of 3), self 0.500 ms") in out
    assert "decode 3 x median 1.000 ms" in out and "prefill 1 x median 1.000 ms" in out
    assert "reads: 4 x median wait" in out
    # a window with no decode dispatch in it, and one with no span at all
    assert _reader("dispatch_ms").read(line.trace(0.0, 1.5), [], {}) is None
    assert _reader("dispatch_ms").read(line.trace(500.0, 600.0), [], {}) is None


def test_a_call_that_straddles_the_windows_edge_counts_for_seconds_only(ring):
    line = Timeline(ring)
    for _ in range(6):
        line.step("decode", device_ms=2.0)
    line.step()
    # the window opens inside the second call's execution, with the third
    # already dispatched, and closes inside the fifth's, the sixth dispatched
    t0, t1 = 1e3 * line.modules[1][0] + 1.5, 1e3 * line.modules[4][0] + 1.5
    trace = line.trace(t0, t1)
    joined = call_device.join(trace)
    assert (joined.seen, joined.matched) == (1, 1)
    assert [c.seq for c in joined.calls if c.whole] == [4]
    # the edges' calls are tied too, and count for seconds, cut to the window
    assert [c.seq for c in joined.calls] == [2, 3, 4, 5, 6]
    assert joined.matched_s == pytest.approx(joined.busy_s) == pytest.approx(6.0e-3)
    assert _reader("decode_device_ms").read(trace, [], {}) == pytest.approx(2.0)
    assert _reader("prefill_device_share").read(trace, [], {}) == 0.0


def test_an_execution_at_the_edge_whose_call_has_no_span_is_left_out_of_the_share(ring, capsys):
    """The capture opens while a long chunk runs whose call was read in a beat
    that began untraced: no span, so its seconds are nobody's. The share is
    over the stretch the tied calls cover, not over a window that holds it."""
    line = Timeline(ring, with_calls=False)
    line.step("prefill", device_ms=50.0)
    line.step("decode")                  # reads the chunk's call: no span
    line.with_calls = True
    line.step("prefill", device_ms=4.0)
    for _ in range(3):
        line.step("decode")
    line.step()
    trace = line.trace(1e3 * line.modules[0][0] + 20.0, 200.0)
    joined = call_device.join(trace)
    # the decode call of that beat went out before the window opened: tied, not whole
    assert (joined.seen, joined.matched, len(joined.calls)) == (4, 4, 5)
    assert joined.covered[0] == pytest.approx(line.modules[1][0])
    assert joined.matched_s == pytest.approx(joined.busy_s) == pytest.approx(12.0e-3)
    assert joined.covers
    assert "(0.030 s at its edges belong to calls without a span)" in capsys.readouterr().out
    assert _reader("prefill_device_share").read(trace, [], {}) == pytest.approx(100.0 * 4 / 12)


def test_a_share_needs_the_tied_seconds_within_two_percent_of_the_busy_seconds(ring, capsys):
    line = Timeline(ring)
    line.step("prefill")
    line.step("decode")
    line.foreign(ms=1.0)                 # somebody else's program, a ninth of the busy time
    line.ops.append((*line.modules[-1][:2], tr.category(OP)))
    for _ in range(2):
        line.step("decode")
    line.step()
    trace = line.trace(-1.0, 100.0)
    joined = call_device.join(trace)
    assert (joined.seen, joined.matched, joined.foreign) == (4, 4, 1)
    assert joined.usable and not joined.covers
    assert (joined.matched_s, joined.busy_s) == (pytest.approx(8e-3), pytest.approx(9e-3))
    assert "no share over them is reported" in capsys.readouterr().out
    assert _reader("prefill_device_share").read(trace, [], {}) is None
    assert _reader("decode_device_ms").read(trace, [], {}) == pytest.approx(2.0)


def test_a_foreign_execution_between_two_calls_is_passed_over(ring, capsys):
    line = Timeline(ring)
    line.step("decode")
    line.foreign()
    line.step("decode", carried=2)
    line.foreign(ms=0.1)
    line.step("prefill")
    line.step()
    trace = line.trace(-1.0, 100.0)
    joined = call_device.join(trace)
    assert (joined.seen, joined.matched, joined.foreign) == (3, 3, 2)
    assert [[m[2] for m in c.runs] for c in joined.calls] == [
        [DECODE], [CARRY, DECODE], [PREFILL]]
    # an execution of a program the calls know, where the next call's own should
    # come: that call stays unmatched, the walk goes on, the readers say nothing
    call_device._memo.clear()
    broken = tr.Trace(devices=[tr.DeviceTrace(
        ops=trace.devices[0].ops,
        modules=[m if i != 2 else (m[0], m[1], PREFILL)
                 for i, m in enumerate(trace.devices[0].modules)])],
        t0=trace.t0, t1=trace.t1, clock_shift_s=0.0)
    joined = call_device.join(broken)
    assert joined.seen == 3 and joined.matched < 3 and not joined.usable
    out = capsys.readouterr().out
    assert "seq 2 (decode): an execution of jit__prefill_fn comes first" in out
    assert "under 99% matched" in out
    assert _reader("decode_device_ms").read(broken, [], {}) is None
    assert _reader("prefill_device_share").read(broken, [], {}) is None


def test_a_ring_that_dropped_events_reads_nothing(ring, capsys):
    line = Timeline(ring)
    for _ in range(4):
        line.step("decode")
    line.step()
    trace = line.trace(-1.0, 100.0)
    assert _reader("decode_device_ms").read(trace, [], {}) == pytest.approx(2.0)
    ring._dropped = 7
    _forget()
    for name in ("dispatch_ms", "decode_device_ms", "prefill_device_share",
                 "idle_dispatch_share"):
        assert _reader(name).read(trace, [], {}) is None, name
    assert "dropped 7 events" in capsys.readouterr().out


def test_a_program_without_the_calls_span_reads_what_its_beats_give(ring, capsys):
    """The parent commit: beats and dispatches, no `serving.call`, no children
    of the dispatch."""
    line = Timeline(ring, with_calls=False)
    for _ in range(4):
        line.step("decode")
    line.step()
    trace = line.trace(-1.0, 100.0)
    assert call_device.join(trace) is None
    assert "emits no serving.call span" in capsys.readouterr().out
    assert _reader("decode_device_ms").read(trace, [], {}) is None
    assert _reader("prefill_device_share").read(trace, [], {}) is None
    assert _reader("dispatch_ms").read(trace, [], {}) == pytest.approx(1.0)
    assert _reader("idle_dispatch_share").read(trace, [], {}) is not None
    ring.reset()
    _forget()
    for name in ("dispatch_ms", "decode_device_ms", "prefill_device_share",
                 "idle_dispatch_share"):
        assert _reader(name).read(trace, [], {}) is None, name


def test_idle_time_goes_to_the_innermost_phase_the_host_was_in(ring, capsys):
    line = Timeline(ring)
    for _ in range(3):
        line.step("decode", device_ms=0.5, carried=1, carry_ms=0.05)
    line.step()
    trace = line.trace(0.0, line.t - 0.05)
    share = _reader("idle_dispatch_share").read(trace, [], {})
    out = capsys.readouterr().out
    idle = tr.idle_gaps(trace, [])["unattributed"]
    assert idle == pytest.approx(trace.window_s - 3 * 0.55e-3)
    assert "100.0% under a named phase" in out
    for phase in ("admit", "build", "dispatch ", "dispatch.sample_args", "dispatch.carry",
                  "read", "absorb", "decode", "between beats"):
        assert f" {phase}" in out, phase
    # a beat's dispatch is idle until the carry starts on the device at 0.7 ms of
    # it, but for the first beat's tail of the call before: three dispatches of
    # 1.0 ms, busy from 0.7 on, the first with nothing before it
    assert share == pytest.approx(100.0 * (3 * 0.7e-3) / idle)
