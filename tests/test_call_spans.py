"""A program call gets a span of its own (ISSUE 38): `serving.call`, from the
call's dispatch to its tokens on the host, emitted at the read under the
`serving.dispatch` span that sent it; always-on stamps on the call behind it;
and `ServingStats` fed a call's OWN time under its own kind. Held on the five
scheduler families' CPU stand-ins (slot K/V, paged K/V, state lanes, latent
pages, pages of two lifetimes)."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.observability.tracing import _NULL_SPAN, tracer
from paddle_tpu.profiler.pipeline import ServingStats

from test_lagged_beat import _engine as _family_engine, _settle

FAMILIES = ("slots", "paged", "state", "latent", "windowed")
PAGED = ("paged", "latent", "windowed")     # the schedulers that build sampling arguments

_WINDOWED = {}


def _engine(family, **kw):
    if family != "windowed":
        return _family_engine(family, **kw)
    if not _WINDOWED:
        from paddle_tpu.models import Cohere2MoEForCausalLM, cohere2_moe_tiny

        paddle.seed(5)
        model = Cohere2MoEForCausalLM(cohere2_moe_tiny(initializer_range=0.16),
                                      expert_share=(1, 2))
        model.eval()
        _WINDOWED["model"] = model
    kw.setdefault("stats", ServingStats())
    return serving.DecodeEngine(_WINDOWED["model"], max_slots=4, max_seq=128,
                                seq_buckets=[8, 16, 32], page_size=8, pool_pages=48, **kw)


def _prompts(n, seed, hi=15):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 256, size=int(k)).astype(np.int32)
            for k in rs.randint(3, hi, size=n)]


class _Run:
    """One engine served under the tracer from before its first beat: the
    complete events, the stats, and the scheduler's calls as `_read` saw them."""

    def __init__(self, family, asked=(1, 2, 5, 9, 3, 6, 4), **engine_kw):
        tracer.reset()
        was = tracer.enabled
        tracer.enable()
        try:
            eng = _engine(family, **engine_kw).warmup()
            try:
                reqs = []
                for i, (p, m) in enumerate(zip(_prompts(len(asked), seed=7), asked)):
                    reqs.append(eng.submit("t", p, max_new_tokens=m))
                    if i == 3:
                        time.sleep(0.01)      # the rest join a batch in mid-flight
                for r in reqs:
                    r.result(60)
                _settle(eng)
            finally:
                eng.shutdown(drain=True)
            trace = tracer.to_chrome_trace()
        finally:
            tracer.enabled = was
            tracer.reset()
        assert "otherData" not in trace          # nothing dropped
        self.events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        self.by_id = {e["id"]: e for e in self.events}
        self.stats = eng.stats
        self.calls = sorted(self.named("serving.call"), key=lambda e: e["args"]["seq"])

    def named(self, name):
        return [e for e in self.events if e["name"] == name]

    def children(self, span, name):
        return [e for e in self.named(name) if e["parent"] == span["id"]]


@pytest.fixture(scope="module", params=FAMILIES)
def ran(request):
    return request.param, _Run(request.param)


def _end(e):
    return e["ts"] + e["dur"]


def test_every_call_read_has_one_span_and_seq_runs_without_a_gap(ran):
    _, run = ran
    cell = run.stats.summary()["decode"]
    assert len(run.calls) == cell["prefill_steps"] + cell["decode_steps"] > 5
    seqs = [c["args"]["seq"] for c in run.calls]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert {c["args"]["kind"] for c in run.calls} == {"prefill", "decode"}
    assert all(c["cat"] == "serving.calls" for c in run.calls)
    # the device runs calls in the order of seq: so were they dispatched, and read
    assert [c["ts"] for c in run.calls] == sorted(c["ts"] for c in run.calls)
    assert [_end(c) for c in run.calls] == sorted(_end(c) for c in run.calls)


def test_a_call_runs_from_its_dispatch_to_the_end_of_its_read(ran):
    _, run = ran
    reads = {e["args"]["of_beat"]: e for e in run.named("serving.read")
             if e["args"]["of_beat"] is not None}
    for c in run.calls:
        a = c["args"]
        sent = run.by_id[c["parent"]]
        assert sent["name"] == "serving.dispatch" and sent["args"]["program"] == a["kind"]
        # it starts inside the dispatch, before any of the dispatch's own work
        assert sent["ts"] <= c["ts"] <= _end(sent)
        parts = [e for e in run.events if e["parent"] == sent["id"] and e is not c]
        assert all(c["ts"] <= p["ts"] and _end(p) <= _end(sent) + 1e-3 for p in parts)
        assert c["ts"] + 1e3 * a["enqueue_ms"] <= _end(sent) + 1e-3
        # and ends inside the read of it, which names the beat it went out in
        read = reads[a["beat"]]
        assert read["ts"] <= _end(c) <= _end(read) + 1e-3
        assert 0 <= 1e3 * a["read_wait_ms"] <= read["dur"] + 1e-3
        assert run.by_id[run.by_id[read["parent"]]["parent"]]["args"]["beat"] == a["read_beat"]
        assert run.by_id[run.by_id[sent["parent"]]["parent"]]["args"]["beat"] == a["beat"]


def test_a_call_is_read_a_beat_late_and_overlapped_when_another_went_out_first(ran):
    _, run = ran
    sent_in = {c["args"]["beat"] for c in run.calls}
    for c in run.calls:
        a = c["args"]
        assert a["read_beat"] == a["beat"] + 1
        assert a["overlapped"] == (a["read_beat"] in sent_in)
    flushed = [c for c in run.calls if not c["args"]["overlapped"]]
    cell = run.stats.summary()["decode"]
    assert len(flushed) == cell["reads_flushed"] >= 1
    assert len(run.calls) - len(flushed) == cell["reads_overlapped"] >= 1


def test_a_call_enqueued_two_executions_exactly_when_lanes_were_carried(ran):
    family, run = ran
    twice = 0
    for c in run.calls:
        sent = run.by_id[c["parent"]]
        carries = run.children(sent, "serving.dispatch.carry")
        assert c["args"]["executions"] == 1 + len(carries)
        assert len(carries) <= 1 and (not carries or c["args"]["kind"] == "decode")
        twice += len(carries)
        # a plain step builds its sampling arguments inside the program call;
        # a chunk's are its builder's (under serving.build: not this name)
        built = run.children(sent, "serving.dispatch.sample_args")
        inside = family in PAGED and (c["args"]["kind"] == "decode" or family == "paged")
        assert len(built) == int(inside)
    assert twice >= 1
    # outside a dispatch (a chunk's arguments are built under serving.build)
    # the same code records nothing under that name
    for e in run.named("serving.dispatch.carry") + run.named("serving.dispatch.sample_args"):
        assert run.by_id[e["parent"]]["name"] == "serving.dispatch"
    assert c["args"]["program"] in ("jit__decode_fn", "jit__prefill_fn")
    assert {c["args"]["program"] for c in run.calls} == {"jit__decode_fn", "jit__prefill_fn"}


def test_a_call_says_what_its_steps_span_says_after_the_read(ran):
    family, run = ran
    for c in run.calls:
        step = run.by_id[run.by_id[c["parent"]]["parent"]]
        assert step["name"] == "serving.decode"
        for key, value in step["args"].items():
            assert c["args"][key] == value, key
        assert c["args"]["lanes"] == len(c["args"]["requests"])
    if family in ("latent", "windowed"):
        # what the program said of its step came with the read, a beat late
        assert all(c["args"]["pairs"] >= c["args"]["experts_hit"] >= 0 for c in run.calls)
        assert any(c["args"]["pairs"] > 0 for c in run.calls)
    if family == "windowed":
        assert all("window_pages_live" in c["args"] for c in run.calls)


def test_the_stats_take_a_calls_own_time_under_its_own_kind(ran):
    _, run = ran
    cell = run.stats._decode
    for kind in ("prefill", "decode"):
        own = sorted(c["dur"] / 1e3 for c in run.calls if c["args"]["kind"] == kind)
        assert sorted(cell[f"{kind}_ms"]) == pytest.approx(own, rel=1e-6)
        enq = sorted(c["args"]["enqueue_ms"] for c in run.calls if c["args"]["kind"] == kind)
        assert sorted(cell[f"{kind}_dispatch_ms"]) == pytest.approx(enq, rel=1e-6)
    # the counters' sums are the spans'
    assert cell["dispatch_s"] == pytest.approx(
        sum(c["args"]["enqueue_ms"] for c in run.calls) / 1e3, rel=1e-6)
    assert cell["read_wait_s"] == pytest.approx(
        sum(c["args"]["read_wait_ms"] for c in run.calls) / 1e3, rel=1e-6)
    report = run.stats.summary()["decode"]
    assert report["decode_p50_ms"] == pytest.approx(
        ServingStats._pct(sorted(cell["decode_ms"]), 0.5), abs=1e-3)
    # the window runs from the first call's dispatch to the last one's read
    window = (_end(run.calls[-1]) - run.calls[0]["ts"]) / 1e6
    assert cell["t_last"] - cell["t_first"] == pytest.approx(window, rel=1e-6)
    assert 0 < report["dispatch_share"] and 0 <= report["read_wait_share"]
    assert report["dispatch_share"] + report["read_wait_share"] <= 1.0
    assert report["dispatch_p50_ms"] > 0 and report["prefill_dispatch_p50_ms"] > 0


def test_a_slow_prefill_call_is_charged_to_prefill_not_to_the_beat_after():
    """What `decode_p50_ms` / `prefill_p50_ms` meant since the late read: the
    duration of the beat that READ the call. A prefill call that takes 60 ms
    and decode calls that take none now read so."""
    eng = _engine("paged").warmup()
    plain = eng.programs.prefill

    def slow(*args):
        time.sleep(0.06)
        return plain(*args)

    eng.programs.prefill = slow
    try:
        eng.generate("t", np.arange(5, dtype=np.int32), max_new_tokens=6)
        _settle(eng)
        report = eng.serving_report()["decode"]
    finally:
        eng.shutdown(drain=True)
    assert report["prefill_steps"] == 1 and report["decode_steps"] == 5
    assert report["prefill_p50_ms"] >= 60.0 > report["decode_p99_ms"]
    assert report["prefill_dispatch_p50_ms"] >= 60.0 > report["dispatch_p99_ms"]


def test_a_call_the_fault_wall_took_has_no_span():
    tracer.reset()
    was = tracer.enabled
    tracer.enable()
    eng = _engine("paged").warmup()
    try:
        good = eng.submit("t", np.arange(4, dtype=np.int32), max_new_tokens=3)
        good.result(30)
        _settle(eng)
        seq = eng._scheduler._seq

        def boom(*a, **k):
            raise RuntimeError("seeded prefill crash")

        eng.programs.prefill = boom
        doomed = eng.submit("t", np.arange(6, dtype=np.int32), max_new_tokens=4)
        with pytest.raises(RuntimeError):
            doomed.result(30)
        _settle(eng)
        events = [e for e in tracer.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
    finally:
        tracer.enabled = was
        tracer.reset()
        eng.shutdown(drain=False)
    assert eng._scheduler._seq == seq            # nothing reached the device
    calls = [e for e in events if e["name"] == "serving.call"]
    assert len(calls) == 3 and all(good.id in c["args"]["requests"] for c in calls)
    assert [e["args"]["reason"] for e in events
            if e["name"] == "serving.request.failed"] == ["RuntimeError"]


def test_a_speculation_round_has_two_calls_each_read_in_its_own_beat():
    run = _Run("paged", asked=(6, 7, 5), speculate_k=2, spec_draft_layers=1)
    rounds = [e for e in run.named("serving.decode") if e["args"]["kind"] == "speculate"]
    assert rounds
    for step in rounds:
        sent = run.children(step, "serving.dispatch")
        calls = [c for c in run.calls if c["parent"] in {d["id"] for d in sent}]
        assert [c["args"]["kind"] for c in calls] == ["draft", "verify"]
        for c in calls:
            a = c["args"]
            assert a["read_beat"] == a["beat"] and not a["overlapped"]
            assert a["executions"] == 1 and a["program"] == f"jit__{a['kind']}_fn"
            assert step["ts"] <= c["ts"] and _end(c) <= _end(step) + 1e-3
    seqs = [c["args"]["seq"] for c in run.calls]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    cell = run.stats.summary()["decode"]
    assert cell["draft_steps"] == cell["verify_steps"] == cell["spec_rounds"] == len(rounds)
    # a round counts as one flushed read, its two calls' times under their kinds
    plain = [c for c in run.calls if c["args"]["kind"] in ("prefill", "decode")]
    assert cell["reads_flushed"] == len(rounds) + sum(not c["args"]["overlapped"] for c in plain)
    assert sorted(run.stats._decode["verify_ms"]) == pytest.approx(
        sorted(c["dur"] / 1e3 for c in run.calls if c["args"]["kind"] == "verify"), rel=1e-6)
    # and the part of each inside its dispatch, beside draft_p50_ms / verify_p50_ms
    for kind in ("draft", "verify"):
        sent = sorted(c["args"]["enqueue_ms"] for c in run.calls if c["args"]["kind"] == kind)
        assert cell[f"{kind}_dispatch_p50_ms"] == pytest.approx(
            ServingStats._pct(sent, 0.50), abs=1e-3)
        assert 0 < cell[f"{kind}_dispatch_p50_ms"] <= cell[f"{kind}_p50_ms"]


@pytest.mark.parametrize("family", ("slots", "paged"))
def test_a_call_dispatched_before_the_tracer_came_on_has_its_span_without_a_parent(family):
    """A capture opens in the middle of a beat: the call in flight then was
    dispatched untraced, and the device's first execution in the capture is
    its. Its stamps are always on, so its span is there, tied to no dispatch."""
    assert not tracer.enabled
    tracer.reset()
    eng = _engine(family).warmup()
    sched = eng._scheduler
    dispatch, sent = sched._dispatch, []

    def switched(call):
        dispatch(call)
        if call is not None:
            sent.append(call)
            if len(sent) == 3:
                tracer.enable()      # in the middle of the beat, after its dispatch

    sched._dispatch = switched
    try:
        reqs = [eng.submit("t", p, max_new_tokens=6) for p in _prompts(3, seed=11)]
        for r in reqs:
            r.result(60)
        _settle(eng)
        trace = tracer.to_chrome_trace()
    finally:
        tracer.disable()
        eng.shutdown(drain=True)
        tracer.reset()
    calls = sorted((e for e in trace["traceEvents"] if e["name"] == "serving.call"),
                   key=lambda e: e["args"]["seq"])
    # the beat that switched it on is not recorded: the call it read has no span
    assert [c["args"]["seq"] for c in calls] == list(range(3, len(sent) + 1))
    first, rest = calls[0], calls[1:]
    assert first["parent"] is None and first["args"]["beat"] < first["args"]["read_beat"]
    assert first["ts"] == pytest.approx(1e6 * sent[2].t_dispatch)
    assert first["args"]["kind"] == sent[2].kind and first["args"]["lanes"] == len(sent[2].lanes)
    ids = {e["id"]: e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert rest and all(ids[c["parent"]] == "serving.dispatch" for c in rest)


@pytest.mark.parametrize("family", FAMILIES)
def test_with_the_tracer_off_the_stamps_are_set_and_nothing_is_recorded(family):
    assert not tracer.enabled
    eng = _engine(family).warmup()
    sched = eng._scheduler
    seen, read = [], sched._read

    def watched(call):
        toks = read(call)
        if call is not None:
            seen.append((call, sched._span("x"), sched._dispatch_part("carry")))
        return toks

    sched._read = watched
    try:
        time.sleep(0.1)     # a beat that an earlier test's tracer left recording ends
        before = len(tracer)
        for p in _prompts(3, seed=9):
            eng.submit("t", p, max_new_tokens=4)
        _settle(eng)
        assert len(tracer) == before
    finally:
        eng.shutdown(drain=True)
    assert len(seen) >= 5
    for call, span, part in seen:
        assert span is _NULL_SPAN and part is _NULL_SPAN and call.sent_by is None
        assert call.t_dispatch <= call.t_enqueued <= call.t_read0 <= call.t_read
    assert [c.seq for c, _, _ in seen] == list(range(1, len(seen) + 1))
    assert all(r.t_first_token in {c.t_read for c, _, _ in seen}
               for c, _, _ in seen for r in c.lanes)


def test_stats_on_a_scripted_clock_and_on_the_metrics_page():
    from paddle_tpu.observability.export import prometheus_text
    from paddle_tpu.observability.metrics import MetricsRegistry

    stats = ServingStats()
    reg = MetricsRegistry()
    reg.register_collector("serving", stats.summary)
    # a prefill call of 80 ms read by the beat after it, which took 30
    stats.record_decode_step("prefill", 0.080, 1, 1, t_end=1.080, dispatch_s=0.002,
                             read_wait_s=0.070, overlapped=True, lanes_carried=1)
    stats.record_decode_step("decode", 0.030, 3, 3, t_end=1.110, dispatch_s=0.001,
                             read_wait_s=0.025, overlapped=True, lanes_carried=3)
    stats.record_decode_step("decode", 0.020, 3, 3, t_end=1.200, dispatch_s=0.001,
                             read_wait_s=0.015, overlapped=False)
    cell = stats.summary()["decode"]
    assert (cell["prefill_p50_ms"], cell["decode_p50_ms"]) == (80.0, 30.0)
    assert (cell["dispatch_p50_ms"], cell["prefill_dispatch_p50_ms"]) == (1.0, 2.0)
    assert (cell["dispatch_share"], cell["read_wait_share"]) == (0.02, 0.55)
    assert cell["dispatch_share"] + cell["read_wait_share"] <= 1
    assert (cell["reads_overlapped"], cell["reads_flushed"], cell["lanes_carried"]) == (2, 1, 4)
    assert cell["tokens_per_sec"] == 35.0
    lines = prometheus_text(reg.snapshot()).splitlines()
    for line in ("paddle_serving_decode_dispatch_share 0.02",
                 "paddle_serving_decode_read_wait_share 0.55",
                 "paddle_serving_decode_dispatch_p50_ms 1.0",
                 "paddle_serving_decode_dispatch_p99_ms 1.0",
                 "paddle_serving_decode_prefill_dispatch_p50_ms 2.0",
                 "paddle_serving_decode_prefill_p50_ms 80.0",
                 "paddle_serving_decode_decode_p50_ms 30.0"):
        assert line in lines, line
    # the sums that no summary read are gone
    assert not {"prefill_s", "decode_s", "draft_s", "verify_s"} & set(stats._decode)
    # one method records one thing: a call with its stamps, none without
    with pytest.raises(TypeError):
        stats.record_decode_step("decode", 0.001, 3, 3)
