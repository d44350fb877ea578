"""The decode loop reads a call's tokens one beat late (ISSUE 35): a beat
dispatches call N, then reads and absorbs call N-1. These tests hold the
four program families (slot K/V, paged K/V, state lanes, latent pages) to
one behaviour, on the tiny CPU models the serving tests use.

The oracle is the request decoded ALONE on a *synchronous twin*: an engine
of the same family whose scheduler reads every call in the beat that
dispatched it (``_sync``: a flush right after each dispatch), which is the
loop as it was before: no token carried on the device, nothing counted
ahead, ``eos`` known at once. The same programs, none of the lag."""
import threading
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import reliability as rel
from paddle_tpu import serving
from paddle_tpu.profiler.pipeline import ServingStats
from paddle_tpu.serving.scheduler import DecodeScheduler

FAMILIES = ("slots", "paged", "state", "latent")
SAMPLING = ("paged", "latent")          # the families whose programs sample
MAX_SEQ = 32


def _build_model(family):
    if family in ("slots", "paged"):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

        paddle.seed(0)
        model = GPTForCausalLM(gpt_tiny(
            num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
            max_position_embeddings=64))
    elif family == "state":
        from paddle_tpu.models import BrumbyForCausalLM, brumby_tiny

        paddle.seed(5)
        model = BrumbyForCausalLM(brumby_tiny())
    else:
        from paddle_tpu.models import AXK1ForCausalLM, axk1_tiny

        paddle.seed(5)
        model = AXK1ForCausalLM(axk1_tiny(), expert_share=(1, 4))
    model.eval()
    return model


_MODELS = {}


def _model(family):
    if family not in _MODELS:
        _MODELS[family] = _build_model(family)
    return _MODELS[family]


def _engine(family, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("stats", ServingStats())
    if family in ("slots", "paged"):
        kw.setdefault("kv_mode", family)
        kw.setdefault("seq_buckets", [8, 16])
        kw.setdefault("prefill_max_batch", 2)
    if family == "state":
        kw.setdefault("seq_buckets", [4, 8])
    if family == "latent":
        kw.setdefault("seq_buckets", [8, 16])
        kw.setdefault("pool_pages", 24)
    if family in ("paged", "latent"):
        kw.setdefault("page_size", 8)
    return serving.DecodeEngine(_model(family), **kw)


def _sync(engine):
    """Make ``engine`` the synchronous twin: every call read at once."""
    sched = engine._scheduler
    run = DecodeScheduler._run

    def sync_run(self, build=None):
        run(self, build)
        if self._flight is not None:
            run(self)

    sched._run = types.MethodType(sync_run, sched)
    return engine


class _Pair:
    """A family's lagged engine and its synchronous twin."""

    def __init__(self, family, **kw):
        self.family = family
        self.engine = _engine(family, **kw).warmup()
        self.twin = _sync(_engine(family, **kw)).warmup()
        self.vocab = int(_model(family).config.vocab_size)
        # chunked programs cut a long prompt; the others take one seq rung
        self.longest = MAX_SEQ - 1 if self.engine.programs.chunked else 16

    def alone(self, prompt, m, **sampling):
        out = self.twin.submit("ref", prompt, max_new_tokens=m, **sampling).result(60)
        assert self.twin._scheduler._flight is None
        return list(out)

    def prompts(self, n, seed, lo=3, hi=None):
        rs = np.random.RandomState(seed)
        return [rs.randint(0, self.vocab, size=int(k)).astype(np.int32)
                for k in rs.randint(lo, (hi or self.longest) + 1, size=n)]

    def set_eos(self, eos):
        self.engine._scheduler.eos_id = self.twin._scheduler.eos_id = eos

    def shutdown(self):
        self.engine.shutdown(drain=True)
        self.twin.shutdown(drain=True)


@pytest.fixture(scope="module", params=FAMILIES)
def pair(request):
    p = _Pair(request.param)
    yield p
    p.shutdown()


def _until(cond, seconds=10.0):
    deadline = time.time() + seconds
    while not cond():
        assert time.time() < deadline, "timed out"
        time.sleep(0.001)


def _settle(engine):
    """Wait until nothing is in flight and the beat that absorbed the last
    call has ended (an idle engine starts a beat every 50 ms)."""
    sched = engine._scheduler
    _until(lambda: sched._flight is None and not sched._active and not sched._pending)
    beat = sched._beat
    _until(lambda: sched._beat > beat)


def _decode_cell(engine):
    return engine.stats.summary()["decode"] or {
        "reads_overlapped": 0, "reads_flushed": 0, "lanes_carried": 0,
        "decode_steps": 0, "prefill_steps": 0, "tokens": 0}


# ---------------------------------------------------------- (a) the streams
def test_streams_under_churn_equal_the_request_alone(pair):
    """Join/leave churn, answers of 1 and 2 tokens, and lanes that run into
    ``max_seq``: every stream is bitwise the request's own, alone."""
    eng = pair.engine
    prompts = pair.prompts(10, seed=7)
    asked = [1, 2, 5, 9, 1, 3, 2, 12, 6, 4]
    # two that run into the sequence's capacity whatever they ask for
    prompts += [p[:pair.longest] for p in pair.prompts(2, seed=8, lo=pair.longest)]
    asked += [50, 50]
    reqs = []
    for i, (p, m) in enumerate(zip(prompts, asked)):
        reqs.append(eng.submit(f"t{i % 3}", p, max_new_tokens=m))
        if i in (3, 7):
            time.sleep(0.01)    # later ones join a batch in mid-flight
    outs = [list(r.result(60)) for r in reqs]
    for p, m, out in zip(prompts, asked, outs):
        assert out == pair.alone(p, m)
        assert len(out) == min(m, MAX_SEQ - len(p) + 1)
    assert eng.compiles_after_warmup == 0
    assert eng.kv_pool.in_use() == 0 and eng.active_requests() == 0
    assert eng._scheduler._flight is None


# ------------------------------------------------------------------ (b) eos
def _eos_case(pair, seed):
    """A prompt whose answer alone holds, at index 2 or later, a token that
    did not come before it: (prompt, answer alone, that index)."""
    for p in pair.prompts(40, seed=seed, hi=12):
        full = pair.alone(p, 8)
        for k in range(2, 7):
            if full[k] not in full[:k]:
                return p, full, k
    raise AssertionError("no prompt of the seed emits a fresh token mid-answer")


def test_eos_ends_the_stream_and_the_overshoot_is_dropped(pair):
    """The host learns of ``eos`` one beat late, so the lane rides one call
    past it. Nothing of that call is emitted, the lane's pages (or slot, or
    state lane) come back once, and the requests that take them decode
    bitwise as they do alone."""
    eng = pair.engine
    prompt, full, k = _eos_case(pair, seed=11)
    pair.set_eos(full[k])
    try:
        before = _decode_cell(eng)
        req = eng.submit("e", prompt, max_new_tokens=8)
        out = list(req.result(60))
        assert out == full[:k + 1] == pair.alone(prompt, 8)
        assert list(req.generated) == out          # nothing after eos got in
        _settle(eng)
        after = _decode_cell(eng)
        # one prefill call and k decode calls made the k + 1 tokens; the lane
        # rode one decode call more, dispatched before its eos was read
        assert after["decode_steps"] - before["decode_steps"] == k + 1
        assert after["tokens"] - before["tokens"] == k + 1
        assert eng.kv_pool.in_use() == 0 and eng.active_requests() == 0
        # every lane taken at once: one of them is the lane just released,
        # with the overshoot's row still in it
        prompts = pair.prompts(4, seed=12, lo=6)
        reqs = [eng.submit("n", p, max_new_tokens=6) for p in prompts]
        for p, r in zip(prompts, reqs):
            assert list(r.result(60)) == pair.alone(p, 6)
        assert eng.kv_pool.in_use() == 0     # released once: a second release raises
    finally:
        pair.set_eos(None)


# ------------------------------------------------------------- (c) sampling
@pytest.fixture(scope="module", params=SAMPLING)
def sampling_pair(request):
    p = _Pair(request.param)
    yield p
    p.shutdown()


def test_sampled_lanes_keep_their_stream_per_seed(sampling_pair):
    """A lane with ``temperature > 0`` needs nothing from the host between
    steps but its key index, which counts tokens sent for: the stream per
    seed is the synchronous loop's, alone and in a mixed batch."""
    pair = sampling_pair
    eng = pair.engine
    knobs = dict(temperature=0.9, top_k=40, top_p=0.95)
    prompts = pair.prompts(5, seed=21, hi=12)
    want = [pair.alone(p, 7, seed=100 + i, **knobs) for i, p in enumerate(prompts)]
    assert want[0] != pair.alone(prompts[0], 7, seed=999, **knobs)
    for i, p in enumerate(prompts[:2]):      # alone
        assert list(eng.submit("s", p, max_new_tokens=7, seed=100 + i,
                               **knobs).result(60)) == want[i]
    greedy = pair.prompts(3, seed=22, hi=12)
    reqs = [eng.submit("s", p, max_new_tokens=7, seed=100 + i, **knobs)
            for i, p in enumerate(prompts)]
    mixed = [eng.submit("g", p, max_new_tokens=5) for p in greedy]
    assert [list(r.result(60)) for r in reqs] == want
    for p, r in zip(greedy, mixed):
        assert list(r.result(60)) == pair.alone(p, 5)


# --------------------------------------------- (d) nothing in flight forgotten
def test_a_lone_request_is_read_without_the_queues_wait(pair):
    """A call in flight makes the scheduler not idle: admission never waits
    on the queue (its 50 ms) while a token is unread, and a lone request
    completes within a few beats of its submission."""
    eng = pair.engine
    sched, queue = eng._scheduler, eng.queue
    take, waits = queue.take_slots, []

    def watched(n, timeout=None, **kw):
        waits.append((timeout, sched._flight is not None))
        return take(n, timeout=timeout, **kw)

    queue.take_slots = watched
    try:
        for m in (1, 2, 3):
            time.sleep(0.06)          # the engine idles: a beat waits on the queue
            b0 = sched._beat
            req = eng.submit("l", pair.prompts(1, seed=30 + m, hi=8)[0], max_new_tokens=m)
            assert len(req.result(60)) == m
            # the idle beat it woke, one beat a call, the flush (and one more
            # that may have begun): not a beat waited out beside them
            assert sched._beat - b0 <= m + 3
    finally:
        queue.take_slots = take
    assert any(t for t, _ in waits)                    # idle beats do wait
    assert not [t for t, flight in waits if flight and t]


# ---------------------------------------------------------------- (e) drain
@pytest.mark.parametrize("family", FAMILIES)
def test_shutdown_drains_the_call_in_flight(family):
    eng = _engine(family).warmup()
    rs = np.random.RandomState(41)
    vocab = int(_model(family).config.vocab_size)
    reqs = [eng.submit("d", rs.randint(0, vocab, size=6).astype(np.int32),
                       max_new_tokens=m) for m in (1, 4, 7)]
    eng.shutdown(drain=True)     # at once: calls are in flight or not yet built
    assert [len(r.result(0)) for r in reqs] == [1, 4, 7]
    assert eng._scheduler._flight is None and not eng._scheduler.alive()
    assert eng.kv_pool.in_use() == 0 and eng.active_requests() == 0


# ------------------------------------------------------------ (f) the fault
@pytest.mark.parametrize("family", FAMILIES)
def test_a_fault_fails_the_failed_calls_lanes_and_the_flight_is_absorbed(family):
    """Lane A's last token (by length) is in flight when call N, which
    carries lane B alone, meets an injected ``serving.decode_step`` fault:
    B fails, A's token is absorbed and A completes in full."""
    eng = _engine(family, prefill_max_batch=1).warmup()
    sched = eng._scheduler
    rs = np.random.RandomState(51)
    vocab = int(_model(family).config.vocab_size)
    pa, pb = (rs.randint(0, vocab, size=6).astype(np.int32) for _ in range(2))
    want_a = list(eng.generate("ref", pa, max_new_tokens=6))
    real, seen, who = sched._program_call, [], {}

    def watched(fn):
        # the first call that carries B and not A, with A's last token unread
        flight, a, b = sched._flight, who.get("a"), who.get("b")
        if (not seen and flight is not None and b is not None
                and a in flight.lanes and a.sent == a.max_new_tokens
                and len(a.generated) == a.sent - 1
                and sched._step_lanes == [b]):
            seen.append((list(flight.lanes), list(sched._step_lanes)))
            inj = rel.FaultInjector(seed=0)
            inj.plan("serving.decode_step", rate=1.0, transient=False, max_fires=1)
            rel.arm(inj)
            try:
                return real(fn)
            finally:
                rel.disarm()
        return real(fn)

    sched._program_call = watched
    try:
        # A asks for less, so A leaves first and B decodes on alone
        a = who["a"] = eng.submit("a", pa, max_new_tokens=6)
        b = who["b"] = eng.submit("b", pb, max_new_tokens=24)
        assert list(a.result(60)) == want_a
        with pytest.raises(rel.FaultInjection):
            b.result(60)
    finally:
        sched._program_call = real
    assert len(seen) == 1 and a in seen[0][0]
    assert eng.kv_pool.in_use() == 0 and eng.active_requests() == 0
    assert len(eng.generate("after", pb, max_new_tokens=4)) == 4   # the loop lives
    eng.shutdown(drain=True)


# ------------------------------------------------------------- (g) compiles
def test_the_carry_is_warmed_for_every_pair_of_shapes(pair):
    """The carry is the programs' own: counted by ``traces``, warmed for
    every (rows of the call before, batch rung) it can meet, so that
    traffic and every pair called by hand compile nothing."""
    import jax.numpy as jnp

    eng = pair.engine
    programs = eng.programs
    rows = sorted(set(programs.decode_rungs) | set(programs.prefill_batch_rungs))
    assert set(programs.carry_rungs) == {("carry", p, b) for p in rows
                                         for b in programs.decode_rungs}
    assert set(programs.carry_rungs) <= set(programs.warmed) == set(programs.rungs)
    met, carry = set(), programs.carry

    def watched(prev, tokens):
        met.add(("carry", int(prev.shape[0]), int(tokens.shape[0])))
        return carry(prev, tokens)

    programs.carry = watched
    try:
        traces = programs.traces
        prompts = pair.prompts(12, seed=61, hi=12)
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(eng.submit("c", p, max_new_tokens=2 + i % 5))
            if i % 4 == 3:
                time.sleep(0.005)
        for r in reqs:
            r.result(60)
    finally:
        programs.carry = carry
    assert met and met <= set(programs.carry_rungs)
    for _, p, b in programs.carry_rungs:
        prev = jnp.arange(100, 100 + p, dtype=jnp.int32)
        fed = np.asarray(programs.carry(prev, np.asarray(
            [7 if i % 2 else -1 - (i % p) for i in range(b)], np.int32)))
        assert list(fed) == [7 if i % 2 else 100 + i % p for i in range(b)]
    assert programs.traces == traces and eng.compiles_after_warmup == 0


# ------------------------------------------------------------- (h) counters
def test_reads_and_carried_lanes_are_counted(pair):
    """A lone request of m tokens is m calls: each but the last is read
    after the next went out (overlapped) and feeds the next its one lane
    from the device (carried); the last is flushed."""
    eng = pair.engine
    for m in (1, 4):
        before = _decode_cell(eng)
        eng.generate("h", pair.prompts(1, seed=70 + m, hi=8)[0], max_new_tokens=m)
        _settle(eng)
        after = _decode_cell(eng)
        delta = {k: after[k] - before[k] for k in
                 ("reads_overlapped", "reads_flushed", "lanes_carried")}
        assert delta == {"reads_overlapped": m - 1, "reads_flushed": 1,
                         "lanes_carried": m - 1}
    _settle(eng)
    report = eng.serving_report()["decode"]
    assert 0 < report["reads_overlapped_share"] < 1
    assert report["reads_overlapped"] + report["reads_flushed"] == (
        report["prefill_steps"] + report["decode_steps"])


def test_the_counters_reach_the_metrics_page():
    from paddle_tpu.observability.export import prometheus_text
    from paddle_tpu.observability.metrics import MetricsRegistry

    stats = ServingStats()
    reg = MetricsRegistry()
    reg.register_collector("serving", stats.summary)
    took = dict(dispatch_s=0.0004, read_wait_s=0.0002)
    for i in range(3):
        stats.record_decode_step("decode", 0.001, 3, 3, t_end=1.0 + i, **took,
                                 overlapped=True, lanes_carried=3)
    stats.record_decode_step("decode", 0.001, 3, 3, t_end=4.0, **took, overlapped=False)
    lines = prometheus_text(reg.snapshot()).splitlines()
    assert "paddle_serving_decode_reads_overlapped 3" in lines
    assert "paddle_serving_decode_reads_flushed 1" in lines
    assert "paddle_serving_decode_lanes_carried 9" in lines
    assert "paddle_serving_decode_reads_overlapped_share 0.75" in lines


def test_a_speculation_round_reads_its_own_calls_and_counts_one_flush():
    """A round compares drafts with verified tokens on the host: what is in
    flight is flushed first, the round reads its own two calls (one flush a
    round in the stats), and the tokens are the plain loop's."""
    plain = _Pair("paged")
    spec = _engine("paged", speculate_k=2, spec_draft_layers=1).warmup()
    try:
        prompts = plain.prompts(5, seed=81, hi=12)
        reqs = [spec.submit("s", p, max_new_tokens=9) for p in prompts]
        outs = [list(r.result(60)) for r in reqs]
        assert outs == [plain.alone(p, 9) for p in prompts]
        assert outs == [list(plain.engine.submit("p", p, max_new_tokens=9).result(60))
                        for p in prompts]
        cell = spec.serving_report()["decode"]
        assert cell["spec_rounds"] >= 1
        # each round one flush, and one for each prefill call read before a round
        assert cell["spec_rounds"] <= cell["reads_flushed"] <= (
            cell["spec_rounds"] + cell["prefill_steps"])
        assert cell["lanes_carried"] == 0      # a round is fed from the host
        assert spec._scheduler._flight is None and spec.compiles_after_warmup == 0
    finally:
        spec.shutdown(drain=True)
        plain.shutdown()


# ------------------------------------------------------- the host counts ahead
def test_a_lane_known_to_finish_is_not_put_into_the_next_call(pair):
    """``sent`` runs ahead of ``generated`` by at most one, and a lane whose
    token in flight is its last by length rides no further call: calls a
    request rode = tokens it was given."""
    eng = pair.engine
    rides, lock = {}, threading.Lock()

    def tap(kind, lanes, rung, emitted):
        with lock:
            rides.setdefault(kind, []).append((lanes, emitted))

    eng._scheduler.on_step = tap
    try:
        prompts = pair.prompts(6, seed=91, hi=8)
        asked = [1, 2, 3, 5, 8, 4]
        reqs = [eng.submit("k", p, max_new_tokens=m) for p, m in zip(prompts, asked)]
        for r, m in zip(reqs, asked):
            assert len(r.result(60)) == m and r.sent == m == len(r.generated)
    finally:
        eng._scheduler.on_step = None
    # every lane of every emitting call emitted: none rode for nothing
    decodes = rides.get("decode", [])
    assert decodes and all(lanes == emitted for lanes, emitted in decodes)
    assert sum(e for _, e in decodes) == sum(asked) - len(asked)
