"""Brumby (power retention) from the functional to the engine, on the CPU at
a small size: hidden 64, 4 query and 2 key/value heads of 16, 2 layers,
vocabulary 256, seeded. The reference is benchmark/reference_brumby.py (the
one copy: float32, quadratic form), the stand-in configuration and the
logit tolerance are the benchmark's own files."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmark import harness, models_brumby  # noqa: E402
from benchmark import reference_brumby as ref  # noqa: E402
from benchmark.drivers import closed_loop_lm  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.models import BrumbyForCausalLM, brumby_tiny  # noqa: E402
from paddle_tpu.nn.functional import power_retention as pr  # noqa: E402
from paddle_tpu.serving import decode as decode_mod  # noqa: E402
from paddle_tpu.serving.kv_cache import StateLanePool  # noqa: E402

STAND_IN = harness.load_json(os.path.join(ROOT, "benchmark", "tests", "tiny-brumby.json"))
TRAFFIC = harness.load_json(os.path.join(ROOT, "benchmark", "traffic", "longgen-closed.json"))
REF_KEYS = ("rms_norm_eps", "head_dim", "num_attention_heads",
            "num_key_value_heads", "rope_theta")


def weights_of(model):
    params, _ = decode_mod._extract_brumby(model)
    return params


def ref_config(cfg):
    return {k: getattr(cfg, k) for k in REF_KEYS}


def ref_logits(model, ids):
    return np.asarray(ref.forward_logits(weights_of(model), jnp.asarray(ids, jnp.int32),
                                         ref_config(model.config)))


@pytest.fixture(scope="module")
def model():
    paddle.seed(5)
    m = BrumbyForCausalLM(brumby_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(model):
    eng = serving.DecodeEngine(model, max_slots=4, seq_buckets=[4, 8], max_seq=128)
    eng.warmup()
    yield eng
    eng.shutdown()


# ------------------------------------------------------------ the functional
def _qkvg(T, Hq=4, Hkv=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((T, Hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, Hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, Hkv, d)), jnp.float32)
    log_g = jnp.asarray(-0.3 * np.abs(rng.standard_normal((T, Hkv))), jnp.float32)
    return q, k, v, log_g


@pytest.mark.parametrize("d", [8, 16, 32, 128])
def test_phi_is_the_exact_second_power(d):
    rng = np.random.default_rng(d)
    a = jnp.asarray(rng.standard_normal((7, d)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((7, d)), jnp.float32)
    assert pr.phi(a).shape == (7, pr.phi_dim(d))
    np.testing.assert_allclose((pr.phi(a) * pr.phi(b)).sum(-1), (a * b).sum(-1) ** 2,
                               rtol=2e-5, atol=1e-4)


def test_phi_dim_at_the_published_head_size_is_whole_lanes():
    assert pr.phi_dim(128) == 8704 and pr.phi_dim(128) % 128 == 0
    assert pr.state_rows(128) == 136


@pytest.mark.parametrize("chunk", [21, 7, 8, 5, 1])
def test_chunked_form_agrees_with_the_quadratic_form(chunk):
    """Chunk lengths that divide T = 21 (21, 7, 1) and that do not (8, 5)."""
    q, k, v, log_g = _qkvg(21)
    want = ref.retention_quadratic(q, k, v, log_g)
    got = pr.power_retention(q[None], k[None], v[None], log_g[None], chunk=chunk)
    np.testing.assert_allclose(np.asarray(got._value)[0], want, atol=2e-5)


def test_one_step_recurrence_agrees_with_the_quadratic_form():
    q, k, v, log_g = _qkvg(21, seed=3)
    want = ref.retention_quadratic(q, k, v, log_g)
    state = jnp.zeros((1, 2, pr.state_rows(16), pr.phi_dim(16)))
    ys = []
    for t in range(21):
        y, state = pr.retention_step(q[t][None], k[t][None], v[t][None],
                                     log_g[t][None], state)
        ys.append(y[0])
    np.testing.assert_allclose(jnp.stack(ys), want, atol=1e-4)
    # the rows under z stay zero: the state is S^T, z and nothing else
    assert float(jnp.abs(state[:, :, 17:]).max()) == 0.0


def test_chunk_then_steps_carry_one_state():
    """A prefix through the chunk form, the rest through the step form."""
    q, k, v, log_g = _qkvg(21, seed=4)
    want = ref.retention_quadratic(q, k, v, log_g)
    state = jnp.zeros((2, pr.state_rows(16), pr.phi_dim(16)))
    y0, state = pr.retention_chunk(q[:13], k[:13], v[:13], log_g[:13], state)
    ys, state = [y0], state[None]
    for t in range(13, 21):
        y, state = pr.retention_step(q[t][None], k[t][None], v[t][None],
                                     log_g[t][None], state)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys), want, atol=1e-4)


def test_pallas_step_kernel_agrees_with_the_jnp_step_in_interpret_mode():
    from paddle_tpu.ops.pallas import retention as kernel

    rng = np.random.default_rng(0)
    L, N, Hkv, d, G, B = 2, 5, 2, 16, 2, 3
    pool = jnp.asarray(rng.standard_normal((L, N, Hkv, pr.state_rows(d), pr.phi_dim(d))),
                       jnp.float32)
    q, k, v, log_g = _qkvg(B, Hq=Hkv * G, Hkv=Hkv, d=d, seed=9)
    slots = jnp.asarray([3, 0, 4], jnp.int32)      # 4 is the pad lane of a 4-lane pool
    new, total = kernel.retention_step(pool, jnp.int32(1), slots,
                                       *pr.step_operands(q, k, v, log_g), interpret=True)
    y, held = pr.retention_step(q, k, v, log_g, pool[1, slots])
    np.testing.assert_allclose(pr.finish_step(total), y, atol=1e-4)
    np.testing.assert_allclose(new, pool.at[1, slots].set(held), atol=1e-5)


# ------------------------------------------------------------------ the model
def test_model_logits_agree_with_the_reference(model):
    ids = np.random.default_rng(1).integers(0, 256, (2, 19)).astype(np.int32)
    got = model(paddle.to_tensor(ids)).numpy()
    for i in range(2):
        np.testing.assert_allclose(got[i], ref_logits(model, ids[i]), atol=1e-4)


def test_model_holds_its_layers_stacked_and_says_its_residency(model):
    stack = model.brumby.layers
    assert stack.qkv_proj.shape == [2, 64, (4 + 2 * 2) * 16]
    assert stack.g_bias.shape == [2, 2] and model.serving_residency == "state"


# ----------------------------------------------------------------- the engine
def _gaps(model, prompt, out):
    logits = ref_logits(model, np.concatenate([prompt, out]))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
    return rows.max(-1) - rows[np.arange(len(out)), out]


@pytest.mark.parametrize("length", [3, 8, 13, 16, 21, 24])
def test_engine_prefill_then_decode_agrees_with_the_reference(engine, model, length):
    """One chunk (3, 8), two (13: a ragged last chunk; 16), three (21 ragged,
    24 whole), then nine decode steps through the state pool: every token
    the engine returns is the float32 reference's own argmax given the
    engine's earlier tokens."""
    prompt = np.random.default_rng(length).integers(0, 256, length).astype(np.int32)
    out = np.asarray(engine.generate("t", prompt, max_new_tokens=9))
    assert len(out) == 9
    assert _gaps(model, prompt, out).max() < 1e-4


def test_a_lane_joining_and_a_lane_leaving_leave_the_others_alone(engine):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (9, 20, 5, 14)]
    alone = [np.asarray(engine.generate("t", p, max_new_tokens=12)) for p in prompts]
    # together: different answer lengths, so lanes leave at different steps,
    # and a fifth request joins the lane the first to finish leaves
    asked = [12, 4, 12, 7]
    sent = [engine.submit("t", p, max_new_tokens=a) for p, a in zip(prompts, asked)]
    late = engine.submit("t", prompts[0], max_new_tokens=12)
    for req, want, a in zip(sent, alone, asked):
        np.testing.assert_array_equal(np.asarray(req.result(timeout=120)), want[:a])
    np.testing.assert_array_equal(np.asarray(late.result(timeout=120)), alone[0])


def test_pool_bytes_constant_and_no_compile_under_churn(engine):
    rng = np.random.default_rng(12)
    pool = engine.kv_pool
    sent = [engine.submit("t", rng.integers(0, 256, int(n)).astype(np.int32),
                          max_new_tokens=int(a))
            for n, a in zip(rng.integers(1, 40, 24), rng.integers(1, 10, 24))]
    for req in sent:
        req.result(timeout=120)
    assert engine.compiles_after_warmup == 0
    assert pool.device_bytes() == pool.bytes_at_warmup
    assert pool.in_use() == 0 and engine.active_requests() == 0
    report = engine.serving_report()
    assert report["kv_mode"] == "state" and report["kv_pool_bytes_constant"]


def test_the_pad_lane_takes_the_padded_rungs_writes(model):
    """Three live lanes ride the 4-lane decode rung: the fourth batch lane
    names the pad lane, whose state changes, and no free lane's does."""
    eng = serving.DecodeEngine(model, max_slots=8, seq_buckets=[8], max_seq=64)
    eng.warmup()
    try:
        pool = eng.kv_pool
        before = np.asarray(pool.state)
        rng = np.random.default_rng(13)
        for req in [eng.submit("t", rng.integers(0, 256, 6).astype(np.int32),
                               max_new_tokens=5) for _ in range(3)]:
            req.result(timeout=120)
        after = np.asarray(pool.state)
        changed = [lane for lane in range(9)
                   if not np.array_equal(before[:, lane], after[:, lane])]
        assert pool.pad_slot == 8 and 8 in changed
        assert len(changed) == 4       # three lanes held, and the pad lane
    finally:
        eng.shutdown()


def test_a_crashed_chunk_fails_only_its_request_and_frees_the_lane(engine):
    """The second chunk of a three-chunk prompt crashes: that request fails,
    its lane comes back, nothing is left pending, the loop keeps serving."""
    real, calls = engine.programs.prefill, {"n": 0}

    def boom(*args):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("seeded chunk crash")
        return real(*args)

    engine.programs.prefill = boom
    try:
        prompt = np.random.default_rng(15).integers(0, 256, 21).astype(np.int32)
        doomed = engine.submit("t", prompt, max_new_tokens=4)
        with pytest.raises(RuntimeError, match="seeded chunk crash"):
            doomed.result(60)
    finally:
        engine.programs.prefill = real
    assert engine.kv_pool.in_use() == 0 and engine.active_requests() == 0
    assert len(engine.generate("t", prompt, max_new_tokens=3)) == 3


def test_state_pool_is_a_lane_pool_like_the_slot_pool():
    pool = StateLanePool(num_layers=2, max_slots=3, num_kv_heads=2, head_dim=16, max_seq=64)
    assert pool.state.shape == (2, 4, 2, 24, 192) and pool.state.dtype == jnp.float32
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1} and pool.in_use() == 2 and pool.free_count() == 1
    pool.release(a)
    with pytest.raises(ValueError):
        pool.release(a)
    pool.mark_warm()
    assert pool.device_bytes() == pool.bytes_at_warmup == 2 * 4 * 2 * 24 * 192 * 4
    with pytest.raises(ValueError):
        pool.commit(jnp.zeros((2, 4, 2, 24, 192), jnp.bfloat16))
    from paddle_tpu.observability.metrics import registry

    assert registry.gauge("serving.state_lanes_in_use").value() == 1
    assert registry.gauge("serving.state_pool_bytes").value() == pool.device_bytes()


def test_residency_follows_the_model_and_speculation_is_refused(model):
    with pytest.raises(ValueError, match="speculat"):
        serving.DecodeEngine(model, max_slots=2, speculate_k=2)
    eng = serving.DecodeEngine(model, max_slots=2, max_seq=64, kv_mode="paged")
    assert eng.kv_mode == "state" and isinstance(eng.kv_pool, StateLanePool)
    assert eng.programs.seq_ladder == [64] and eng.programs.chunked


def test_prefill_spans_carry_the_chunk_and_the_beat_still_tiles(engine):
    from paddle_tpu.observability.metrics import registry
    from paddle_tpu.observability.tracing import tracer

    chunks = registry.counter("serving.prefill_chunks")
    ran = chunks.value()
    tracer.reset()
    tracer.enable()
    try:
        time.sleep(0.15)     # the idle beat that began untraced runs out
        prompt = np.random.default_rng(14).integers(0, 256, 21).astype(np.int32)
        engine.generate("t", prompt, max_new_tokens=3)
    finally:
        tracer.disable()
    events = [e for e in tracer.to_chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    prefill = [e["args"] for e in events
               if e["name"] == "serving.decode" and e["args"].get("kind") == "prefill"]
    assert [(a["chunk"], a["chunks"], a["tokens"]) for a in prefill] == [
        (0, 3, 8), (1, 3, 8), (2, 3, 5)]
    assert [tuple(a["rung"]) for a in prefill] == [(1, 8), (1, 8), (1, 8)]
    assert chunks.value() - ran == 3
    beats = {e["id"]: e for e in events if e["name"] == "serving.beat"}
    kids = {}
    for e in events:
        if e.get("parent") in beats and e["name"].startswith("serving.") \
                and not e["name"].startswith("serving.request"):
            kids.setdefault(e["parent"], []).append(e["name"])
    for names in kids.values():
        if "serving.decode" in names:
            assert names == ["serving.admit", "serving.build", "serving.decode",
                             "serving.absorb"]


@pytest.mark.parametrize("key", [("decode", 2), ("prefill", 1, 8)], ids=lambda k: k[0])
def test_lowered_retention_program_names_its_regions(engine, key):
    """Each program body under its own root, every region of the retention
    vocabulary in some operation's name (the layers are one scan body, whose
    names XLA joins to the root's when it inlines the call)."""
    import re

    from paddle_tpu.base import regions

    programs = engine.programs
    text = programs._jitted(key).lower(
        programs.params, *engine.kv_pool.arrays(), *programs._zero_args(key)
    ).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    assert any(f"/{key[0]}/" in f"/{n}" for n in names)
    other = regions.RETN_CHUNK if key[0] == "decode" else regions.RETN_STATE
    for name in regions.RETENTION:
        assert any(f"/{name}/" in f"/{n}/" for n in names) == (name != other), name


# ------------------------------------------------- the tolerance, and faults
def _stand_in_check(monkeypatch=None, fault=None):
    """The benchmark's own two comparisons (models_brumby: the engine's
    tokens judged by the reference over the engine's bfloat16 weights, and
    the retention path alone on float32 inputs) on the CPU stand-in, with
    prompts of one to thirty-eight chunks of 16."""
    if fault is not None:
        fault(monkeypatch)
    traffic = dict(TRAFFIC, check_prompts=[600, 290, 70, 16, 17, 401], check_answer=24,
                   check_width=640, retention_check=dict(TRAFFIC["retention_check"], ragged=5))
    model = models_brumby.build(STAND_IN, 2147483900)
    engine = serving.DecodeEngine(model, **STAND_IN["engine"])
    engine.warmup()
    try:
        answered = models_brumby.collect_check(
            models_brumby.send_check(engine, STAND_IN, traffic, 2147483900), traffic)
    finally:
        engine.shutdown()
    retention = models_brumby.retention_error(engine, STAND_IN, traffic, 2147483900)
    if monkeypatch is not None:
        monkeypatch.undo()          # the judge runs the sound reference, and only it
    check = models_brumby.judge_check(weights_of(model), STAND_IN, traffic, answered)
    return check, retention, models_brumby.verdict(check, retention, traffic, True, 0, 0, True)


def _state_in_bfloat16(mp):
    def rounded(fn):
        def wrapped(*args, **kwargs):
            y, state = fn(*args, **kwargs)
            return y, jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return wrapped
    mp.setattr(pr, "retention_chunk", rounded(pr.retention_chunk))
    mp.setattr(pr, "retention_step", rounded(pr.retention_step))


def _gate_dropped(mp):
    project = decode_mod.RetentionPrograms._project

    def ungated(self, w, x, positions):
        q, k, v, log_g = project(self, w, x, positions)
        return q, k, v, jnp.zeros_like(log_g)
    mp.setattr(decode_mod.RetentionPrograms, "_project", ungated)


def _normaliser_dropped(mp):
    def unnormed(total):
        B, Hkv, G, rows = total.shape
        return total[..., :rows - pr.STATE_PAD].reshape(B, Hkv * G, -1)
    mp.setattr(pr, "finish_step", unnormed)


def _sqrt2_dropped(mp):
    pairs = pr._pairs

    def flat(head_dim):
        left, right, coef = pairs(head_dim)
        return left, right, np.ones_like(coef)
    mp.setattr(pr, "_pairs", flat)


def _carry_in_zeroed(mp):
    chunk = pr.retention_chunk
    mp.setattr(pr, "retention_chunk",
               lambda q, k, v, log_g, state, *a, **kw: chunk(q, k, v, log_g, state * 0.0, *a, **kw))


@pytest.mark.parametrize("stale,fresh_honoured", [(0.0, True), (1.0, True), (1.0, False)],
                         ids=["empty-pool", "stale-pool", "stale-pool-kept"])
def test_retention_check_drives_the_engines_own_pool(monkeypatch, stale, fresh_honoured):
    """The retention check writes the ENGINE'S pool array (its dtype, every
    lane, the last layer) through the programs' own chunk and step. A lane
    that still holds a former request's state reads the same, because the
    first chunk says `fresh`; a program that kept it is refused."""
    traffic = dict(TRAFFIC, retention_check=dict(TRAFFIC["retention_check"], ragged=5))
    engine = serving.DecodeEngine(models_brumby.build(STAND_IN, 7), **STAND_IN["engine"])
    try:
        pool = engine.kv_pool
        pool.commit(jnp.full_like(pool.state, stale))
        if not fresh_honoured:
            chunk = decode_mod.RetentionPrograms._state_chunk
            monkeypatch.setattr(
                decode_mod.RetentionPrograms, "_state_chunk",
                lambda self, state, li, slot, fresh, *rest: chunk(self, state, li, slot, 0, *rest))
        error = models_brumby.retention_error(engine, STAND_IN, traffic, 7)
        assert (error < traffic["retention_check"]["tolerance"] / 10) == fresh_honoured
        state = np.asarray(pool.state)
        assert pool.state.dtype == jnp.float32
        assert (state[:-1] == stale).all() and (state[-1, pool.pad_slot] == stale).all()
        assert all((state[-1, lane] != stale).any() for lane in range(engine.max_slots))
    finally:
        engine.shutdown()


class _Lived:
    def __init__(self, first, complete):
        self.t_first_token, self.t_complete = first, complete


@pytest.mark.parametrize("others,fewest", [
    ([(0.0, 9.0), (0.5, 9.0)], 3),                 # two lanes decode all through it
    ([(0.0, 2.0), (3.0, 9.0)], 1),                 # one leaves, its successor joins later
    ([(0.0, 2.0), (1.5, 9.0), (None, None)], 2),   # the successor is out first; one never started
], ids=["full", "a-gap", "overlap"])
def test_lanes_beside_a_check_request(others, fewest):
    """What `correct` holds the check requests to: the fewest lanes decoding
    at any moment of a check request's own decode, itself among them."""
    check = _Lived(1.0, 4.0)
    assert closed_loop_lm.lanes_beside(check, [check] + [_Lived(*o) for o in others]) == fewest
    assert closed_loop_lm.lanes_beside(_Lived(None, None), [check]) == 0


def test_sound_program_is_well_inside_both_tolerances():
    check, retention, correct = _stand_in_check()
    assert correct and check["complete"] and check["tokens"] == 6 * 24
    assert check["worst_gap"] < TRAFFIC["logit_tolerance"] / 2
    assert retention < TRAFFIC["retention_check"]["tolerance"] / 10


@pytest.mark.parametrize("fault,limit", [
    (_state_in_bfloat16, "retention"), (_gate_dropped, "logit"),
    (_normaliser_dropped, "both"), (_sqrt2_dropped, "both"), (_carry_in_zeroed, "both")],
    ids=lambda f: f.__name__.strip("_") if callable(f) else f)
def test_each_fault_fails_a_tolerance(monkeypatch, fault, limit):
    """The five ways of computing less than the configuration states, each
    refused by one of the cell's limits: the logit tolerance (what the
    engine's tokens say), the retention tolerance (the state's path alone,
    float32 in), or both. A state kept in bfloat16 passes the first, whose
    floor is the bfloat16 activations', and fails the second a
    hundredfold; a dropped gate lives outside the second."""
    check, retention, correct = _stand_in_check(monkeypatch, fault)
    assert not correct
    by_logit = check["worst_gap"] > TRAFFIC["logit_tolerance"]
    by_retention = retention > 10 * TRAFFIC["retention_check"]["tolerance"]
    assert (by_logit, by_retention) == {
        "logit": (True, False), "retention": (False, True), "both": (True, True)}[limit]
