"""A.X-K1 (latent attention, sparse experts) from the functionals to the
model, on the CPU at a small size, against benchmark/reference_axk1.py (the
one copy: float32, expanded attention, a loop over the experts)."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmark import reference_axk1 as ref  # noqa: E402
from paddle_tpu.models import AXK1Config, AXK1ForCausalLM, axk1_tiny  # noqa: E402
from paddle_tpu.nn.functional import latent_attention as la  # noqa: E402
from paddle_tpu.nn.functional import sparse_experts as se  # noqa: E402
from paddle_tpu.serving import decode as decode_mod  # noqa: E402

PUBLISHED = AXK1Config()


def weights_of(model):
    return decode_mod._extract_axk1(model)[0]


def ref_config(cfg):
    return dataclasses.asdict(cfg)


# ------------------------------------------------------------------ rotary
def test_yarn_frequencies_and_scale_at_the_published_sizes():
    """The numbers ISSUE 34 spells out: the ramp runs over the pairs 10..23,
    pairs below it keep the plain frequency, pairs above it a 32nd of it, and
    the softmax scale is 192^-0.5 x (0.1 ln 32 + 1)^2."""
    freqs = PUBLISHED.inv_freq()
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert freqs.shape == (32,)
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 32, rtol=1e-6)
    assert np.all(freqs[11:23] < plain[11:23]) and np.all(freqs[11:23] > plain[11:23] / 32)
    assert PUBLISHED.softmax_scale == pytest.approx(0.1309, abs=5e-5)
    assert PUBLISHED.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)
    np.testing.assert_allclose(freqs, ref.inv_freq(64, 10000.0, PUBLISHED.rope_scaling))
    assert PUBLISHED.latent_width == 576


def test_rope_agrees_with_the_reference_and_rotates_by_position():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((9, 3, 8)), jnp.float32)
    freqs = la.yarn_inv_freq(8, 10000.0, None)
    np.testing.assert_allclose(la.rope(x, jnp.arange(9), freqs),
                               ref.rope(x, jnp.asarray(freqs)), atol=1e-6)
    # position 0 is the identity; a later position is not
    np.testing.assert_allclose(la.rope(x, jnp.zeros(9, jnp.int32), freqs), x, atol=1e-7)
    assert float(jnp.abs(la.rope(x, jnp.arange(9) + 5, freqs) - x).max()) > 0.1


# --------------------------------------------------------------- attention
def _attention_inputs(T, H=4, dn=16, dr=8, rank=32, dv=16, seed=1):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(T, H, dn), f(T, H, dr), f(T, rank), f(T, dr),
            0.2 * f(rank, H * dn), 0.2 * f(rank, H * dv))


def test_expanded_attention_agrees_with_the_reference():
    qn, qr, c, kr, wk, wv = _attention_inputs(23)
    got = la.attend_expanded(qn, qr, c, kr, wk, wv, 0.3)
    want = ref.attention(qn, qr, c, kr, wk, wv, 0.3).reshape(23, -1)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_absorbed_attention_agrees_with_the_expanded_form():
    """Decode's form against prefill's: every position as its own lane over
    the same rows, `positions` the causal limit."""
    T = 19
    qn, qr, c, kr, wk, wv = _attention_inputs(T)
    rows = jnp.concatenate([c, kr, jnp.zeros((T, 24))], -1)       # [c_kv | k_rope | padding]
    o_lat = la.attend_absorbed(la.absorb_q(qn, wk), qr, jnp.broadcast_to(rows, (T,) + rows.shape),
                               jnp.arange(T), 32, 0.3)
    got = la.unabsorb(o_lat, wv)
    np.testing.assert_allclose(got, la.attend_expanded(qn, qr, c, kr, wk, wv, 0.3), atol=2e-5)


@pytest.mark.parametrize("cut", [1, 8, 16])
def test_blocks_of_keys_under_a_running_softmax_agree_with_one_block(cut):
    T = 17
    qn, qr, c, kr, wk, wv = _attention_inputs(T, seed=2)
    wk3, wv3 = wk.reshape(32, 4, 16), wv.reshape(32, 4, 16)
    causal = jnp.tril(jnp.ones((T, T), bool))
    carry = la.start_blocks(T, 4, 16)
    for lo, hi in ((0, cut), (cut, T)):
        carry = la.expanded_block(carry, jnp.concatenate([qn, qr], -1), c[lo:hi], kr[lo:hi],
                                  wk3, wv3, causal[:, lo:hi], 0.3)
    np.testing.assert_allclose(la.finish_blocks(carry, jnp.float32).reshape(T, -1),
                               la.attend_expanded(qn, qr, c, kr, wk, wv, 0.3), atol=2e-5)


# ------------------------------------------------------------------ router
def _router_for(scores):
    """A router whose sigmoid scores for the token `x = e_0` are `scores`."""
    s = np.asarray(scores, np.float64)
    w = np.zeros((4, s.size), np.float32)
    w[0] = np.log(s / (1 - s))
    return jnp.asarray([[1.0, 0, 0, 0]], jnp.float32), jnp.asarray(w)


SCORES = [0.9, 0.1, 0.1,  0.6, 0.6, 0.1,  0.56, 0.55, 0.5,  0.2, 0.2, 0.2]


def test_the_group_limit_on_a_hand_made_score_vector():
    """Four groups of three; a group's score is the sum of its two largest
    (1.0, 1.2, 1.11, 0.4): groups 1 and 2 stay, so expert 0, the single
    largest score, is out, and the three largest of the kept are 3, 4, 6."""
    x, w = _router_for(SCORES)
    kw = dict(n_group=4, topk_group=2, top_k=3, scaling=2.5)
    idx, wt = se.route(x, w, **kw)
    assert sorted(np.asarray(idx[0]).tolist()) == [3, 4, 6]
    chosen = np.asarray([SCORES[i] for i in np.asarray(idx[0])])
    np.testing.assert_allclose(wt[0], chosen / chosen.sum() * 2.5, rtol=1e-5)
    free, _ = se.route(x, w, group_limited=False, **kw)
    assert sorted(np.asarray(free[0]).tolist()) == [0, 3, 4]
    raw, wr = se.route(x, w, norm_topk=False, **kw)
    np.testing.assert_allclose(wr[0], chosen * 2.5, rtol=1e-5)
    cfg = dict(n_group=4, topk_group=2, num_experts_per_tok=3, routed_scaling_factor=2.5,
               norm_topk_prob=True)
    ridx, rwt = ref.router(x, w, cfg)
    assert sorted(np.asarray(ridx[0]).tolist()) == [3, 4, 6]
    np.testing.assert_allclose(np.sort(rwt[0]), np.sort(wt[0]), rtol=1e-5)


# --------------------------------------------------------------- the share
def _expert_layer(skew, seed=3, N=41, H=16, F=8, E=24):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    # every token's first coordinate is 2, and the router's first row leans
    # towards the low experts
    router = f(H, E).at[0].set(skew * jnp.linspace(2.0, -2.0, E))
    return f(N, H).at[:, 0].set(2.0), {"router": router, "shared_gate_up": 0.3 * f(H, 2 * F),
                     "shared_down": 0.3 * f(F, H), "experts_gate_up": 0.3 * f(E, H, 2 * F),
                     "experts_down": 0.3 * f(E, F, H)}


CFG = dict(n_group=4, topk_group=2, num_experts_per_tok=4, routed_scaling_factor=2.5,
           norm_topk_prob=True)


@pytest.mark.parametrize("skew", [0.0, 1.5], ids=["even", "uneven"])
@pytest.mark.parametrize("R", [1, 2, 4])
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer(R, skew):
    """ISSUE 34's share test: what the R shares' held experts give, plus the
    shared expert counted once, is the uncut reference's layer."""
    x, w = _expert_layer(skew)
    with jax.default_matmul_precision("highest"):
        whole = ref.sparse_ffn(x, w, CFG, first=0)
        idx, wt = se.route(x, w["router"], n_group=4, topk_group=2, top_k=4, scaling=2.5)
        total, pairs, held = se.swiglu(x, w["shared_gate_up"], w["shared_down"]), [], 24 // R
        for r in range(R):
            part, counts = se.held_experts(
                x, idx, wt, w["experts_gate_up"][r * held:(r + 1) * held],
                w["experts_down"][r * held:(r + 1) * held], first=r * held, held=held, window=32)
            mine = {k: (v[r * held:(r + 1) * held] if k.startswith("experts") else v)
                    for k, v in w.items()}
            np.testing.assert_allclose(        # the reference, given the same share
                part + se.swiglu(x, w["shared_gate_up"], w["shared_down"]),
                ref.sparse_ffn(x, mine, CFG, first=r * held), atol=2e-5)
            total, pairs = total + part, pairs + [int(counts.sum())]
    assert sum(pairs) == 41 * 4                          # no pair dropped
    if skew and R == 4:
        assert pairs[0] > 3 * pairs[-1]                  # the first share gets most of them
    np.testing.assert_allclose(total, whole, atol=5e-5)


def test_a_stack_of_layers_read_as_one_run_of_groups():
    """`group_offset`: the held experts of layer 2 of a `[3 x 6, ...]` stack,
    without slicing them out; the other layers' groups get no rows."""
    x, w = _expert_layer(0.0, E=18)
    idx, wt = se.route(x, w["router"][:, :6], n_group=1, topk_group=1, top_k=2, scaling=1.0)
    args = dict(first=0, held=6, window=64)
    alone, c0 = se.held_experts(x, idx, wt, w["experts_gate_up"][12:], w["experts_down"][12:], **args)
    stacked, c1 = jax.jit(lambda off: se.held_experts(
        x, idx, wt, w["experts_gate_up"], w["experts_down"], group_offset=off, **args))(12)
    np.testing.assert_allclose(stacked, alone, atol=1e-6)
    assert (np.asarray(c0) == np.asarray(c1)).all() and int(c0.sum()) == 41 * 2


# --------------------------------------------------------------- the model
@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = AXK1ForCausalLM(axk1_tiny())
    m.eval()
    return m


def test_model_logits_agree_with_the_reference(model):
    """Float32 both ways, the framework's ops against plain jax.numpy:
    2e-4 is a hundred times the difference seen (1.5e-6) and a thousandth of
    the logits' spread."""
    ids = np.random.default_rng(2).integers(0, 256, (2, 37)).astype(np.int32)
    got = model(paddle.to_tensor(ids)).numpy()
    for b in range(2):
        want = ref.forward_logits(weights_of(model), jnp.asarray(ids[b]), ref_config(model.config))
        assert float(np.abs(got[b] - np.asarray(want)).max()) < 2e-4
    assert got.std() > 0.05


def test_a_share_of_the_model_agrees_with_the_reference_given_the_same_share():
    paddle.seed(12)
    m = AXK1ForCausalLM(axk1_tiny(), expert_share=(2, 4), vocab_slice=(64, 192))
    m.eval()
    w = weights_of(m)
    assert w["sparse"]["experts_gate_up"].shape[:2] == (2, 4)      # 2 sparse layers, 4 of 16 held
    assert w["sparse"]["router"].shape == (2, 64, 16) and w["head"].shape == (64, 128)
    ids = np.random.default_rng(3).integers(0, 128, (1, 29)).astype(np.int32)
    want = ref.forward_logits(w, jnp.asarray(ids[0]), ref_config(m.config), (2, 4))
    assert float(np.abs(m(paddle.to_tensor(ids)).numpy()[0] - np.asarray(want)).max()) < 2e-4
    whole = ref.forward_logits(w, jnp.asarray(ids[0]), ref_config(m.config), (0, 4))
    assert float(np.abs(np.asarray(whole) - np.asarray(want)).max()) > 1e-3   # the share matters


def test_model_holds_its_layers_stacked_and_says_its_residency(model):
    assert AXK1ForCausalLM.serving_residency == "latent"
    assert model.axk1.dense.q_a_proj.shape == [1, 64, 24]
    assert model.axk1.sparse.experts_gate_up.shape == [2, 16, 64, 64]
    assert model.axk1.sparse.router._value.dtype == jnp.float32
    with pytest.raises(ValueError):
        AXK1ForCausalLM(axk1_tiny(), expert_share=(0, 3))           # 3 does not divide 16
    with pytest.raises(ValueError):
        AXK1Config(tie_word_embeddings=True)


def test_the_published_configuration_counts_what_issue_34_counts():
    from benchmark import flops_axk1, harness

    c = harness.load_json(os.path.join(ROOT, "benchmark", "configs", "ax-k1.json"))
    assert flops_axk1.attention_parameters(c) == 101_122_048
    assert flops_axk1.expert_parameters(c) == 44_040_192
    assert flops_axk1.router_parameters(c) == 7168 * 192
    assert flops_axk1.held_pairs_per_token(c) == 0.5
    assert flops_axk1.prompt_flops_per_token(c) == pytest.approx(3.02e9, rel=5e-3)
    assert flops_axk1.answer_flops_per_token(c) - flops_axk1.prompt_flops_per_token(c) \
        == 2 * 20480 * 7168
    assert flops_axk1.latent_row_bytes(c) == 1152 and flops_axk1.expert_bytes(c) == 88_080_384
    assert flops_axk1.latent_attention_flops_per_row(c) == 139_264
