"""Command A+ (`models/cohere2_moe.py`) on the CPU at a small size: the
Layer-path model against the benchmark's plain reference on seeded weights,
the rotary convention, the window's edge, the grouped-query kernel in
interpret mode against its jnp oracle, and the expert layer's shares adding
up to the uncut layer."""
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
from benchmark import reference_cohere2_moe as ref  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.models import (Cohere2MoEConfig, Cohere2MoEForCausalLM,  # noqa: E402
                               cohere2_moe_tiny)
from paddle_tpu.nn.functional import sparse_experts as se  # noqa: E402
from paddle_tpu.nn.functional import window_attention as wa  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as kernel  # noqa: E402
from paddle_tpu.serving import decode as decode_mod  # noqa: E402


def weights_of(model):
    return decode_mod._extract_cohere2(model)[0]


def built(share=(0, 1), vocab_slice=None, **overrides):
    paddle.seed(7)
    m = Cohere2MoEForCausalLM(cohere2_moe_tiny(initializer_range=0.16, **overrides),
                              expert_share=share, vocab_slice=vocab_slice)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return built()


# -------------------------------------------------------------- the model
@pytest.mark.parametrize("length", [8, 16, 17, 48])
def test_model_agrees_with_the_plain_reference(model, length):
    """Window 16: 16 fits one window, 17 is the first sequence whose last
    query does not see key 0, 48 is three windows."""
    ids = np.random.default_rng(length).integers(0, 256, (2, length)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    cfg = dataclasses.asdict(model.config)
    for row, mine in zip(ids, got):
        want = np.asarray(ref.forward_logits(weights_of(model), jnp.asarray(row), cfg))
        assert np.abs(mine - want).max() < 2e-5 * max(np.abs(want).max(), 1.0)


def test_reference_in_blocks_of_query_rows_is_the_reference(model):
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, 64).astype(np.int32))
    cfg, w = dataclasses.asdict(model.config), weights_of(model)
    whole = ref.hidden_states(w, ids, cfg)
    assert np.allclose(ref.hidden_states(w, ids, cfg, block=16), whole, atol=1e-5)


def test_a_share_and_a_vocabulary_slice_agree_with_the_reference_given_the_same():
    m = built(share=(1, 4), vocab_slice=(64, 192))
    assert m.model.vocab_rows == 128 and m.model.layers[0].held == 4
    assert m.model.layers[0].first == 4 and m.model.layers[0].experts_down.shape[0] == 4
    ids = np.random.default_rng(2).integers(0, 128, (1, 40)).astype(np.int32)
    got = np.asarray(m(paddle.to_tensor(ids))._value)[0]
    want = np.asarray(ref.forward_logits(weights_of(m), jnp.asarray(ids[0]),
                                         dataclasses.asdict(m.config), (1, 4)))
    assert got.shape == (40, 128) and np.abs(got - want).max() < 2e-5


def test_the_model_is_in_the_zoo_and_names_its_residency(model):
    assert models.Cohere2MoEForCausalLM is Cohere2MoEForCausalLM
    assert model.serving_residency == "windowed"
    c = Cohere2MoEConfig()
    assert c.layer_types == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.num_experts, c.num_experts_per_tok, c.num_shared_experts,
            c.sliding_window, c.vocab_size) == (4096, 128, 8, 128, 128, 8, 4, 4096, 262144)
    assert [c.window_of(i) for i in range(4)] == [4096, 4096, 4096, None]


@pytest.mark.parametrize("change", [
    dict(use_parallel_block=False), dict(tie_word_embeddings=False), dict(use_qk_norm=True),
    dict(first_k_dense_replace=1), dict(shared_expert_combination_strategy="sum"),
    dict(position_embedding_type="rope_neox"), dict(layer_types=["full_attention"]),
    dict(num_key_value_heads=3)], ids=lambda c: next(iter(c)))
def test_config_builds_nothing_but_command_a_plus(change):
    with pytest.raises(ValueError):
        cohere2_moe_tiny(**change)


# ---------------------------------------------------------------- rotation
@pytest.mark.parametrize("offset", [0, 5, 4095])
def test_rotary_is_interleaved_pairs(offset):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((6, 4, 16)).astype(np.float32))
    at = jnp.arange(6) + offset
    mine = wa.rope_interleaved(x, at, 50000.0)
    assert np.allclose(mine, ref.rope(x, 50000.0, at), atol=1e-5)
    # a pair turns by pos x theta^(-2i/d): pair 0 by `pos` radians
    pair = np.asarray(mine)[1, 0, :2]
    c, s = np.cos(float(at[1])), np.sin(float(at[1]))
    x0, x1 = np.asarray(x)[1, 0, :2]
    assert np.allclose(pair, [x0 * c - x1 * s, x1 * c + x0 * s], atol=1e-5)


# ------------------------------------------------------- attention's forms
def _paged(rng, lanes_at, page=8, G=2, d=16, layers=2):
    """A pool, tables and positions for lanes at the given depths."""
    pages = sum(p // page + 1 for p in lanes_at)
    kp, vp = (jnp.asarray(rng.standard_normal((layers, pages + 1, page, G * d)),
                          jnp.float32) for _ in range(2))
    tables = np.zeros((len(lanes_at), max(lanes_at) // page + 2), np.int32)
    nxt = 1
    for b, p in enumerate(lanes_at):
        n = p // page + 1
        tables[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return kp, vp, tables, np.asarray(lanes_at, np.int32)


@pytest.mark.parametrize("window", [None, 24, 17, 16, 1])
def test_grouped_oracle_has_the_references_window(window):
    rng = np.random.default_rng(4)
    kp, vp, tables, pos = _paged(rng, [0, 7, 16, 17, 40])
    q = jnp.asarray(rng.standard_normal((5, 2 * 16 * 16)), jnp.float32)
    keys, vals = (a[1][tables].reshape(5, -1, 32) for a in (kp, vp))
    got = wa.attend_grouped(q, keys, vals, jnp.asarray(pos), 2, 0.25, window)
    for b in range(5):
        T = keys.shape[1]
        want = ref.attention(q[b].reshape(1, 32, 16), keys[b].reshape(T, 2, 16),
                             vals[b].reshape(T, 2, 16), jnp.asarray(pos[b:b + 1]),
                             window, 0.25)
        assert np.allclose(got[b], np.asarray(want).reshape(-1), atol=1e-5)


@pytest.mark.parametrize("window", [None, 24, 17, 1])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_gqa_kernel_agrees_with_the_dense_oracle_in_interpret_mode(window, dtype, tol):
    """16 query heads a K/V head, as the published model has them; lanes
    before, at and past the window; with a window the columns behind it
    read 0, the pad page, as a released page's column does."""
    rng = np.random.default_rng(5)
    kp, vp, tables, pos = _paged(rng, [0, 7, 23, 24, 31, 40, 95])
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    q = jnp.asarray(rng.standard_normal((7, 2 * 16 * 16)), dtype)
    keys, vals = (a[1][tables].reshape(7, -1, 32) for a in (kp, vp))
    want = wa.attend_grouped(q, keys, vals, jnp.asarray(pos), 2, 0.25, window)
    released = tables.copy()
    if window:
        for b, p in enumerate(pos):
            released[b, :max(p - (window - 1), 0) // 8] = 0
    got = kernel.gqa_paged_attention(q, kp, vp, 1, jnp.asarray(released), jnp.asarray(pos),
                                     kv_heads=2, scale=0.25, window=window, interpret=True)
    assert got.dtype == q.dtype and got.shape == q.shape
    assert np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max() < tol


def test_gqa_kernel_cuts_the_table_to_the_windows_columns():
    assert kernel.window_columns(4096, 256) == 17 and kernel.window_columns(16, 8) == 3
    assert kernel.window_columns(1, 8) == 1 and kernel.window_columns(9, 8) == 2
    # the grid is as narrow as the window whatever the table's width
    rng = np.random.default_rng(6)
    kp, vp, tables, pos = _paged(rng, [200])
    q = jnp.asarray(rng.standard_normal((1, 512)), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *a: kernel.gqa_paged_attention(
        *a, kv_heads=2, scale=0.25, window=16, interpret=True))(
            q, kp, vp, 0, jnp.asarray(tables), jnp.asarray(pos))
    calls = [e for e in jaxpr.jaxpr.eqns[-1].params["jaxpr"].jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    (call,) = calls
    assert tuple(call.params["grid_mapping"].grid) == (1, 1)      # 3 columns, one step


def test_paged_and_latent_kernels_keep_their_signatures():
    import inspect

    names = lambda f: list(inspect.signature(f.__wrapped__).parameters)
    assert names(kernel.paged_attention) == ["q", "k_pool", "v_pool", "layer", "tables",
                                             "positions", "heads", "scale", "interpret"]
    assert names(kernel.latent_paged_attention) == ["q", "pool", "layer", "tables",
                                                    "positions", "v_cols", "scale", "interpret"]


# --------------------------------------------------------- the expert layer
@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_shares_routed_parts_and_the_shared_part_once_add_up_to_the_uncut_layer(shares):
    """What `shares` processes that hold 16 / shares experts each give, with
    what every one of them computes alike (the shared experts' mean) counted
    once, is the reference's whole feed-forward."""
    rng = np.random.default_rng(8)
    h, f, E, S, k = 32, 16, 16, 2, 4
    w = {"router": rng.standard_normal((h, E)).astype(np.float32),
         "experts_gate_up": 0.3 * rng.standard_normal((E, h, 2 * f)).astype(np.float32),
         "experts_down": 0.3 * rng.standard_normal((E, f, h)).astype(np.float32),
         "shared_gate_up": 0.3 * rng.standard_normal((h, 2 * S * f)).astype(np.float32),
         "shared_down": 0.3 * rng.standard_normal((S * f, h)).astype(np.float32)}
    w = {name: jnp.asarray(a) for name, a in w.items()}
    n = jnp.asarray(rng.standard_normal((24, h)).astype(np.float32))
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True, "num_shared_experts": S}
    want = ref.routed_part(n, w, cfg, 0) + ref.shared_part(n, w, cfg)
    idx, wt = se.route(n, w["router"], n_group=1, topk_group=1, top_k=k, scaling=1.0,
                       norm_topk=True, group_limited=False)
    held = E // shares
    total, pairs = se.swiglu(n, w["shared_gate_up"], w["shared_down"]) / S, 0
    for r in range(shares):
        part, counts = se.held_experts(
            n, idx, wt, w["experts_gate_up"][r * held:(r + 1) * held],
            w["experts_down"][r * held:(r + 1) * held], first=r * held, held=held)
        total, pairs = total + part, pairs + int(counts.sum())
    assert pairs == 24 * k and np.allclose(total, want, atol=2e-5)
    # a share alone is the reference given that share
    one = ref.routed_part(n, dict(w, experts_gate_up=w["experts_gate_up"][:held],
                                  experts_down=w["experts_down"][:held]), cfg, 0)
    mine, _ = se.held_experts(n, idx, wt, w["experts_gate_up"][:held],
                              w["experts_down"][:held], first=0, held=held)
    assert np.allclose(mine, one, atol=2e-5)


def test_shared_experts_are_averaged_not_summed():
    rng = np.random.default_rng(9)
    w = {"shared_gate_up": jnp.asarray(rng.standard_normal((8, 2 * 3 * 4)), jnp.float32),
         "shared_down": jnp.asarray(rng.standard_normal((3 * 4, 8)), jnp.float32)}
    n = jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
    cfg = {"num_shared_experts": 3}
    mean = ref.shared_part(n, w, cfg)
    assert np.allclose(mean, se.swiglu(n, w["shared_gate_up"], w["shared_down"]) / 3, atol=1e-5)
    assert np.allclose(ref.shared_part(n, w, cfg, average=False), 3 * mean, atol=1e-5)
