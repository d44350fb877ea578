"""Flagship GPT model tests: eager forward/backward, compiled train step,
TP-vs-serial numerical parity (reference analog:
test/collective/fleet/hybrid_parallel_mp_model.py compares parallel and
serial model losses)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit.api import TrainStep
from paddle_tpu.models import (
    GPTForCausalLM,
    GPTPretrainingCriterion,
    gpt_tiny,
)


def _batch(cfg, batch=2, seq=16, seed=0):
    rs = np.random.RandomState(seed)
    return paddle.Tensor(
        rs.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int64),
        stop_gradient=True,
    )


def test_forward_shape_and_grad():
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    ids = _batch(cfg)
    logits = model(ids)
    assert logits.shape == [2, 16, cfg.vocab_size]
    loss = crit(logits, ids)
    loss.backward()
    assert model.gpt.h[0].attn.qkv_proj.weight.grad is not None
    assert np.isfinite(float(loss.numpy()))


def test_criterion_is_next_token_cross_entropy():
    """The criterion shifts the labels and ignores the last position; the
    value is the mean cross entropy of logits[:, :-1] against labels[:, 1:]."""
    cfg = gpt_tiny()
    rs = np.random.RandomState(3)
    logits = rs.randn(2, 16, cfg.vocab_size).astype(np.float32)
    ids = _batch(cfg)
    got = float(GPTPretrainingCriterion(cfg)(paddle.Tensor(logits), ids).numpy())
    z = logits[:, :-1].reshape(-1, cfg.vocab_size).astype(np.float64)
    y = ids.numpy()[:, 1:].reshape(-1)
    logp = z - z.max(-1, keepdims=True)
    logp -= np.log(np.exp(logp).sum(-1, keepdims=True))
    np.testing.assert_allclose(got, -logp[np.arange(len(y)), y].mean(), rtol=1e-5)


@pytest.mark.slow
def test_train_step_loss_decreases():
    cfg = gpt_tiny()
    paddle.seed(7)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    ids = _batch(cfg, batch=4, seq=32)

    step = TrainStep(model=model, optimizer=opt,
                     loss_fn=lambda x: crit(model(x), x))
    first = float(step(ids).numpy())
    for _ in range(10):
        last = float(step(ids).numpy())  # noqa: TS107 (test asserts per-step loss on purpose)
    assert last < first, (first, last)


def test_untied_head():
    cfg = gpt_tiny(tie_word_embeddings=False)
    model = GPTForCausalLM(cfg)
    ids = _batch(cfg)
    assert model(ids).shape == [2, 16, cfg.vocab_size]


def test_tensor_parallel_parity():
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(3)
    serial = GPTForCausalLM(gpt_tiny())
    paddle.seed(3)
    tp_cfg = gpt_tiny(tensor_parallel=True, sequence_parallel=True)
    tp = GPTForCausalLM(tp_cfg)
    tp.set_state_dict(serial.state_dict())

    ids = _batch(tp_cfg, batch=4, seq=16)
    out_serial = serial(ids)
    out_tp = tp(ids)
    np.testing.assert_allclose(
        out_serial.numpy(), out_tp.numpy(), rtol=2e-3, atol=2e-3
    )


@pytest.mark.slow
def test_graft_entry_single_and_multichip():
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        import __graft_entry__ as ge
    finally:
        sys.path.pop(0)

    import jax

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (4, 32, 512)
    ge.dryrun_multichip(8)
