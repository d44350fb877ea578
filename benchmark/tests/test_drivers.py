"""Every driver's run() called directly on the CPU at a tiny size (run.py
itself keeps its refusal off a TPU and has no CPU mode)."""
import math
import os
import subprocess
import sys

import pytest

from benchmark import harness, trace_reduce
from benchmark.drivers import closed_loop, open_loop, train_stream

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL_CHAT = dict(prompt={"median": 20, "sigma": 0.6, "min": 4, "max": 60},
                  answer={"median": 8, "sigma": 0.5, "min": 2, "max": 16},
                  max_total=100, check_max_total=64, check_answer_max=8,
                  distinct_requests=32, ramp_seconds=0.5, drain_seconds=60)


def tiny(**more):
    return dict(harness.load_json(os.path.join(HERE, "tiny-gpt.json")), **more)


def mix(name, **changes):
    return dict(harness.load_json(f"{harness.HERE}/traffic/{name}.json"), **changes)


def test_train_stream_counts_steps_and_traces():
    traffic = mix("pretrain-1k", batch=2, seq=32, distinct_batches=4,
                  first_loss_tolerance=0.1, grad_tolerance=0.02, trace_seconds=1)
    r = train_stream.run(tiny(), traffic, 2147483700, 5.0, True)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] % traffic["read_loss_every"] == 0 and r["attempted"] >= 10
    f = r["facts"]
    assert f["tokens_per_step"] == 64 and f["discovery_s"] > 0
    assert r["measured"]["train_tokens_per_s"] == pytest.approx(
        f["steps"] * 64 / f["window_s"])
    assert f["window_s"] < 3.0            # a traced run's window is trace_seconds
    assert {s[0] for s in r["spans"]} == {"data_next", "step_dispatch", "loss_read"}
    # the CPU's capture holds the benchmark's annotation and no TPU plane
    c = r["capture"]
    trace = trace_reduce.load(c.path, c.t_sync, c.t0, c.t1)
    assert trace.devices == [] and trace_reduce.busy_seconds(trace) == 0.0


def _zeroed(attention):
    import jax.numpy as jnp

    return lambda q, k, v, **kw: jnp.zeros_like(q)


def _fp8_operands(attention):
    """q, k and v rounded to float8 (3 mantissa bits) on the way in; the
    gradient passes straight through the rounding."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        return x + jax.lax.stop_gradient(x.astype(jnp.float8_e4m3fn).astype(x.dtype) - x)

    return lambda q, k, v, **kw: attention(rounded(q), rounded(k), rounded(v), **kw)


def _backward_drops_dk(attention):
    """The forward pass is right; the backward pass returns no key gradient."""
    import jax
    import jax.numpy as jnp

    def faulty(q, k, v, **kw):
        def plain(q, k, v):
            return attention(q, k, v, **kw)

        f = jax.custom_vjp(plain)

        def backward(saved, g):
            dq, dk, dv = jax.vjp(plain, *saved)[1](g)
            return dq, jnp.zeros_like(dk), dv

        f.defvjp(lambda q, k, v: (plain(q, k, v), (q, k, v)), backward)
        return f(q, k, v)

    return faulty


@pytest.mark.parametrize("fault", [_zeroed, _fp8_operands, _backward_drops_dk])
def test_train_stream_is_incorrect_when_attention_is_at_fault(fault, monkeypatch, capsys):
    """What REVIEW (PR 24) asked for: the loss at random initialisation does
    not see the attention, the first step's gradients do. The tolerance here is
    twice what the sound bfloat16 program reads at this size (0.009)."""
    from paddle_tpu.nn.functional import attention

    monkeypatch.setattr(attention, "_xla_attention", fault(attention._xla_attention))
    traffic = mix("pretrain-1k", batch=2, seq=32, distinct_batches=4,
                  first_loss_tolerance=0.1, grad_tolerance=0.02)
    r = train_stream.run(tiny(), traffic, 5, 0.3, False)
    out = capsys.readouterr().out
    assert not r["correct"]
    assert "INCORRECT: not true that the first step's attention gradients" in out
    assert "INCORRECT: not true that the first loss" not in out


def test_train_stream_builds_a_dp2_mp2_mesh_from_a_layout_block():
    import jax

    assert len(jax.devices()) == 4
    traffic = mix("pretrain-1k", batch=4, seq=32, distinct_batches=2,
                  first_loss_tolerance=0.1)
    r = train_stream.run(tiny(layout={"dp": 2, "mp": 2}), traffic, 5, 0.5, False)
    assert r["correct"] and r["facts"]["chips"] == 4
    from paddle_tpu.distributed import env

    assert dict(env.get_mesh().shape)["dp"] == 2 and dict(env.get_mesh().shape)["mp"] == 2


def test_closed_loop_keeps_the_engine_full():
    r = closed_loop.run(tiny(serve={"weights_dtype": "bfloat16"}),
                        mix("chat-closed", **SMALL_CHAT), 2147483700, 1.5, True)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["measured"]["serve_tokens_per_s"] > 0
    assert r["facts"]["lanes"] == 4 and 0 < r["facts"]["pool_peak_share"] <= 100
    kinds = {s[3].get("kind") for s in r["spans"]}
    assert {s[0] for s in r["spans"]} == {"serving.decode"} and kinds == {"prefill", "decode"}
    lanes = [s[3]["lanes"] for s in r["spans"] if s[3]["kind"] == "decode"]
    assert sum(lanes) / len(lanes) > 3.0


def test_open_loop_times_each_request_from_when_it_was_due():
    r = open_loop.run(tiny(serve={"weights_dtype": "bfloat16"}),
                      mix("chat-open", rate_per_s=20.0, **SMALL_CHAT), 5, 2.0, False)
    assert r["correct"] and r["failed"] == 0
    assert 39 <= r["attempted"] <= 41     # 20/s for 2 s, on a fixed schedule
    assert math.isfinite(r["measured"]["norm_latency_p95_ms"])
    assert r["measured"]["norm_latency_p95_ms"] > 0
    assert len(r["facts"]["queue_waits_s"]) == r["attempted"]
    assert min(r["facts"]["queue_waits_s"]) >= 0


def test_run_py_refuses_off_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "train-gpt2m-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "no TPU" in out.stderr and not out.stdout.strip().endswith("}")
