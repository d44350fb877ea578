"""What the two serving drivers share: the request mix drawn from a traffic
file, the engine built from a configuration file, the check of its answers
against reference.py, and the reading of a finished request.

The mix is a fixed set: n (prompt, answer) lengths drawn once from
`lengths_seed`, so that every --seed offers the same set of sizes in another
order, with other token ids. Different seeds then differ by order alone, not
by the amount of work. The driver says how many: the closed loop its mix's
`distinct_requests`, the open loop as many as arrive in one window.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import harness, reference

TENANT = "bench"


def _lognormal(rng, spec: dict, n: int):
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def length_pool(traffic: dict, n: int, max_total=None, answer_max=None):
    """The mix's first n (prompt, answer) lengths, the same for every seed."""
    rng = np.random.default_rng(traffic["lengths_seed"])
    prompts = _lognormal(rng, traffic["prompt"], n)
    answers = _lognormal(rng, traffic["answer"], n)
    if answer_max:
        answers = np.minimum(answers, answer_max)
    total = max_total or traffic["max_total"]
    prompts = np.minimum(prompts, total - traffic["answer"]["min"])
    answers = np.minimum(answers, total - prompts)
    return prompts, answers


class RequestStream:
    """Request i of a run: lengths from the pool in this seed's order (the
    permutation repeats when it is used up), token ids from this seed."""

    def __init__(self, traffic: dict, n: int, vocab: int, seed: int, **limits):
        self.prompts, self.answers = length_pool(traffic, n, **limits)
        rng = np.random.default_rng([int(seed), 1])
        self.order = rng.permutation(len(self.prompts))
        self.tokens = rng.integers(0, vocab, (len(self.prompts), int(self.prompts.max())),
                                   dtype=np.int32)

    def __call__(self, i: int):
        j = self.order[i % len(self.order)]
        return self.tokens[j, :self.prompts[j]], int(self.answers[j])


def arrival_times(traffic: dict, n: int, seed: int, horizon_s: float):
    """Arrivals at `rate_per_s` on a fixed schedule: n exponential gaps drawn
    once (from `lengths_seed`), scaled so that one pass through them lasts
    exactly n / rate_per_s seconds, in this seed's order, repeated until the
    horizon. Any stretch of that length then holds every gap once: seeds
    differ by order alone. With n = rate_per_s x the window (the open loop's
    choice) every window holds the same n arrivals. This is not a Poisson
    process: the gaps have its shape, the count in a window does not vary."""
    gaps = np.random.default_rng([traffic["lengths_seed"], 2]).exponential(1.0, n)
    gaps *= n / traffic["rate_per_s"] / gaps.sum()
    order = np.random.default_rng([int(seed), 2]).permutation(len(gaps))
    times, t, i = [], 0.0, 0
    while True:
        t += gaps[order[i % len(order)]]
        if t >= horizon_s:
            return np.asarray(times)
        times.append(t)
        i += 1


def serving_model(config: dict, seed: int):
    """The program's model in eval mode, its weights cast as the
    configuration's `serve.weights_dtype` says."""
    import paddle_tpu as paddle

    model = harness.build_model(config, seed)
    if config["serve"]["weights_dtype"] != "float32":
        paddle.amp.decorate(model, level="O2", dtype=config["serve"]["weights_dtype"])
    model.eval()
    return model


def build_engine(config: dict, seed: int):
    """That model behind the program's DecodeEngine with the configuration's
    `engine` arguments, warmed."""
    import jax.numpy as jnp

    from paddle_tpu import serving

    t = time.perf_counter()
    model = serving_model(config, seed)
    engine = serving.DecodeEngine(model, **config["engine"])
    t_built = time.perf_counter()
    engine.warmup()
    harness.log(f"engine: {engine.max_slots} lanes, pool "
                f"{tuple(engine.kv_pool.k.shape)} {jnp.dtype(engine.kv_pool.k.dtype).name} "
                f"x2; built in {t_built - t:.1f} s, {len(engine.programs.warmed)} "
                f"programs warmed in {time.perf_counter() - t_built:.1f} s")
    return model, engine


def start_capture(trace: bool):
    """A traced run's window opens: the program's tracer on, the profiler on."""
    if not trace:
        return None
    from paddle_tpu.observability.tracing import tracer

    tracer.reset()
    tracer.enable()
    capture = harness.TraceCapture()
    capture.start()
    return capture


def stop_capture(capture) -> list:
    """The window closes: profiler and tracer off. Returns the engine's own
    `serving.decode` spans (one per prefill or decode step) as host spans."""
    spans = harness.Spans(capture is not None)
    if capture is not None:
        from paddle_tpu.observability.tracing import tracer

        capture.stop()
        tracer.disable()
        spans.extend_from_tracer(tracer, {"serving.decode"})
    return spans.items


def verdict(check: dict, traffic: dict, compiles, leaked: int, window_ok: bool) -> bool:
    return harness.verdict({
        "every check request was answered in full": check["complete"],
        "no returned token is further than the tolerance from the reference's best":
            check["worst_gap"] <= traffic["logit_tolerance"],
        "nothing compiled after warm-up": compiles == 0,
        "no page leaked after the drain": leaked == 0,
        "no request of the window failed": window_ok,
    })


def wait_for(request, deadline: float) -> None:
    """Block until the request is resolved, or the deadline (perf_counter)."""
    try:
        request.result(timeout=max(0.0, deadline - time.perf_counter()))
    except Exception:  # noqa: BLE001 - `finished` reports how it ended
        pass


def finished(request, asked: int):
    """(tokens, ok) of a resolved request: ok when it was answered in full."""
    try:
        tokens = request.result(timeout=0)
    except Exception as e:  # noqa: BLE001 - refused, shed or failed: it counts as failed
        harness.log(f"request {request.id} failed: {type(e).__name__}: {e}")
        return 0, False
    return len(tokens), len(tokens) == asked


def check_answers(model, engine, config: dict, traffic: dict, seed: int) -> dict:
    """Outside the window: `check_requests` seeded requests through the
    engine; then, for each token it returned, the reference's logit for that
    token against the reference's largest logit at that position, given the
    engine's own earlier tokens. Tokens themselves flip on rounding with
    random weights; a token from far down the reference's distribution does
    not come from rounding."""
    import jax
    import jax.numpy as jnp

    n, width = traffic["check_requests"], traffic["check_max_total"]
    stream = RequestStream(traffic, n, config["tokenizer_vocab"], seed + 1,
                           max_total=width, answer_max=traffic["check_answer_max"])
    asked = [stream(i) for i in range(n)]
    sent = [engine.submit(TENANT, p, max_new_tokens=a) for p, a in asked]
    ids = np.zeros((n, width), np.int32)
    rows, cols, picked, complete = [], [], [], True
    for i, (req, (prompt, a)) in enumerate(zip(sent, asked)):
        try:
            out = np.asarray(req.result(timeout=traffic["drain_seconds"]))
        except Exception as e:  # noqa: BLE001
            harness.log(f"check request {i} failed: {type(e).__name__}: {e}")
            complete = False
            continue
        complete &= len(out) == a
        L = len(prompt)
        ids[i, :L] = prompt
        ids[i, L:L + len(out)] = out
        rows += [i] * len(out)
        cols += list(range(L - 1, L - 1 + len(out)))   # position L-1+j predicts out[j]
        picked += out.tolist()
    if not picked:
        return {"complete": False, "worst_gap": float("inf"), "tokens": 0}
    weights = reference.weights_of(model)

    @jax.jit
    def gaps(weights, ids, rows, cols, picked):
        hidden = reference.hidden_states(weights, ids, config["n_head"],
                                         config["layer_norm_epsilon"])
        logits = reference.logits_at(weights, hidden[rows, cols])
        return logits.max(-1) - jnp.take_along_axis(logits, picked[:, None], -1)[:, 0]

    # one shape whatever the engine returned: pad the position list
    count = n * traffic["check_answer_max"]
    pad = count - len(picked)
    got = np.asarray(gaps(weights, ids, np.asarray(rows + [0] * pad, np.int32),
                          np.asarray(cols + [0] * pad, np.int32),
                          np.asarray(picked + [0] * pad, np.int32)))[:len(picked)]
    return {"complete": complete, "worst_gap": float(got.max()),
            "exact": int((got == 0).sum()), "tokens": len(picked)}
