"""Training driver: one model under jit.api.TrainStep, fed a seeded cycle of
token batches, steps dispatched back to back for the window.

Traffic parameters: batch, seq, distinct_batches (the cycle), warmup_steps,
read_loss_every (the host reads the loss every that many steps, as a training
loop that logs does; in between it only dispatches), trace_seconds (a traced
run's window is the shorter of this and --seconds), first_loss_tolerance,
grad_tolerance (see `first_step_gradient_error`).
The configuration's optional `layout` block (dp, mp) builds a fleet mesh.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark import flops, harness, reference

# the matrices whose gradients `correct` compares, in the first, the middle
# and the last block: the attention projections, which the flash kernel's
# forward and backward feed (every block runs the same kernel on other numbers)
GRAD_NAMES = ("qkv_w", "out_w")


def _mesh_sharding(layout):
    """fleet.init for a dp x mp layout; the batch is split over dp."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import env, fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": layout.get("dp", 1),
                               "mp_degree": layout.get("mp", 1),
                               "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    return NamedSharding(env.get_mesh(), P("dp", None))


def first_step_gradient_error(model, optimizer, beta1: float, want_grads):
    """After exactly one optimizer step from zero moments, Adam's first moment
    is (1 - beta1) x the gradient, so the step program's own gradients can be
    read from the optimizer's public state. Returns the worst relative error
    against `want_grads` (as reference.block_grads gives them) and where it
    was, over each of those blocks' output projection and the query, key and
    value thirds of its fused projection: taken apart, because with random weights
    the value path carries nearly all of that matrix's gradient, and a fault
    in the query or key path (the softmax's backward) would hide behind it."""
    state = optimizer.state_dict()
    heads = model.config.num_attention_heads
    errors = {}
    for i, want in want_grads.items():
        blk = model.gpt.h[i]
        for name, layer in zip(GRAD_NAMES, (blk.attn.qkv_proj, blk.attn.out_proj)):
            moment = state[f"{layer.weight.name}_moment1"]
            got = np.asarray(moment._value, np.float64) / (1.0 - beta1)
            parts = {name: (got, np.asarray(want[name]))}
            if name == "qkv_w":     # columns are laid out [heads, (q, k, v), d]
                split = [a.reshape(a.shape[0], heads, 3, -1) for a in parts[name]]
                parts = {f"{name}[{part}]": tuple(a[:, :, j] for a in split)
                         for j, part in enumerate("qkv")}
            for part, (g, w) in parts.items():
                err = reference.relative_error(g, w)
                errors[f"block {i} {part}"] = err if math.isfinite(err) else math.inf
    where = max(errors, key=errors.get)
    by_part = {part: max(e for k, e in errors.items() if k.endswith(" " + part))
               for part in sorted({k.split(" ", 2)[2] for k in errors})}
    harness.log("first step's gradients against the reference, worst relative "
                "error by matrix: " + ", ".join(f"{k} {e:.5f}" for k, e in by_part.items()))
    return errors[where], where


def run(config, traffic, seed, seconds, trace):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.amp import debugging
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.observability.tracing import tracer

    cache = harness.CacheCounter()
    device_kind = jax.devices()[0].device_kind
    train = config["train"]
    layout = config.get("layout") or {}
    chips = layout.get("dp", 1) * layout.get("mp", 1)
    sharding = _mesh_sharding(layout) if chips > 1 else None
    batch, seq = traffic["batch"], traffic["seq"]

    model = harness.build_model(config, seed, tensor_parallel=chips > 1
                                and layout.get("mp", 1) > 1)
    rng = np.random.default_rng(seed)
    cycle = rng.integers(0, config["tokenizer_vocab"],
                         (traffic["distinct_batches"], batch, seq)).astype(np.int64)

    criterion = GPTPretrainingCriterion(model.config)
    paddle.amp.decorate(model, level=train["amp_level"], dtype=train["amp_dtype"])

    # The reference on the first batch, from a float32 copy of the weights as
    # amp left them (so both sides start from the same numbers) and before
    # discovery fills the memory: the loss, and its gradient with respect to
    # the attention matrices of the first, the middle and the last block.
    t = time.perf_counter()
    weights = reference.weights_of(model)
    last = config["n_layer"] - 1
    want, want_grads = jax.jit(reference.block_grads, static_argnums=(2, 3, 4, 5))(
        weights, cycle[0], config["n_head"], config["layer_norm_epsilon"], GRAD_NAMES,
        tuple(sorted({0, last // 2, last})))
    want = float(want)
    want_grads = jax.device_get(want_grads)
    del weights
    harness.log(f"reference loss {want:.4f} on the first batch, and its gradients "
                f"in blocks {sorted(want_grads)} ({time.perf_counter() - t:.1f} s)")

    optimizer = getattr(paddle.optimizer, train["optimizer"])(
        learning_rate=train["learning_rate"], beta1=train["beta1"],
        parameters=model.parameters())

    def loss_fn(ids):
        with paddle.amp.auto_cast(level=train["amp_level"], dtype=train["amp_dtype"]):
            logits = model(ids)
        return criterion(logits, ids)

    step = TrainStep(model=model, optimizer=optimizer, loss_fn=loss_fn)

    def place(ids):
        if sharding is not None:
            ids = jax.device_put(ids, sharding)
        return paddle.Tensor(ids, stop_gradient=True)

    batches = [place(ids) for ids in cycle]

    # First call: the step runs op by op (discovery, the jit.build span), then
    # traces and compiles the one program.
    tracer.reset()
    tracer.enable()
    debugging.enable_operator_stats_collection()
    try:
        losses = [float(step(batches[0]).numpy())]
        ops = debugging.get_operator_stats()
    finally:
        debugging.disable_operator_stats_collection()
        tracer.disable()
    grad_error, grad_worst = first_step_gradient_error(
        model, optimizer, train["beta1"], want_grads)
    del want_grads
    harness.log(f"worst of them: {grad_error:.5f} ({grad_worst})")
    discovery_s = sum(e["dur"] for e in tracer.to_chrome_trace()["traceEvents"]
                      if e.get("name") == "jit.build") / 1e6
    tracer.reset()
    n = 1
    for _ in range(traffic["warmup_steps"] - 1):
        losses.append(float(step(batches[n % len(batches)]).numpy()))
        n += 1
    first_loss = losses[0]
    harness.log("warm-up: " + " ".join(f"{x:.4f}" for x in losses))

    window = min(seconds, traffic["trace_seconds"]) if trace else seconds
    every = traffic["read_loss_every"]
    spans = harness.Spans(trace)
    capture = harness.TraceCapture() if trace else None
    if capture:
        capture.start()
    setup_s = time.perf_counter() - harness.PROCESS_START
    steps, reads = 0, []
    t_start = time.perf_counter()
    while True:
        with spans.span("data_next"):
            ids = batches[n % len(batches)]
        with spans.span("step_dispatch"):
            loss = step(ids)
        n += 1
        steps += 1
        if steps % every == 0:
            with spans.span("loss_read"):
                reads.append(float(loss.numpy()))
            if time.perf_counter() - t_start >= window:
                break
    elapsed = time.perf_counter() - t_start
    if capture:
        capture.stop()
    harness.note_memory()
    tokens_per_step = batch * seq
    rate = steps * tokens_per_step / elapsed
    harness.log(f"window: {steps} steps, {steps * tokens_per_step} tokens in "
                f"{elapsed:.3f} s; losses first {first_loss:.4f} (reference "
                f"{want:.4f}), last read {reads[-1]:.4f}")

    attention = sorted(op for op in ops if op.startswith("sdpa_"))
    checks = {
        "first loss within tolerance of the reference":
            abs(first_loss - want) <= traffic["first_loss_tolerance"],
        "the first step's attention gradients are within tolerance of the reference's":
            grad_error <= traffic["grad_tolerance"],
        "every loss read is finite": all(math.isfinite(x) for x in losses + reads),
        "the loss fell": reads[-1] < first_loss,
        f"attention ran as {train['attention_op']}": attention == [train["attention_op"]],
        "TrainStep did not fall back to eager": step.fallback_reason is None,
    }
    correct = harness.verdict(checks, f" (attention {attention}, fallback "
                                      f"{step.fallback_reason!r})")

    facts = {
        "device_kind": device_kind, "chips": chips,
        "tokens_per_step": tokens_per_step, "batch": batch, "seq": seq,
        "layers": config["n_layer"], "heads": config["n_head"],
        "head_dim": config["n_embd"] // config["n_head"],
        "flops_per_token": flops.train_flops_per_token(
            harness.matmul_parameters(config), config["n_layer"], seq, config["n_embd"]),
        "discovery_s": discovery_s,
        "cache_hits": cache.hits, "cache_misses": cache.misses,
        "steps": steps, "window_s": elapsed,
    }
    return {
        "correct": correct,
        "attempted": steps, "failed": 0,
        "measured": {"train_tokens_per_s": rate, "setup_s": setup_s},
        "facts": facts, "spans": spans.items, "capture": capture,
    }
