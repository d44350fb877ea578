"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the repo's two main paths once, through the entry points a
user calls, at the full width of GPT-2 small on a TPU:

    device     what jax sees; anything but a TPU ends the run
    kernel     Pallas flash attention, compiled by Mosaic, forward and
               backward, against the XLA composition on the same chip
    trainer    GPTForCausalLM(gpt2_small) under amp O2 bf16 + AdamW through
               jit.api.TrainStep, batch 8 x 1024
    server     the trained model behind serving.DecodeEngine(kv_mode="paged")
    multichip  dp 2 x mp 2 fleet training when four chips are visible

It is a smoke, not a measurement: the seconds it prints say where set-up time
goes, and no number here is a benchmark metric. There is no CPU mode, no
retry and no fallback. The first phase that fails ends the run with a
non-zero exit; the last line of a passing run is one JSON object naming the
device.

    python chip_smoke.py

JAX's persistent compilation cache is kept where JAX_COMPILATION_CACHE_DIR
says, else in <checkout>/.jax_cache; a second process finds the first one's
programs there and reports the hits.
"""
from __future__ import annotations

import contextlib
import json
import math
import time

import numpy as np

BATCH, SEQ = 8, 1024
TRAIN_STEPS = 4
# The four-chip phase cuts depth, not width: sharding is what it checks.
MULTICHIP_LAYERS = 4
MULTICHIP_STEPS = 3

# Flash-attention parity, |kernel - oracle| <= PARITY_TOL * (1 + |oracle|).
# Both sides see the same bf16 inputs and the oracle is the XLA composition
# run in float32, so what differs is the kernel's rounding. P is cast to bf16
# before P.V and dS before dS.K, and the output is stored in bf16 (each a
# relative 2^-8); the backward's delta = rowsum(dO * O) is then taken from
# that rounded output, as in every flash-attention backward, which is the
# largest term. On the v5e this came to 1.1e-2 for dq and dk at the first
# shape below (3e-3 for the output), beside 5e-3 in interpret mode at a small
# shape. 2^-5 leaves a factor of three over that. The bf16 XLA composition's
# own error against the same oracle is printed next to it as the yardstick:
# the kernel should be no worse than the path it replaces.
PARITY_TOL = 2.0 ** -5
PARITY_SHAPES = (
    (8, 1024, 12, 64),    # gpt2_small's own attention at batch 8 x 1024
    (2, 2048, 8, 128),    # Llama-like head width
    (2, 197, 4, 64),      # a sequence no 128-block divides: full-extent blocks
)

# A random-init causal LM predicts nearly uniformly. With tied embeddings of
# std 0.02 at width 768 the logits have variance 768 * 0.02^2 = 0.31, which
# adds about half of that to ln(vocab).
FIRST_LOSS_SLACK = 0.5

# Serving: 4 lanes make 3 decode batch rungs, times 2 table rungs, plus 2 x 2
# prefill rungs: 10 compiled programs, not a ladder.
SERVE = dict(max_slots=4, max_seq=512, seq_buckets=[64, 256],
             prefill_max_batch=2, page_size=256, kv_dtype="bfloat16")
PROMPT_LENS = (12, 200, 40, 150, 33, 100)
NEW_TOKENS = 32


class CacheCounter:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@contextlib.contextmanager
def phase(name):
    """Print one phase's outcome with its seconds and the facts it gathered.
    A failure prints too, then propagates, so the process ends non-zero at
    the first failed phase."""
    facts = {}
    t0 = time.perf_counter()

    def report(outcome):
        line = " ".join(f"{k}={v}" for k, v in facts.items())
        print(f"[{name}] {outcome} {time.perf_counter() - t0:.1f} s {line}",
              flush=True)

    try:
        yield facts
    except BaseException as e:
        report(f"FAILED {type(e).__name__}: {e} after")
        raise
    report("ok")


def check(cond, message):
    if not cond:
        raise AssertionError(message)


def device_phase():
    import jax

    devices = jax.devices()
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices)}
    print(f"[device] platform={d.platform} device_kind={d.device_kind!r} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU. jax reports platform {d.platform!r} "
            f"({d.device_kind}); this script has no CPU mode.")
    return device


def kernel_phase(shape, facts):
    """Compiled Pallas flash attention, forward and backward, against
    nn.functional.attention._xla_attention in float32 on the same device."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_value

    scale = 1.0 / math.sqrt(shape[-1])
    q, k, v, do = (jax.random.normal(key, shape, jnp.bfloat16)
                   for key in jax.random.split(jax.random.PRNGKey(0), 4))

    def fwd_bwd(attention, *qkv):
        out, vjp = jax.vjp(attention, *qkv)
        return (out, *vjp(do.astype(out.dtype)))

    def xla(*qkv):
        return fwd_bwd(lambda q, k, v: _xla_attention(
            q, k, v, causal=True, scale=scale), *qkv)

    kernel = jax.jit(lambda *qkv: fwd_bwd(
        lambda q, k, v: flash_attention_value(q, k, v, True, scale), *qkv))
    check("tpu_custom_call" in kernel.lower(q, k, v).as_text(),
          "the kernel's program holds no Mosaic custom call")

    t0 = time.perf_counter()
    got = jax.block_until_ready(kernel(q, k, v))
    facts["first_call_s"] = round(time.perf_counter() - t0, 2)
    want = jax.jit(xla)(*(x.astype(jnp.float32) for x in (q, k, v)))
    yardstick = jax.jit(xla)(q, k, v)

    def error(x, w):
        x = np.asarray(x, np.float32)
        return float(np.max(np.abs(x - w) / (1.0 + np.abs(w))))

    for name, g, w, y in zip(("out", "dq", "dk", "dv"), got, want, yardstick):
        w = np.asarray(w)
        check(g.shape == w.shape, f"{name}: shape {g.shape} != {w.shape}")
        check(np.isfinite(np.asarray(g, np.float32)).all(),
              f"{name}: non-finite values")
        err = error(g, w)
        check(err <= PARITY_TOL,
              f"{name}: error {err:.2e} over tolerance {PARITY_TOL:.2e}")
        facts[f"{name}_err"] = f"{err:.1e}(xla_bf16:{error(y, w):.1e})"


def train(cfg, batch, seq, steps, attention_op, facts, sharding=None):
    """Build the model, take ``steps`` TrainStep steps on one seeded batch
    (placed with ``sharding`` when given), check the losses and that
    attention ran as ``attention_op``. Returns the trained model and the
    batch's device array."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.amp import debugging
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.observability.tracing import tracer

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    criterion = GPTPretrainingCriterion(cfg)
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    # 1e-4 as in the train cell: with no warm-up, 3e-4 overshot on the 4th step
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(ids):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            logits = model(ids)
        return criterion(logits, ids)

    step = TrainStep(model=model, optimizer=opt, loss_fn=loss_fn)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    if sharding is not None:
        ids = jax.device_put(ids, sharding)
    ids = paddle.Tensor(ids, stop_gradient=True)

    # The first call runs the step op by op (discovery), then traces and
    # compiles the one program. The tracer's jit.build span is the first
    # part; the operator statistics say which attention primitive ran.
    tracer.reset()
    tracer.enable()
    debugging.enable_operator_stats_collection()
    try:
        t0 = time.perf_counter()
        losses = [float(step(ids).numpy())]
        first = time.perf_counter() - t0
        ops = debugging.get_operator_stats()
    finally:
        debugging.disable_operator_stats_collection()
        tracer.disable()
    discovery = sum(e["dur"] for e in tracer.to_chrome_trace()["traceEvents"]
                    if e.get("name") == "jit.build") / 1e6
    check(discovery > 0, "the tracer recorded no jit.build span")
    facts["discovery_s"] = round(discovery, 1)
    facts["compile_and_first_run_s"] = round(first - discovery, 1)

    steady = []
    for _ in range(steps - 1):
        t0 = time.perf_counter()
        losses.append(float(step(ids).numpy()))  # host read ends the step
        steady.append(time.perf_counter() - t0)
    facts["steady_step_s"] = "/".join(f"{s:.3f}" for s in steady)
    facts["losses"] = "/".join(f"{x:.4f}" for x in losses)

    attention = sorted(op for op in ops if op.startswith("sdpa_"))
    facts["attention"] = ",".join(attention)
    check(attention == [attention_op],
          f"attention ran as {attention}, expected only {attention_op!r}")
    check(step.fallback_reason is None,
          f"TrainStep fell back to eager: {step.fallback_reason}")
    expected = math.log(cfg.vocab_size)
    check(abs(losses[0] - expected) <= FIRST_LOSS_SLACK,
          f"first loss {losses[0]:.3f} is not near ln(vocab) = {expected:.3f}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return model, ids._value


def server_phase(model, facts):
    """The model behind the paged decode engine: every request answered in
    full, no compile after warm-up, no page leaked, pool bytes constant."""
    from paddle_tpu import serving

    model.eval()
    vocab = model.config.vocab_size
    engine = serving.DecodeEngine(model, kv_mode="paged", **SERVE)
    pool = engine.kv_pool
    pool_bytes = pool.device_bytes()
    t0 = time.perf_counter()
    engine.warmup()
    facts["warmup_s"] = round(time.perf_counter() - t0, 1)
    facts["programs"] = engine.compile_count
    try:
        rs = np.random.RandomState(1)
        t0 = time.perf_counter()
        requests = [
            engine.submit(f"tenant-{i % 2}", rs.randint(0, vocab, n),
                          max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]
        answers = [r.result(timeout=600) for r in requests]
        facts["serve_s"] = round(time.perf_counter() - t0, 1)
    finally:
        engine.shutdown(drain=True)
    facts["requests"] = len(answers)
    facts["tokens"] = "/".join(str(len(a)) for a in answers)
    for n, a in zip(PROMPT_LENS, answers):
        check(len(a) == NEW_TOKENS,
              f"prompt of {n}: {len(a)} tokens back, {NEW_TOKENS} asked")
        check(((0 <= a) & (a < vocab)).all(), f"prompt of {n}: token out of range")
    check(engine.compiles_after_warmup == 0,
          f"compiles_after_warmup = {engine.compiles_after_warmup}")
    check(pool.free_count() == pool.num_pages and pool.in_use() == 0,
          f"pages leaked: {pool.in_use()} of {pool.num_pages} still in use")
    check(pool.device_bytes() == pool_bytes == pool.bytes_at_warmup,
          f"pool bytes moved: {pool_bytes} -> {pool.device_bytes()}")
    facts["compiles_after_warmup"] = 0
    facts["pool_bytes"] = pool_bytes


def multichip_phase(facts):
    """dp 2 x mp 2 tensor-parallel training in this same process. Under a
    multi-device mesh attention takes the XLA composition: jax will not
    auto-partition a Mosaic kernel (ops.pallas.enabled)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import env, fleet
    from paddle_tpu.models import gpt2_small

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    cfg = gpt2_small(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     tensor_parallel=True, num_hidden_layers=MULTICHIP_LAYERS)
    model, batch = train(cfg, BATCH, SEQ, MULTICHIP_STEPS, "sdpa_xla", facts,
                         NamedSharding(env.get_mesh(), P("dp", None)))
    weight = model.gpt.h[0].attn.qkv_proj.weight._value
    for name, array in (("qkv weight", weight), ("batch", batch)):
        spans = len(array.sharding.device_set)
        check(spans == 4 and not array.sharding.is_fully_replicated,
              f"{name} spans {spans} device(s) as {array.sharding}")
    facts["weight_sharding"] = f"'{weight.sharding.spec}'"
    facts["device_set"] = 4


def main():
    from paddle_tpu.compile_cache.jax_cache import enable_jax_cache

    t0 = time.perf_counter()
    cache_dir = enable_jax_cache()
    cache = CacheCounter()
    device = device_phase()

    from paddle_tpu.models import gpt2_small

    for shape in PARITY_SHAPES:
        with phase(f"kernel {shape}") as facts:
            kernel_phase(shape, facts)
    with phase("trainer") as facts:
        cfg = gpt2_small(hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        model, _ = train(cfg, BATCH, SEQ, TRAIN_STEPS, "sdpa_flash", facts)
    with phase("server") as facts:
        server_phase(model, facts)
    if device["count"] == 1:
        print("multichip: skipped, 1 device", flush=True)
    else:
        with phase("multichip") as facts:
            multichip_phase(facts)
    print(f"[cache] dir={cache_dir} persistent_hits={cache.hits} "
          f"misses={cache.misses}", flush=True)
    print(f"[total] {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
