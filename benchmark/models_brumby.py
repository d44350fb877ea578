"""`model_type: brumby` for the serving drivers that look their model up by
the configuration's `model_type` (`drivers/closed_loop_lm.py`): the
program's own model built from the configuration file, the program's engine
around it, the check of its answers against `reference_brumby.py`, and the
shape facts the per-layer readers need.

The configuration file carries the source's key names, which are also
`models/brumby.py:BrumbyConfig`'s.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import flops_brumby, harness, reference_brumby
from benchmark.serve_common import TENANT

_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "max_position_embeddings", "rms_norm_eps", "rope_theta",
         "initializer_range", "tie_word_embeddings", "attention_bias", "hidden_act")


def build(config: dict, seed: int):
    """The program's model, its weights drawn on the device from --seed in
    the dtype they are served in, in eval mode."""
    import paddle_tpu as paddle
    from paddle_tpu.models import BrumbyConfig, BrumbyForCausalLM

    paddle.seed(int(seed))
    model = BrumbyForCausalLM(BrumbyConfig(
        dtype=config["serve"]["weights_dtype"], **{k: config[k] for k in _KEYS}))
    model.eval()
    return model


def build_engine(config: dict, seed: int):
    """That model behind the program's DecodeEngine with the configuration's
    `engine` arguments, warmed. No argument selects the residency: the
    engine takes it from the model."""
    from paddle_tpu import serving

    t = time.perf_counter()
    model = build(config, seed)
    engine = serving.DecodeEngine(model, **config["engine"])
    t_built = time.perf_counter()
    engine.warmup()
    pool = engine.kv_pool
    harness.log(f"engine: {engine.max_slots} lanes, state pool "
                f"{tuple(pool.state.shape)} {pool.state.dtype.name} = "
                f"{pool.device_bytes() / 1e9:.3f} GB; built in {t_built - t:.1f} s, "
                f"{len(engine.programs.warmed)} programs warmed in "
                f"{time.perf_counter() - t_built:.1f} s")
    return model, engine


def facts(config: dict) -> dict:
    """Shape facts for the readers (`layers/retn_*`, `layers/serve_mfu`)."""
    return {
        "layers": config["num_hidden_layers"],
        "state_bytes_per_lane": config["num_hidden_layers"] * flops_brumby.state_bytes(config),
        "retention_flops_per_token": flops_brumby.retention_flops_per_token(config),
        "prompt_flops_per_token": flops_brumby.prompt_flops_per_token(config),
        "answer_flops_per_token": flops_brumby.answer_flops_per_token(config),
    }


def retention_program(programs, layer: int, ragged: int):
    """`retention_error`'s program over the engine's pool array: `chunks`
    (the first lane's whole chunks, `[n, top rung, ...]` each of q, k, v,
    log g) and `tails` (every lane's ragged chunk, `[lanes, its rung, ...]`,
    `ragged` tokens valid) through `programs._state_chunk`, lane by lane,
    then `after` (`[steps, lanes, ...]`) through `programs._state_step`
    with every lane in the call."""
    import jax
    import jax.numpy as jnp

    def program(state, slots, fresh, chunks, tails, after):
        li = jnp.asarray(layer, jnp.int32)

        def chunk(state, x):
            y, state = programs._state_chunk(state, li, slots[0], x[0] == 0, *x[1:],
                                             jnp.ones(x[1].shape[0], bool))
            return state, y

        def tail(state, x):
            y, state = programs._state_chunk(state, li, *x, jnp.arange(x[2].shape[0]) < ragged)
            return state, y[:ragged]

        def step(state, x):
            y, state = programs._state_step(state, li, slots, *x)
            return state, y

        whole = chunks[0].shape[0]
        state, y_chunks = jax.lax.scan(chunk, state, (jnp.arange(whole),) + tuple(chunks))
        state, y_tails = jax.lax.scan(tail, state, (slots, fresh) + tuple(tails))
        state, y_steps = jax.lax.scan(step, state, tuple(after))
        return state, y_chunks, y_tails, y_steps

    return program


def retention_error(engine, config: dict, traffic: dict, seed: int) -> float:
    """The engine's retention path alone, on float32 inputs, against the
    reference's quadratic form, THROUGH THE ENGINE'S OWN POOL at the shapes
    the window runs: seeded q, k, v and gates at the configuration's head
    size and head counts, one sequence a lane of the engine, every lane
    taken, in an order that is not the pool's. The first lane's sequence is
    prefilled by `chunks - 1` whole chunks of the engine's chunk rung and a
    ragged one of `ragged` tokens, every other lane's by one ragged chunk
    (on the rung the scheduler would give it), all through the programs'
    own `_state_chunk`; then `steps` tokens a lane through the programs'
    own `_state_step` with every lane live: the top decode rung, the kernel
    where the engine uses it. The pool is the engine's array as the drain
    left it (its dtype, its lanes holding the window's last states, which
    `fresh` has to ignore), one layer of it (the last), donated and
    committed back. The worst difference over the largest value, over
    every lane. The logit check cannot tell a state kept in bfloat16 from a
    sound one (activations are bfloat16 already, and the state's rounding
    adds about as much again); this can, by three orders of magnitude."""
    import jax
    import jax.numpy as jnp

    spec = traffic["retention_check"]
    programs, pool = engine.programs, engine.kv_pool
    d, hq, hkv = (config[k] for k in ("head_dim", "num_attention_heads", "num_key_value_heads"))
    lanes, steps, ragged = engine.max_slots, spec["steps"], spec["ragged"]
    top, whole = programs.seq_ladder[-1], spec["chunks"] - 1
    small = min(c for c in programs.seq_ladder if c >= ragged)
    layer = pool.state.shape[0] - 1
    rng = np.random.default_rng([int(seed), 4])
    spread = np.log(np.geomspace(16.0, 4096.0, hkv) - 1.0)

    def draw(T):
        q, k, v = (jnp.asarray(rng.standard_normal((T, h, d)), jnp.float32)
                   for h in (hq, hkv, hkv))
        return q, k, v, jax.nn.log_sigmoid(jnp.asarray(
            spread + 1.4 * rng.standard_normal((T, hkv)), jnp.float32))

    prefilled = [whole * top + ragged] + [ragged] * (lanes - 1)
    seqs = [draw(n + steps) for n in prefilled]
    slots = jnp.asarray(rng.permutation(lanes), jnp.int32)
    fresh = jnp.asarray([whole == 0] + [True] * (lanes - 1), jnp.int32)
    # the first lane's whole chunks [whole, top, ...]; every lane's ragged
    # chunk, padded to its rung [lanes, small, ...]; the steps [steps, lanes, ...]
    chunks = tuple(a[:whole * top].reshape((whole, top) + a.shape[1:]) for a in seqs[0])
    tails = tuple(jnp.stack([jnp.pad(s[i][n - ragged:n], ((0, small - ragged),)
                                     + ((0, 0),) * (s[i].ndim - 1))
                             for s, n in zip(seqs, prefilled)]) for i in range(4))
    after = tuple(jnp.stack([s[i][n:] for s, n in zip(seqs, prefilled)], axis=1)
                  for i in range(4))

    state, y_chunks, y_tails, y_steps = jax.jit(
        retention_program(programs, layer, ragged), donate_argnums=0)(
            pool.state, slots, fresh, chunks, tails, after)
    pool.commit(state)
    worst, quadratic = 0.0, jax.jit(reference_brumby.retention_quadratic)
    with jax.default_matmul_precision("highest"):
        for i, (seq, n) in enumerate(zip(seqs, prefilled)):
            want = quadratic(*seq)
            got = jnp.concatenate(
                ([y_chunks.reshape((whole * top,) + y_chunks.shape[2:])] if i == 0 else [])
                + [y_tails[i], y_steps[:, i]])
            worst = max(worst, float(jnp.abs(got - want).max() / jnp.abs(want).max()))
    return worst


def send_check(engine, config: dict, traffic: dict, seed: int) -> list:
    """The check requests into the engine's queue: [(prompt, asked,
    request)]. The driver sends them between the load's first requests, so
    that they decode beside a full engine."""
    rng = np.random.default_rng([int(seed), 3])
    sent = []
    for length in traffic["check_prompts"]:
        prompt = rng.integers(0, config["tokenizer_vocab"], int(length), dtype=np.int32)
        sent.append((prompt, int(traffic["check_answer"]),
                     engine.submit(TENANT, prompt, max_new_tokens=traffic["check_answer"])))
    return sent


def collect_check(sent: list, traffic: dict) -> list:
    """[(prompt, asked, tokens or None)] once every check request resolved."""
    out = []
    for i, (prompt, asked, req) in enumerate(sent):
        try:
            tokens = np.asarray(req.result(timeout=traffic["drain_seconds"]))
        except Exception as e:  # noqa: BLE001
            harness.log(f"check request {i} failed: {type(e).__name__}: {e}")
            tokens = None
        out.append((prompt, asked, tokens))
    return out


def judge_check(weights: dict, config: dict, traffic: dict, answered: list) -> dict:
    """For each token the engine returned, the reference's logit for it
    against the reference's largest at that position, given the engine's own
    earlier tokens (the accepted cells' comparison). Tokens themselves flip
    on rounding with random weights; a token from far down the reference's
    distribution does not come from rounding. `weights` is the engine's own
    parameter tree (bfloat16-rounded), which the reference upcasts."""
    import jax.numpy as jnp

    width = int(traffic["check_width"])
    complete, worst, exact, count = True, 0.0, 0, 0
    for prompt, asked, tokens in answered:
        if tokens is None or len(tokens) != asked:
            complete = False
        if tokens is None or not len(tokens):
            continue
        L, n = len(prompt), len(tokens)
        ids = np.zeros(width, np.int32)
        ids[:L] = prompt
        ids[L:L + n] = tokens
        hidden = reference_brumby.hidden_states(weights, jnp.asarray(ids), config)
        rows = hidden[L - 1:L - 1 + n]            # position L-1+j predicts tokens[j]
        logits = reference_brumby.logits_at(weights, rows, config["rms_norm_eps"],
                                            block=traffic.get("check_head_block"))
        gaps = np.asarray(logits.max(-1) - logits[jnp.arange(n), jnp.asarray(tokens)])
        harness.log(f"check: prompt {L}, {n} tokens, worst gap {gaps.max():.5f}, "
                    f"{int((gaps == 0).sum())} the reference's own")
        worst, exact, count = max(worst, float(gaps.max())), exact + int((gaps == 0).sum()), count + n
    if not count:
        return {"complete": False, "worst_gap": float("inf"), "tokens": 0}
    return {"complete": complete, "worst_gap": worst, "exact": exact, "tokens": count}


def verdict(check: dict, retention: float, traffic: dict, on_top_rung: bool, compiles,
            leaked: int, window_ok: bool) -> bool:
    return harness.verdict({
        "every check request was answered in full": check["complete"],
        "no returned token is further than the tolerance from the reference's best":
            check["worst_gap"] <= traffic["logit_tolerance"],
        "every check request decoded beside a full engine, on the window's decode rung":
            on_top_rung,
        "the retention path in float32 is within its tolerance of the quadratic form":
            retention <= traffic["retention_check"]["tolerance"],
        "nothing compiled after warm-up": compiles == 0,
        "no lane is held after the drain": leaked == 0,
        "no request of the window failed": window_ok,
    })
