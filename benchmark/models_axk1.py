"""`model_type: axk1` for the serving driver that looks its model up by the
configuration's `model_type` (`drivers/closed_loop_pages.py`): the program's
own model built from the configuration file, the program's engine around
it, the check of its answers against `reference_axk1.py`, the check of its
attention path alone, and the shape facts the per-layer readers need.

The configuration file carries the source's key names, which are also
`models/axk1.py:AXK1Config`'s. `n_routed_experts` there is the experts HELD
here and `expert_share` `[r, R]` the share: the model is built with `R` times
as many routed experts and told its share.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import flops_axk1, harness, reference_axk1
from benchmark.models_brumby import collect_check, send_check  # noqa: F401  (the driver's)

_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
         "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "n_shared_experts", "num_experts_per_tok", "n_group",
         "topk_group", "routed_scaling_factor", "norm_topk_prob", "scoring_func",
         "topk_method", "max_position_embeddings", "rms_norm_eps", "rope_theta",
         "rope_scaling", "initializer_range", "tie_word_embeddings",
         "attention_bias", "hidden_act")


def build(config: dict, seed: int):
    """The program's model, its weights drawn on the device from --seed in
    the dtype they are served in, in eval mode, holding its share."""
    import paddle_tpu as paddle
    from paddle_tpu.models import AXK1Config, AXK1ForCausalLM

    r, R = config["expert_share"]
    paddle.seed(int(seed))
    model = AXK1ForCausalLM(AXK1Config(
        dtype=config["serve"]["weights_dtype"],
        n_routed_experts=config["n_routed_experts"] * R,
        initializer_layers=config.get("initializer_layers"),
        **{k: config[k] for k in _KEYS}), expert_share=(r, R))
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
    harness.log(f"engine: {engine.max_slots} lanes, latent pool "
                f"{tuple(pool.k.shape)} {pool.k.dtype.name} = "
                f"{pool.device_bytes() / 1e9:.3f} GB; built in {t_built - t:.1f} s, "
                f"{len(engine.programs.warmed)} programs warmed in "
                f"{time.perf_counter() - t_built:.1f} s")
    return model, engine


def facts(config: dict) -> dict:
    """Shape facts for the readers (`layers/serve_mfu`, `layers/latent_*`,
    `layers/moe_*`)."""
    return {
        "layers": config["num_hidden_layers"],
        "sparse_layers": config["num_hidden_layers"] - config["first_k_dense_replace"],
        "held_experts": config["n_routed_experts"],
        "page_size": config["engine"]["page_size"],
        "latent_row_bytes": flops_axk1.latent_row_bytes(config),
        "latent_flops_per_row": flops_axk1.latent_attention_flops_per_row(config),
        "expert_bytes": flops_axk1.expert_bytes(config),
        "expert_flops_per_pair": flops_axk1.expert_flops_per_pair(config),
        "prompt_flops_per_token": flops_axk1.prompt_flops_per_token(config),
        "answer_flops_per_token": flops_axk1.answer_flops_per_token(config),
    }


# ------------------------------------------------------ the attention alone
def latent_programs(programs, layer: int):
    """`latent_error`'s two programs over the engine's pool array, both
    through the programs' own row layout, rotation and page writes.
    `prefill`: the first lane's whole chunks (every row written, the last
    `queries` positions of each asked through `_attend_chunk`), its ragged
    chunk, then every other lane's ragged chunk, lane by lane. `steps`: a
    group of decode steps through `append_token_paged` and `_attend_step`
    with every lane in the call."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import latent_attention as la
    from paddle_tpu.serving import kv_cache as kvc

    ps, top = programs.pool.page_size, programs.seq_ladder[-1]
    li = jnp.asarray(layer, jnp.int32)

    def rows_of(c_kv, k_rope, positions, dtype):
        """The cache rows as the programs lay them out, k_rope rotated."""
        return programs._cache_rows(
            c_kv, la.rope(k_rope, positions, programs._inv_freq)).astype(dtype)

    def write(pool, table, start, c_kv, k_rope, rung):
        """A chunk's rows at `start`, padded to `rung` as the engine pads."""
        c_kv, k_rope = (jnp.pad(a, ((0, rung - a.shape[0]), (0, 0))) for a in (c_kv, k_rope))
        positions = start + jnp.arange(rung, dtype=jnp.int32)
        pages = jax.lax.dynamic_slice(table, (start // ps,), (rung // ps,))
        return kvc.write_chunk_pages(pool, li, pages,
                                     rows_of(c_kv, k_rope, positions, pool.dtype))

    def ask(pool, w, table, first, qn, qr, rung):
        """`_attend_chunk` for the queries at `first ..`, padded to `rung`."""
        n = qn.shape[0]
        qn, qr = (jnp.pad(a, ((0, rung - n), (0, 0), (0, 0))) for a in (qn, qr))
        positions = first + jnp.arange(rung, dtype=jnp.int32)
        return programs._attend_chunk(
            w, qn, la.rope(qr, positions, programs._inv_freq), pool, li, table, first)[:n]

    def prefill(pool, w, tables, chunks, tail, others, small):
        def chunk(pool, x):
            j, c_kv, k_rope, qn, qr = x
            pool = write(pool, tables[0], j * top, c_kv, k_rope, top)
            return pool, ask(pool, w, tables[0], (j + 1) * top - qn.shape[0], qn, qr,
                             qn.shape[0])

        def lane(pool, x):
            table, c_kv, k_rope, qn, qr = x
            pool = write(pool, table, 0, c_kv, k_rope, small)
            return pool, ask(pool, w, table, 0, qn, qr, small)

        whole = chunks[0].shape[0]
        pool, y_chunks = jax.lax.scan(chunk, pool, (jnp.arange(whole),) + tuple(chunks))
        pool = write(pool, tables[0], whole * top, tail[0], tail[1], small)
        y_tail = ask(pool, w, tables[0], whole * top, tail[2], tail[3], small)
        pool, y_others = jax.lax.scan(lane, pool, (tables[1:],) + tuple(others))
        return pool, y_chunks, y_tail, y_others

    def steps(pool, w, tables, at, after):
        def step(pool, x):
            positions, c_kv, k_rope, qn, qr = x
            pages = jnp.take_along_axis(tables, (positions // ps)[:, None], axis=1)[:, 0]
            pool = kvc.append_token_paged(
                pool, li, pages, positions % ps,
                rows_of(c_kv, k_rope, positions, pool.dtype))
            return pool, programs._attend_step(
                w, qn, la.rope(qr, positions, programs._inv_freq), pool, li, tables,
                positions)

        return jax.lax.scan(step, pool, (at,) + tuple(after))

    return prefill, steps


def latent_error(engine, config: dict, traffic: dict, seed: int) -> float:
    """The engine's attention path alone against the reference's expanded
    attention, THROUGH THE ENGINE'S OWN POOL: seeded float32 q (before its
    rotation), latent rows and rotary parts (before theirs), one sequence a
    lane of the engine, every lane taken. The first lane's sequence is
    prefilled by `chunks - 1` whole chunks of the engine's chunk rung and a
    ragged one of `ragged` tokens, every other lane's by a ragged chunk of
    `others`, all through the programs' own row layout, rotation, page write
    and `_attend_chunk` (the expanded form over the pages before the cursor
    and the chunk); then `steps` tokens a lane through the programs' own
    `_attend_step` with every lane live: the absorbed form on the top decode
    rung, the kernel where the engine uses it, the two absorb products with
    the last layer's own `k_b_proj` and `v_b_proj`. The inputs are scaled so
    that the scores spread with a standard deviation near `logit_std` and
    the rotary half carries most of it: with seeded weights the model's own
    attention is nearly uniform, and a wrong scale or an unrotated k_rope
    would hide under the logit limit. The pool is the engine's array as the
    drain left it (its dtype, the window's last rows still in its pages),
    one layer of it (the last), donated and committed back.

    The engine's programs are still loaded and the chip is full (0.5 GB
    free at the cell's size), so the check keeps its own arrays small: a
    whole chunk writes all its rows but asks only its last `queries`
    positions (the smallest chunk rung), and the decode steps run in groups
    of `step_group`. The reference is given the latent rows as the pool's
    dtype holds them and asked the same positions. The worst difference
    over the largest value, over every lane."""
    import jax
    import jax.numpy as jnp

    spec = traffic["latent_check"]
    programs, pool = engine.programs, engine.kv_pool
    H, dn, dr = (config[k] for k in ("num_attention_heads", "qk_nope_head_dim",
                                     "qk_rope_head_dim"))
    rank = config["kv_lora_rank"]
    lanes, steps, ragged, others = (engine.max_slots, spec["steps"], spec["ragged"],
                                    spec["others"])
    top, whole = programs.seq_ladder[-1], spec["chunks"] - 1
    small = min(c for c in programs.seq_ladder if c >= max(ragged, others))
    asked = min(spec["queries"], top)
    layer = pool.num_layers - 1
    rng = np.random.default_rng([int(seed), 4])
    stack = programs.params["sparse"]
    w = {name: stack[name][-1] for name in ("k_b_proj", "v_b_proj")}
    # the nope half of a score spreads by |q| x 0.02 sqrt(rank) sqrt(dn), the
    # rotary half by |q| sqrt(dr): the rotary half is three quarters of it
    scale = reference_axk1.softmax_scale(config)
    q_std = spec["logit_std"] / (scale * np.sqrt(
        dr + config["initializer_range"] ** 2 * rank * dn))

    def draw(T, at):
        """A sequence's rows, and q for the positions `at` of it."""
        normal = lambda s, *shape: (s * rng.standard_normal(shape)).astype(np.float32)
        return {"c_kv": normal(1.0, T, rank), "k_rope": normal(1.0, T, dr), "at": np.asarray(at),
                "qn": normal(q_std, len(at), H, dn), "qr": normal(q_std, len(at), H, dr)}

    prefilled = [whole * top + ragged] + [others] * (lanes - 1)
    seqs = []
    for i, n in enumerate(prefilled):
        windows = [np.arange((j + 1) * top - asked, (j + 1) * top) for j in range(whole)] \
            if i == 0 else []
        seqs.append(draw(n + steps, np.concatenate(
            windows + [np.arange(n - (ragged if i == 0 else others), n + steps)])))
    tables = np.zeros((lanes, programs.table_rungs[-1]), np.int32)
    held = []
    for i, n in enumerate(prefilled):
        held.append(pool.alloc(-(-(n + steps) // pool.page_size)))
        tables[i, :len(held[-1])] = held[-1]
    tables = jnp.asarray(tables)

    def rows(s, lo, hi):
        return s["c_kv"][lo:hi], s["k_rope"][lo:hi]

    def queries(s, lo, hi):       # by index into the sequence's asked positions
        return s["qn"][lo:hi], s["qr"][lo:hi]

    first, rest, q0 = seqs[0], seqs[1:], whole * asked
    chunks = tuple(a.reshape((whole, -1) + a.shape[1:]) for a in
                   rows(first, 0, whole * top) + queries(first, 0, q0))
    tail = rows(first, whole * top, prefilled[0]) + queries(first, q0, q0 + ragged)
    lane_rows = tuple(np.stack(a) for a in zip(*(rows(s, 0, others) for s in rest)))
    lane_q = tuple(np.stack(a) for a in zip(*(queries(s, 0, others) for s in rest)))
    prefill, step_group = (jax.jit(f, donate_argnums=0, static_argnames=("small",)) if i == 0
                           else jax.jit(f, donate_argnums=0)
                           for i, f in enumerate(latent_programs(programs, layer)))
    got = [[] for _ in seqs]
    try:
        out, y_chunks, y_tail, y_others = prefill(
            pool.k, w, tables, chunks, tail, lane_rows + lane_q, small=small)
        pool.commit(out)
        got[0] += [np.asarray(y_chunks).reshape(q0, -1), np.asarray(y_tail)]
        for i, y in enumerate(np.asarray(y_others)):
            got[i + 1].append(y)
        starts = np.asarray(prefilled, np.int32)
        for lo in range(0, steps, spec["step_group"]):
            hi = min(lo + spec["step_group"], steps)
            at = starts[None, :] + np.arange(lo, hi, dtype=np.int32)[:, None]
            after = tuple(np.stack(a, axis=1) for a in zip(*(
                rows(s, n + lo, n + hi) + queries(s, len(s["at"]) - steps + lo,
                                                  len(s["at"]) - steps + hi)
                for s, n in zip(seqs, prefilled))))
            out, y = step_group(pool.k, w, tables, jnp.asarray(at), after)
            pool.commit(out)
            for i, lane in enumerate(np.asarray(y).transpose(1, 0, 2)):
                got[i].append(lane)
    finally:
        for pages in held:
            pool.release(pages)

    freqs = jnp.asarray(reference_axk1.inv_freq(dr, config["rope_theta"],
                                                config["rope_scaling"]))

    @jax.jit
    def want_of(qn, qr, at, c_kv, k_rope):
        with jax.default_matmul_precision("highest"):
            stored = lambda a: a.astype(pool.k.dtype).astype(jnp.float32)
            return reference_axk1.attention(
                qn, reference_axk1.rope(qr, freqs, at), stored(c_kv),
                stored(reference_axk1.rope(k_rope, freqs)),
                w["k_b_proj"], w["v_b_proj"], scale, at)

    worst = 0.0
    for s, mine in zip(seqs, got):
        want = np.asarray(want_of(s["qn"], s["qr"], s["at"], s["c_kv"], s["k_rope"]))
        want = want.reshape(len(s["at"]), -1)
        mine = np.concatenate(mine).astype(np.float32)
        worst = max(worst, float(np.abs(mine - want).max() / np.abs(want).max()))
    return worst


# ------------------------------------------------------- the logits' check
def judge_check(weights: dict, config: dict, traffic: dict, answered: list) -> dict:
    """For each token the engine returned, the reference's logit for it
    against the reference's largest at that position, given the engine's own
    earlier tokens (the accepted cells' comparison), over the same share and
    the same slice of the vocabulary. `weights` is the engine's own parameter
    tree (bfloat16-rounded), which the reference upcasts a matrix at a time.
    Each request runs padded to the smallest of `check_widths` that holds it
    (causal: the padding changes nothing before it). Two readings: the worst
    gap, and how many of the tokens are the reference's own argmax (`exact`):
    a router picks experts, so a rounding that tips one choice moves one
    token's logits by a whole expert's output, and the worst gap of a sound
    run has a long tail; a fault in the expert layer moves EVERY token a
    little, which the worst gap may miss and the count does not."""
    import jax.numpy as jnp

    complete, worst, exact, count = True, 0.0, 0, 0
    for prompt, asked, tokens in answered:
        if tokens is None or len(tokens) != asked:
            complete = False
        if tokens is None or not len(tokens):
            continue
        L, n = len(prompt), len(tokens)
        ids = np.zeros(min(w for w in traffic["check_widths"] if w >= L + n), np.int32)
        ids[:L] = prompt
        ids[L:L + n] = tokens
        hidden = reference_axk1.hidden_states(weights, jnp.asarray(ids), config,
                                              config["expert_share"])
        rows = hidden[L - 1:L - 1 + n]            # position L-1+j predicts tokens[j]
        logits = reference_axk1.logits_at(weights, rows, config["rms_norm_eps"],
                                          block=traffic.get("check_head_block"))
        gaps = np.asarray(logits.max(-1) - logits[jnp.arange(n), jnp.asarray(tokens)])
        harness.log(f"check: prompt {L}, {n} tokens, worst gap {gaps.max():.5f}, "
                    f"{int((gaps == 0).sum())} the reference's own")
        worst, exact, count = max(worst, float(gaps.max())), exact + int((gaps == 0).sum()), count + n
    if not count:
        return {"complete": False, "worst_gap": float("inf"), "tokens": 0}
    return {"complete": complete, "worst_gap": worst, "exact": exact, "tokens": count}


def verdict(check: dict, latent: float, traffic: dict, on_top_rung: bool, compiles,
            leaked: int, window_ok: bool) -> bool:
    return harness.verdict({
        "every check request was answered in full": check["complete"],
        "no returned token is further than the tolerance from the reference's best":
            check["worst_gap"] <= traffic["logit_tolerance"],
        "at least the floor's share of the returned tokens are the reference's own choice":
            check.get("exact", 0) >= traffic["exact_floor"] * check["tokens"],
        "every check request decoded beside a full engine, on the window's decode rung":
            on_top_rung,
        "the attention path alone is within its tolerance of the reference's expanded form":
            latent <= traffic["latent_check"]["tolerance"],
        "nothing compiled after warm-up": compiles == 0,
        "no page is held after the drain": leaked == 0,
        "no request of the window failed": window_ok,
    })
