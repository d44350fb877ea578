"""`model_type: cohere2_moe` for the serving driver that looks its model up
by the configuration's `model_type` (`drivers/closed_loop_pages.py`): the
program's own model built from the configuration file, the program's engine
around it, the check of its answers against `reference_cohere2_moe.py`, the
check of its attention path alone (the driver calls that hook `latent_error`,
after the first model it served), and the shape facts the per-layer readers
need.

The configuration file carries the source's key names, which are also
`models/cohere2_moe.py:Cohere2MoEConfig`'s. `num_experts` there is the
experts HELD here and `expert_share` `[r, R]` the share: the model is built
with `R` times as many routed experts and told its share.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import flops_cohere2_moe as fl
from benchmark import harness
from benchmark import reference_cohere2_moe as ref
from benchmark.models_brumby import collect_check, send_check  # noqa: F401  (the driver's)

_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim", "layer_types",
         "layer_switch", "sliding_window", "num_experts_per_tok",
         "num_shared_experts", "norm_topk_prob", "expert_selection_fn",
         "shared_expert_combination_strategy", "first_k_dense_replace",
         "layer_norm_eps", "rope_theta", "rotary_pct", "position_embedding_type",
         "logit_scale", "max_position_embeddings", "tie_word_embeddings",
         "use_parallel_block", "use_qk_norm", "use_gated_activation",
         "attention_bias", "hidden_act", "initializer_range")
FAULTS = ("edge", "unrotated", "rotated_global", "released")
LOWER_PRECISION = "e4m3"   # K and V rows kept in 8-bit floats: the control below bfloat16


def build(config: dict, seed: int):
    """The program's model, its weights drawn on the device from --seed in
    the dtype they are served in, in eval mode, holding its share."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Cohere2MoEConfig, Cohere2MoEForCausalLM

    r, R = config["expert_share"]
    paddle.seed(int(seed))
    model = Cohere2MoEForCausalLM(Cohere2MoEConfig(
        dtype=config["serve"]["weights_dtype"],
        num_experts=config["num_experts"] * R,
        initializer_layers=config.get("initializer_layers"),
        embedding_initializer_range=config.get("embedding_initializer_range"),
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
    harness.log(f"engine: {engine.max_slots} lanes, window pool "
                f"{tuple(pool.window.k.shape)} and global pool "
                f"{tuple(pool.full.k.shape)} {pool.full.k.dtype.name}, K and V each = "
                f"{pool.window.device_bytes() / 1e9:.3f} + {pool.full.device_bytes() / 1e9:.3f}"
                f" GB; built in {t_built - t:.1f} s, {len(engine.programs.warmed)} "
                f"programs warmed in {time.perf_counter() - t_built:.1f} s")
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    harness.log("device memory after warm-up: " + ", ".join(
        f"{k} {stats[k] / 1e9:.3f} GB" for k in ("bytes_in_use", "peak_bytes_in_use",
                                                  "bytes_reserved", "bytes_limit",
                                                  "largest_free_block_bytes") if k in stats))
    return model, engine


def facts(config: dict) -> dict:
    """Shape facts for the readers (`layers/serve_mfu`, `layers/win_*`,
    `layers/moe_*`)."""
    window_layers, full_layers = fl.layer_counts(config)
    return {
        "layers": config["num_hidden_layers"],
        "sparse_layers": config["num_hidden_layers"],
        "window_layers": window_layers, "full_layers": full_layers,
        "held_experts": config["num_experts"],
        "page_size": config["engine"]["page_size"],
        "kv_row_bytes": fl.kv_row_bytes(config),
        "attn_flops_per_row": fl.attention_flops_per_row(config),
        "expert_bytes": fl.expert_bytes(config),
        "expert_flops_per_pair": fl.expert_flops_per_pair(config),
        "prompt_flops_per_token": fl.prompt_flops_per_token(config),
        "answer_flops_per_token": fl.answer_flops_per_token(config),
    }


# ------------------------------------------------------ the attention alone
def attention_programs(programs, kind: int, fault=None):
    """The attention check's three programs over ONE of the engine's pools
    (`kind` 0: the window pool and a window layer's rules, 1: the global
    pool and a global layer's), all through the programs' own rotation, page
    writes and attention. `write`: one chunk's K and V rows into a lane's
    pages. `ask`: `WindowedPrograms._attend_chunk` for queries that lie in
    one block. `steps`: a group of decode steps through `append_token_paged`
    and `_attend_step` with every lane in the call. `fault` is one of
    `FAULTS` or `LOWER_PRECISION`, for the controls that have to fail."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import window_attention as wa
    from paddle_tpu.serving import kv_cache as kvc

    pool = programs.pool
    ps, d = pool.page_size, programs._head_dim
    window = pool.window_rows if kind == 0 else None
    rotated, seen = kind == 0, window
    if kind == 0 and fault == "unrotated":
        rotated = False
    if kind == 1 and fault == "rotated_global":
        rotated = True
    if kind == 0 and fault == "edge":
        seen = window + 1
    if kind == 0 and fault == "released":
        seen = None      # the released columns' pad page is read as if live
    li = jnp.asarray((pool.window if kind == 0 else pool.full).num_layers - 1, jnp.int32)

    def rot(x, positions):
        if not rotated:
            return x
        return wa.rope_interleaved(x.reshape(x.shape[0], -1, d), positions,
                                   programs._theta).reshape(x.shape)

    def stored(rows):
        """What the pool is given: the rows, or for the precision control
        the rows as 8-bit floats would hold them (4 exponent bits, 3 of
        mantissa; not `astype` there and back, which XLA may drop)."""
        if fault == LOWER_PRECISION:
            return jax.lax.reduce_precision(rows, exponent_bits=4, mantissa_bits=3)
        return rows

    def write(kp, vp, table, start, k, v):
        """A chunk's rows at `start` (a multiple of the page), `k`, `v`
        `[rung, kv_heads x d]` padded to a rung as the engine pads."""
        rung = k.shape[0]
        positions = start + jnp.arange(rung, dtype=jnp.int32)
        pages = jax.lax.dynamic_slice(table, (start // ps,), (rung // ps,))
        return (kvc.write_chunk_pages(kp, li, pages,
                                      stored(rot(k, positions)).astype(kp.dtype)),
                kvc.write_chunk_pages(vp, li, pages, stored(v).astype(vp.dtype)))

    def ask(kp, vp, table, first, q):
        positions = first + jnp.arange(q.shape[0], dtype=jnp.int32)
        return programs._attend_chunk(rot(q, positions).astype(kp.dtype), kp, vp, li,
                                      table, first, seen)

    def steps(kp, vp, tables, at, k, v, q):
        def step(carry, x):
            kp, vp = carry
            positions, k, v, q = x
            pages = jnp.take_along_axis(tables, (positions // ps)[:, None], axis=1)[:, 0]
            kp = kvc.append_token_paged(kp, li, pages, positions % ps,
                                        stored(rot(k, positions)))
            vp = kvc.append_token_paged(vp, li, pages, positions % ps, stored(v))
            return (kp, vp), programs._attend_step(
                rot(q, positions).astype(kp.dtype), kp, vp, li, tables, positions, seen)

        (kp, vp), out = jax.lax.scan(step, (kp, vp), (at, k, v, q))
        return kp, vp, out

    return (jax.jit(write, donate_argnums=(0, 1)), jax.jit(ask),
            jax.jit(steps, donate_argnums=(0, 1)))


def _peaked_queries(rng, keys, at, targets, group: int, peak: float, scale: float,
                    theta, noise: float):
    """Queries `[S, H, d]` at positions `at` whose scores against `keys`
    `[T, G, d]` (not yet rotated) peak at `targets[s]` (key positions) with
    a logit near `peak`: every query head of a K/V head asks for the sum of
    its targets' keys, turned back by the rotation that the layer will apply
    (`theta` None: a layer without positions), plus noise of its own."""
    import jax.numpy as jnp

    S, (T, G, d) = len(at), keys.shape
    norm = float((keys ** 2).sum(-1).mean())
    wanted = np.asarray(targets)                            # [S, n]
    picked = jnp.asarray(keys[wanted.reshape(-1)])          # [S x n, G, d]
    if theta is not None:
        # q . R(j - i) k is what the rotated pair scores: ask for R(j - i) k
        picked = ref.rope(picked, theta,
                          jnp.asarray((wanted - np.asarray(at)[:, None]).reshape(-1)))
    q = np.asarray(picked).reshape(S, wanted.shape[1], G, d).sum(1)
    q = q * (peak / (norm * scale))
    q = np.repeat(q[:, :, None, :], group, axis=2)
    q = q + noise * rng.standard_normal(q.shape).astype(np.float32)
    return q.reshape(S, G * group, d)


def latent_error(engine, config: dict, traffic: dict, seed: int, fault=None) -> float:
    """The engine's ATTENTION PATH alone against the reference's attention,
    through the engine's own two pools and its own kernels, once under a
    window layer's rules (the window pool's last layer, rotated, the table's
    columns behind the window released as the scheduler releases them) and
    once under a global layer's (the global pool, no rotation, no window):
    seeded float32 q, k, v at the configuration's head sizes, one sequence a
    lane of the engine, every lane taken.

    - lane 0 is prefilled by `chunks - 1` whole chunks of the engine's chunk
      rung and a ragged one of `ragged` tokens through the programs' own
      rotation, page writes and `_attend_chunk` (every row written; a whole
      chunk asked at its last `queries` positions, the ragged one at all);
    - the lanes after it are `deep` rows deep (multiples of the chunk: rows
      written, nothing asked), so the decode steps meet the window's edge at
      several depths and tables with many released columns;
    - every other lane holds `others` rows;
    - then `steps` tokens a lane through the programs' own `_attend_step`
      with every lane in the call: the top decode rung, on the chip the
      kernel `gqa_paged_attn`. Before each group of `step_group` steps, and
      after each prefill chunk, the window pages that lie behind the next
      position's window go back to the pool and their columns read 0, as the
      scheduler does it: a sound program never reads them, and a later lane
      may be given them.

    With seeded weights the model's own attention is nearly uniform, and a
    window's edge off by one, a missing rotation or a released page read
    would hide under the logit limit. So the queries are BUILT to put their
    weight on the two keys at the window's edge: query `i` scores a logit
    near `peak` against the keys at `i - window + 1` (the last the window
    covers) and `i - window` (the first it does not), and noise elsewhere;
    before the window is full, against two keys inside it. The reference is
    given k and v as the pool's dtype holds them and asked the same
    positions. The worst difference over the largest value, over both kinds
    and every lane. `fault` (one of `FAULTS`) breaks the engine's side the
    named way: the control that has to read past the tolerance."""
    import jax
    import jax.numpy as jnp

    spec = traffic["attention_check"]
    programs, pool = engine.programs, engine.kv_pool
    H, G, d = (config[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    W, ps, top = config["sliding_window"], pool.page_size, programs.seq_ladder[-1]
    lanes, steps = engine.max_slots, spec["steps"]
    whole, ragged, others = spec["chunks"] - 1, spec["ragged"], spec["others"]
    asked = min(spec["queries"], top)
    rung_of = lambda n: min(c for c in programs.seq_ladder if c >= n)
    scale, theta = d ** -0.5, config["rope_theta"]
    prefilled = ([whole * top + ragged] + list(spec["deep"])
                 + [others] * lanes)[:lanes]
    worst = {}
    for kind, name in ((0, "window"), (1, "global")):
        rng = np.random.default_rng([int(seed), 4, kind])
        sub = pool.window if kind == 0 else pool.full
        write, ask, step_group = attention_programs(programs, kind, fault)
        window = W if kind == 0 else None
        dtype = sub.k.dtype

        def targets(at):
            out = []
            for i in at:
                j = i - W + 1
                out.append([j, j - 1] if j >= 1 else [i // 2, max(i - 1, 0)])
            return out

        seqs = []
        for lane, n in enumerate(prefilled):
            keys = rng.standard_normal((n + steps, G, d)).astype(np.float32)
            vals = rng.standard_normal((n + steps, G, d)).astype(np.float32)
            if lane == 0:
                at = np.concatenate(
                    [np.arange((j + 1) * top - asked, (j + 1) * top) for j in range(whole)]
                    + [np.arange(whole * top, n + steps)])
            elif n == others:
                at = np.arange(n + steps)
            else:
                at = np.arange(n, n + steps)
            q = _peaked_queries(rng, keys, at, targets(at), H // G, spec["peak"], scale,
                                theta if kind == 0 else None, spec["noise"])
            seqs.append({"k": keys.reshape(len(keys), -1), "v": vals.reshape(len(vals), -1),
                         "q": q.reshape(len(at), -1), "at": at})
        T = programs.table_rungs[-1]
        tables = np.zeros((lanes, T), np.int32)
        held = [{} for _ in prefilled]           # a lane's pages by table column
        gone = [0] * lanes                       # columns released so far

        def grow(lane, rows):
            """Pages for the lane's rows `0 .. rows - 1`, those released apart."""
            for col in range(gone[lane], -(-rows // ps)):
                if col not in held[lane]:
                    (held[lane][col],) = sub.alloc(1)
                    tables[lane, col] = held[lane][col]

        def release(lane, position):
            """What the scheduler's `_trim` does before a query at `position`:
            the window pages wholly behind its window go back to the pool,
            their columns read 0."""
            if kind == 0:
                first = pool.first_live_column(position)
                sub.release([held[lane].pop(c) for c in sorted(held[lane]) if c < first])
                tables[lane, :first] = 0
                gone[lane] = max(gone[lane], first)

        def padded(a, rung):
            return np.pad(a, ((0, rung - len(a)), (0, 0)))

        got = [[] for _ in seqs]
        kp, vp = sub.arrays()
        try:
            for lane, (s, n) in enumerate(zip(seqs, prefilled)):
                done, qi = 0, 0
                while done < n:
                    c = min(top, n - done)
                    rung = rung_of(c)
                    grow(lane, done + c)
                    table = jnp.asarray(tables[lane])
                    kp, vp = write(kp, vp, table, done, padded(s["k"][done:done + c], rung),
                                   padded(s["v"][done:done + c], rung))
                    if lane == 0 or n == others:
                        m = min(asked, c) if c == top else c
                        first = done + c - m
                        y = ask(kp, vp, table, first, padded(s["q"][qi:qi + m], rung_of(m)))
                        got[lane].append(np.asarray(y)[:m])
                        qi += m
                    done += c
                    release(lane, done)
            starts = np.asarray(prefilled, np.int32)
            for lo in range(0, steps, spec["step_group"]):
                hi = min(lo + spec["step_group"], steps)
                for lane, n in enumerate(prefilled):
                    release(lane, n + lo)
                    grow(lane, n + hi)
                at = starts[None, :] + np.arange(lo, hi, dtype=np.int32)[:, None]
                k, v, q = (np.stack([s[part][len(s[part]) - steps + lo:
                                             len(s[part]) - steps + hi]
                                     for s in seqs], axis=1) for part in ("k", "v", "q"))
                kp, vp, y = step_group(kp, vp, jnp.asarray(tables), jnp.asarray(at), k, v, q)
                for lane, mine in enumerate(np.asarray(y).transpose(1, 0, 2)):
                    got[lane].append(mine)
        finally:
            sub.commit(kp, vp)
            for pages in held:
                sub.release(pages.values())

        @jax.jit
        def want_of(q, k, v, at):
            with jax.default_matmul_precision("highest"):
                stored = lambda a: a.astype(dtype).astype(jnp.float32)
                T = k.shape[0]
                q, k, v = q.reshape(len(at), H, d), k.reshape(T, G, d), v.reshape(T, G, d)
                if kind == 0:
                    q, k = ref.rope(q, theta, at), ref.rope(k, theta, jnp.arange(T))
                return ref.attention(stored(q), stored(k), stored(v), at, window, scale)

        worst[name] = 0.0
        for s, mine in zip(seqs, got):
            want = np.asarray(want_of(s["q"], s["k"], s["v"], jnp.asarray(s["at"])))
            want = want.reshape(len(s["at"]), -1)
            mine = np.concatenate(mine).astype(np.float32)
            worst[name] = max(worst[name],
                              float(np.abs(mine - want).max() / np.abs(want).max()))
    harness.log(f"attention check{f' (fault {fault})' if fault else ''}: window layer "
                f"{worst['window']:.3e}, global layer {worst['global']:.3e}")
    return max(worst.values())


# ------------------------------------------------------- the logits' check
def judge_check(weights: dict, config: dict, traffic: dict, answered: list,
                average: bool = True) -> dict:
    """For each token the engine returned, the reference's logit for it
    against the reference's largest at that position, given the engine's own
    earlier tokens (the accepted cells' comparison), over the same share and
    the same slice of the vocabulary. `weights` is the engine's own parameter
    tree (bfloat16-rounded), which the reference upcasts a matrix at a time.
    Each request runs padded to the smallest of `check_widths` that holds it
    (causal: the padding changes nothing before it). Two readings, as for
    A.X-K1 and for its reason (a router's choice is discrete): the worst gap,
    and how many of the tokens are the reference's own argmax (`exact`).
    `average` False is the control: the reference's shared experts summed,
    not averaged."""
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
        hidden = ref.hidden_states(weights, jnp.asarray(ids), config, config["expert_share"],
                                   block=min(traffic["check_block"], len(ids)),
                                   average=average)
        rows = hidden[L - 1:L - 1 + n]            # position L-1+j predicts tokens[j]
        logits = ref.logits_at(weights, rows, config["layer_norm_eps"],
                               float(config["logit_scale"]),
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
        "the attention path alone, window layer and global, is within its tolerance of "
        "the reference's attention":
            latent <= traffic["attention_check"]["tolerance"],
        "nothing compiled after warm-up": compiles == 0,
        "no page of either kind is held after the drain": leaked == 0,
        "no request of the window failed": window_ok,
    })
