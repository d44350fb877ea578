"""Serving driver, closed loop, for a model looked up by the configuration's
`model_type` whose engine holds its sequences in PAGES: `closed_loop_lm.py`'s
loop (its docstring says what it is and why: `clients_per_lane` x the
engine's lanes clients; the check requests sent after the load's first
requests and judged after the drain; the lengths in `lengths_seed`'s order for
every seed; the window opened at the `ramp_completions`-th completion and not
before `ramp_seconds`; the pool's arrays deleted before the reference runs)
with what differs for a page pool:

- lanes are the ENGINE's (`engine.max_slots`): a page pool has no lanes of
  its own, and its `in_use()` counts pages;
- `pool_peak_share` is pages held over `num_pages`, sampled between the
  loop's own calls;
- the mechanism check is `benchmark/models_<model_type>.py:latent_error`
  (the engine's attention path alone, through the engine's own pool), and
  `leaked` counts pages.

`closed_loop_lm.py` is accepted and reads `pool.max_slots` and
`lm.retention_error`, so it cannot run a page pool; this is the THIRD copy of
the loop (`closed_loop.py`, `closed_loop_lm.py`), owed to the `benchmark`
issue that folds them (PERF.md section 7).
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import harness, serve_common


from benchmark.drivers.closed_loop_lm import lanes_beside


def run(config, traffic, seed, seconds, trace):
    import jax

    lm = importlib.import_module(f"benchmark.models_{config['model_type']}")
    cache = harness.CacheCounter()
    device_kind = jax.devices()[0].device_kind
    model, engine = lm.build_engine(config, seed)
    pool = engine.kv_pool
    try:
        stream = serve_common.RequestStream(traffic, traffic["distinct_requests"],
                                            config["tokenizer_vocab"], seed)
        stream.order = np.arange(len(stream.order))
        clients = traffic["clients_per_lane"] * engine.max_slots
        window = min(seconds, traffic["trace_seconds"]) if trace else seconds

        sent = 0
        outstanding = []
        everyone = []      # every request of the load, for `lanes_beside`

        def submit():
            nonlocal sent
            prompt, asked = stream(sent)
            sent += 1
            outstanding.append((engine.submit(serve_common.TENANT, prompt,
                                              max_new_tokens=asked), asked))
            everyone.append(outstanding[-1][0])

        done = []          # (t_complete, tokens, ok)
        peak_pages = 0

        def generated():
            """Answer tokens so far, over every request sent: the finished
            ones' answers and what the outstanding ones have got to."""
            return (sum(n for _, n, _ in done)
                    + sum(len(req.generated) for req, _ in outstanding))

        def poll():
            nonlocal peak_pages
            still = []
            for req, asked in outstanding:
                if req.done():
                    done.append((req.t_complete,) + serve_common.finished(req, asked))
                else:
                    still.append((req, asked))
            refill = len(outstanding) - len(still)
            outstanding[:] = still
            peak_pages = max(peak_pages, pool.in_use())
            return refill

        ahead = max(engine.max_slots - len(traffic["check_prompts"]), engine.max_slots // 2)
        for _ in range(ahead):
            submit()
        checks = lm.send_check(engine, config, traffic, seed)
        for _ in range(clients - ahead):
            submit()
        # the ramp, and past it should a check request still be unanswered
        # (at the cell's size they are done in about half of it); the window
        # opens at a completion chosen by count, which is the same point of
        # the loop's one sequence of beats in every run (the docstring's last
        # item)
        t_load = time.perf_counter()
        t_ramp = t_load + traffic["ramp_seconds"]
        ramped, ramp_times = 0, []
        while True:
            completed = poll()
            ramped += completed
            ramp_times += [time.perf_counter() - t_load] * completed
            for _ in range(completed):
                submit()
            if (completed and ramped >= traffic["ramp_completions"]
                    and time.perf_counter() >= t_ramp
                    and all(c.done() for _, _, c in checks)):
                break
            time.sleep(traffic["poll_seconds"])

        harness.log(f"ramp: the window opens at the load's completion {ramped}, "
                    f"{ramp_times[-1]:.2f} s after its start; completions at "
                    + " ".join(f"{t:.1f}" for t in ramp_times[-12:]))
        capture = serve_common.start_capture(trace)
        done.clear()
        peak_pages = 0
        setup_s = time.perf_counter() - harness.PROCESS_START
        tokens_before = generated()
        t_start = time.perf_counter()
        # the longest stretch of the window in which no token came out: a
        # decode step is 25 ms and a chunk under 0.4 s, so a pause of seconds
        # is a stall of the host or the runtime, not work (PERF.md section 7)
        seen, t_seen, pause = tokens_before, t_start, 0.0
        while time.perf_counter() < t_start + window:
            for _ in range(poll()):
                submit()
            now, count = time.perf_counter(), generated()
            if count != seen:
                seen, t_seen = count, now
            pause = max(pause, now - t_seen)
            time.sleep(traffic["poll_seconds"])
        poll()
        tokens = generated() - tokens_before
        t_end = time.perf_counter()
        spans = serve_common.stop_capture(capture)
        harness.note_memory()

        # outside the window: refuse what still waits in the queue, let the
        # lanes in flight run out, and see that every lane came back
        in_flight = len(outstanding)
        engine.shutdown(drain=False, timeout=traffic["drain_seconds"])
        leaked = pool.in_use()
        compiles = engine.compiles_after_warmup
        steps = engine.stats.summary().get("decode") or {}
        harness.log("engine, whole run: " + ", ".join(
            f"{k} {steps.get(k)}" for k in ("decode_steps", "decode_p50_ms", "decode_p99_ms",
                                            "prefill_steps", "prefill_p50_ms", "prefill_p99_ms")))
        weights = engine.programs.params
        answered = lm.collect_check(checks, traffic)
        beside = min(lanes_beside(req, everyone + [c for _, _, c in checks])
                     for _, _, req in checks)
        # the fewest live lanes that the engine rounds up to its top decode rung
        top_rung_from = ([0] + list(engine.programs.decode_rungs))[-2] + 1
        latent = lm.latent_error(engine, config, traffic, seed)
    finally:
        engine.shutdown(drain=False)

    # the pool's arrays go, and the reference takes their room
    for array in pool.arrays():
        array.delete()
    check = lm.judge_check(weights, config, traffic, answered)
    harness.log(f"check: {check}; fewest lanes decoding beside a check request: {beside} of "
                f"{engine.max_slots}; the attention path alone, float32 in: {latent:.3e}")

    elapsed = t_end - t_start
    inside = [d for d in done if t_start <= d[0] <= t_end]
    failed = sum(1 for _, _, ok in inside if not ok)
    harness.log(f"window: {tokens} answer tokens generated in {elapsed:.3f} s; "
                f"{len(inside)} requests completed ({failed} failed) with "
                f"{sum(n for _, n, ok in inside if ok)} tokens = "
                f"{len(inside) / elapsed:.3f} requests/s; {in_flight} in flight at "
                f"its end, the longest pause between tokens {pause:.2f} s, "
                f"{leaked} pages held after the drain, "
                f"{compiles} compiles after warm-up")
    facts = {
        "device_kind": device_kind, "chips": 1, "lanes": engine.max_slots,
        "pool_peak_share": 100.0 * peak_pages / pool.num_pages,
        "requests_per_s": len(inside) / elapsed,
        "cache_hits": cache.hits, "cache_misses": cache.misses,
    }
    facts.update(lm.facts(config))
    return {
        "correct": lm.verdict(check, latent, traffic, beside >= top_rung_from, compiles,
                              leaked, failed == 0),
        "attempted": len(inside), "failed": failed,
        "measured": {"serve_tokens_per_s": tokens / elapsed, "setup_s": setup_s},
        "facts": facts, "spans": spans, "capture": capture,
    }
