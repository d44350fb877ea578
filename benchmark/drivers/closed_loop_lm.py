"""Serving driver, closed loop, for a model looked up by the
configuration's `model_type`: `closed_loop.py`'s loop (`clients_per_lane` x
the engine's lanes clients, each sending its next request the moment its
last completes, polled every `poll_seconds`; `ramp_seconds` of load before
the window, counted in set-up; the rate counts every answer token generated
inside the window) with the GPT-bound calls replaced by
`benchmark/models_<model_type>.py`: `build_engine`, `send_check` /
`collect_check` / `judge_check`, `retention_error`, `verdict`, `facts`.

What differs from `closed_loop.py` besides the look-up:

- The check requests do not run alone. They are sent between the load's
  first requests (after as many of them as leave the check requests a
  lane each, and at least half the lanes), so they are prefilled between
  other lanes' decode beats and decode beside a full engine, on the decode
  rung the window times; `lanes_beside` counts it and `correct` holds it
  to the engine's top rung.
  They finish inside the ramp and are JUDGED after the drain: the engine's
  own retention path first, through the engine's own pool, then, when the
  pool's arrays are gone, the reference in their room.
- The pool is whatever the engine holds lanes in (`in_use()`,
  `max_slots`); `pool_peak_share` is lanes held over lanes.
- The lengths go out in the order `lengths_seed` drew them, for every seed
  (the seed gives the token ids and the weights): a window of this driver's
  mixes holds a few dozen long requests, and `longgen-closed.json` says
  what an order per seed does to the rate.
- The window opens when the load's `ramp_completions`-th request completes
  (and not before `ramp_seconds`), not at the clock alone. With one order
  of lengths and lanes that never wait for a client, the engine runs one
  sequence of beats in every run, and the n-th completion is a point of
  that sequence. Opened by the clock, the window began 0.1-0.4 s earlier
  or later in it from run to run, and a prompt of one to four chunks of
  0.2 s falling in or out at an edge moved the rate by 0.5-2%; opened at
  the first completion after the clock's mark, it began at one of two
  completions a second apart, because one falls on the mark
  (`longgen-closed.json` has the readings). It still lasts `--seconds` on
  the clock.

The copy of the loop is owed to a `benchmark` issue that folds the two
drivers (PERF.md section 7).
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import harness, serve_common


def lanes_beside(check, requests) -> int:
    """The fewest lanes decoding (first token out, not yet complete) at any
    moment between the check request's first token and its last, itself
    among them; 0 if it never got that far."""
    t0, t1 = check.t_first_token, check.t_complete
    if t0 is None or t1 is None:
        return 0
    lives = [(r.t_first_token, r.t_complete) for r in requests if r.t_first_token is not None]
    moments = [t0] + [b for _, b in lives if b is not None and t0 < b < t1]
    return min(sum(1 for a, b in lives if a <= t and (b is None or b > t)) for t in moments)


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
        peak_lanes = 0

        def generated():
            """Answer tokens so far, over every request sent: the finished
            ones' answers and what the outstanding ones have got to."""
            return (sum(n for _, n, _ in done)
                    + sum(len(req.generated) for req, _ in outstanding))

        def poll():
            nonlocal peak_lanes
            still = []
            for req, asked in outstanding:
                if req.done():
                    done.append((req.t_complete,) + serve_common.finished(req, asked))
                else:
                    still.append((req, asked))
            refill = len(outstanding) - len(still)
            outstanding[:] = still
            peak_lanes = max(peak_lanes, pool.in_use())
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
        t_ramp = time.perf_counter() + traffic["ramp_seconds"]
        ramped = 0
        while True:
            completed = poll()
            ramped += completed
            for _ in range(completed):
                submit()
            if (completed and ramped >= traffic["ramp_completions"]
                    and time.perf_counter() >= t_ramp
                    and all(c.done() for _, _, c in checks)):
                break
            time.sleep(traffic["poll_seconds"])

        capture = serve_common.start_capture(trace)
        done.clear()
        peak_lanes = 0
        setup_s = time.perf_counter() - harness.PROCESS_START
        tokens_before = generated()
        t_start = time.perf_counter()
        while time.perf_counter() < t_start + window:
            for _ in range(poll()):
                submit()
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
        retention = lm.retention_error(engine, config, traffic, seed)
    finally:
        engine.shutdown(drain=False)

    # the pool's arrays go, and the reference takes their room
    for array in pool.arrays():
        array.delete()
    check = lm.judge_check(weights, config, traffic, answered)
    harness.log(f"check: {check}; fewest lanes decoding beside a check request: {beside} of "
                f"{engine.max_slots}; the retention path alone, float32 in: {retention:.3e}")

    elapsed = t_end - t_start
    inside = [d for d in done if t_start <= d[0] <= t_end]
    failed = sum(1 for _, _, ok in inside if not ok)
    harness.log(f"window: {tokens} answer tokens generated in {elapsed:.3f} s; "
                f"{len(inside)} requests completed ({failed} failed) with "
                f"{sum(n for _, n, ok in inside if ok)} tokens = "
                f"{len(inside) / elapsed:.3f} requests/s; {in_flight} in flight at "
                f"its end, {leaked} lanes held after the drain, "
                f"{compiles} compiles after warm-up")
    facts = {
        "device_kind": device_kind, "chips": 1, "lanes": engine.max_slots,
        "pool_peak_share": 100.0 * peak_lanes / pool.max_slots,
        "requests_per_s": len(inside) / elapsed,
        "cache_hits": cache.hits, "cache_misses": cache.misses,
    }
    facts.update(lm.facts(config))
    return {
        "correct": lm.verdict(check, retention, traffic, beside >= top_rung_from, compiles,
                              leaked, failed == 0),
        "attempted": len(inside), "failed": failed,
        "measured": {"serve_tokens_per_s": tokens / elapsed, "setup_s": setup_s},
        "facts": facts, "spans": spans, "capture": capture,
    }
