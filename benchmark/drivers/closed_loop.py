"""Serving driver, closed loop: `clients_per_lane` x the engine's lanes
clients, each sending its next request the moment its last completes. One
thread plays all the clients: it polls the outstanding requests every
`poll_seconds`, so the engine is never short of waiting requests however fast
it is. The load runs for `ramp_seconds` before the window opens (counted in
set-up), so the window sees the engine in its steady state.

The rate counts every answer token generated inside the window, whichever
request it belongs to: the thread reads how far each outstanding request has
got (`DecodeRequest.generated`, which grows as the engine decodes) when the
window opens and when it closes. Counting only the tokens of requests that
*completed* inside the window made the rate depend on which long requests
straddled its ends: 3-4% between seeds that offer the same work (PERF.md).

`attempted` counts the requests that completed or failed inside the window;
those in flight at its end are dropped from both counts: the ones still
queued are refused, the ones in a lane run out (outside the window), and a
page still in use after that is a leak.
"""
from __future__ import annotations

import time

from benchmark import harness, serve_common


def run(config, traffic, seed, seconds, trace):
    import jax

    cache = harness.CacheCounter()
    device_kind = jax.devices()[0].device_kind
    model, engine = serve_common.build_engine(config, seed)
    pool = engine.kv_pool
    try:
        check = serve_common.check_answers(model, engine, config, traffic, seed)
        harness.log(f"check: {check}")
        stream = serve_common.RequestStream(traffic, traffic["distinct_requests"],
                                            config["tokenizer_vocab"], seed)
        clients = traffic["clients_per_lane"] * engine.max_slots
        window = min(seconds, traffic["trace_seconds"]) if trace else seconds

        sent = 0
        outstanding = []

        def submit():
            nonlocal sent
            prompt, asked = stream(sent)
            sent += 1
            outstanding.append((engine.submit(serve_common.TENANT, prompt,
                                              max_new_tokens=asked), asked))

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

        for _ in range(clients):
            submit()
        t_ramp = time.perf_counter() + traffic["ramp_seconds"]
        while time.perf_counter() < t_ramp:
            for _ in range(poll()):
                submit()
            time.sleep(traffic["poll_seconds"])

        capture = serve_common.start_capture(trace)
        done.clear()
        peak_pages = 0
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
        # lanes in flight run out, and see that every page came back
        in_flight = len(outstanding)
        engine.shutdown(drain=False, timeout=traffic["drain_seconds"])
        leaked = pool.in_use()
        compiles = engine.compiles_after_warmup
    finally:
        engine.shutdown(drain=False)

    elapsed = t_end - t_start
    inside = [d for d in done if t_start <= d[0] <= t_end]
    failed = sum(1 for _, _, ok in inside if not ok)
    harness.log(f"window: {tokens} answer tokens generated in {elapsed:.3f} s; "
                f"{len(inside)} requests completed ({failed} failed) with "
                f"{sum(n for _, n, ok in inside if ok)} tokens = "
                f"{len(inside) / elapsed:.3f} requests/s; {in_flight} in flight at "
                f"its end, {leaked} pages in use after the drain, "
                f"{compiles} compiles after warm-up")
    facts = {
        "device_kind": device_kind, "chips": 1, "lanes": engine.max_slots,
        "pool_peak_share": 100.0 * peak_pages / pool.num_pages,
        "requests_per_s": len(inside) / elapsed,
        "cache_hits": cache.hits, "cache_misses": cache.misses,
    }
    return {
        "correct": serve_common.verdict(check, traffic, compiles, leaked, failed == 0),
        "attempted": len(inside), "failed": failed,
        "measured": {"serve_tokens_per_s": tokens / elapsed, "setup_s": setup_s},
        "facts": facts, "spans": spans, "capture": capture,
    }
