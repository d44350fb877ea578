"""Serving driver, open loop: arrivals at the traffic file's fixed
`rate_per_s` on a schedule of exponential gaps (serve_common.arrival_times),
sent whatever the engine does, each request timed from when it was due. One
window holds round(rate_per_s x its seconds) arrivals and as many sizes, the
same for every seed, in the seed's order. Arrivals run for `ramp_seconds` before the window opens
(counted in set-up). The generator stops at the window's end; the driver then
waits up to `drain_seconds` for every request that was due inside the window.
Those are `attempted`; one that errs, is refused, comes back short or does
not come back is `failed`.
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
        window = min(seconds, traffic["trace_seconds"]) if trace else seconds
        n = max(1, round(traffic["rate_per_s"] * window))
        stream = serve_common.RequestStream(traffic, n, config["tokenizer_vocab"], seed)
        ramp = traffic["ramp_seconds"]
        due_after = serve_common.arrival_times(traffic, n, seed, ramp + window)

        capture = None
        sent = []           # (due, request, asked)
        t_load = time.perf_counter()
        t_start = t_load + ramp
        setup_s = None
        for i, offset in enumerate(due_after):
            due = t_load + offset
            if setup_s is None and due >= t_start:
                # the window opens: between two arrivals, so nobody waits for it
                capture = serve_common.start_capture(trace)
                setup_s = time.perf_counter() - harness.PROCESS_START
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            prompt, asked = stream(i)
            sent.append((due, engine.submit(serve_common.TENANT, prompt,
                                            max_new_tokens=asked), asked))
        t_end = t_start + window
        wait = t_end - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        spans = serve_common.stop_capture(capture)
        harness.note_memory()

        t_drain = time.perf_counter() + traffic["drain_seconds"]
        for _, req, _ in sent:
            serve_common.wait_for(req, t_drain)
        leaked = pool.in_use()
        compiles = engine.compiles_after_warmup
    finally:
        engine.shutdown(drain=False)

    inside = [(due, req, asked) for due, req, asked in sent if t_start <= due < t_end]
    latencies, waits, lateness, failed = [], [], [], 0
    for due, req, asked in inside:
        n, ok = serve_common.finished(req, asked) if req.done() else (0, False)
        if not ok:
            failed += 1
            continue
        latencies.append((req.t_complete - due) / n)
        waits.append(req.t_dispatch - due)
        lateness.append(req.t_enqueue - due)

    def backlog(t):
        return sum(1 for due, req, _ in sent
                   if due <= t and (req.t_complete is None or req.t_complete > t))

    harness.log(f"window: {len(inside)} requests due in {window:.3f} s "
                f"({len(inside) / window:.3f} requests/s offered), {failed} failed; "
                f"backlog {backlog((t_start + t_end) / 2)} at mid-window, "
                f"{backlog(t_end)} at its end; generator lateness p95 "
                f"{1e3 * harness.percentile(lateness or [0.0], 95):.3f} ms; "
                f"{leaked} pages in use after the drain, {compiles} compiles "
                f"after warm-up")
    facts = {
        "device_kind": device_kind, "chips": 1, "lanes": engine.max_slots,
        "queue_waits_s": waits,
        "cache_hits": cache.hits, "cache_misses": cache.misses,
    }
    p95 = 1e3 * harness.percentile(latencies, 95.0) if latencies else float("inf")
    return {
        "correct": serve_common.verdict(check, traffic, compiles, leaked,
                                        failed == 0 and bool(latencies)),
        "attempted": len(inside), "failed": failed,
        "measured": {"norm_latency_p95_ms": p95, "setup_s": setup_s},
        "facts": facts, "spans": spans, "capture": capture,
    }
