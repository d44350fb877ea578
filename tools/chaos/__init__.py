"""``python -m tools.chaos`` — the seeded end-to-end chaos schedule.

Runs every reliability scenario under ONE deterministic fault schedule
(``--seed``, default 0) and asserts the stack's recovery invariants
instead of hoping:

==================  ====================================================
train_resume        SIGTERM mid-epoch → snapshot at the step boundary →
                    ``Model.fit(resume=...)`` continues; the merged loss
                    stream must be BIT-IDENTICAL to an uninterrupted run
serving_retry       injected ``serving.execute`` faults under the
                    scheduler's RetryPolicy: every request completes,
                    outputs bit-exact, zero duplicate resolutions, zero
                    post-warmup compiles
decode_faults       injected ``serving.decode_step`` + ``kv.commit``
                    crashes through the decode fault wall: every future
                    resolves, ZERO leaked KV slots (JX333 clean), pool
                    bytes constant, zero post-warmup compiles
prefetch_crash      injected ``io.h2d`` fault in the DeviceLoader
                    staging thread: the error propagates to ``fit``
                    promptly — never a deadlocked queue
ckpt_torn_write     injected ``ckpt.write`` crash between tmp-write and
                    rename: the previous snapshot stays the committed
                    one; the retry lands the new one
watchdog_hang       injected ``comm.watchdog`` hang: the timeout
                    handler fires and ``comm.watchdog_timeout`` ticks
nonfinite_grad      injected ``numerics.nonfinite_grad`` NaN under a
                    live GradScaler: the lit numerics witness dumps
                    exactly one NM1104 bundle, the poisoned update
                    reverts, the scale backs off, training continues
==================  ====================================================

Exit code: 0 = every invariant held, 1 = any breach (CI-gateable).
``--json`` prints the machine-readable report. The injector is armed
per scenario and ALWAYS disarmed (FT900 would flag a leak).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time


def _fresh_seed():
    import numpy as np

    import paddle_tpu as paddle

    paddle.seed(0)
    return np.random.RandomState(0)


# --------------------------------------------------------------- scenarios
def scenario_train_resume(seed: int) -> dict:
    """Preemption mid-epoch → snapshot → resume, bit-identical stream."""
    import signal
    import threading

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.hapi.model import Model

    def build():
        paddle.seed(7)
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
        m = Model(net)
        m.prepare(optimizer=paddle.optimizer.Adam(
            learning_rate=0.01, parameters=net.parameters()),
            loss=nn.MSELoss())
        return m

    rs = np.random.RandomState(seed)
    data = [(rs.randn(4, 4).astype(np.float32),
             rs.randn(4, 1).astype(np.float32)) for _ in range(10)]

    class LossRec(Callback):
        def __init__(self):
            super().__init__()
            self.losses = []

        def on_train_batch_end(self, step, logs=None):
            self.losses.append(float(logs["loss"]))

    # the reference: one uninterrupted run
    ref = LossRec()
    build().fit(data, epochs=2, sync_every=1, verbose=0, shuffle=False,
                callbacks=[ref])

    snapdir = tempfile.mkdtemp(prefix="chaos_snap_")
    on_main = threading.current_thread() is threading.main_thread()
    try:
        first = LossRec()
        kill_at = 7

        class Preempt(Callback):
            def on_train_batch_end(self, step, logs=None):
                if len(first.losses) == kill_at:
                    if on_main:
                        # the real preemption path: SIGTERM → handler →
                        # snapshot at this boundary → clean stop
                        signal.raise_signal(signal.SIGTERM)
                    else:
                        raise RuntimeError("simulated preemption")

        t0 = time.perf_counter()
        try:
            build().fit(data, epochs=2, sync_every=1, verbose=0,
                        shuffle=False, callbacks=[first, Preempt()],
                        snapshot_dir=snapdir, snapshot_every=4)
        except RuntimeError:
            pass  # non-main-thread fallback: crash after a snapshot
        resumed = LossRec()
        build().fit(data, epochs=2, sync_every=1, verbose=0, shuffle=False,
                    callbacks=[resumed], snapshot_dir=snapdir, resume=True)
        recovery_s = time.perf_counter() - t0
        cut = len(ref.losses) - len(resumed.losses)
        merged = first.losses[:cut] + resumed.losses
        # recovery_steps = batches replayed by the resumed run (its first
        # batch index vs where the interrupted run actually stopped)
        recovery_steps = len(first.losses) - cut
        ok = (merged == ref.losses and len(first.losses) >= kill_at
              and 0 <= recovery_steps <= 4)
        return {"ok": bool(ok), "steps": len(ref.losses),
                "killed_after": len(first.losses), "resumed_at": cut,
                "recovery_steps": recovery_steps,
                "bit_identical": merged == ref.losses,
                "sigterm_path": on_main,
                "recovery_wall_s": round(recovery_s, 3)}
    finally:
        shutil.rmtree(snapdir, ignore_errors=True)


def scenario_serving_retry(seed: int) -> dict:
    """Injected program-call faults under retry: nothing lost, nothing
    duplicated, nothing recompiled."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import reliability as rel
    from paddle_tpu.observability.metrics import registry
    from paddle_tpu.profiler.pipeline import ServingStats
    from paddle_tpu.serving import ServingEngine

    def _counter_total(name):
        inst = registry.snapshot()["metrics"].get(name)
        if not inst:
            return 0.0
        return float(sum(cell.get("value", 0)
                         for cell in inst.get("values", [])))

    tmpdir = tempfile.mkdtemp(prefix="chaos_serving_")
    try:
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        net.eval()
        prefix = os.path.join(tmpdir, "model")
        paddle.jit.save(net, prefix, input_spec=[
            paddle.static.InputSpec([None, 8], "float32")])
        engine = ServingEngine(prefix, buckets=[1, 2, 4],
                               stats=ServingStats())
        engine.warmup()
        oracle = engine.predictor  # same program, direct call path
        rs = np.random.RandomState(seed)
        dup_before = _counter_total("serving.duplicate_resolution")
        inj = rel.arm(rel.FaultInjector(seed=seed).plan(
            "serving.execute", rate=0.25))
        try:
            cases = [("a", 1), ("b", 3), ("a", 2), ("b", 4), ("a", 1),
                     ("b", 2), ("a", 4), ("b", 1), ("a", 3), ("b", 2),
                     ("a", 2), ("b", 1)]
            inputs = [rs.randn(n, 8).astype(np.float32) for _, n in cases]
            reqs = [engine.submit(t, x) for (t, _), x in zip(cases, inputs)]
            outs = [r.result(60) for r in reqs]
        finally:
            rel.disarm()
        engine.shutdown(drain=True)
        exact = all(
            np.array_equal(np.asarray(o[0]),
                           np.asarray(oracle.run([x])[0]))
            for o, x in zip(outs, inputs))
        dup_delta = _counter_total("serving.duplicate_resolution") - dup_before
        summary = inj.summary()
        ok = (exact and engine.compiles_after_warmup == 0
              and summary["total_injected"] > 0 and dup_delta == 0)
        return {"ok": bool(ok), "requests": len(cases),
                "requests_lost": 0 if exact else sum(
                    0 if o is not None else 1 for o in outs),
                "bit_exact": bool(exact),
                "injected": summary["total_injected"],
                "duplicate_resolutions": dup_delta,
                "compiles_after_warmup": engine.compiles_after_warmup}
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def scenario_decode_faults(seed: int) -> dict:
    """Decode-step + KV-commit crashes: slots always come home."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import reliability as rel
    from paddle_tpu.analysis.jaxpr_audit import audit_serving
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle_tpu.profiler.pipeline import ServingStats
    from paddle_tpu.serving import DecodeEngine

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(
        num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
        max_position_embeddings=32))
    model.eval()
    engine = DecodeEngine(model, max_slots=2, max_seq=16, seq_buckets=[8],
                          prefill_max_batch=2, stats=ServingStats())
    engine.warmup()
    rs = np.random.RandomState(seed)
    inj = rel.arm(rel.FaultInjector(seed=seed)
                  .plan("serving.decode_step", rate=0.2)
                  .plan("kv.commit", rate=0.05))
    failed = completed = 0
    try:
        reqs = [engine.submit(t, rs.randint(0, 512, size=n).astype(np.int32),
                              max_new_tokens=3)
                for t, n in (("a", 4), ("b", 6), ("a", 3), ("b", 5),
                             ("a", 6), ("b", 4))]
        for r in reqs:
            try:
                r.result(60)
                completed += 1
            except rel.FaultInjection:
                failed += 1  # resolved-with-error: the future came home
    finally:
        rel.disarm()
    engine.shutdown(drain=True)
    findings = [str(f) for f in audit_serving(engine)]
    slots_leaked = engine.kv_pool.in_use()
    summary = inj.summary()
    ok = (completed + failed == len(reqs) and slots_leaked == 0
          and not findings and summary["total_injected"] > 0
          and engine.compiles_after_warmup == 0)
    return {"ok": bool(ok), "requests": len(reqs), "completed": completed,
            "failed_resolved": failed,
            "unresolved": len(reqs) - completed - failed,
            "kv_slots_leaked": slots_leaked,
            "audit_findings": findings,
            "injected": summary["total_injected"],
            "injected_by_site": summary["by_site"],
            "compiles_after_warmup": engine.compiles_after_warmup}


def scenario_page_pressure(seed: int) -> dict:
    """KV page-allocation failure under pool pressure: the starved
    request sheds with ``AdmissionError`` (reason ``kv_pages``), its
    pages come home (no leak — JX333 clean), and every in-flight lane
    keeps decoding to completion."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import reliability as rel
    from paddle_tpu.analysis.jaxpr_audit import audit_serving
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle_tpu.profiler.pipeline import ServingStats
    from paddle_tpu.serving import AdmissionError, DecodeEngine

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(
        num_hidden_layers=1, hidden_size=32, num_attention_heads=2,
        max_position_embeddings=32))
    model.eval()
    engine = DecodeEngine(model, kv_mode="paged", max_slots=3, max_seq=16,
                          seq_buckets=[8, 16], prefill_max_batch=2,
                          page_size=8, stats=ServingStats())
    engine.warmup()
    rs = np.random.RandomState(seed)
    inj = rel.arm(rel.FaultInjector(seed=seed)
                  .plan("kv.page_alloc", rate=0.25))
    completed = shed = other = 0
    try:
        reqs = [engine.submit(t, rs.randint(0, 512, size=n).astype(np.int32),
                              max_new_tokens=6)
                for t, n in (("a", 4), ("b", 9), ("a", 3), ("b", 12),
                             ("a", 6), ("b", 5), ("a", 10), ("b", 7))]
        for r in reqs:
            try:
                r.result(60)
                completed += 1
            except AdmissionError as e:
                assert e.reason == "kv_pages", e.reason
                shed += 1
            except Exception:
                other += 1
    finally:
        rel.disarm()
    engine.shutdown(drain=True)
    findings = [str(f) for f in audit_serving(engine)]
    pages_leaked = engine.kv_pool.in_use()
    summary = inj.summary()
    ok = (completed + shed == len(reqs) and other == 0 and shed > 0
          and completed > 0 and pages_leaked == 0 and not findings
          and summary["total_injected"] > 0
          and engine.compiles_after_warmup == 0)
    return {"ok": bool(ok), "requests": len(reqs), "completed": completed,
            "shed_admission_error": shed, "other_failures": other,
            "kv_pages_leaked": pages_leaked,
            "audit_findings": findings,
            "injected": summary["total_injected"],
            "injected_by_site": summary["by_site"],
            "compiles_after_warmup": engine.compiles_after_warmup}


def scenario_spec_rollback(seed: int) -> dict:
    """Self-speculative decoding under page-allocation faults (ISSUE
    20): seeded ``kv.page_alloc`` failures land mid-speculation — while
    lanes grow lookahead pages for the draft/verify round — and the
    contract holds anyway: zero pages leak (speculative-suffix rollback
    plus the shed path both drain through the free-list), every
    COMPLETED greedy stream is token-for-token the non-speculative
    stream, the serving audit stays clean and nothing retraces."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import reliability as rel
    from paddle_tpu.analysis.jaxpr_audit import audit_serving
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle_tpu.profiler.pipeline import ServingStats
    from paddle_tpu.serving import AdmissionError, DecodeEngine

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(
        num_hidden_layers=2, hidden_size=32, num_attention_heads=2,
        max_position_embeddings=32))
    model.eval()
    kw = dict(kv_mode="paged", max_slots=3, max_seq=32,
              seq_buckets=[8, 16], prefill_max_batch=2, page_size=8)
    rs = np.random.RandomState(seed)
    cases = [(t, rs.randint(0, 512, size=n).astype(np.int32))
             for t, n in (("a", 4), ("b", 9), ("a", 3), ("b", 12),
                          ("a", 6), ("b", 5), ("a", 10), ("b", 7))]
    # the non-speculative reference streams, faults disarmed
    ref_engine = DecodeEngine(model, stats=ServingStats(), **kw)
    ref_engine.warmup()
    ref = [np.asarray(ref_engine.generate(t, p, max_new_tokens=8))
           for t, p in cases]
    ref_engine.shutdown(drain=True)

    engine = DecodeEngine(model, speculate_k=4, spec_draft_layers=1,
                          spec_min_accept=0.0, stats=ServingStats(), **kw)
    engine.warmup()
    inj = rel.arm(rel.FaultInjector(seed=seed)
                  .plan("kv.page_alloc", rate=0.25))
    outs = [None] * len(cases)
    completed = shed = other = 0
    try:
        reqs = [engine.submit(t, p, max_new_tokens=8) for t, p in cases]
        for i, r in enumerate(reqs):
            try:
                outs[i] = np.asarray(r.result(60))
                completed += 1
            except AdmissionError as e:
                assert e.reason == "kv_pages", e.reason
                shed += 1
            except Exception:
                other += 1
    finally:
        rel.disarm()
    engine.shutdown(drain=True)
    findings = [str(f) for f in audit_serving(engine)]
    pages_leaked = engine.kv_pool.in_use()
    summary = inj.summary()
    exact = all(o is None or np.array_equal(o, r)
                for o, r in zip(outs, ref))
    spec_rounds = (engine.stats.summary()["decode"] or {}).get(
        "spec_rounds", 0)
    ok = (completed + shed == len(cases) and other == 0 and shed > 0
          and completed > 0 and exact and spec_rounds > 0
          and pages_leaked == 0 and not findings
          and summary["total_injected"] > 0
          and engine.compiles_after_warmup == 0)
    return {"ok": bool(ok), "requests": len(cases), "completed": completed,
            "shed_admission_error": shed, "other_failures": other,
            "bit_exact_vs_nonspec": bool(exact),
            "spec_rounds": spec_rounds,
            "kv_pages_leaked": pages_leaked,
            "audit_findings": findings,
            "injected": summary["total_injected"],
            "injected_by_site": summary["by_site"],
            "compiles_after_warmup": engine.compiles_after_warmup}


def scenario_prefetch_crash(seed: int) -> dict:
    """A killed prefetch thread must fail fit, not deadlock it."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import reliability as rel
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.io import DeviceLoader

    paddle.seed(3)
    net = nn.Sequential(nn.Linear(4, 4), nn.ReLU(), nn.Linear(4, 1))
    m = Model(net)
    m.prepare(optimizer=paddle.optimizer.SGD(
        learning_rate=0.01, parameters=net.parameters()), loss=nn.MSELoss())
    rs = np.random.RandomState(seed)
    data = [(rs.randn(2, 4).astype(np.float32),
             rs.randn(2, 1).astype(np.float32)) for _ in range(8)]
    rel.arm(rel.FaultInjector(seed=seed).plan("io.h2d", rate=1.0))
    t0 = time.perf_counter()
    try:
        try:
            m.fit(DeviceLoader(data, depth=2), epochs=1, verbose=0,
                  sync_every=1)
            propagated = False
        except rel.FaultInjection:
            propagated = True
    finally:
        rel.disarm()
    wall = time.perf_counter() - t0
    ok = propagated and wall < 30.0
    return {"ok": bool(ok), "error_propagated": propagated,
            "wall_s": round(wall, 3)}


def scenario_ckpt_torn_write(seed: int) -> dict:
    """A crash between tmp-write and rename never tears a snapshot."""
    import paddle_tpu  # noqa: F401 — flag registry
    from paddle_tpu import reliability as rel
    from paddle_tpu.reliability.snapshot import TrainSnapshotter

    snapdir = tempfile.mkdtemp(prefix="chaos_ckpt_")
    try:
        snap = TrainSnapshotter(snapdir, keep=3)
        base = snap.save(step=1, epoch=0, next_batch=1)
        # one injected crash: attempt 1 dies post-tmp pre-rename, the
        # retry (attempt 2) lands the snapshot
        rel.arm(rel.FaultInjector(seed=seed).plan(
            "ckpt.write", rate=1.0, max_fires=1))
        try:
            second = snap.save(step=2, epoch=0, next_batch=2)
        finally:
            rel.disarm()
        retried_ok = snap.latest() == second
        # unbounded crashes: the save gives up loudly, the previous
        # snapshot stays the committed latest
        rel.arm(rel.FaultInjector(seed=seed).plan("ckpt.write", rate=1.0))
        try:
            try:
                snap.save(step=3, epoch=0, next_batch=3)
                gave_up = False
            except rel.FaultInjection:
                gave_up = True
        finally:
            rel.disarm()
        survived = snap.latest() == second
        ok = retried_ok and gave_up and survived and base != second
        return {"ok": bool(ok), "retried_commit": retried_ok,
                "giveup_raised": gave_up,
                "previous_snapshot_intact": survived}
    finally:
        shutil.rmtree(snapdir, ignore_errors=True)


def scenario_watchdog_hang(seed: int) -> dict:
    """A simulated hung collective fires the watchdog's timeout path."""
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401
    from paddle_tpu import reliability as rel
    from paddle_tpu.distributed.utils.watchdog import (
        disable_comm_watchdog, enable_comm_watchdog)

    fired = []
    manager = enable_comm_watchdog(
        timeout=30.0, on_timeout=lambda tag, age: fired.append(tag))
    rel.arm(rel.FaultInjector(seed=seed).plan("comm.watchdog", rate=1.0))
    try:
        manager.watch("chaos.allreduce", jnp.ones(4))
        deadline = time.monotonic() + 5.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        rel.disarm()
        disable_comm_watchdog()
    ok = fired == ["chaos.allreduce"] and "chaos.allreduce" in manager.timeouts
    return {"ok": bool(ok), "handler_fired": list(fired),
            "timeouts": list(manager.timeouts)}


def scenario_nonfinite_grad(seed: int) -> dict:
    """An injected NaN grad under a live fp16-style GradScaler: the lit
    numerics witness dumps exactly ONE NM1104 flight-recorder bundle,
    the poisoned step's optimizer update reverts (params unchanged),
    the dynamic scale backs off, and later steps train on finite."""
    import glob

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import reliability as rel
    from paddle_tpu.observability import numerics as num
    from paddle_tpu.observability.anomaly import AnomalyMonitor

    dumpdir = tempfile.mkdtemp(prefix="chaos_numerics_")
    paddle.seed(seed)
    model = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    scaler = paddle.amp.GradScaler(enable=True, init_loss_scaling=8.0)
    crit = nn.MSELoss()
    x = paddle.Tensor(np.ones((2, 4), np.float32), stop_gradient=True)
    y = paddle.Tensor(np.zeros((2, 4), np.float32), stop_gradient=True)

    mon = AnomalyMonitor(dump_dir=dumpdir, cooldown_s=60.0)
    bundles = []
    orig_notify = num._notify

    def notify(verdict):
        out = mon.on_numerics(verdict)
        if out:
            bundles.append(out)

    num._notify = notify
    was = num.set_witness(True)
    # one poisoned step: unscale_ NaNs the first grad, found_inf trips
    rel.arm(rel.FaultInjector(seed=seed).plan(
        "numerics.nonfinite_grad", rate=1.0, kind="corrupt", max_fires=1))
    try:
        w_before = np.asarray(model.weight._value).copy()
        losses = []
        for _ in range(3):
            loss = crit(model(x), y)
            scaler.scale(loss).backward()
            scaler.step(opt)
            scaler.update()
            opt.clear_grad()
            losses.append(float(loss._value))
    finally:
        rel.disarm()
        num.set_witness(was)
        num._notify = orig_notify
    try:
        violations = num.witness_violations()
        nonfinite = [v for v in violations if v["code"] == "NM1104"]
        # exactly one bundle: the monitor's cooldown absorbs repeats
        on_disk = glob.glob(os.path.join(dumpdir, "anomaly_numerics*"))
        w_final = np.asarray(model.weight._value)
        scale_backed_off = float(scaler._scale._value) < 8.0
        recovered = (np.isfinite(w_final).all()
                     and not np.allclose(w_final, w_before))
        ok = (len(nonfinite) == 1 and len(bundles) == 1
              and len(on_disk) == 1 and scale_backed_off and recovered
              and all(np.isfinite(losses)))
        return {"ok": bool(ok), "nm1104_verdicts": len(nonfinite),
                "bundles": len(bundles), "bundles_on_disk": len(on_disk),
                "scale_backed_off": bool(scale_backed_off),
                "trained_after_poison": bool(recovered),
                "losses_finite": bool(all(np.isfinite(losses)))}
    finally:
        num.witness_reset()
        shutil.rmtree(dumpdir, ignore_errors=True)


_SCENARIOS = (
    ("train_resume", scenario_train_resume),
    ("serving_retry", scenario_serving_retry),
    ("decode_faults", scenario_decode_faults),
    ("page_pressure", scenario_page_pressure),
    ("spec_rollback", scenario_spec_rollback),
    ("prefetch_crash", scenario_prefetch_crash),
    ("ckpt_torn_write", scenario_ckpt_torn_write),
    ("watchdog_hang", scenario_watchdog_hang),
    ("nonfinite_grad", scenario_nonfinite_grad),
)


def run_schedule(seed: int = 0, only=None) -> dict:
    """Run the (selected) scenarios; returns the full report with the
    aggregate verdict + distinct injected-site coverage."""
    report = {"seed": int(seed), "scenarios": {}}
    sites = set()
    for name, fn in _SCENARIOS:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            result = fn(seed)
        except Exception as e:  # a crashed scenario is a breach
            result = {"ok": False,
                      "error": f"{type(e).__name__}: {e}"}
        result["wall_s"] = round(time.perf_counter() - t0, 3)
        report["scenarios"][name] = result
        for site in (result.get("injected_by_site") or {}):
            sites.add(site)
    # distinct sites actually injected across the schedule (scenarios
    # that don't report per-site detail contribute their known site)
    known = {"train_resume": None, "serving_retry": "serving.execute",
             "prefetch_crash": "io.h2d",
             "ckpt_torn_write": "ckpt.write",
             "watchdog_hang": "comm.watchdog",
             "nonfinite_grad": "numerics.nonfinite_grad"}
    for name, result in report["scenarios"].items():
        site = known.get(name)
        if site and result.get("ok"):
            sites.add(site)
    report["distinct_sites_injected"] = sorted(sites)
    # the coverage gate is part of the verdict, not just the tests': a
    # FULL schedule that stopped injecting at ≥5 distinct sites means
    # fault_point wiring rotted somewhere even if every scenario "passed"
    full_run = set(report["scenarios"]) == {n for n, _ in _SCENARIOS}
    report["site_gate_ok"] = (not full_run) or len(sites) >= 5
    report["ok"] = (all(r.get("ok") for r in report["scenarios"].values())
                    and report["site_gate_ok"])
    return report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.chaos",
        description="seeded chaos schedule over train + serving: inject "
                    "faults at every reliability site, assert the "
                    "recovery invariants (exit 1 on any breach)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--json", action="store_true", dest="as_json")
    parser.add_argument("--only", action="append",
                        choices=[n for n, _ in _SCENARIOS],
                        help="run only the named scenario(s)")
    args = parser.parse_args(argv)

    report = run_schedule(seed=args.seed, only=args.only)
    if args.as_json:
        print(json.dumps(report, indent=2, default=str))
    else:
        for name, result in report["scenarios"].items():
            verdict = "ok" if result.get("ok") else "BREACH"
            detail = {k: v for k, v in result.items()
                      if k not in ("ok",)}
            print(f"{name:18s} {verdict:7s} {detail}")
        print(f"distinct sites injected: "
              f"{len(report['distinct_sites_injected'])} "
              f"{report['distinct_sites_injected']}")
        print("chaos:", "all invariants held" if report["ok"]
              else "INVARIANT BREACH")
    return 0 if report["ok"] else 1


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
