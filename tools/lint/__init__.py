"""``python -m tools.lint`` — the repo's static-analysis driver.

Runs the fourteen ``paddle_tpu.analysis`` analyzers and reports findings:

- **trace**:    the trace-safety AST linter over ``paddle_tpu/`` (or the
                paths given on the command line),
- **registry**: the op-registry consistency gate (including the legacy
                ``op_compat`` alias tier),
- **program**:  the Program verify pass, exercised on a freshly recorded
                representative static program (build → verify → clone →
                verify clone invariants), so IR-level regressions surface
                without needing a checked-in graph,
- **jaxpr**:    the trace-level auditor, exercised on a freshly compiled
                representative whole-step TrainStep (build → run → audit
                every cached program's ClosedJaxpr + the recompilation
                heuristics) plus the eager kernel-cache counters (JX32x),
- **spmd**:     the static mesh-axis checker over the same paths as the
                trace linter (one-hop cross-file mesh resolution),
- **cost**:     the static jaxpr cost model (CM5xx) over the same
                representative train step: oversized intermediates,
                arithmetic-intensity cliffs, comm-bound collectives and
                peak residency vs the FLAGS budgets,
- **serving**:  the serving tier's retrace-free contract (JX33x) over a
                freshly built representative ServingEngine (export a tiny
                model → warm the bucket ladder → drive mixed-size tenant
                traffic → assert zero post-warmup compiles and full
                ladder coverage),
- **telemetry**: the observability layer's contract (OB6xx): static scan
                of ``paddle_tpu/observability/`` for device syncs inside
                memory samplers, plus unclosed-span / duplicate-metric /
                dead-anomaly-monitor / unbounded-egress audits over a
                demo telemetry session (with a fed demo monitor) AND the
                live process tracer + registry + monitor + exporters,
- **comm**:     the comm-efficient collective tier's contract (QZ8xx)
                over a fresh demo sync session: quantized-allreduce
                accuracy vs the exact fp32 sum, bitwise determinism /
                replica identity of the wire path, the portable reshard
                route engaging for s_to_s, and no mesh axis mixing
                gradient-sync wire dtypes.
- **fault**:    the reliability layer's hygiene (FT9xx) over the same
                paths as the trace linter plus the live process: no
                FaultInjector left armed outside a chaos run, no
                RetryPolicy with a dead deadline budget, no injection
                into an undeclared fault site.
- **ckpt**:     the sharded-checkpoint manifest contract (CK95x) over a
                freshly recorded demo checkpoint (two tensors saved
                through the public ``save_sharded`` path, round-tripped
                through ``load_sharded``): every piece present, byte-
                and sha256-exact, bounds covering each tensor exactly,
                no orphan pieces or stale writer tmp dirs.
- **concurrency**: the threaded runtime's lock discipline (CX10xx) over
                the same paths as the trace linter plus a lit-witness
                demo (ServingEngine under traffic + DeviceLoader
                prefetch): no unguarded shared mutation across thread
                entry closures, no static lock-order cycle, no blocking
                call under a held lock, no bare lock outside the
                ``observability.locks`` registry, and no runtime order
                inversion / hold-budget breach recorded by the witness.
                ``--select CX`` is the pre-fleet gate before launching
                multi-thread serving work.
- **numerics**: the mixed-precision discipline (NM11xx) over the same
                paths as the trace linter plus the shared demo TrainStep
                and a traced bf16 matmul: no dtype string surgery, no
                hardcoded fp32 cast inside AMP white-listed ops, no
                float64 into jnp calls, no narrow-float dot accumulation
                or oversized bf16 reductions in the audited programs, no
                int8-to-bf16 dequant epilogue, and no NaN/Inf or range
                collapse recorded by the lit runtime witness
                (``observability/numerics.py``). ``--select NM`` is the
                pre-run gate before a long mixed-precision job.
- **drift**:    the program-drift gate (PD12xx) over the committed
                ``programs.lock.json``: every representative program
                (TrainStep replicated/gspmd/zero1 tiers, serving batch
                ladder, paged-decode rung grid, qpsum oracle, reshard
                route) is retraced, canonically fingerprinted
                (primitive histogram, donation, per-dtype bytes,
                per-axis collectives, cost-model scalars) and compared
                against the lock — new primitives, lost donation,
                dtype narrowing, rung-grid shrinkage and cost growth
                past the ``FLAGS_drift_max_*_ratio`` tolerances all
                fail. ``--update-lock`` regenerates the lockfile
                deterministically (byte-identical when nothing
                changed), then exits.

Exit-code contract (stable, CI-gateable):
  0 = no error-severity findings (warnings never gate)
  1 = at least one error-severity finding
  2 = an analyzer crashed (the crash is reported as a finding too)

``--json`` prints one machine-readable object with every finding plus
per-family wall-time under ``timings_s``.
``--select``/``--ignore`` filter findings by code prefix (e.g.
``--select JX,SP4`` or ``--ignore PV008``) so CI can gate on specific
families. ``--include-tests`` adds the ``tests/`` tree to the
source-scanning analyzers (trace, spmd).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_ANALYZERS = ("trace", "registry", "program", "jaxpr", "spmd", "cost",
              "serving", "telemetry", "comm", "fault", "ckpt",
              "concurrency", "numerics", "drift")


def _source_paths(paths, include_tests=False):
    out = list(paths) if paths else [os.path.join(_REPO_ROOT, "paddle_tpu")]
    tests_dir = os.path.join(_REPO_ROOT, "tests")
    if include_tests and tests_dir not in out:
        out.append(tests_dir)
    return out


def _run_trace(paths, include_tests=False):
    from paddle_tpu.analysis.trace_safety import lint_paths

    return lint_paths(_source_paths(paths, include_tests))


def _run_spmd(paths, include_tests=False):
    from paddle_tpu.analysis.spmd_check import check_paths

    return check_paths(_source_paths(paths, include_tests))


def _run_registry(_paths, include_tests=False):
    from paddle_tpu.analysis.registry_check import check_registry

    return check_registry()


def _run_program(_paths, include_tests=False):
    """Record the shared representative program and verify it + its clone."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.analysis.program_verify import (
        record_demo_program, verify_clone, verify_program)

    from paddle_tpu.analysis import Finding

    main, x, hidden, loss = record_demo_program()
    findings = verify_program(main, fetch_ids=[id(loss), id(hidden)])
    findings += verify_clone(main, main.clone(for_test=True))
    # smoke the wired Executor path too — a failure must surface as a
    # finding (parseable --json, nonzero exit), never a bare traceback
    try:
        exe = paddle.static.Executor()
        got = exe.run(main, feed={"x": np.zeros((2, 8), np.float32)},
                      fetch_list=[loss])
        if not np.isfinite(np.asarray(got[0])).all():
            raise ValueError("demo program produced non-finite loss")
    except Exception as e:
        findings.append(Finding(
            "program", "PV100", "error",
            f"Executor.run failed on the recorded demo program: {e}",
            "executor"))
    return findings


# the representative TrainStep is built once per process and shared by the
# jaxpr and cost families (audit/cost are read-only on it): two model
# builds + compiles for the same demo program would double the dominant
# wall-time of a full lint run
_demo_step_memo: list = []


def _demo_step():
    if not _demo_step_memo:
        from paddle_tpu.analysis.jaxpr_audit import record_demo_step

        _demo_step_memo.append(record_demo_step())
    return _demo_step_memo[0]


def _run_jaxpr(_paths, include_tests=False):
    """Compile the shared representative whole-step TrainStep and audit
    every cached program (trace-level verification + recompilation audit
    + guard-family coverage, see analysis/jaxpr_audit.py), then the eager
    kernel-cache counters (JX32x over core.kernel_cache.stats())."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis.jaxpr_audit import audit_kernel_cache

    step = _demo_step()
    findings = step.audit()
    # a guarded program too, so the branch-coverage checks run per commit
    from paddle_tpu.jit.functionalize import functionalize

    @functionalize
    def guarded(x):
        if paddle.sum(x) > 0:
            return x * 2
        return x * 3

    guarded(paddle.ones([4]))
    findings += guarded.audit()
    # exercise the eager fast path so a fresh CLI process audits live
    # counters, not an empty dict (in-process runs also fold in whatever
    # the session already dispatched — that's the point of the audit)
    from paddle_tpu.base.flags import get_flag
    if get_flag("eager_kernel_cache"):
        a = paddle.ones([4])
        for _ in range(3):
            paddle.add(a, a)
    findings += audit_kernel_cache()
    return findings


def _run_cost(_paths, include_tests=False):
    """Static cost model over the shared representative whole-step
    TrainStep (same step the jaxpr family audits — retrace →
    FLOPs/bytes/liveness walk, see analysis/cost_model.py): CM5xx
    findings vs the FLAGS budgets."""
    from paddle_tpu.analysis.cost_model import check_cost

    return check_cost(_demo_step().cost())


def _run_serving(_paths, include_tests=False):
    """Build the representative serving engines — the batch tier (tiny
    exported MLP, warmed 3-rung ladder, two tenants' mixed-size traffic)
    AND the decode tier (tiny GPT over a KV slot pool, mixed prompts
    joining/leaving the running batch) — and audit the retrace-free +
    slot-residency contracts (JX330-JX333, analysis/jaxpr_audit.py)."""
    import shutil
    import tempfile

    from paddle_tpu.analysis.jaxpr_audit import (
        audit_serving, record_demo_decode_engine, record_demo_engine)

    tmpdir = tempfile.mkdtemp(prefix="paddle_lint_serving_")
    try:
        findings = list(audit_serving(record_demo_engine(tmpdir)))
        findings += audit_serving(record_demo_decode_engine())
        return findings
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _run_telemetry(_paths, include_tests=False):
    """The observability layer's own contract (OB6xx): static OB602 scan
    of the ``paddle_tpu/observability/`` sources, then OB600/OB601 over a
    representative demo telemetry session (spans on every runtime track,
    every instrument kind) AND over the live process tracer/registry —
    an unclosed span or schema collision anywhere this process fails the
    commit, not just in the demo."""
    from paddle_tpu.analysis.telemetry_check import (
        audit_telemetry, check_paths, record_demo_monitor,
        record_demo_telemetry)

    findings = check_paths(
        [os.path.join(_REPO_ROOT, "paddle_tpu", "observability")])
    demo_tracer, demo_registry = record_demo_telemetry()
    demo_monitor = record_demo_monitor(demo_tracer, demo_registry)
    # hermetic demo pass: servers=[] — any live exporter belongs to the
    # live audit below, not to the demo session (and would double-count)
    findings += audit_telemetry(demo_tracer, demo_registry,
                                monitor=demo_monitor, servers=[])
    # the live global tracer/registry/monitor + any running exporters
    findings += audit_telemetry()
    return findings


def _run_comm(_paths, include_tests=False):
    """Record the representative quantized-sync session (accuracy +
    determinism gates over the qpsum oracle and, multi-device, the
    shard_map wire path) and audit the comm tier's contract (QZ8xx,
    analysis/comm_check.py) plus the live per-axis wire-dtype record."""
    from paddle_tpu.analysis.comm_check import audit_comm

    return audit_comm()


def _run_fault(paths, include_tests=False):
    """FT9xx over the same source paths as the trace linter (reliability
    hygiene: armed injectors, dead retry deadlines, undeclared fault
    sites). Never scans tests/ — chaos tests arm injectors on purpose
    and carry their own disarm discipline."""
    from paddle_tpu.analysis.fault_check import check_paths

    return check_paths(_source_paths(paths, include_tests=False))


def _run_ckpt(_paths, include_tests=False):
    """Record the representative sharded checkpoint (two tensors saved
    and round-tripped through the public save/load path into a temp
    dir) and audit its manifest contract (CK95x,
    analysis/ckpt_check.py)."""
    import shutil
    import tempfile

    from paddle_tpu.analysis.ckpt_check import (audit_ckpt_dir,
                                                record_demo_checkpoint)

    tmpdir = tempfile.mkdtemp(prefix="paddle_lint_ckpt_")
    try:
        return audit_ckpt_dir(record_demo_checkpoint(tmpdir))
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _run_concurrency(paths, include_tests=False):
    """CX10xx: static lock discipline over the same source paths as the
    trace linter (unguarded shared mutation, static lock-order cycles,
    blocking under a lock, unregistered bare locks) plus the lit-witness
    demo — one warmed ServingEngine taking traffic while a DeviceLoader
    prefetches, with ``FLAGS_concurrency_witness`` recording every
    named-lock acquisition (CX1004 inversions / CX1005 hold budget).
    Never scans tests/ — concurrency tests seed inversions on purpose."""
    from paddle_tpu.analysis.concurrency_check import (check_paths,
                                                       record_demo_concurrency)

    findings = list(record_demo_concurrency())
    findings.extend(check_paths(_source_paths(paths, include_tests=False)))
    return findings


def _run_numerics(paths, include_tests=False):
    """NM11xx: static mixed-precision discipline over the same source
    paths as the trace linter (dtype string surgery, hardcoded fp32
    casts in AMP ops, float64 into jnp) plus the dtype-flow audit of
    the shared demo TrainStep's cached programs, a traced bf16 matmul
    through the ops-layer accumulation helper, and a short lit-witness
    run (NM1104/NM1105). Never scans tests/ — numerics tests seed
    NaN/float64 negatives on purpose."""
    from paddle_tpu.analysis.numerics_check import (check_paths,
                                                    record_demo_numerics)

    findings = list(record_demo_numerics(_demo_step()))
    findings.extend(check_paths(_source_paths(paths, include_tests=False)))
    return findings


def _run_drift(_paths, include_tests=False):
    """PD12xx: retrace + fingerprint every representative program and
    compare against the committed ``programs.lock.json`` (see
    analysis/drift_check.py). ``--update-lock`` regenerates the lock."""
    from paddle_tpu.analysis.drift_check import check_drift

    return check_drift()


_RUNNERS = {"trace": _run_trace, "registry": _run_registry,
            "program": _run_program, "jaxpr": _run_jaxpr,
            "spmd": _run_spmd, "cost": _run_cost,
            "serving": _run_serving, "telemetry": _run_telemetry,
            "comm": _run_comm, "fault": _run_fault,
            "ckpt": _run_ckpt, "concurrency": _run_concurrency,
            "numerics": _run_numerics, "drift": _run_drift}

# analyzer -> its finding-code family prefix, so a crash finding
# (<PREFIX>999) stays visible under --select filters for that family
_FAMILY_PREFIX = {"trace": "TS", "registry": "RC", "program": "PV",
                  "jaxpr": "JX", "spmd": "SP", "cost": "CM",
                  "serving": "JX", "telemetry": "OB", "comm": "QZ",
                  "fault": "FT", "ckpt": "CK",
                  "concurrency": "CX", "numerics": "NM", "drift": "PD"}


def run_analyzers(selected=_ANALYZERS, paths=None, include_tests=False):
    """Run the named analyzers; returns ``(findings, crashed, timings)``
    where ``crashed`` lists analyzers that raised (each crash is also
    appended to the findings as an <NAME>999 error) and ``timings`` maps
    each analyzer family to its wall-time in seconds."""
    import time

    from paddle_tpu.analysis import Finding

    findings = []
    crashed = []
    timings = {}
    for name in selected:
        t0 = time.perf_counter()
        try:
            findings.extend(_RUNNERS[name](paths, include_tests=include_tests))
        except Exception as e:
            crashed.append(name)
            findings.append(Finding(
                name, f"{_FAMILY_PREFIX.get(name, name[:2].upper())}999",
                "error",
                f"analyzer '{name}' crashed: {type(e).__name__}: "
                f"{str(e).splitlines()[0] if str(e) else ''}", "analyzer"))
        timings[name] = round(time.perf_counter() - t0, 3)
    try:
        # re-home the per-family wall-times into the process metrics
        # registry (ISSUE 7): `timings_s` stays the CLI surface, the
        # labeled gauge is the snapshot()-visible copy
        from paddle_tpu.observability import registry as _obs_registry

        gauge = _obs_registry.gauge(
            "lint.family_seconds",
            "wall-time of each tools.lint analyzer family's last run")
        for family, seconds in timings.items():
            gauge.set(seconds, family=family)
    except Exception:
        pass
    return findings, crashed, timings


def _split_codes(values):
    out = []
    for v in values or []:
        out.extend(c.strip().upper() for c in v.split(",") if c.strip())
    return out


def filter_findings(findings, select=None, ignore=None):
    """Keep findings whose code matches a ``select`` prefix (all, when no
    select is given) and matches no ``ignore`` prefix."""
    if select:
        findings = [f for f in findings
                    if any(f.code.upper().startswith(p) for p in select)]
    if ignore:
        findings = [f for f in findings
                    if not any(f.code.upper().startswith(p) for p in ignore)]
    return findings


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description="paddle_tpu static analysis: trace-safety linter, "
                    "registry consistency gate, Program verify pass, jaxpr "
                    "auditor, SPMD axis checker")
    parser.add_argument("paths", nargs="*",
                        help="files/directories for the source-scanning "
                             "analyzers (default: paddle_tpu/)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable output")
    parser.add_argument("--analyzer", action="append", choices=_ANALYZERS,
                        help="run only the named analyzer(s); default: all")
    parser.add_argument("--include-tests", action="store_true",
                        help="also scan the tests/ tree with the "
                             "source-scanning analyzers (trace, spmd)")
    parser.add_argument("--select", action="append", metavar="CODES",
                        help="only report findings whose code starts with "
                             "one of these comma-separated prefixes "
                             "(e.g. --select TS,JX3)")
    parser.add_argument("--ignore", action="append", metavar="CODES",
                        help="drop findings whose code starts with one of "
                             "these comma-separated prefixes")
    parser.add_argument("--update-lock", action="store_true",
                        help="regenerate programs.lock.json from a fresh "
                             "build of every representative program "
                             "(deterministic: byte-identical when nothing "
                             "changed), then exit without linting")
    args = parser.parse_args(argv)

    if args.update_lock:
        from paddle_tpu.analysis.drift_check import lock_digest, update_lock

        path = update_lock()
        print(f"tools.lint: wrote {path} "
              f"(sha256 {lock_digest(path)[:16]})")
        return 0

    selected = tuple(dict.fromkeys(args.analyzer)) if args.analyzer else _ANALYZERS
    findings, crashed, timings = run_analyzers(selected, args.paths,
                                               include_tests=args.include_tests)
    findings = filter_findings(findings, _split_codes(args.select),
                               _split_codes(args.ignore))

    from paddle_tpu.analysis import errors as _errors

    n_errors = len(_errors(findings))
    n_warnings = len(findings) - n_errors
    if args.as_json:
        print(json.dumps({
            "analyzers": list(selected),
            "crashed": crashed,
            "errors": n_errors,
            "warnings": n_warnings,
            "timings_s": timings,
            "findings": [f.to_dict() for f in findings],
        }, indent=2))
    else:
        for f in findings:
            print(f)
        timing_txt = ", ".join(f"{k} {v:.2f}s" for k, v in timings.items())
        print(f"tools.lint: {n_errors} error(s), {n_warnings} warning(s) "
              f"[{timing_txt}]"
              + (f" CRASHED: {', '.join(crashed)}" if crashed else ""))
    if crashed:
        return 2
    return 1 if n_errors else 0


if __name__ == "__main__":  # pragma: no cover - `python tools/lint/__init__.py`
    sys.exit(main())
