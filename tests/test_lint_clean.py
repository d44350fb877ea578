"""CI gate: the repo itself passes its own static analysis.

Runs all fourteen ``paddle_tpu.analysis`` analyzer families over the live
codebase and asserts ZERO error-severity findings, so a regression (a new
jit-unsafe pattern in a kernel, a broken alias row, an IR recording bug,
a host callback in a compiled step, a typo'd mesh axis, a cost-model
budget blowout, a serving-tier steady-state recompile, a leaked telemetry
span, a sync inside a memory sampler, an armed fault injector /
undeclared fault site, a sharded checkpoint whose manifest stopped
holding its pieces, a narrow-float accumulation / dtype-surgery numerics
hazard or a representative program drifting from its committed ``programs.lock.json`` fingerprint) fails
tier-1 instead of rotting until pod scale. The
``python -m tools.lint`` CLI contract (exit 0, machine-readable JSON
with per-family wall-time, ``--include-tests``) is gated here too.
"""
import json
import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _errors(findings):
    from paddle_tpu.analysis import errors

    return [str(f) for f in errors(findings)]


def test_trace_safety_clean_over_source_tree():
    from paddle_tpu.analysis.trace_safety import lint_paths

    findings = lint_paths([os.path.join(_REPO, "paddle_tpu")])
    assert _errors(findings) == []


def test_trace_safety_clean_over_tests_tree():
    """ROADMAP item: the tests/ tree holds trace-safe idioms too —
    deliberate violations carry # noqa with a reason."""
    from paddle_tpu.analysis.trace_safety import lint_paths

    findings = lint_paths([os.path.join(_REPO, "tests")])
    assert _errors(findings) == []


def test_registry_gate_green():
    from paddle_tpu.analysis.registry_check import check_registry

    findings = check_registry()
    assert _errors(findings) == []


def test_program_verifier_green_on_recorded_program():
    from paddle_tpu.analysis.program_verify import (
        record_demo_program, verify_clone, verify_program)

    main, x, hidden, loss = record_demo_program()
    findings = verify_program(main, fetch_ids=[id(loss), id(hidden)])
    assert _errors(findings) == []
    assert _errors(verify_clone(main, main.clone(for_test=True))) == []


def test_jaxpr_auditor_green_on_demo_step():
    """The representative whole-step program audits clean: no callbacks,
    no 64-bit leaks, no donation aliasing, full guard coverage, and
    audit_report() reads counters without building anything new."""
    from paddle_tpu.analysis.jaxpr_audit import record_demo_step

    step = record_demo_step()
    findings = step.audit()
    assert [str(f) for f in findings] == []
    before = step._compiled.stats["compiled_steps"]
    report = step.audit_report()
    assert report["n_cache_keys"] == 1
    assert report["total_builds"] == 1
    assert step._compiled.stats["compiled_steps"] == before


def test_spmd_checker_clean_over_source_and_tests():
    from paddle_tpu.analysis.spmd_check import check_paths

    findings = check_paths([os.path.join(_REPO, "paddle_tpu"),
                            os.path.join(_REPO, "tests")])
    assert _errors(findings) == []


def test_cost_model_clean_on_demo_step():
    """The representative whole-step program costs clean: no oversized
    intermediates, no intensity cliff, no comm-bound axis, peak within
    the HBM budget — and the report carries real numbers (a zeroed-out
    walker would pass the finding gate while measuring nothing)."""
    from paddle_tpu.analysis.cost_model import check_cost
    from paddle_tpu.analysis.jaxpr_audit import record_demo_step

    step = record_demo_step()
    report = step.cost()
    assert report.flops > 0 and report.peak_bytes > 0, report.to_dict()
    assert report.retrace_errors == []
    findings = check_cost(report)
    assert [str(f) for f in findings] == []


def test_trace_safety_covers_serving_tree():
    """ISSUE 6 satellite: the serving/ subsystem is inside the
    zero-findings gate — scanned (non-empty module list, guarding against
    a silently skipped directory) and clean."""
    import glob

    from paddle_tpu.analysis.trace_safety import lint_paths

    serving_dir = os.path.join(_REPO, "paddle_tpu", "serving")
    modules = glob.glob(os.path.join(serving_dir, "*.py"))
    assert len(modules) >= 3, modules  # __init__, request_queue, scheduler, engine
    assert _errors(lint_paths([serving_dir])) == []


def test_serving_audit_green_on_demo_engine(tmp_path):
    """The representative serving engine holds the retrace-free contract:
    warmed ladder, zero post-warmup compiles, no JX33x findings — and the
    report carries real traffic (a dead engine would pass the finding
    gate while proving nothing)."""
    from paddle_tpu.analysis.jaxpr_audit import audit_serving, record_demo_engine

    engine = record_demo_engine(str(tmp_path))
    assert [str(f) for f in audit_serving(engine)] == []
    assert engine.compiles_after_warmup == 0
    report = engine.serving_report()
    assert report["requests"] == 4 and report["batches"] >= 1
    assert report["compiled_rungs"] == 3  # one per demo ladder rung


def test_serving_audit_green_on_demo_decode_engine():
    """ISSUE 13 satellite: the serving lint family audits the KV decode
    path too — the demo decode engine holds the retrace-free AND
    slot-residency contracts (JX330-JX333) under real joined/left
    traffic."""
    from paddle_tpu.analysis.jaxpr_audit import (audit_serving,
                                                 record_demo_decode_engine)

    engine = record_demo_decode_engine()
    assert [str(f) for f in audit_serving(engine)] == []
    assert engine.compiles_after_warmup == 0
    report = engine.serving_report()
    assert report["requests"] == 3
    assert report["kv_pool_bytes_constant"] is True
    assert report["decode"]["tokens"] > 0
    assert engine.kv_pool.in_use() == 0  # every slot released


def test_telemetry_contract_green_on_live_process():
    """ISSUE 7 + 8: the observability layer's own contract holds — the
    observability/ tree has no device sync inside a sampler (OB602), the
    demo telemetry session (with its fed demo anomaly monitor) and the
    LIVE process tracer/registry/monitor/exporters audit clean
    (OB600/OB601/OB603/OB604)."""
    from paddle_tpu.analysis.telemetry_check import (
        audit_telemetry, check_paths, record_demo_monitor,
        record_demo_telemetry)

    obs_dir = os.path.join(_REPO, "paddle_tpu", "observability")
    assert _errors(check_paths([obs_dir])) == []
    tracer, registry = record_demo_telemetry()
    monitor = record_demo_monitor(tracer, registry)
    assert [str(f) for f in audit_telemetry(tracer, registry, monitor=monitor,
                                            servers=[])] == []  # hermetic demo
    assert [str(f) for f in audit_telemetry()] == []  # live process state


def test_ckpt_audit_green_on_demo_checkpoint(tmp_path):
    """ISSUE 15: the sharded-checkpoint manifest contract holds on the
    representative checkpoint — two tensors saved through the public
    ``save_sharded`` path and round-tripped, every piece present and
    sha256-exact, bounds covering each tensor, no orphans — and
    ``tools.ckpt verify`` agrees with exit 0."""
    from paddle_tpu.analysis.ckpt_check import (audit_ckpt_dir,
                                                record_demo_checkpoint)

    ck = record_demo_checkpoint(str(tmp_path))
    assert [str(f) for f in audit_ckpt_dir(ck)] == []
    import tools.ckpt as ckpt_cli

    assert ckpt_cli.main(["verify", ck]) == 0


def test_comm_audit_green_on_demo_session():
    """ISSUE 10 + 12: the comm-efficient collective tier's contract
    holds — the quantized allreduce passes its accuracy gate against the
    exact fp32 sum, the wire path is bitwise deterministic /
    replica-identical / oracle-matching (this CI forces 8 CPU devices,
    so the shard_map wire path really runs), the portable reshard tier
    plans all_to_all for s_to_s, no mesh axis mixed gradient-sync wire
    dtypes, the zero1 sharded weight update tracks the replicated
    oracle (QZ804) and its shard plan holds the padding invariant
    (QZ805)."""
    from paddle_tpu.analysis.comm_check import audit_comm, record_demo_comm

    report = record_demo_comm()
    assert report["wire_checked"], report  # 8-device CI must gate the wire
    assert report["zero1_wire_checked"], report  # ...and the zero1 update
    assert report["zero1_parity_max_err"] <= 1e-5
    assert any(r["sharded"] for r in report["zero1_plan"])
    assert [str(f) for f in audit_comm(report)] == []


def test_fault_hygiene_clean_over_source_tree():
    """ISSUE 14: the reliability layer's own hygiene holds — no
    FaultInjector armed in the CI process (FT900), no RetryPolicy with a
    dead deadline budget (FT901), and every literal fault site injected
    anywhere in paddle_tpu/ is declared (with its cleanup path) in
    reliability.faults.SITES (FT902)."""
    from paddle_tpu.analysis.fault_check import check_paths

    findings = check_paths([os.path.join(_REPO, "paddle_tpu")])
    assert _errors(findings) == []


def test_concurrency_clean_over_source_tree():
    """ISSUE 16: the threaded runtime's lock discipline holds — no
    unguarded shared mutation across thread entry points (CX1000), no
    static lock-order cycle (CX1001), no blocking call under a held lock
    (CX1002), no bare ``threading.Lock()`` outside the named-lock
    registry (CX1003, bootstrap modules noqa'd with reasons)."""
    from paddle_tpu.analysis.concurrency_check import check_paths

    findings = check_paths([os.path.join(_REPO, "paddle_tpu")])
    assert _errors(findings) == []


def test_concurrency_demo_green_under_witness():
    """ISSUE 16: a warmed ServingEngine taking live traffic while a
    DeviceLoader prefetches, with the runtime lock-order witness lit,
    records acquisitions across the migrated runtime locks and finds no
    order inversion (CX1004) and no hold-budget breach (CX1005)."""
    from paddle_tpu.analysis.concurrency_check import record_demo_concurrency

    assert [str(f) for f in record_demo_concurrency()] == []


def test_numerics_clean_over_source_tree():
    """ISSUE 17: paddle_tpu/ is NM-clean — no dtype string surgery, no
    hardcoded fp32 cast inside an AMP white-listed op, no float64
    handed to a jnp call (deliberate widenings carry a reasoned
    noqa)."""
    from paddle_tpu.analysis.numerics_check import check_paths

    findings = check_paths([os.path.join(_REPO, "paddle_tpu")])
    assert _errors(findings) == []


def test_numerics_demo_green():
    """ISSUE 17: the representative numerics session — dtype-flow audit
    of the demo TrainStep's programs, a traced bf16 matmul through the
    ops-layer wide-accumulation helper, and a lit-witness run over
    healthy tensors — records zero NM findings."""
    from paddle_tpu.analysis.numerics_check import record_demo_numerics

    assert [str(f) for f in record_demo_numerics()] == []


def test_drift_gate_green_against_committed_lockfile():
    """ISSUE 19: the committed ``programs.lock.json`` matches a fresh
    retrace + canonical fingerprint of every representative program
    (PD12xx clean on the 8-device harness, nothing skipped) — and
    ``render_lock`` over the live set reproduces the committed bytes
    EXACTLY, which is the cross-process determinism proof for
    ``--update-lock`` (the lockfile was generated in a different
    process than this test)."""
    from paddle_tpu.analysis.drift_check import (
        check_drift, default_lock_path, record_drift_programs, render_lock)

    live = record_drift_programs()
    assert live["skipped"] == {}, live["skipped"]  # every tier built
    assert len(live["programs"]) >= 10
    assert [str(f) for f in check_drift(live)] == []
    with open(default_lock_path(), "r", encoding="utf-8") as fh:
        committed = fh.read()
    assert render_lock(live) == committed


def test_cli_exits_zero_with_machine_readable_findings(capsys):
    """`tools.lint --json --include-tests` over the repo: exit 0,
    parseable. Run in-process (the tests above already paid the analyzer
    costs once; a fresh subprocess would re-import jax + paddle_tpu just
    to check exit code)."""
    import tools.lint as lint_cli

    rc = lint_cli.main(["--json", "--include-tests"])
    out = capsys.readouterr().out
    assert rc == 0, out
    payload = json.loads(out)
    assert payload["errors"] == 0
    assert payload["crashed"] == []
    assert set(payload["analyzers"]) == {"trace", "registry", "program",
                                         "jaxpr", "spmd", "cost", "serving",
                                         "telemetry", "comm", "fault",
                                         "ckpt", "concurrency", "numerics",
                                         "drift"}
    assert isinstance(payload["findings"], list)
    # per-family wall-time (CI satellite): one entry per analyzer run
    assert set(payload["timings_s"]) == set(payload["analyzers"])
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in payload["timings_s"].values())
