"""The program-drift gate (PD12xx, ``analysis/drift_check.py``).

``compare_lock`` is pure over two program-set dicts, so every PD code
gets a seeded negative on a tampered copy of the committed lockfile —
no rebuilds, no tracing. The build-dependent contracts (the CLI exit-1
path on a tampered lock, ``--update-lock`` determinism and its
shrunken-lockfile refusal) share the process-wide live memo so the
representative programs are built at most once per test session. The
``--select``/``--ignore`` multi-prefix CLI contract rides along here
(ISSUE 19 satellite) because the drift family is its flagship consumer
(``--select PD`` as a CI gate).
"""
import copy
import hashlib
import json
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LOCK = os.path.join(_REPO, "programs.lock.json")


def _lock():
    with open(_LOCK, "r", encoding="utf-8") as fh:
        return json.load(fh)


def _live_from(lock):
    """A live set that compares clean against ``lock`` — the tamper base."""
    return {"programs": copy.deepcopy(lock["programs"]),
            "rung_grids": copy.deepcopy(lock["rung_grids"]),
            "skipped": {}}


def _compare(lock, live):
    from paddle_tpu.analysis.drift_check import compare_lock

    return compare_lock(lock, live)


def _errors(findings):
    return [f for f in findings if f.severity == "error"]


# ---------------------------------------------------------------------------
# the committed lockfile itself
# ---------------------------------------------------------------------------

def test_committed_lockfile_shape_and_coverage():
    """The acceptance floor: version pinned, >= 10 programs, all three
    TrainStep tiers, >= 2 serving rungs, >= 2 paged-decode rungs, the
    state residency's prefill chunk and decode rung, the qpsum oracle and a reshard route — the full performance story."""
    lock = _lock()
    assert lock["version"] == 1
    progs = lock["programs"]
    assert len(progs) >= 10
    for tier in ("replicated", "gspmd_int8", "zero1"):
        assert f"train_step/{tier}" in progs
    assert len([n for n in progs if n.startswith("serving/batch:")]) >= 2
    assert len([n for n in progs if n.startswith("decode/paged:")]) >= 2
    assert {n for n in progs if n.startswith("decode/state:")} == {
        "decode/state:decode:2", "decode/state:prefill:1:8",
        "decode/state:carry:1:2", "decode/state:carry:2:2"}
    assert "collective/qpsum" in progs
    assert "reshard/s_to_s" in progs
    # every fingerprint carries the full canonical schema
    for name, fp in progs.items():
        assert set(fp) == {"primitives", "dtype_bytes", "collectives",
                           "donation", "cost"}, name
        assert set(fp["cost"]) == {"flops", "bytes_read", "bytes_written",
                                   "comm_bytes", "peak_bytes",
                                   "guard_preds"}, name
    # the rung grids cover the serving + decode groups
    assert {n for n in progs if n.startswith("decode/latent:")} == {
        "decode/latent:decode:2", "decode/latent:prefill:1:8",
        "decode/latent:carry:1:2", "decode/latent:carry:2:2"}
    assert {n for n in progs if n.startswith("decode/windowed:")} == {
        "decode/windowed:decode:2", "decode/windowed:prefill:1:8",
        "decode/windowed:carry:1:2", "decode/windowed:carry:2:2"}
    assert set(lock["rung_grids"]) == {"serving/batch", "decode/paged",
                                       "decode/state", "decode/latent",
                                       "decode/windowed"}


def test_lock_digest_matches_committed_bytes():
    from paddle_tpu.analysis.drift_check import lock_digest

    with open(_LOCK, "rb") as fh:
        want = hashlib.sha256(fh.read()).hexdigest()
    assert lock_digest() == want
    assert lock_digest(os.path.join(_REPO, "no_such.lock.json")) is None


def test_lock_compares_clean_against_itself():
    lock = _lock()
    assert _compare(lock, _live_from(lock)) == []


# ---------------------------------------------------------------------------
# seeded negatives, one per PD code (pure: tampered dict copies)
# ---------------------------------------------------------------------------

def test_pd1200_extinct_program_is_an_error():
    lock = _lock()
    live = _live_from(lock)
    del live["programs"]["collective/qpsum"]
    (f,) = _compare(lock, live)
    assert (f.code, f.severity) == ("PD1200", "error")
    assert "extinct" in f.message and f.location == "collective/qpsum"


def test_pd1200_skipped_program_is_only_a_warning():
    """A program missing for lack of devices must not gate a small box."""
    lock = _lock()
    live = _live_from(lock)
    del live["programs"]["train_step/zero1"]
    live["skipped"]["train_step/zero1"] = 8
    (f,) = _compare(lock, live)
    assert (f.code, f.severity) == ("PD1200", "warning")
    assert "UNCHECKED" in f.message


def test_pd1200_stale_lockfile_is_a_loud_error():
    """A live program the lock never recorded = someone added a
    representative program without regenerating the lockfile."""
    lock = _lock()
    live = _live_from(lock)
    live["programs"]["train_step/new_tier"] = copy.deepcopy(
        live["programs"]["train_step/replicated"])
    (f,) = _compare(lock, live)
    assert (f.code, f.severity) == ("PD1200", "error")
    assert "stale" in f.message and "--update-lock" in f.message


def test_pd1200_missing_lockfile(tmp_path):
    from paddle_tpu.analysis.drift_check import check_drift

    (f,) = check_drift(live=_live_from(_lock()),
                       lock_path=str(tmp_path / "programs.lock.json"))
    assert (f.code, f.severity) == ("PD1200", "error")
    assert "--update-lock" in f.message


def test_pd999_corrupt_lockfile(tmp_path):
    from paddle_tpu.analysis.drift_check import check_drift

    bad = tmp_path / "programs.lock.json"
    bad.write_text("{not json", encoding="utf-8")
    (f,) = check_drift(live=_live_from(_lock()), lock_path=str(bad))
    assert (f.code, f.severity) == ("PD999", "error")
    assert "does not parse" in f.message


def test_pd1201_new_primitive_is_an_error():
    lock = _lock()
    live = _live_from(lock)
    live["programs"]["train_step/replicated"]["primitives"][
        "io_callback"] = 1
    findings = _compare(lock, live)
    (f,) = [f for f in findings if f.code == "PD1201"]
    assert f.severity == "error"
    assert "io_callback" in f.message
    assert f.location == "train_step/replicated:io_callback"


def test_pd1201_vanished_collective_is_an_error():
    """reshard/s_to_s carries an explicit all_to_all on dp — losing it
    means the route silently stopped moving shards."""
    lock = _lock()
    assert "all_to_all" in lock["programs"]["reshard/s_to_s"]["primitives"]
    live = _live_from(lock)
    del live["programs"]["reshard/s_to_s"]["primitives"]["all_to_all"]
    live["programs"]["reshard/s_to_s"]["collectives"] = {}
    codes = {(f.code, f.severity, f.location) for f in _compare(lock, live)}
    assert ("PD1201", "error", "reshard/s_to_s:all_to_all") in codes
    assert ("PD1201", "error", "reshard/s_to_s:axis:dp") in codes


def test_pd1201_vanished_plain_primitive_is_only_a_warning():
    lock = _lock()
    live = _live_from(lock)
    prims = live["programs"]["collective/qpsum"]["primitives"]
    gone = sorted(prims)[0]
    del prims[gone]
    (f,) = _compare(lock, live)
    assert (f.code, f.severity) == ("PD1201", "warning")
    assert "fused" in f.message


def test_pd1202_flops_growth_past_tolerance():
    lock = _lock()
    live = _live_from(lock)
    cost = live["programs"]["train_step/replicated"]["cost"]
    cost["flops"] = cost["flops"] * 2  # 2x > the 1.25x default cap
    (f,) = _compare(lock, live)
    assert (f.code, f.severity) == ("PD1202", "error")
    assert "flops" in f.message and "drift_max_flops_ratio" in f.message
    assert f.location == "train_step/replicated:flops"


def test_pd1202_growth_inside_tolerance_passes():
    lock = _lock()
    live = _live_from(lock)
    cost = live["programs"]["train_step/replicated"]["cost"]
    cost["flops"] = cost["flops"] * 1.2  # under the 1.25x budget
    assert _compare(lock, live) == []


def test_pd1202_comm_bytes_from_zero_is_an_error():
    """The replicated tier moves no collective traffic — ANY comm
    appearing there is a new sync, whatever the ratio says (0 -> x has
    no ratio)."""
    lock = _lock()
    assert lock["programs"]["train_step/replicated"]["cost"][
        "comm_bytes"] == 0
    live = _live_from(lock)
    live["programs"]["train_step/replicated"]["cost"]["comm_bytes"] = 16.0
    (f,) = _compare(lock, live)
    assert (f.code, f.severity) == ("PD1202", "error")
    assert "appeared from zero" in f.message


def test_pd1202_guard_pred_growth_is_an_error():
    lock = _lock()
    live = _live_from(lock)
    live["programs"]["train_step/replicated"]["cost"]["guard_preds"] = 2
    (f,) = _compare(lock, live)
    assert (f.code, f.severity) == ("PD1202", "error")
    assert f.location == "train_step/replicated:guard_preds"


def test_pd1203_lost_donation_is_an_error():
    lock = _lock()
    assert lock["programs"]["train_step/replicated"]["donation"] == ["cells"]
    live = _live_from(lock)
    live["programs"]["train_step/replicated"]["donation"] = []
    (f,) = _compare(lock, live)
    assert (f.code, f.severity) == ("PD1203", "error")
    assert "'cells'" in f.message
    assert f.location == "train_step/replicated:cells"


def test_pd1204_dtype_narrowing_is_an_error():
    """fp32 operand traffic halves while bf16 traffic appears: an
    accumulator silently narrowed."""
    lock = _lock()
    live = _live_from(lock)
    db = live["programs"]["train_step/replicated"]["dtype_bytes"]
    moved = db["float32"] // 2
    db["float32"] -= moved
    db["bfloat16"] = db.get("bfloat16", 0) + moved
    findings = _compare(lock, live)
    (f,) = [f for f in findings if f.code == "PD1204"]
    assert f.severity == "error"
    assert "float32" in f.message
    assert f.location == "train_step/replicated:float32"


def test_pd1205_rung_grid_shrinkage_is_an_error():
    lock = _lock()
    live = _live_from(lock)
    dropped = live["rung_grids"]["serving/batch"].pop()
    (f,) = [f for f in _compare(lock, live) if f.code == "PD1205"]
    assert f.severity == "error"
    assert dropped in f.message and f.location == "serving/batch"


def test_pd1205_vanished_grid_group_is_an_error():
    lock = _lock()
    live = _live_from(lock)
    del live["rung_grids"]["decode/paged"]
    (f,) = [f for f in _compare(lock, live) if f.code == "PD1205"]
    assert f.severity == "error" and "vanished" in f.message


# ---------------------------------------------------------------------------
# fingerprint + lockfile determinism
# ---------------------------------------------------------------------------

def test_fingerprint_jaxpr_is_deterministic_and_json_stable():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.analysis.drift_check import fingerprint_jaxpr

    def f(a, b):
        return jnp.sum(jnp.dot(a, b).astype(jnp.bfloat16))

    sds = jax.ShapeDtypeStruct((8, 8), np.dtype("float32"))
    fp1 = fingerprint_jaxpr(jax.make_jaxpr(f)(sds, sds), donation=("arg0",))
    fp2 = fingerprint_jaxpr(jax.make_jaxpr(f)(sds, sds), donation=("arg0",))
    assert json.dumps(fp1, sort_keys=True) == json.dumps(fp2, sort_keys=True)
    assert fp1["primitives"]["dot_general"] == 1
    assert fp1["donation"] == ["arg0"]
    assert "bfloat16" in fp1["dtype_bytes"]  # the cast's operand traffic
    assert fp1["cost"]["flops"] > 0


def test_render_lock_is_byte_deterministic():
    from paddle_tpu.analysis.drift_check import render_lock

    live = _live_from(_lock())
    text = render_lock(live)
    assert text == render_lock(copy.deepcopy(live))
    assert text.endswith("\n")
    assert json.loads(text)["version"] == 1


def test_update_lock_round_trips_the_committed_file(tmp_path):
    """Regenerating into a fresh path reproduces the committed bytes
    exactly — the committed lock was written by a DIFFERENT process, so
    this is the cross-process determinism proof."""
    from paddle_tpu.analysis.drift_check import update_lock

    out = tmp_path / "programs.lock.json"
    update_lock(lock_path=str(out), refresh=False)
    with open(_LOCK, "rb") as fh:
        committed = fh.read()
    assert out.read_bytes() == committed
    # and a second write is byte-identical to the first
    first = out.read_bytes()
    update_lock(lock_path=str(out), refresh=False)
    assert out.read_bytes() == first


def test_update_lock_refuses_a_shrunken_program_set(tmp_path, monkeypatch):
    """On a <8-device box the gspmd/zero1 tiers skip — writing that
    lockfile would silently stop gating them forever."""
    from paddle_tpu.analysis import drift_check

    shrunken = {"programs": {}, "rung_grids": {},
                "skipped": {"train_step/zero1": 8}}
    monkeypatch.setattr(drift_check, "record_drift_programs",
                        lambda refresh=False: shrunken)
    out = tmp_path / "programs.lock.json"
    with pytest.raises(RuntimeError, match="shrunken lockfile"):
        drift_check.update_lock(lock_path=str(out))
    assert not out.exists()


# ---------------------------------------------------------------------------
# CLI contract: --select PD trips exit 1 on a tampered lock
# ---------------------------------------------------------------------------

def test_cli_drift_gate_trips_on_tampered_lock(tmp_path, monkeypatch, capsys):
    """End-to-end acceptance path: halve the locked flops budget of one
    train tier, point the analyzer at the tampered lock, and
    ``tools.lint --analyzer drift --select PD`` must exit 1 naming the
    offending program and metric."""
    import tools.lint as lint_cli

    from paddle_tpu.analysis import drift_check

    lock = _lock()
    lock["programs"]["train_step/replicated"]["cost"]["flops"] /= 2
    tampered = tmp_path / "programs.lock.json"
    tampered.write_text(json.dumps(lock), encoding="utf-8")
    monkeypatch.setattr(drift_check, "default_lock_path",
                        lambda: str(tampered))
    rc = lint_cli.main(["--analyzer", "drift", "--select", "PD", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["crashed"] == []
    locs = [f["location"] for f in out["findings"]
            if f["code"] == "PD1202"]
    assert "train_step/replicated:flops" in locs


def test_cli_update_lock_flag_writes_and_reports_digest(tmp_path, monkeypatch,
                                                        capsys):
    import tools.lint as lint_cli

    from paddle_tpu.analysis import drift_check

    out_path = tmp_path / "programs.lock.json"
    monkeypatch.setattr(drift_check, "default_lock_path",
                        lambda: str(out_path))
    # a complete live set (skipped empty): no rebuild needed in this test
    monkeypatch.setattr(drift_check, "record_drift_programs",
                        lambda refresh=False: _live_from(_lock()))
    rc = lint_cli.main(["--update-lock"])
    msg = capsys.readouterr().out
    assert rc == 0
    assert str(out_path) in msg and "sha256" in msg
    from paddle_tpu.analysis.drift_check import lock_digest

    assert lock_digest(str(out_path))[:16] in msg


# ---------------------------------------------------------------------------
# CLI contract: --select / --ignore multi-prefix comma lists
# ---------------------------------------------------------------------------

def test_split_codes_handles_commas_repeats_and_case():
    from tools.lint import _split_codes

    assert _split_codes(["PD,NM", " jx3 ", ""]) == ["PD", "NM", "JX3"]
    assert _split_codes(None) == []


def test_filter_findings_multi_prefix_select_and_ignore():
    from paddle_tpu.analysis import Finding
    from tools.lint import filter_findings

    fs = [Finding("drift", "PD1202", "error", "m", "l"),
          Finding("numerics", "NM1101", "error", "m", "l"),
          Finding("trace", "TS101", "error", "m", "l")]
    got = filter_findings(fs, select=["PD", "NM"])
    assert [f.code for f in got] == ["PD1202", "NM1101"]
    got = filter_findings(fs, select=["PD", "NM"], ignore=["NM11"])
    assert [f.code for f in got] == ["PD1202"]
    assert [f.code for f in filter_findings(fs)] == ["PD1202", "NM1101",
                                                     "TS101"]


def test_cli_select_and_ignore_govern_the_exit_code(monkeypatch, capsys):
    """Filters apply BEFORE the exit-code decision: selecting a family
    with errors exits 1, ignoring every error family exits 0."""
    import tools.lint as lint_cli

    from paddle_tpu.analysis import Finding

    fs = [Finding("drift", "PD1202", "error", "flops drifted", "p:flops"),
          Finding("numerics", "NM1101", "error", "narrow dot", "q"),
          Finding("trace", "TS101", "warning", "advisory", "r")]
    monkeypatch.setattr(lint_cli, "run_analyzers",
                        lambda *a, **k: (list(fs), [], {"drift": 0.0}))

    rc = lint_cli.main(["--select", "PD,NM", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert sorted(f["code"] for f in out["findings"]) == ["NM1101", "PD1202"]

    rc = lint_cli.main(["--select", "PD,NM", "--ignore", "PD12,NM11",
                        "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["findings"] == []

