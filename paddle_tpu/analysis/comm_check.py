"""Comm-efficient collectives auditor (QZ8xx): the ``comm`` lint family.

The quantized allreduce tier (``distributed/collective_opt``) trades
wire bytes for controlled quantization noise — a trade that is only safe
while its contracts hold per commit: the noise stays inside the accuracy
gate, the wire math stays deterministic and replica-identical, the
portable reshard routes actually engage, and one mesh axis never mixes
wire dtypes. This pass audits a hermetic demo session
(:func:`record_demo_comm`) plus the live per-axis wire-dtype record:

QZ800  accuracy gate          the quantized allreduce's error against the
                              exact fp32 sum exceeds the gate (or the
                              gate could not run at all): quantized
                              gradient sync is running WITHOUT a passing
                              tier-1 accuracy gate (error)
QZ801  nondeterministic sync  qpsum broke its bit-stability contract:
                              two identical runs differ, replicas
                              disagree, or the shard_map wire path
                              diverges from the single-device oracle —
                              a replica-divergent gradient sync corrupts
                              training silently (error)
QZ802  reshard gather fall   the portable reshard tier is enabled but
                              the canonical s_to_s transition planned a
                              gather-path fallback — every axis move
                              silently pays O(full array) residency
                              again (warning)
QZ803  mixed comm dtypes      one mesh axis carried both int8 and dense
                              wire dtypes for engaged, size-eligible
                              syncs (multi-axis groups / unresolvable
                              axis sizes forced dense fallbacks next to
                              quantized traffic): the axis pays both
                              tiers' costs and the bandwidth win is
                              partial (warning)
QZ804  zero1 parity break     the zero1 sharded weight update (reduce-
                              scatter → shard-space optimizer update →
                              all-gather) diverges from the single-
                              device replicated oracle beyond its
                              tier's gate (fp32 gather: ~ulp; int8
                              gather: the quantization gate) — a
                              sharded update that drifts from the
                              replicated rule corrupts training
                              silently (error)
QZ805  shard-padding waste    a zero1 shard-plan row breaks the padding
                              invariant: a sharded tensor carries a full
                              block (or more) of padding per shard, or
                              was sharded with no per-replica byte win —
                              the plan *grows* optimizer state instead
                              of shrinking it (warning)

Driven by the ``comm`` analyzer of ``python -m tools.lint`` and the
tier-1 zero-findings gate (``tests/test_lint_clean.py``).
"""
from __future__ import annotations

from typing import List, Optional

from . import Finding

_ANALYZER = "comm"

# relative-to-max error two blockwise int8 quantize→sum→requantize
# passes may introduce: ~2/127 per pass plus summation headroom
ACCURACY_GATE = 0.05


def record_demo_comm() -> dict:
    """Run the representative quantized-sync session and return its
    report. Hermetic: fixed seed, no flags flipped, no global state
    mutated — the accuracy/determinism gate runs whether or not the
    quantized tier is engaged in this process. The shard_map wire path
    is exercised when the process has a multi-device platform (tier-1
    CI forces 8 CPU devices); single-device processes still gate the
    oracle math."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..base.flags import get_flag
    from ..distributed import collective_opt as copt

    report: dict = {"engaged": copt.engaged_comm_dtype() == "int8"}

    rs = np.random.RandomState(7)
    n_emu = 4
    data = (rs.randn(n_emu, 33, 65) * 2.5).astype(np.float32)
    stacked = jnp.asarray(data)
    r1 = np.asarray(copt.qpsum_reference(stacked))
    r2 = np.asarray(copt.qpsum_reference(stacked))
    exact = data.sum(axis=0)
    report["max_rel_err"] = float(
        np.abs(r1 - exact).max() / np.abs(exact).max())
    report["bitwise_deterministic"] = bool((r1 == r2).all())

    devs = jax.devices()
    report["wire_checked"] = False
    if len(devs) >= 2:
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        n = min(len(devs), 8)
        wire_data = (rs.randn(n, 17, 23) * 3).astype(np.float32)
        mesh = Mesh(np.array(devs[:n]).reshape(n), ("dp",))
        f = shard_map(lambda x: copt.qpsum_lax(x[0], "dp", n),
                      mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                      check_vma=False)
        out = np.asarray(f(jnp.asarray(wire_data[:, None])))
        oracle = np.asarray(copt.qpsum_reference(jnp.asarray(wire_data)))
        report["wire_checked"] = True
        report["replica_identical"] = bool(
            all((out[i] == out[0]).all() for i in range(n)))
        report["wire_matches_oracle"] = bool((out[0] == oracle).all())

    # canonical s_to_s plan: does the portable tier engage?
    from ..distributed.auto_parallel.placement_type import Shard

    class _MeshView:
        dim_names = ["dp"]
        shape = [4]

    route = copt.plan_route([Shard(0)], [Shard(1)], _MeshView(), (8, 8), 4)
    report["portable_reshard_enabled"] = bool(
        get_flag("comm_portable_reshard"))
    report["s_to_s_route"] = route.kind
    report["axis_wire_dtypes"] = copt.axis_wire_dtypes()
    _record_zero1(report, rs, devs)
    return report


def _record_zero1(report: dict, rs, devs) -> None:
    """The zero1 sharded-update section of the demo report (QZ804/QZ805
    feed): the REAL strategy path (pad → reduce-scatter constraint →
    shard-space ``_apply_one`` → all-gather) run against a replicated
    single-device oracle on a demo mesh, plus the shard plan whose
    padding invariant QZ805 audits. Hermetic: a throwaway optimizer, a
    demo mesh built directly from the device list — no env/flag
    mutation. Single-device processes fall back to the replicated rule
    (axis size 1), so only the plan is gated there."""
    import numpy as np

    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ..core.tensor import Parameter, Tensor
    from ..distributed import collective_opt as copt
    from ..distributed.sharding import zero1
    from ..optimizer.optimizers import AdamW

    w0 = (rs.randn(37, 21) * 0.5).astype(np.float32)
    gs = (rs.randn(3, 37, 21) * 0.2).astype(np.float32)

    def run(spec):
        p = Parameter(w0.copy(), name="zero1_demo_w")
        opt = AdamW(learning_rate=1e-2, parameters=[p], weight_decay=0.01)
        st = zero1.Zero1Strategy(opt)
        for g0 in gs:
            g = Tensor(g0.copy(), stop_gradient=True)
            opt._step_tensor._replace_value(opt._step_tensor._value + 1)
            if spec is None:
                opt._apply_one(p, g, 1e-2, None)
            else:
                st.apply_one(opt, p, g, 1e-2, None, spec)
        return np.asarray(jnp.asarray(p._value))

    ref = run(None)
    report["zero1_gather_dtype"] = copt.engaged_comm_dtype() or "fp32"
    report["zero1_wire_checked"] = False
    if len(devs) >= 2:
        n = min(len(devs), 4)
        mesh = Mesh(np.array(devs[:n]).reshape(n), ("dp",))
        got = run((mesh, "dp", n))
        report["zero1_wire_checked"] = True
        report["zero1_parity_max_err"] = float(
            np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-9))
    report["zero1_plan"] = [r.to_dict() for r in zero1.plan_shards(
        [("w", 37 * 21, 4), ("bias", 7, 4), ("emb", 50000, 4)], 4)]


def audit_comm(report: Optional[dict] = None) -> List[Finding]:
    """QZ80x findings over one demo report (recorded fresh when not
    given) plus the live per-axis wire-dtype record."""
    if report is None:
        report = record_demo_comm()
    findings: List[Finding] = []

    err = report.get("max_rel_err")
    if err is None:
        findings.append(Finding(
            _ANALYZER, "QZ800", "error",
            "quantized allreduce accuracy gate did not run — the int8 sync "
            "tier is shipping without its tier-1 accuracy contract",
            "qpsum"))
    elif err > ACCURACY_GATE:
        findings.append(Finding(
            _ANALYZER, "QZ800", "error",
            f"quantized allreduce error {err:.4f} (relative to the exact "
            f"fp32 sum's max) exceeds the {ACCURACY_GATE} accuracy gate — "
            "blockwise scales or the requantize pass regressed; gradients "
            "synced through this tier corrupt training", "qpsum"))

    issues = []
    if not report.get("bitwise_deterministic", True):
        issues.append("two identical runs differ bit-for-bit")
    if report.get("wire_checked"):
        if not report.get("replica_identical", True):
            issues.append("replicas disagree on the synced result")
        if not report.get("wire_matches_oracle", True):
            issues.append("the shard_map wire path diverges from the "
                          "single-device oracle")
    for issue in issues:
        findings.append(Finding(
            _ANALYZER, "QZ801", "error",
            f"qpsum broke its determinism contract: {issue} — a "
            "replica-divergent or run-unstable gradient sync corrupts "
            "training silently", "qpsum"))

    if report.get("portable_reshard_enabled") and \
            report.get("s_to_s_route") != "all_to_all":
        findings.append(Finding(
            _ANALYZER, "QZ802", "warning",
            "portable resharding is enabled but the canonical s_to_s "
            f"transition planned route {report.get('s_to_s_route')!r} "
            "instead of the O(shard) all_to_all — axis moves are silently "
            "paying the gather path's O(full array) residency again",
            "reshard"))

    for ax, dtypes in sorted((report.get("axis_wire_dtypes") or {}).items()):
        if len(dtypes) > 1:
            findings.append(Finding(
                _ANALYZER, "QZ803", "warning",
                f"mesh axis '{ax}' carried mixed gradient-sync wire dtypes "
                f"({', '.join(dtypes)}): engaged, size-eligible syncs fell "
                "back to dense transport next to quantized traffic "
                "(multi-axis group or unresolvable axis size) — the axis "
                "pays both tiers and the bandwidth win is partial", "qpsum"))

    # QZ804: zero1 sharded-update parity vs the replicated oracle. The
    # fp32 gather tier must track the oracle to reduction-order ulps;
    # the int8 gather tier inherits the quantization gate.
    if report.get("zero1_wire_checked"):
        err = report.get("zero1_parity_max_err")
        gate = (ACCURACY_GATE
                if report.get("zero1_gather_dtype") == "int8" else 1e-5)
        if err is None or err > gate:
            findings.append(Finding(
                _ANALYZER, "QZ804", "error",
                f"zero1 sharded weight update diverges from the replicated "
                f"single-device oracle (max rel err "
                f"{'unmeasured' if err is None else f'{err:.2e}'} > "
                f"{gate:g} gate, gather tier "
                f"{report.get('zero1_gather_dtype')}) — the reduce-scatter/"
                "shard-update/all-gather pipeline drifted from the "
                "optimizer's replicated rule; sharded training corrupts "
                "silently", "zero1"))

    # QZ805: the shard plan's padding invariant — every sharded tensor
    # must shrink per-replica bytes and carry less than one block of
    # padding per shard.
    for row in report.get("zero1_plan") or []:
        name = row.get("name", "?")
        if not row.get("sharded"):
            continue
        if row.get("shard_elems", 0) >= row.get("numel", 0):
            findings.append(Finding(
                _ANALYZER, "QZ805", "warning",
                f"zero1 shard plan row '{name}' is sharded with no "
                f"per-replica byte win (shard {row.get('shard_elems')} ≥ "
                f"numel {row.get('numel')}) — block padding grew the "
                "optimizer state this tensor was supposed to shrink; it "
                "belongs on the replicated update path", "zero1"))
        elif row.get("pad_per_shard", 0) >= row.get("block", 256):
            findings.append(Finding(
                _ANALYZER, "QZ805", "warning",
                f"zero1 shard plan row '{name}' carries "
                f"{row.get('pad_per_shard'):.0f} padding elements per "
                f"shard (≥ one {row.get('block')}-element block) — the "
                "plan wastes a full block of optimizer-state bytes per "
                "replica on this tensor", "zero1"))
    return findings
