"""ISSUE 10 — comm-efficient collectives: quantized dp gradient
allreduce (qpsum) + portable collective resharding.

Covers the blockwise-int8 wire math (accuracy, bitwise determinism,
replica identity, oracle equivalence), the engagement policy
(flag / amp comm_dtype / per-call override, min-bytes and dtype gates),
the three wiring points (communication.all_reduce, TrainStep's GSPMD
dp grad-sync stage, the reshard routes in auto_parallel.api), the
gpt_tiny quantized-vs-fp32 convergence gate, the QZ8xx lint family's
seeded negatives, and the planner/cost-model byte accounting the bench
cross-checks. conftest forces 8 CPU devices, so every collective here
is real.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.base.flags import get_flags, set_flags
from jax import shard_map
from paddle_tpu.distributed import collective_opt as copt

N_DEV = len(jax.devices())
_COMM_FLAGS = ("comm_quantize_dp_grads", "comm_quantize_min_bytes",
               "comm_quantize_block", "comm_portable_reshard")


@pytest.fixture(autouse=True)
def _comm_flag_isolation():
    """Restore the comm flags and clear the per-axis wire-dtype record
    after every test — a leaked engaged flag (or a seeded mixed-dtype
    record) would poison the repo-wide QZ lint gate."""
    prev = get_flags(_COMM_FLAGS)
    yield
    set_flags(prev)
    copt.reset_comm_records()


def _dp_mesh(n=None):
    n = n or N_DEV
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("dp",))


def _wire_qpsum(stacked, n, block=None):
    """Run the real qpsum wire path: replica r's tensor at stacked[r];
    returns the per-replica results stacked [n, ...]."""
    f = shard_map(lambda x: copt.qpsum_lax(x[0], "dp", n, block),
                  mesh=_dp_mesh(n), in_specs=P("dp"), out_specs=P("dp"),
                  check_vma=False)
    return np.asarray(f(jnp.asarray(stacked[:, None])))


# ---------------------------------------------------------------- wire math
class TestQpsumMath:
    def test_reference_matches_exact_sum_within_gate(self):
        rs = np.random.RandomState(0)
        data = (rs.randn(8, 37, 51) * 4).astype(np.float32)
        got = np.asarray(copt.qpsum_reference(jnp.asarray(data)))
        exact = data.sum(axis=0)
        rel = np.abs(got - exact).max() / np.abs(exact).max()
        # two int8 blockwise passes: ~2/127 each plus summation headroom
        assert rel < 0.05, rel

    def test_zero_and_single_replica_are_exact(self):
        zeros = jnp.zeros((4, 16, 16), jnp.float32)
        assert np.asarray(copt.qpsum_reference(zeros)).sum() == 0.0
        one = jnp.ones((1, 8, 8), jnp.float32)
        np.testing.assert_array_equal(np.asarray(copt.qpsum_reference(one)),
                                      np.ones((8, 8), np.float32))

    def test_odd_sizes_pad_cleanly(self):
        """Shapes that don't divide n·block round-trip through the
        pad/unpad path without bleeding padding into the result."""
        rs = np.random.RandomState(1)
        data = rs.randn(8, 13).astype(np.float32)  # 13 elems << one block
        got = np.asarray(copt.qpsum_reference(jnp.asarray(data), block=8))
        exact = data.sum(axis=0)
        assert np.abs(got - exact).max() / np.abs(exact).max() < 0.05

    @pytest.mark.skipif(N_DEV < 8, reason="needs the 8-device CPU mesh")
    def test_wire_path_bitwise_matches_oracle_and_replicas_agree(self):
        rs = np.random.RandomState(2)
        data = (rs.randn(8, 40, 33) * 3).astype(np.float32)
        out = _wire_qpsum(data, 8)
        oracle = np.asarray(copt.qpsum_reference(jnp.asarray(data)))
        assert all((out[i] == out[0]).all() for i in range(8))
        assert (out[0] == oracle).all()

    @pytest.mark.skipif(N_DEV < 8, reason="needs the 8-device CPU mesh")
    def test_wire_path_bitwise_deterministic_across_runs(self):
        rs = np.random.RandomState(3)
        data = (rs.randn(8, 129) * 2).astype(np.float32)
        assert (_wire_qpsum(data, 8) == _wire_qpsum(data, 8)).all()

    def test_axis_size_one_is_identity(self):
        x = jnp.arange(12.0)
        assert (np.asarray(copt.qpsum_lax(x, "dp", 1)) ==
                np.asarray(x)).all()

    def test_payload_accounting_saves_over_3_5x_at_default_block(self):
        row = copt.tensor_wire_bytes(512 * 64, 4, 8)
        assert row["dense_bytes"] / row["wire_bytes"] > 3.5
        rep = copt.wire_report([(512 * 64, 4, True), (64, 4, True)], 8)
        assert rep["n_quantized"] == 1 and rep["n_fallback"] == 1
        assert rep["saved_ratio"] > 3.0


# ----------------------------------------------------------- all_reduce tier
@pytest.mark.skipif(N_DEV < 8, reason="needs the 8-device CPU mesh")
class TestAllReduceQuantized:
    def _allreduce(self, data, **kwargs):
        @dist.spmd(in_specs=P("dp"), out_specs=P("dp"), axes=("dp",))
        def f(x):
            return dist.all_reduce(x, **kwargs)

        t = paddle.Tensor(data, stop_gradient=True)
        return np.asarray(f(t)._value)

    def test_explicit_opt_in_quantizes(self):
        rs = np.random.RandomState(4)
        data = (rs.randn(8 * 32, 40) * 2).astype(np.float32)
        out = self._allreduce(data.copy(), quantized=True)
        exact = data.reshape(8, 32, 40).sum(axis=0)
        rel = np.abs(out.reshape(8, 32, 40)[0] - exact).max() / \
            np.abs(exact).max()
        assert 0 < rel < 0.05  # quantized (noisy) but inside the gate
        assert copt.axis_wire_dtypes() == {"dp": ["int8"]}

    def test_flag_engages_and_explicit_false_overrides(self):
        rs = np.random.RandomState(5)
        data = (rs.randn(8 * 32, 40) * 2).astype(np.float32)
        dense = self._allreduce(data.copy())
        set_flags({"comm_quantize_dp_grads": True})
        quant = self._allreduce(data.copy())
        forced_dense = self._allreduce(data.copy(), quantized=False)
        assert (forced_dense == dense).all()   # bit-identical psum
        assert not (quant == dense).all()      # the tier really engaged

    def test_small_tensors_fall_back_to_exact_psum(self):
        set_flags({"comm_quantize_dp_grads": True})
        data = np.arange(8 * 4, dtype=np.float32).reshape(8 * 4, 1)
        out = self._allreduce(data.copy())   # 4 floats/rank << min_bytes
        exact = data.reshape(8, 4, 1).sum(axis=0)
        np.testing.assert_array_equal(out.reshape(8, 4, 1)[0], exact)

    def test_int_tensors_fall_back(self):
        set_flags({"comm_quantize_dp_grads": True,
                   "comm_quantize_min_bytes": 0})
        data = np.arange(8 * 1024, dtype=np.int32).reshape(8 * 64, 16)
        out = self._allreduce(data.copy())
        exact = data.reshape(8, 64, 16).sum(axis=0)
        np.testing.assert_array_equal(out.reshape(8, 64, 16)[0], exact)

    def test_non_sum_ops_never_quantize(self):
        set_flags({"comm_quantize_dp_grads": True,
                   "comm_quantize_min_bytes": 0})
        data = np.tile(np.arange(8, dtype=np.float32)[:, None, None],
                       (1, 64, 16)).reshape(8 * 64, 16)
        out = self._allreduce(data.copy(), op=dist.ReduceOp.MAX)
        assert (out == 7.0).all()

    def test_amp_comm_dtype_engages_the_tier(self):
        assert copt.engaged_comm_dtype() is None
        with paddle.amp.auto_cast(comm_dtype="int8"):
            assert copt.engaged_comm_dtype() == "int8"
        assert copt.engaged_comm_dtype() is None
        with pytest.raises(ValueError, match="comm_dtype"):
            paddle.amp.auto_cast(comm_dtype="fp4").__enter__()

    def test_explicit_axis_size_beats_env_mesh_lookup(self):
        """Callers that know their collective's mesh (pipeline schedules)
        pass axis_size; the decision must not consult — or build — the
        env mesh for an axis it doesn't carry."""
        set_flags({"comm_quantize_dp_grads": True,
                   "comm_quantize_min_bytes": 0})
        big = jnp.ones((64, 64), jnp.float32)
        d = copt.quantize_decision(big, is_sum=True, axes=("ring",),
                                   explicit=None, axis_size=4)
        assert d.quantize and d.axis_size == 4
        # unknown axis with no size hint: structural fallback, not a crash
        d2 = copt.quantize_decision(big, is_sum=True, axes=("ring",),
                                    explicit=None)
        assert not d2.quantize and d2.reason in ("axis_size_unknown",
                                                 "axis_size_1")

    def test_multi_axis_group_records_mixed_wire_dtype(self):
        """A structurally unquantizable engaged sync (multi-axis group)
        records the dense dtype next to int8 — the QZ803 feed."""
        set_flags({"comm_quantize_dp_grads": True,
                   "comm_quantize_min_bytes": 0})
        decision = copt.quantize_decision(
            jnp.ones((64, 64), jnp.float32), is_sum=True,
            axes=("dp", "mp"), explicit=None)
        assert not decision.quantize and decision.reason == "multi_axis"
        assert "float32" in copt.axis_wire_dtypes()["dp"]


# ------------------------------------------------------- reduce_scatter ops
@pytest.mark.skipif(N_DEV < 8, reason="needs the 8-device CPU mesh")
class TestReduceScatterOps:
    def _run(self, op):
        data = np.tile(np.arange(8, dtype=np.float32)[None, :],
                       (8, 1)).reshape(8, 8) + \
            np.arange(8, dtype=np.float32)[:, None]

        @dist.spmd(in_specs=P(None), out_specs=P("dp"), axes=("dp",))
        def f(x):
            out = paddle.zeros([1, 8])
            return dist.reduce_scatter(out, x, op=op)

        t = paddle.Tensor(data, stop_gradient=True)
        return np.asarray(f(t)._value)

    def test_max_and_min(self):
        got_max = self._run(dist.ReduceOp.MAX)
        # replicated input: every rank's max row r is row r itself; rank i
        # keeps chunk i (one row each)
        expect = (np.arange(8)[None, :] + np.arange(8)[:, None]).astype(
            np.float32)
        np.testing.assert_array_equal(got_max.reshape(8, 8), expect)
        got_min = self._run(dist.ReduceOp.MIN)
        np.testing.assert_array_equal(got_min.reshape(8, 8), expect)

    def test_unsupported_op_names_op_and_supported_set(self):
        with pytest.raises(NotImplementedError) as ei:
            self._run(dist.ReduceOp.PROD)
        msg = str(ei.value)
        assert "PROD" in msg and "SUM" in msg and "MAX" in msg \
            and "MIN" in msg

    def test_max_indivisible_scatter_dim_errors_like_sum(self):
        """MAX/MIN must not silently drop trailing rows: a scatter dim
        that doesn't divide the group errors, matching the SUM path."""
        data = np.zeros((10, 8), np.float32)  # 10 % 8 != 0

        @dist.spmd(in_specs=P(None), out_specs=P("dp"), axes=("dp",))
        def f(x):
            out = paddle.zeros([1, 8])
            return dist.reduce_scatter(out, x, op=dist.ReduceOp.MAX)

        with pytest.raises(ValueError, match="divisible"):
            f(paddle.Tensor(data, stop_gradient=True))


# ------------------------------------------------------------ GSPMD tier
@pytest.mark.skipif(N_DEV < 8, reason="needs the 8-device CPU mesh")
class TestGspmdSync:
    def test_numerics_and_int8_on_the_wire(self):
        dist.init_parallel_env()
        jmesh = dist.env.get_mesh()
        rs = np.random.RandomState(6)
        g = jnp.asarray((rs.randn(512, 64) * 0.1).astype(np.float32))
        fn = jax.jit(lambda v: copt.dp_sync_gspmd(v, jmesh, "dp"))
        out = fn(g)
        rel = float(jnp.max(jnp.abs(out - g)) / jnp.max(jnp.abs(g)))
        assert rel < 0.02  # one quantize pass on the gather half
        txt = fn.lower(g).compile().as_text()
        assert "s8" in txt  # int8 payload really crosses the wire

    def test_engagement_requires_installed_mesh_and_dp(self):
        set_flags({"comm_quantize_dp_grads": True})
        assert copt.gspmd_sync_axis() is not None  # dp=8 mesh installed
        set_flags({"comm_quantize_dp_grads": False})
        assert copt.gspmd_sync_axis() is None


# --------------------------------------------------- TrainStep convergence
@pytest.mark.skipif(N_DEV < 8, reason="needs the 8-device CPU mesh")
class TestTrainStepConvergence:
    """ISSUE 10 acceptance: gpt_tiny N-step training on the CPU dp mesh
    stays inside the loss-curve tolerance gate with quantized dp grad
    sync, and the quantized run is bitwise reproducible."""

    STEPS = 5
    GATE = 0.10

    def _train(self):
        from paddle_tpu.distributed.parallel import replicate_layer, shard_batch
        from paddle_tpu.jit.api import TrainStep
        from paddle_tpu.models import (GPTForCausalLM,
                                       GPTPretrainingCriterion, gpt_tiny)

        dist.init_parallel_env()
        jmesh = dist.env.get_mesh()
        cfg = gpt_tiny()
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        replicate_layer(model, jmesh)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        step = TrainStep(model=model, optimizer=opt,
                         loss_fn=lambda ids: crit(model(ids), ids))
        rs = np.random.RandomState(0)
        losses = []
        for i in range(self.STEPS):
            ids = paddle.Tensor(
                rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64),
                stop_gradient=True)
            shard_batch(ids, jmesh)
            losses.append(float(step(ids).numpy()))  # noqa: TS107 (gate compares per-step losses on purpose)
        return losses, step

    def test_quantized_loss_curve_within_gate_and_deterministic(self):
        fp32, _ = self._train()
        set_flags({"comm_quantize_dp_grads": True})
        q1, step = self._train()
        q2, _ = self._train()
        assert q1 == q2, "quantized training must be bitwise reproducible"
        deltas = [abs(a - b) / max(abs(a), 1e-9) for a, b in zip(fp32, q1)]
        assert max(deltas) <= self.GATE, (fp32, q1)
        assert q1 != fp32, "the quantized tier never engaged"
        assert copt.axis_wire_dtypes().get("dp") == ["int8"]

    def test_flag_flip_recompiles_not_silently_reuses(self):
        """The dp-sync engagement is part of the static cache key: the
        same TrainStep object serves both tiers as separate programs."""
        fp32, step = self._train()
        assert step.audit_report()["n_cache_keys"] == 1
        set_flags({"comm_quantize_dp_grads": True})
        ids = paddle.Tensor(np.zeros((8, 32), np.int64), stop_gradient=True)
        float(step(ids).numpy())
        assert step.audit_report()["n_cache_keys"] == 2


# ------------------------------------------------------------ reshard tier
@pytest.mark.skipif(N_DEV < 8, reason="needs the 8-device CPU mesh")
class TestPortableReshard:
    def _mesh(self):
        from paddle_tpu.distributed.auto_parallel.process_mesh import ProcessMesh

        dist.init_parallel_env({"dp": 8})
        return ProcessMesh(np.arange(8), dim_names=["dp"])

    def _snapshot_routes(self):
        from paddle_tpu.observability import registry

        metric = registry.snapshot()["metrics"].get("comm.reshard_route")
        if not metric:
            return {}
        return {row["labels"]["route"]: row["value"]
                for row in metric["values"]}

    def test_routes_preserve_values_and_engage(self):
        from paddle_tpu.distributed.auto_parallel import api as ap
        from paddle_tpu.distributed.auto_parallel.placement_type import (
            Replicate, Shard)

        pm = self._mesh()
        ref = np.arange(64 * 24, dtype=np.float32).reshape(64, 24)
        t = ap.shard_tensor(paddle.Tensor(ref.copy(), stop_gradient=True),
                            pm, [Shard(0)])
        before = self._snapshot_routes()
        moved = ap.reshard(t, pm, [Shard(1)])          # s_to_s
        gathered = ap.reshard(moved, pm, [Replicate()])  # s_to_r
        sliced = ap.reshard(gathered, pm, [Shard(0)])    # r_to_s
        for out in (moved, gathered, sliced):
            np.testing.assert_array_equal(np.asarray(out._value), ref)
        noop = ap.reshard(sliced, pm, [Shard(0)])  # same placement
        np.testing.assert_array_equal(np.asarray(noop._value), ref)
        after = self._snapshot_routes()
        for route in ("all_to_all", "all_gather", "slice", "noop"):
            assert after.get(route, 0) > before.get(route, 0), after
        assert not any(k.startswith("device_put:noop")
                       for k in after), after

    def test_flag_off_and_indivisible_fall_back_to_device_put(self):
        from paddle_tpu.distributed.auto_parallel import api as ap
        from paddle_tpu.distributed.auto_parallel.placement_type import Shard

        pm = self._mesh()
        ref = np.arange(64 * 24, dtype=np.float32).reshape(64, 24)
        t = ap.shard_tensor(paddle.Tensor(ref.copy(), stop_gradient=True),
                            pm, [Shard(0)])
        set_flags({"comm_portable_reshard": False})
        out = ap.reshard(t, pm, [Shard(1)])
        np.testing.assert_array_equal(np.asarray(out._value), ref)
        assert self._snapshot_routes().get("device_put:flag_off", 0) > 0

        set_flags({"comm_portable_reshard": True})
        unplaced = paddle.Tensor(np.zeros((64, 24), np.float32),
                                 stop_gradient=True)  # no recorded source
        out2 = ap.reshard(unplaced, pm, [Shard(1)])
        assert np.asarray(out2._value).sum() == 0.0
        assert self._snapshot_routes().get(
            "device_put:unknown_source", 0) > 0
        # and the pure planner still names the indivisible hazard
        r = copt.plan_route([Shard(0)], [Shard(1)], pm, (64, 13), 4)
        assert r.kind == "fallback" and r.reason == "indivisible_dim"

    def test_plan_route_numbers_rank_the_portable_path(self):
        from paddle_tpu.distributed.auto_parallel.placement_type import Shard

        pm = self._mesh()
        r = copt.plan_route([Shard(0)], [Shard(1)], pm, (64, 24), 4)
        full = 64 * 24 * 4
        assert r.kind == "all_to_all"
        assert r.comm_bytes_new == pytest.approx(7 / 8 * full / 8)
        assert r.comm_bytes_old == pytest.approx(7 / 8 * full)
        assert r.peak_bytes_new < r.peak_bytes_old

    def test_partial_to_shard_lax_kernel(self):
        """partial→shard inside an spmd region: one psum_scatter."""
        data = np.tile(np.arange(8, dtype=np.float32)[:, None], (1, 8))

        f = shard_map(
            lambda x: copt.partial_to_shard(x[0], "dp", 0),
            mesh=_dp_mesh(8), in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False)
        out = np.asarray(f(jnp.asarray(data)))
        # every rank contributed its row vector; rank i keeps element i
        # of the summed vector: sum over ranks = 0+1+...+7 = 28
        np.testing.assert_array_equal(out.reshape(-1), np.full(8, 28.0))


# ------------------------------------------------------------ lint family
class TestCommLintFamily:
    def _clean_report(self):
        from paddle_tpu.analysis.comm_check import record_demo_comm

        return record_demo_comm()

    def test_qz800_accuracy_gate(self):
        from paddle_tpu.analysis.comm_check import audit_comm

        rep = self._clean_report()
        rep["max_rel_err"] = 0.5
        codes = [f.code for f in audit_comm(rep)]
        assert codes == ["QZ800"]
        rep["max_rel_err"] = None
        assert [f.code for f in audit_comm(rep)] == ["QZ800"]

    def test_qz801_determinism_contract(self):
        from paddle_tpu.analysis.comm_check import audit_comm

        rep = self._clean_report()
        rep["bitwise_deterministic"] = False
        rep["wire_checked"] = True
        rep["replica_identical"] = False
        codes = [f.code for f in audit_comm(rep)]
        assert codes.count("QZ801") == 2

    def test_qz802_silent_gather_fallback(self):
        from paddle_tpu.analysis.comm_check import audit_comm

        rep = self._clean_report()
        rep["s_to_s_route"] = "fallback"
        assert [f.code for f in audit_comm(rep)] == ["QZ802"]
        rep["portable_reshard_enabled"] = False  # disabled = deliberate
        assert audit_comm(rep) == []

    def test_qz803_mixed_wire_dtypes(self):
        from paddle_tpu.analysis.comm_check import audit_comm

        rep = self._clean_report()
        rep["axis_wire_dtypes"] = {"dp": ["float32", "int8"]}
        findings = audit_comm(rep)
        assert [f.code for f in findings] == ["QZ803"]
        assert "dp" in findings[0].message

    def test_organic_qz803_from_live_record(self):
        """The engaged-but-structurally-dense path really feeds QZ803."""
        from paddle_tpu.analysis.comm_check import audit_comm

        set_flags({"comm_quantize_dp_grads": True,
                   "comm_quantize_min_bytes": 0})
        copt.quantize_decision(jnp.ones((64, 64), jnp.float32),
                               is_sum=True, axes=("dp",), explicit=None)
        copt.quantize_decision(jnp.ones((64, 64), jnp.float32),
                               is_sum=True, axes=("dp", "mp"),
                               explicit=None)
        assert "QZ803" in [f.code for f in audit_comm()]


# ------------------------------------------------- planner / cost model
class TestByteAccounting:
    def test_planner_prices_quantized_dp_sync(self):
        from paddle_tpu.distributed.auto_parallel.planner import (
            ModelSpec, Plan, estimate_step_cost)

        spec = ModelSpec(num_params=10_000_000, num_layers=4)
        plan = Plan(dp=8, mp=1, pp=1)
        dense = estimate_step_cost(spec, 64, plan, comm_quantize=False)
        quant = estimate_step_cost(spec, 64, plan, comm_quantize=True)
        assert not dense["comm_quantized"] and quant["comm_quantized"]
        ratio = dense["dp_comm_bytes"] / quant["dp_comm_bytes"]
        assert 1.5 < ratio < 4.2  # bf16 grads: ~2/(1+4/block)x
        assert quant["step_seconds"] < dense["step_seconds"]

    @pytest.mark.skipif(N_DEV < 8, reason="needs the 8-device CPU mesh")
    def test_cost_model_volume_matches_wire_bytes_within_1_3x(self):
        """ISSUE 10 acceptance: the static cost model's predicted
        quantized collective volume tracks the wire-format bytes the
        payload accounting measures (within 1.3x)."""
        from paddle_tpu.analysis.cost_model import cost_jaxpr

        n, numel = 8, 512 * 64
        f = shard_map(lambda x: copt.qpsum_lax(x, "dp", n),
                      mesh=_dp_mesh(n), in_specs=P(), out_specs=P(),
                      check_vma=False)
        closed = jax.make_jaxpr(f)(jnp.ones((512, 64), jnp.float32))
        predicted = cost_jaxpr(closed).comm_bytes["dp"]
        measured = copt.tensor_wire_bytes(numel, 4, n)["wire_bytes"]
        assert measured / 1.3 <= predicted <= measured * 1.3, \
            (predicted, measured)


# ------------------------------------------------------------- satellites
class TestShardOptimizerWarning:
    def test_unknown_mesh_dim_logs_both_names(self):
        from tests.helpers import capture_logs

        from paddle_tpu.distributed.auto_parallel.api import (
            ShardingStage1, shard_optimizer)

        dist.init_parallel_env()
        model = paddle.nn.Linear(8, 8)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        with capture_logs() as buf:
            shard_optimizer(opt, ShardingStage1(mesh_dim="zz_typo"))
        log = buf.getvalue()
        assert "zz_typo" in log and "pp" in log  # requested + fallback
