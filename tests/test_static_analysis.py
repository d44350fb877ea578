"""paddle_tpu.analysis: positive/negative cases for each analyzer.

Each analyzer must (a) stay silent on well-formed input and (b) catch its
seeded negative: a deliberately corrupted Program fails verify(), a
jit-unsafe source snippet trips the trace linter, a broken alias/registry
row trips the consistency gate. (ISSUE 1 acceptance criteria.)
"""
import copy

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import Finding
from paddle_tpu.analysis.program_verify import verify_clone, verify_program
from paddle_tpu.analysis.registry_check import check_registry
from paddle_tpu.analysis.trace_safety import lint_source


# ---------------------------------------------------------------- helpers
def _record_fc_program():
    """The shared well-formed program (data → fc → mean over one feed)."""
    from paddle_tpu.analysis.program_verify import record_demo_program

    return record_demo_program()


def _codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------- program
class TestProgramVerifier:
    def test_well_formed_program_is_clean(self):
        main, x, hidden, loss = _record_fc_program()
        findings = verify_program(main, fetch_ids=[id(loss), id(hidden)])
        assert [f for f in findings if f.severity == "error"] == [], \
            [str(f) for f in findings]
        # and via the wired method
        assert main.verify(fetch_list=[loss, hidden]) is not None

    def test_dangling_input_rejected(self):
        main, *_ = _record_fc_program()
        bad = main.clone()
        node = copy.copy(bad.ops[-1])
        node.arg_specs = [("v", 0xDEAD_BEEF, None)]  # input nobody produces
        bad.ops[-1] = node
        assert "PV004" in _codes(verify_program(bad))
        from paddle_tpu.base.enforce import PreconditionNotMetError

        with pytest.raises(PreconditionNotMetError, match="PV004"):
            bad.verify()

    def test_use_before_def_rejected(self):
        main, *_ = _record_fc_program()
        bad = main.clone()
        bad.ops = list(reversed(bad.ops))
        assert "PV001" in _codes(verify_program(bad))

    def test_duplicate_definition_rejected(self):
        main, *_ = _record_fc_program()
        bad = main.clone()
        dup = copy.copy(bad.ops[0])
        bad.ops = bad.ops + [dup]  # same out ids claimed twice
        assert "PV002" in _codes(verify_program(bad))

    def test_dtype_mismatch_vs_producer_rejected(self):
        main, *_ = _record_fc_program()
        bad = main.clone()
        produced_tid = bad.ops[0].out_ids[0]
        wrong = paddle.Tensor(np.zeros((3, 3), np.float64))
        node = copy.copy(bad.ops[-1])
        node.arg_specs = [("v", produced_tid, wrong)]
        bad.ops[-1] = node
        assert "PV005" in _codes(verify_program(bad))

    def test_unresolvable_fetch_rejected(self):
        main, *_ = _record_fc_program()
        findings = verify_program(main, fetch_ids=[123456789])
        assert "PV007" in _codes(findings)

    def test_dead_node_reported_as_warning(self):
        main, x, hidden, loss = _record_fc_program()
        # fetching only `hidden` leaves the mean node outside the slice
        findings = verify_program(main, fetch_ids=[id(hidden)])
        dead = [f for f in findings if f.code == "PV008"]
        assert dead and all(f.severity == "warning" for f in dead)
        # warnings never make verify() raise
        main.verify(fetch_list=[hidden])

    def test_shadowed_feed_rejected(self):
        main, *_ = _record_fc_program()
        bad = main.clone()
        bad.feeds = dict(bad.feeds)
        bad.feeds["shadow"] = bad.ops[0].out_ids[0]  # feed id an op produces
        bad.feed_specs = dict(bad.feed_specs)
        bad.feed_specs["shadow"] = ((1,), "float32")
        assert "PV003" in _codes(verify_program(bad))

    def test_clone_invariants(self):
        main, *_ = _record_fc_program()
        good = main.clone(for_test=True)
        assert verify_clone(main, good) == []
        # clone must retain the feed placeholder refs (the pre-fix defect)
        assert getattr(good, "_placeholders", None), \
            "clone() dropped the feed placeholders"
        dropped = main.clone()
        dropped._placeholders = []
        assert "PV009" in _codes(verify_clone(main, dropped))
        truncated = main.clone()
        truncated.ops = truncated.ops[:-1]
        assert "PV009" in _codes(verify_clone(main, truncated))

    def test_executor_debug_flag_verifies(self):
        from paddle_tpu.base import flags

        main, x, hidden, loss = _record_fc_program()
        flags.set_flags({"static_verify_program": True})
        try:
            exe = paddle.static.Executor()
            (out,) = exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                             fetch_list=[loss])
            assert np.isfinite(out).all()
            # corrupted program: the same flag makes Executor.run raise
            bad = main.clone()
            node = copy.copy(bad.ops[-1])
            node.arg_specs = [("v", 0xBAD, None)]
            bad.ops[-1] = node
            from paddle_tpu.base.enforce import PreconditionNotMetError

            with pytest.raises(PreconditionNotMetError):
                exe.run(bad, feed={"x": np.ones((2, 8), np.float32)},
                        fetch_list=[loss])
        finally:
            flags.set_flags({"static_verify_program": False})


# ---------------------------------------------------------------- trace
_JIT_UNSAFE_SNIPPET = '''
import time
import random
import numpy as np
from paddle_tpu.jit import to_static

@to_static
def step(x, scale=[1.0]):
    global _COUNT
    _COUNT = 1
    v = x.numpy()
    t = time.time()
    r = random.random()
    q = np.random.randn(3)
    return v + t + r + q.sum()

def kernel_op(x):
    def fn(v):
        if v:
            v = v + 1
        while v > 0:
            v = v - 1
        return v.item()
    return primitive("bad_op", fn, [x])
'''

_CLEAN_SNIPPET = '''
import jax.numpy as jnp
from paddle_tpu.jit import to_static

@to_static
def step(x, scale=1.0):
    return x * scale

def optional_bias_op(x, bias=None):
    def fn(v, *b):
        if b:                      # vararg tuple truthiness: static
            v = v + b[0]
        if v.ndim == 2:            # shape attribute: trace-time constant
            v = v * 2
        if not jnp.iscomplexobj(v):  # dtype predicate: static
            v = v + 0.0
        return v
    return primitive("good_op", fn, [x] + ([bias] if bias is not None else []))

def host_side_helper(idx):
    # outside any traced region: host syncs are fine here
    return int(idx.item())
'''


class TestTraceSafetyLinter:
    def test_jit_unsafe_snippet_trips_every_rule(self):
        findings = lint_source(_JIT_UNSAFE_SNIPPET, "snippet.py")
        codes = _codes(findings)
        assert {"TS101", "TS102", "TS103", "TS104",
                "TS105", "TS106"} <= codes, sorted(codes)
        assert all(isinstance(f, Finding) and f.location.startswith("snippet.py:")
                   for f in findings)

    def test_clean_snippet_is_silent(self):
        assert lint_source(_CLEAN_SNIPPET, "clean.py") == []

    def test_noqa_suppression(self):
        src = ('def op(x):\n'
               '    def fn(v):\n'
               '        return v.item()  # noqa: TS101\n'
               '    return primitive("op", fn, [x])\n')
        assert lint_source(src, "s.py") == []
        # a different code on the noqa does NOT suppress
        src_other = src.replace("TS101", "TS999")
        assert _codes(lint_source(src_other, "s.py")) == {"TS101"}

    def test_bare_numpy_random_import_flagged(self):
        src = ('from numpy.random import randn\n'
               'def op(x):\n'
               '    def fn(v):\n'
               '        return v + randn(3)\n'
               '    return primitive("op", fn, [x])\n')
        assert _codes(lint_source(src, "s.py")) == {"TS104"}

    def test_step_fn_is_a_traced_region(self):
        src = ('import time\n'
               'def step_fn(batch):\n'
               '    return time.time()\n')
        assert _codes(lint_source(src, "s.py")) == {"TS103"}

    def test_repo_source_tree_lints(self, tmp_path):
        # lint_paths walks directories and skips caches
        f = tmp_path / "mod.py"
        f.write_text("def op(x):\n    def fn(v):\n        return v.numpy()\n"
                     "    return passthrough('op', fn, [x])\n")
        (tmp_path / "__pycache__").mkdir()
        (tmp_path / "__pycache__" / "junk.py").write_text("def f(:\n")
        from paddle_tpu.analysis.trace_safety import lint_paths

        findings = lint_paths([str(tmp_path)])
        assert _codes(findings) == {"TS101"}

    def test_syntax_error_reported_not_raised(self):
        findings = lint_source("def broken(:\n", "bad.py")
        assert _codes(findings) == {"TS000"}

    # ---- TS107: per-step host syncs in train-step loops (ISSUE 5) ------
    def test_ts107_sync_inside_step_loop_flagged(self):
        src = ('for i, batch in enumerate(loader):\n'
               '    loss = step(batch)\n'
               '    losses.append(float(loss.numpy()))\n')
        findings = lint_source(src, "loop.py")
        assert _codes(findings) == {"TS107"}
        assert findings[0].location == "loop.py:3"

    def test_ts107_block_until_ready_in_train_batch_loop(self):
        src = ('while running:\n'
               '    out = model.train_batch(xs, ys)\n'
               '    out[0].block_until_ready()\n')
        assert _codes(lint_source(src, "loop.py")) == {"TS107"}

    def test_ts107_train_batch_body_is_a_step_region(self):
        src = ('class M:\n'
               '    def train_batch(self, xs):\n'
               '        loss = self._train_step(*xs)\n'
               '        return [float(loss.numpy())]\n')
        assert _codes(lint_source(src, "m.py")) == {"TS107"}
        # unconditional: a train_batch computing its loss inline (no
        # step-named call) is still the per-step path
        src_inline = ('class M:\n'
                      '    def train_batch(self, x):\n'
                      '        loss = self.model(x).mean()\n'
                      '        return [float(loss)]\n')
        assert _codes(lint_source(src_inline, "m.py")) == {"TS107"}

    def test_ts107_keyword_style_step_call_marks_the_loop(self):
        src = ('for batch in loader:\n'
               '    loss = m.train_batch(inputs=xs, labels=ys)\n'
               '    v = float(loss[0].numpy())\n')
        assert _codes(lint_source(src, "loop.py")) == {"TS107"}

    def test_ts107_sync_in_nested_loop_inside_step_loop_flagged(self):
        # the inner for runs once per training step: still a per-step sync
        src = ('for batch in loader:\n'
               '    loss = step(batch)\n'
               '    for k in range(3):\n'
               '        rows.append(float(loss))\n')
        findings = lint_source(src, "loop.py")
        assert _codes(findings) == {"TS107"}
        assert findings[0].location == "loop.py:4"

    def test_ts107_zero_arg_step_calls_do_not_mark_a_loop(self):
        # optimizer.step()/profiler.step()/scheduler.step() are not train
        # steps, and host arithmetic in float()/int() is not a device sync
        src = ('for batch in loader:\n'
               '    opt.step()\n'
               '    elapsed = int(time.time())\n'
               '    ratio = float(done / total)\n')
        assert lint_source(src, "loop.py") == []

    def test_ts107_scheduler_step_with_metric_does_not_mark_epoch_loop(self):
        # ReduceOnPlateau-style scheduler.step(metric): the epoch loop's
        # boundary sync stays sanctioned — only bare-name step(...) (the
        # TrainStep convention) marks a loop under the generic name
        src = ('for epoch in range(10):\n'
               '    for batch in loader:\n'
               '        loss = step(batch)\n'
               '    scheduler.step(loss)\n'
               '    print(float(loss.numpy()))\n')
        assert lint_source(src, "loop.py") == []

    def test_ts107_host_float_of_compound_expr_in_step_loop_is_clean(self):
        src = ('for batch in loader:\n'
               '    loss = step(batch)\n'
               '    pct = float(i / n)\n'        # host arithmetic: clean
               '    bad = float(loss)\n')        # device scalar: flagged
        findings = lint_source(src, "loop.py")
        assert _codes(findings) == {"TS107"}
        assert [f.location for f in findings] == ["loop.py:4"]

    def test_ts107_sync_after_the_loop_is_clean(self):
        src = ('for batch in loader:\n'
               '    loss = step(batch)\n'
               'final = float(loss.numpy())\n')
        assert lint_source(src, "loop.py") == []

    def test_ts107_epoch_level_sync_outside_step_loop_is_clean(self):
        # the sync sits in the OUTER (epoch) loop, after the inner step
        # loop: a boundary sync, exactly the sanctioned pattern
        src = ('for epoch in range(10):\n'
               '    for batch in loader:\n'
               '        loss = step(batch)\n'
               '    epoch_loss = float(loss.numpy())\n')
        assert lint_source(src, "loop.py") == []

    def test_ts107_loop_without_step_call_is_clean(self):
        src = ('for t in tensors:\n'
               '    rows.append(t.numpy())\n')
        assert lint_source(src, "loop.py") == []

    def test_ts107_noqa_suppresses(self):
        src = ('for batch in loader:\n'
               '    loss = step(batch)\n'
               '    v = float(loss.numpy())  # noqa: TS107\n')
        assert lint_source(src, "loop.py") == []


# ---------------------------------------------------------------- registry
class TestRegistryGate:
    def test_live_registry_is_green(self):
        assert check_registry() == []

    def test_dead_alias_rejected(self):
        from paddle_tpu.ops import registry

        registry._ALIASES["totally_fake_op"] = "paddle_tpu.nonexistent:nope"
        try:
            codes = _codes(check_registry())
            assert "RC202" in codes  # target does not resolve
            assert "RC203" in codes  # no OP_DEFS row, not declared
        finally:
            del registry._ALIASES["totally_fake_op"]
        assert check_registry() == []

    def test_broken_alias_signature_rejected(self):
        from paddle_tpu.ops import registry, yaml_compat

        def _needs_five(a, b, c, d, e):  # pragma: no cover - never called
            raise AssertionError

        yaml_compat._lint_probe_impl = _needs_five
        registry._ALIASES["abs"] = "paddle_tpu.ops.yaml_compat:_lint_probe_impl"
        try:
            findings = check_registry()
            assert any(f.code == "RC204" and f.location == "abs"
                       for f in findings), [str(f) for f in findings]
        finally:
            del registry._ALIASES["abs"]
            del yaml_compat._lint_probe_impl

    def test_ambiguous_amp_stem_rejected(self):
        from paddle_tpu.ops.op_defs import OP_DEFS

        # matches _BLACK_RE ('softmax') AND _WHITE_RE ('matmul'); xpu tier
        # keeps RC201 out of the way
        OP_DEFS["softmax_matmul_probe"] = {
            "args": (), "outputs": ("out",), "backward": None,
            "inplace": None, "forward_only": True, "tier": "xpu"}
        try:
            findings = check_registry()
            assert any(f.code == "RC205" and f.location == "softmax_matmul_probe"
                       for f in findings), [str(f) for f in findings]
        finally:
            del OP_DEFS["softmax_matmul_probe"]
        assert check_registry() == []

    def test_unknown_amp_override_rejected(self):
        from paddle_tpu.ops import registry

        registry._AMP_OVERRIDES["ghost_op"] = "purple"
        try:
            codes = _codes(check_registry())
            assert "RC206" in codes
        finally:
            del registry._AMP_OVERRIDES["ghost_op"]

    def test_malformed_op_row_rejected(self):
        bad_defs = {
            "no_keys": {"args": ()},
            "bad_tier": {"args": (), "outputs": ("out",), "backward": None,
                         "inplace": None, "forward_only": True, "tier": "gpu"},
            "no_outputs": {"args": (), "outputs": (), "backward": None,
                           "inplace": None, "forward_only": True, "tier": "xpu"},
        }
        findings = check_registry(op_defs=bad_defs, aliases={})
        assert {f.location for f in findings if f.code == "RC200"} == \
            {"no_keys", "bad_tier", "no_outputs"}

    def test_unresolved_dense_row_rejected(self):
        defs = {"definitely_not_an_op_xyz": {
            "args": (("Tensor", "x"),), "outputs": ("out",), "backward": None,
            "inplace": None, "forward_only": True, "tier": "dense"}}
        findings = check_registry(op_defs=defs, aliases={})
        assert any(f.code == "RC201" for f in findings)

    def test_op_compat_tier_green_and_served(self):
        from paddle_tpu.ops import registry

        assert registry.resolve_legacy("elementwise_add") == "add"
        assert registry.get_op("reduce_sum") is registry.get_op("sum")
        assert registry.get_op("matmul_v2") is not None

    def test_op_compat_cycles_and_chains_do_not_resolve(self):
        # runtime mirror of the RC208 one-hop contract: a cyclic or
        # chained row returns None instead of recursing/serving two hops
        from paddle_tpu.ops import registry

        registry._OP_COMPAT["cyc_a"] = "cyc_b"
        registry._OP_COMPAT["cyc_b"] = "cyc_a"
        try:
            assert registry.get_op("cyc_a") is None
            assert registry.get_op("cyc_b") is None
        finally:
            del registry._OP_COMPAT["cyc_a"], registry._OP_COMPAT["cyc_b"]

    def test_dead_legacy_alias_rejected(self):
        from paddle_tpu.ops import registry

        registry._OP_COMPAT["ancient_op"] = "no_such_current_op_xyz"
        registry._OP_COMPAT["self_op"] = "self_op"
        registry._OP_COMPAT["chain_op"] = "ancient_op"
        try:
            findings = [f for f in check_registry() if f.code == "RC208"]
            assert {f.location for f in findings} == \
                {"ancient_op", "self_op", "chain_op"}, [str(f) for f in findings]
        finally:
            for k in ("ancient_op", "self_op", "chain_op"):
                del registry._OP_COMPAT[k]
        assert check_registry() == []

    def test_dead_kernel_cache_deny_entry_rejected(self):
        """RC209: a deny-list name that no longer resolves protects
        nothing — the renamed op silently becomes cacheable."""
        from paddle_tpu.analysis.registry_check import check_registry
        from paddle_tpu.ops import registry

        orig = registry._KERNEL_CACHE_DENY
        registry._KERNEL_CACHE_DENY = orig | {"op_that_never_existed"}
        try:
            findings = [f for f in check_registry() if f.code == "RC209"]
            assert [f.location for f in findings] == ["op_that_never_existed"]
        finally:
            registry._KERNEL_CACHE_DENY = orig
        assert check_registry() == []


# ---------------------------------------------------------------- jaxpr
class TestJaxprAuditor:
    """Trace-level verification: the auditor walks the ClosedJaxpr of each
    CompiledFunction cache entry (ISSUE 2 tentpole)."""

    def test_demo_train_step_audits_clean(self):
        from paddle_tpu.analysis.jaxpr_audit import record_demo_step

        step = record_demo_step()
        assert step.audit() == [], [str(f) for f in step.audit()]

    def test_record_demo_step_preserves_rng_stream(self):
        """An in-process health check must not reseed the caller's RNG."""
        from paddle_tpu.analysis.jaxpr_audit import record_demo_step
        from paddle_tpu.base import global_state

        paddle.seed(42)
        global_state.default_generator.split()
        before = np.asarray(global_state.default_generator._key)
        record_demo_step()
        after = np.asarray(global_state.default_generator._key)
        assert np.array_equal(before, after)
        assert global_state.default_generator._seed == 42

    def test_callback_inside_to_static_flagged(self):
        import jax

        from paddle_tpu.jit import to_static

        @to_static
        def f(x):
            jax.debug.print("x={x}", x=x._value)
            return x * 2

        f(paddle.ones([3]))
        assert "JX301" in _codes(f.audit())

    def test_f64_literal_in_step_fn_flagged(self):
        import jax

        from paddle_tpu.jit.functionalize import functionalize

        with jax.enable_x64():
            def step_fn(x):
                return x * np.float64(2.0)  # seeded f64 leak

            cf = functionalize(step_fn)
            cf(paddle.Tensor(np.ones(3, np.float32)))
            findings = cf.audit()
        errors = [f for f in findings if f.code == "JX302"]
        assert errors and all(f.severity == "error" for f in errors), \
            [str(f) for f in findings]

    def test_donated_cell_returned_as_output_flagged(self):
        from paddle_tpu.jit.functionalize import functionalize

        w = paddle.Tensor(np.ones(3, np.float32), stop_gradient=True)

        @functionalize
        def f(x):
            out = w * x
            w._replace_value(out._value)
            return out

        f(paddle.ones([3]))
        assert "JX304" in _codes(f.audit())

    def test_guard_family_covered_then_fallback_reported(self):
        from paddle_tpu.jit.functionalize import functionalize

        @functionalize
        def g(x):
            if paddle.sum(x) > 0:
                return x * 2
            return x * 3

        g(paddle.ones([3]))
        g(paddle.full([3], -1.0))  # second branch -> second specialization
        assert g.audit() == [], [str(f) for f in g.audit()]
        report = g.audit_report()
        assert report["keys"][0]["specializations"] == 2

        @functionalize
        def h(x):
            # host float conversion the guards can't see -> eager fallback
            s = float(paddle.sum(x).numpy())  # noqa: TS101
            return x * s

        h(paddle.ones([3]))
        findings = h.audit()
        assert "JX306" in _codes(findings)

    def test_float_and_unhashable_static_keys_flagged(self):
        from paddle_tpu.jit.functionalize import functionalize

        cf = functionalize(lambda x: x * 2, static_key_fn=lambda: 0.125)
        cf(paddle.ones([3]))
        assert "JX311" in _codes(cf.audit())
        # numpy floating keys are just as unbounded as python floats
        cf_np = functionalize(lambda x: x * 2,
                              static_key_fn=lambda: np.float32(0.5))
        assert "JX311" in _codes(cf_np.audit())
        cf2 = functionalize(lambda x: x * 2, static_key_fn=lambda: [1])
        assert "JX312" in _codes(cf2.audit())

    def test_cache_key_cardinality_flagged(self):
        from paddle_tpu.jit.functionalize import functionalize

        cf = functionalize(lambda x: paddle.sum(x * 2))
        for n in range(1, 5):
            cf(paddle.ones([n]))
        assert cf.audit(max_cache_keys=3) and \
            "JX310" in _codes(cf.audit(max_cache_keys=3))
        assert "JX310" not in _codes(cf.audit(max_cache_keys=64))

    def test_bucket_ladder_heuristics(self):
        from paddle_tpu.jit.bucketing import BucketedFunction

        bf = BucketedFunction(lambda x: x * 2, bucket_axes={0: 0},
                              min_len=1, max_len=2 ** 40)
        assert "JX313" in _codes(bf.audit())
        ok = BucketedFunction(lambda x: x * 2, bucket_axes={0: 0},
                              min_len=16, max_len=4096)
        assert "JX313" not in _codes(ok.audit())

    def test_audit_report_triggers_no_compilation(self):
        from paddle_tpu.jit.functionalize import functionalize

        cf = functionalize(lambda x: paddle.sum(x * 2))
        cf(paddle.ones([3]))
        before_cache = dict(cf._cache)
        before_counts = dict(cf._compile_counts)
        before_stats = dict(cf.stats)
        report = cf.audit_report()
        assert report["n_cache_keys"] == 1
        assert report["total_builds"] == 1
        assert report["keys"][0]["builds"] == 1
        assert cf._cache == before_cache
        assert cf._compile_counts == before_counts
        assert cf.stats == before_stats

    def test_constant_output_warns(self):
        from paddle_tpu.jit.functionalize import functionalize

        w = paddle.Tensor(np.ones(3, np.float32), stop_gradient=True)

        @functionalize
        def f(x):
            y = w + x  # w becomes a cell
            return w   # the live cell Tensor: its value is restored post-
                       # trace, so the output bakes in as a constant

        f(paddle.ones([3]))
        warns = [f_ for f_ in f.audit() if f_.code == "JX303"]
        assert warns and all(f_.severity == "warning" for f_ in warns), \
            [str(f_) for f_ in f.audit()]


# ---------------------------------------------------- kernel cache (JX32x)
class TestKernelCacheAudit:
    """ISSUE 3: the eager kernel-cache audit reads counters only (seeded
    snapshots here; ``tools.lint``'s jaxpr analyzer feeds it live
    ``kernel_cache.stats()``)."""

    def _audit(self, ops, **kw):
        from paddle_tpu.analysis.jaxpr_audit import audit_kernel_cache

        return audit_kernel_cache({"ops": ops}, **kw)

    @staticmethod
    def _row(**kw):
        row = {"hits": 0, "misses": 0, "bypasses": 0, "evictions": 0,
               "bypass_reasons": {}}
        row.update(kw)
        return row

    def test_unhashable_bypass_storm_flagged(self):
        ops = {"mul": self._row(bypasses=500,
                                bypass_reasons={"unhashable": 480, "amp": 20})}
        found = self._audit(ops)
        assert "JX320" in _codes(found)
        assert all(f.severity == "warning" for f in found)
        # hook-driven bypasses (amp/discovery) are deliberate, not a storm
        ops = {"mul": self._row(bypasses=500, bypass_reasons={"amp": 500})}
        assert "JX320" not in _codes(self._audit(ops))
        # array/PRNG-key captures (dropout's per-call key) are by design
        ops = {"dropout": self._row(bypasses=500,
                                    bypass_reasons={"array_capture": 500})}
        assert "JX320" not in _codes(self._audit(ops))
        # below the threshold: too little signal to flag
        ops = {"mul": self._row(bypasses=10,
                                bypass_reasons={"unhashable": 10})}
        assert "JX320" not in _codes(self._audit(ops))

    def test_per_op_miss_ladder_flagged(self):
        ops = {"exp": self._row(misses=200, hits=3)}
        assert "JX321" in _codes(self._audit(ops, max_keys_per_op=32))
        # a warm cache with many signatures but dominant hits is healthy
        ops = {"exp": self._row(misses=200, hits=5000)}
        assert "JX321" not in _codes(self._audit(ops, max_keys_per_op=32))
        ops = {"exp": self._row(misses=8, hits=0)}
        assert "JX321" not in _codes(self._audit(ops, max_keys_per_op=32))

    def test_eviction_thrash_flagged(self):
        ops = {"add": self._row(hits=10, evictions=50),
               "mul": self._row(hits=5, evictions=30)}
        assert "JX322" in _codes(self._audit(ops))
        ops = {"add": self._row(hits=5000, evictions=12)}
        assert "JX322" not in _codes(self._audit(ops))

    def test_live_stats_audit_runs_clean_shapes(self):
        """The no-snapshot form pulls the live process counters and always
        returns a (possibly empty) warning-only list."""
        from paddle_tpu.analysis.jaxpr_audit import audit_kernel_cache

        found = audit_kernel_cache()
        assert all(f.severity == "warning" for f in found)
        assert all(f.code.startswith("JX32") for f in found)

    def test_exercised_cache_stays_clean(self):
        from paddle_tpu.analysis.jaxpr_audit import audit_kernel_cache
        from paddle_tpu.core import kernel_cache

        kernel_cache.clear()
        try:
            a = paddle.ones([4])
            for _ in range(4):
                paddle.add(a, a)
            assert audit_kernel_cache() == []
        finally:
            kernel_cache.clear()


# ---------------------------------------------------------------- spmd
_SPMD_BAD_SNIPPET = '''
import jax
from jax import lax
from jax.sharding import PartitionSpec as P
from paddle_tpu.distributed.spmd import spmd, spmd_region

def comm(x):
    return lax.psum(x, "tp")            # undeclared axis

def region(x):
    with spmd_region(["tp", "tp"]):     # undeclared + duplicated
        return x

def annot(x):
    return P("dp", "dp")                # duplicate within one spec

def annot2(x):
    return P("tp", None)                # undeclared axis in a spec
'''

_SPMD_CLEAN_SNIPPET = '''
import numpy as np
import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from paddle_tpu.distributed.spmd import spmd_region

mesh = Mesh(np.array(jax.devices()).reshape(1, -1), ("x", "y"))

def comm(x):
    return lax.psum(x, "x")             # file-declared axis

def hybrid(x):
    return lax.pmax(x, ("dp", "mp"))    # canonical hybrid axes

def annot(x):
    return P("dp", None, "y")

def dynamic(x, axes):
    return lax.psum(x, axes)            # dynamic: out of static reach
'''


class TestSpmdChecker:
    def test_bad_snippet_trips_every_rule(self):
        from paddle_tpu.analysis.spmd_check import check_source

        findings = check_source(_SPMD_BAD_SNIPPET, "bad.py")
        codes = _codes(findings)
        assert {"SP401", "SP402", "SP403", "SP404"} <= codes, sorted(codes)
        assert all(f.severity == "error" and
                   f.location.startswith("bad.py:") for f in findings)

    def test_clean_snippet_is_silent(self):
        from paddle_tpu.analysis.spmd_check import check_source

        assert check_source(_SPMD_CLEAN_SNIPPET, "clean.py") == []

    def test_collective_over_undeclared_mesh_axis(self):
        from paddle_tpu.analysis.spmd_check import check_source

        src = "from jax import lax\ndef f(x):\n    return lax.psum(x, 'tp')\n"
        findings = check_source(src, "s.py")
        assert _codes(findings) == {"SP401"}

    def test_declared_degrees_dict_counts(self):
        from paddle_tpu.analysis.spmd_check import check_source

        src = ("import paddle_tpu.distributed as dist\n"
               "from jax import lax\n"
               "dist.init_parallel_env(degrees={'ring': 4})\n"
               "def f(x):\n    return lax.psum(x, 'ring')\n")
        assert check_source(src, "s.py") == []

    def test_noqa_suppression(self):
        from paddle_tpu.analysis.spmd_check import check_source

        src = ("from jax import lax\n"
               "def f(x):\n"
               "    return lax.psum(x, 'tp')  # noqa: SP401\n")
        assert check_source(src, "s.py") == []

    def test_syntax_error_reported_not_raised(self):
        from paddle_tpu.analysis.spmd_check import check_source

        assert _codes(check_source("def broken(:\n", "b.py")) == {"SP400"}

    def test_check_paths_walks_and_fails_loud(self, tmp_path):
        from paddle_tpu.analysis.spmd_check import check_paths

        f = tmp_path / "mod.py"
        f.write_text("from jax import lax\ndef f(x):\n"
                     "    return lax.pmax(x, 'nope')\n")
        assert _codes(check_paths([str(tmp_path)])) == {"SP401"}
        with pytest.raises(FileNotFoundError):
            check_paths([str(tmp_path / "missing_dir")])


# ---------------------------------------------------------------- CLI
class TestLintCli:
    """--select/--ignore filters and the exit-code contract (ISSUE 2
    satellite: 0 = clean, 1 = findings, 2 = analyzer crash)."""

    def test_select_and_ignore_filters(self):
        from paddle_tpu.analysis import Finding
        from tools.lint import filter_findings

        fs = [Finding("trace", "TS101", "error", "m"),
              Finding("spmd", "SP401", "error", "m"),
              Finding("jaxpr", "JX310", "warning", "m")]
        assert [f.code for f in filter_findings(fs, ["TS"], None)] == ["TS101"]
        assert [f.code for f in filter_findings(fs, ["SP4", "JX"], None)] == \
            ["SP401", "JX310"]
        assert [f.code for f in filter_findings(fs, None, ["TS1", "JX"])] == \
            ["SP401"]

    def test_crash_exits_two(self, capsys, monkeypatch):
        import tools.lint as lint_cli

        def boom(_paths, include_tests=False):
            raise RuntimeError("analyzer exploded")

        monkeypatch.setitem(lint_cli._RUNNERS, "spmd", boom)
        rc = lint_cli.main(["--json", "--analyzer", "spmd"])
        out = capsys.readouterr().out
        assert rc == 2
        import json as _json

        payload = _json.loads(out)
        assert payload["crashed"] == ["spmd"]
        assert any(f["code"] == "SP999" for f in payload["findings"])

    def test_findings_exit_one(self, capsys, tmp_path):
        import tools.lint as lint_cli

        bad = tmp_path / "bad.py"
        bad.write_text("from jax import lax\ndef f(x):\n"
                       "    return lax.psum(x, 'ghost_axis')\n")
        rc = lint_cli.main(["--analyzer", "spmd", str(bad)])
        assert rc == 1
        capsys.readouterr()
        # ...unless the family is deselected
        rc = lint_cli.main(["--analyzer", "spmd", "--select", "TS", str(bad)])
        assert rc == 0
        capsys.readouterr()
