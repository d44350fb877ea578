"""paddle.jit.to_static + TrainStep.

to_static (reference python/paddle/jit/api.py:196) compiles a function or a
Layer's forward into one XLA program via the discovery functionalizer —
the TPU-native replacement for SOT bytecode capture + PIR programs: jax
tracing IS the program capture, XLA IS the executor (SURVEY.md §7).

TrainStep is the blessed whole-step compile: forward + backward + optimizer
in one donated XLA program. hapi.Model and the benchmark's train cell
train through it.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional

from ..core.tensor import Tensor
from .functionalize import CompiledFunction, functionalize


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, full_graph=True, **kwargs):
    """Decorator/wrapper: compile a function or Layer for whole-graph execution."""
    from ..nn.layer.layers import Layer

    if function is None:
        return functools.partial(to_static, input_spec=input_spec, build_strategy=build_strategy, backend=backend, full_graph=full_graph)

    if isinstance(function, Layer):
        layer = function
        orig_forward = layer.forward  # bound method, before the override below
        compiled = CompiledFunction(
            lambda *a, **k: orig_forward(*a, **k),
            static_key_fn=lambda: ("train" if layer.training else "eval"),
            name=type(layer).__name__,
        )
        layer._compiled_forward = compiled
        # Layer.__call__ already runs forward pre/post hooks around
        # self.forward, so the override is just the compiled function
        layer.forward_origin = orig_forward
        object.__setattr__(layer, "forward", compiled)
        return layer

    return CompiledFunction(function, name=getattr(function, "__name__", "fn"))


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    return None


class TrainStep:
    """Compile (forward + loss + backward + optimizer.step) into one XLA
    program with donated parameter/optimizer-state buffers.

    loss_fn(*batch) must build the loss from the model; or pass model and a
    criterion: step = TrainStep(model=m, optimizer=opt, loss_fn=lambda x, y:
    criterion(m(x), y)).

    The scheduler LR enters the program as a traced input (not a baked
    constant), so LR schedules do not retrace.
    """

    def __init__(self, model=None, optimizer=None, loss_fn: Optional[Callable] = None, grad_accum_steps: int = 1,
                 bucket_axes: Optional[dict] = None, bucket_range: Optional[tuple] = None,
                 bucket_pad_values: Optional[dict] = None,
                 sharding: Optional[str] = None):
        import jax.numpy as jnp

        if sharding not in (None, "zero1", "replicated"):
            raise ValueError(f"unknown TrainStep sharding {sharding!r} "
                             "(None|'zero1'|'replicated')")
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        # zero1 engagement override: "zero1" forces the sharded update,
        # "replicated" forces it off, None defers to FLAGS_sharding_stage /
        # an attached group_sharded strategy (distributed/sharding/zero1.py)
        self._sharding = sharding
        self._lr_cell = Tensor(jnp.asarray(0.0, jnp.float32), name="lr_cell")
        # host-side mirror of the cell's value: the device scalar re-uploads
        # only when the schedule actually moves, so a constant-LR steady
        # state issues zero H2D transfers per step
        self._lr_host = 0.0

        def step_fn(*batch):
            loss = self.loss_fn(*batch)
            loss.backward()
            if self._zero1_spec() is None:
                # zero1 replaces the dp grad sync: its reduce-scatter IS
                # the sync, fused into the sharded update
                self._sync_dp_grads()
            # read the LR through the dispatcher so the functionalizer records
            # the cell (traced input, not baked constant)
            lr_traced = (self._lr_cell + 0.0)._value
            prev = getattr(self.optimizer, "_lr_override", None)
            prev_sh = getattr(self.optimizer, "_sharding_override", None)
            self.optimizer._lr_override = lr_traced
            self.optimizer._sharding_override = self._sharding
            try:
                self.optimizer.step()
            finally:
                self.optimizer._lr_override = prev
                self.optimizer._sharding_override = prev_sh
            self.optimizer.clear_grad()
            return loss

        # the quantized dp-sync engagement AND the zero1 sharded-update
        # tier are part of the program's shape: flipping
        # FLAGS_comm_quantize_dp_grads / FLAGS_sharding_stage (or entering
        # an amp.auto_cast(comm_dtype=...) region) must recompile, not
        # silently serve the other tier's cached program
        base_key = (lambda: ("train" if model.training else "eval")) \
            if model is not None else (lambda: "fn")
        static_key = lambda: (base_key(), self._dp_sync_key(), self._sharding_key())  # noqa: E731
        if bucket_axes:
            # dynamic-shape policy: pad variable dims to the log2 bucket
            # ladder so distinct lengths share ≤ log2(max/min)+1 programs
            from .bucketing import BucketedFunction

            lo, hi = bucket_range or (16, 4096)
            self._compiled = BucketedFunction(
                step_fn, bucket_axes=bucket_axes, min_len=lo, max_len=hi,
                pad_values=bucket_pad_values, static_key_fn=static_key,
                name="train_step")
        else:
            self._compiled = CompiledFunction(step_fn, static_key_fn=static_key, name="train_step")

    def _dp_sync_key(self):
        """Static cache-key component for the quantized dp grad-sync tier
        (axis + size when engaged, 'fp32' otherwise)."""
        from ..distributed import collective_opt as copt

        spec = copt.gspmd_sync_axis()
        return "fp32" if spec is None else ("int8", spec[1], spec[2])

    def _zero1_spec(self):
        """(mesh, axis, n) when the zero1 sharded weight update engages
        for this step (explicit sharding= > FLAGS_sharding_stage >
        group_sharded strategy), else None."""
        if self.optimizer is None:
            return None
        from ..distributed.sharding import zero1

        return zero1.step_spec(self.optimizer, explicit=self._sharding)

    def _sharding_key(self):
        """Static cache-key component for the zero1 sharded-update tier:
        (axis, size, gather wire dtype) when engaged, 'replicated'
        otherwise — flag flips retrace instead of replaying the other
        tier's program."""
        spec = self._zero1_spec()
        if spec is None:
            return "replicated"
        from ..distributed import collective_opt as copt

        return ("zero1", spec[1], spec[2],
                copt.engaged_comm_dtype() or "fp32")

    def _sync_dp_grads(self):
        """The dp gradient-sync stage (between backward and the optimizer
        update): when the quantized tier engages
        (FLAGS_comm_quantize_dp_grads / amp comm_dtype) and an installed
        mesh has dp > 1, every eligible parameter grad reduce-scatters in
        fp32 and gathers back as int8 blocks + scales
        (collective_opt.dp_sync_gspmd). Off = zero work."""
        from ..distributed import collective_opt as copt

        spec = copt.gspmd_sync_axis()
        if spec is None:
            return
        mesh, axis, _n = spec
        params = getattr(self.optimizer, "_parameter_list", None) or []
        copt.sync_gspmd_grads(params, mesh, axis)

    def __call__(self, *batch):
        # refresh the LR cell from the schedule before entering the program
        # — but only when the value changed (the compiled program threads
        # the cell through as donated state, so the device scalar persists
        # across steps on its own)
        lr = self.optimizer.get_lr()
        if lr != self._lr_host:
            import jax.numpy as jnp

            self._lr_cell._replace_value(jnp.asarray(lr, jnp.float32))
            self._lr_host = lr
        from ..observability import numerics
        from ..observability.anomaly import monitor
        from ..observability.tracing import tracer

        if not (tracer.enabled or monitor.enabled
                or numerics._enabled):
            # all telemetry surfaces dark: three attribute reads, no clock
            return self._compiled(*batch)
        # snapshot once: the clock is only read for the monitor (tracer-only
        # mode stays clock-free here — the span stamps its own), and a flag
        # flip mid-step must not leave t0 unset at the close
        timed = monitor.enabled
        t0 = time.perf_counter() if timed else 0.0
        if tracer.enabled:
            with tracer.span("train.step", track="train_loop"):
                out = self._compiled(*batch)
        else:
            out = self._compiled(*batch)
        if timed:
            # train-step close: the flight recorder's step-time regression
            # detector sees the host-side dispatch wall (a retrace or a
            # blocking sync shows up here orders of magnitude over median)
            monitor.on_step(time.perf_counter() - t0)
        # NaN/Inf + dynamic-range sentinel on the step's loss (one bool
        # read when the numerics witness is dark)
        numerics.watch("train.loss", out[0] if isinstance(out, (tuple, list))
                       and out else out)
        return out

    @property
    def fallback_reason(self):
        return self._compiled.fallback_reason

    def audit(self, max_cache_keys=None):
        """JX3xx findings over every compiled whole-step program (see
        paddle_tpu.analysis.jaxpr_audit). On-demand only — never runs on
        the step's hot path."""
        return self._compiled.audit(max_cache_keys=max_cache_keys)

    def audit_report(self) -> dict:
        """Per-cache-key compile counts for the whole-step program cache
        (no compilation, no tracing — counter reads only)."""
        return self._compiled.audit_report()

    def cost(self):
        """Static ``CostReport`` of the whole-step program: FLOPs, bytes,
        collective volume per mesh axis, and the liveness peak-residency
        estimate the planner cross-checks against XLA ``memory_analysis``
        (see analysis/cost_model.py). On-demand only — never runs on the
        step's hot path."""
        return self._compiled.cost()
