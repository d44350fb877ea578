"""Optimizer base (reference: python/paddle/optimizer/optimizer.py:127 —
accumulator system, step :1897, minimize :1806).

TPU-native: `step()` updates parameter payloads functionally (async XLA
dispatch in eager; tracer writes under jit so the functionalizer captures
parameter/accumulator updates inside one compiled program). The per-parameter
update rule `_apply_one` is pure, so the same code serves eager and compiled
paths, and accumulators are state cells for distributed sharding (ZeRO stages
shard them over the mesh, paddle_tpu/distributed/sharding.py)."""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..base import regions
from ..base.enforce import enforce
from ..core.tensor import Tensor
from .lr import LRScheduler


class Optimizer:
    _accum_names: List[str] = []

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None, grad_clip=None, name=None):
        enforce(parameters is not None, "parameters must be provided (pass model.parameters())")
        if parameters and isinstance(parameters[0], dict):
            self._param_groups = []
            flat = []
            for group in parameters:
                g = dict(group)
                flat.extend(g["params"])
                self._param_groups.append(g)
            self._parameter_list = flat
        else:
            self._parameter_list = list(parameters)
            self._param_groups = [{"params": self._parameter_list}]
        self._learning_rate = learning_rate
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._accumulators: Dict[str, Dict[int, Tensor]] = defaultdict(dict)
        self._aux_state: Dict[str, Tensor] = {}
        # step counter lives in a Tensor cell so Adam-style bias correction is
        # traced state, not a python constant baked into compiled programs
        self._step_tensor = Tensor(jnp.asarray(0, jnp.int32), name="opt_step")
        self._lr_override = None  # traced LR injected by jit.TrainStep
        # zero1 plumbing (distributed/sharding/zero1.py): the per-step
        # engagement override injected by TrainStep(sharding=...) and the
        # strategy attached by group_sharded_parallel
        self._sharding_override = None
        self._zero1_strategy = None

    # ------------------------------------------------ lr
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        enforce(
            not isinstance(self._learning_rate, LRScheduler),
            "cannot set_lr when using an LRScheduler",
        )
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # ------------------------------------------------ accumulators
    def _get_accumulator(self, name: str, param: Tensor, fill=0.0, dtype=None) -> Tensor:
        store = self._accumulators[name]
        if id(param) not in store:
            v = jnp.full(param._value.shape, fill, dtype or jnp.float32)
            store[id(param)] = Tensor(v, stop_gradient=True, name=f"{param.name}_{name}")
        return store[id(param)]

    def _get_aux(self, name: str, init) -> Tensor:
        if name not in self._aux_state:
            self._aux_state[name] = Tensor(jnp.asarray(init), stop_gradient=True, name=name)
        return self._aux_state[name]

    # ------------------------------------------------ core
    def _collect_params_grads(self):
        out = []
        for group in self._param_groups:
            for p in group["params"]:
                if p.stop_gradient:
                    continue
                out.append((p, p._grad, group))
        return out

    def step(self):
        # one region: clipping and every parameter's update rule
        with regions.region(regions.OPTIMIZER):
            pgs = self._collect_params_grads()
            pg_for_clip = [(p, g) for p, g, _ in pgs if g is not None]
            if self._grad_clip is not None:
                clipped = self._grad_clip(pg_for_clip)
            else:
                clipped = pg_for_clip
            clip_map = {id(p): g for p, g in clipped}
            self._step_tensor._replace_value(self._step_tensor._value + 1)
            lr = self._lr_override if self._lr_override is not None else self.get_lr()
            # zero1 sharded weight update: when engaged (TrainStep override /
            # FLAGS_sharding_stage / group_sharded_parallel) every eligible
            # parameter's update runs in its 1/dp shard space — grad clipping
            # above stays on the full gradients, so clip semantics are
            # identical across tiers
            from ..distributed.sharding import zero1 as _zero1

            spec = _zero1.step_spec(self)
            strategy = _zero1.ensure_strategy(self) if spec is not None else None
            for p, _, group in pgs:
                g = clip_map.get(id(p))
                if g is None:
                    continue
                group_lr = lr * p.optimize_attr.get("learning_rate", 1.0) * group.get("learning_rate", 1.0)
                wd = group.get("weight_decay", self._weight_decay)
                if strategy is not None:
                    strategy.apply_one(self, p, g, group_lr, wd, spec)
                else:
                    self._apply_one(p, g, group_lr, wd)

    def _apply_one(self, p: Tensor, g: Tensor, lr, weight_decay):
        raise NotImplementedError

    def _step_value(self):
        """Current step as a (possibly traced) array for update-rule math."""
        return self._step_tensor._value.astype(jnp.float32)

    @property
    def _step_count(self):
        import numpy as np

        v = self._step_tensor._value
        try:
            return int(np.asarray(v))
        except Exception:
            return v

    @_step_count.setter
    def _step_count(self, v):
        self._step_tensor._replace_value(jnp.asarray(int(v), jnp.int32))

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        return None, [(p, p._grad) for p in self._parameter_list]

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    # ------------------------------------------------ regularization helper
    @staticmethod
    def _decayed_grad(p, g, weight_decay):
        """L2Decay-style regularization folded into the gradient (reference
        regularizer.py applied at optimize time)."""
        if weight_decay is None:
            return g._value
        coeff = getattr(weight_decay, "coeff", weight_decay)
        if p.regularizer is not None:
            coeff = getattr(p.regularizer, "coeff", coeff)
        return g._value + float(coeff) * p._value

    # ------------------------------------------------ state dict
    def _lookup_cell(self, store, p):
        """An accumulator cell for ``p``: the zero1 shard-space proxy's
        when the sharded update owns one, else the param's own."""
        if self._zero1_strategy is not None:
            return self._zero1_strategy.cell_for(store, p)
        return store.get(id(p))

    def state_dict(self):
        out = {}
        for name, store in self._accumulators.items():
            for p in self._parameter_list:
                cell = self._lookup_cell(store, p)
                if cell is not None:
                    out[f"{p.name}_{name}"] = cell
        if self._zero1_strategy is not None:
            for m in self._zero1_strategy.extra_state_cells():
                out[m.name] = m
        for k, v in self._aux_state.items():
            out[k] = v
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        out["@step"] = self._step_count
        return out

    def _prime_target(self, p):
        """The cell owner accumulator priming targets for ``p``: the
        zero1 shard-space proxy (pre-shaped + sharded) when the sharded
        update is engaged, else the param itself — primed cells must be
        the SAME cells the first step will update, or the GradScaler's
        overflow rollback snapshots dead state."""
        from ..distributed.sharding import zero1 as _zero1

        spec = _zero1.step_spec(self)
        if spec is None:
            return p
        return _zero1.ensure_strategy(self).prime_proxy(p, spec)

    def _prime_accumulators(self):
        """Eagerly create every accumulator (GradScaler snapshots and the jit
        functionalizer need the full cell set before the first step)."""
        for p in self._parameter_list:
            if p.stop_gradient:
                continue
            target = self._prime_target(p)
            for name in self._accum_names:
                self._get_accumulator(name, target)

    def set_state_dict(self, state):
        import numpy as np

        for p in self._parameter_list:
            for name in self._accum_names:
                key = f"{p.name}_{name}"
                if key in state:
                    src = state[key]
                    arr = src.numpy() if isinstance(src, Tensor) else np.asarray(src)
                    existing = self._lookup_cell(self._accumulators[name], p)
                    if existing is not None:
                        existing.set_value(arr)
                    else:
                        self._get_accumulator(name, p).set_value(arr)
        strategy = self._zero1_strategy
        if strategy is None and any(k.endswith("_zero1_master")
                                    for k in state):
            # a fresh optimizer restoring a master-carrying state: attach
            # the strategy so the masters land instead of being dropped
            from ..distributed.sharding import zero1 as _zero1

            if _zero1.step_spec(self, explicit="zero1") is not None:
                strategy = _zero1.ensure_strategy(self)
        if strategy is not None:
            strategy.restore_masters(self, state)
        for k in list(self._aux_state):
            if k in state:
                src = state[k]
                arr = src.numpy() if isinstance(src, Tensor) else np.asarray(src)
                self._aux_state[k].set_value(arr)
        if "LR_Scheduler" in state and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
        if "@step" in state:
            self._step_count = int(state["@step"])

    # ------------------------------------------------ introspection for jit/sharding
    def _state_cells(self):
        """All mutable Tensors owned by the optimizer (jit functionalizer +
        ZeRO sharding enumerate these)."""
        cells = []
        for store in self._accumulators.values():
            cells.extend(store.values())
        cells.extend(self._aux_state.values())
        if self._zero1_strategy is not None:
            cells.extend(self._zero1_strategy.extra_state_cells())
        return cells
