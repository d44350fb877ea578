"""Eager autograd engine.

TPU-native rebuild of the reference's dygraph tape
(/root/reference/paddle/fluid/eager/backward.cc RunBackward, grad_node_info.h
GradNodeBase): ops record GradNodes holding a jax VJP closure; ``backward()``
runs a reverse-topological ready-queue with dependency counting and gradient
accumulation, writing ``.grad`` on leaf tensors.

Differences from the reference, by design:
- the VJP of every op comes from jax at forward time instead of hand-written
  GradNode classes. On the dispatch fast path (core/kernel_cache.py) the node
  holds a :class:`~paddle_tpu.core.kernel_cache.CachedVJP` — a residual-
  carrying handle onto a cached backward executable, applied lazily and
  without tracing when backward() reaches the node; on the slow path it holds
  the live jax.vjp closure (residuals are device arrays held by the closure);
- for ``create_graph=True`` (higher-order grad, reference general_grad.h) the
  node re-runs the op's VJP *through the dispatcher* so the backward ops are
  themselves recorded on the tape;
- the engine is pure Python over async XLA dispatch and fully traceable:
  running it under jax.jit (paddle_tpu/jit) stages forward+backward into one
  XLA program.

Cotangents flow through the engine as Tensors (stop_gradient=True on the
first-order path), so hooks, accumulation, and create_graph share one code
path.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..base import global_state
from ..base.enforce import enforce
from ..base.log import get_logger
from .tensor import Tensor

try:  # private, and the only reader of jax's ambient name stack
    from jax._src.source_info_util import current_name_stack as _name_stack
except ImportError:  # moved: backward ops lose their region, nothing else
    _name_stack = None
    get_logger().warning(
        "jax._src.source_info_util.current_name_stack is gone: backward "
        "operations carry no region (base/regions.py), so a device trace "
        "read by region charges them to 'unscoped'")


def _active_scope() -> Optional[str]:
    """The ``jax.named_scope`` path open right now, or None. jax names an
    op's backward after the scopes entered INSIDE the differentiated
    function; a tape op is differentiated one primitive at a time, inside
    the model's scopes, so its pullback would run unnamed. The node records
    the path at forward time and re-enters it around ``vjp_fn``: a device
    trace then charges backward ops to their forward region."""
    if _name_stack is None:
        return None
    stack = _name_stack()
    return str(stack) if stack.stack else None


_NO_SCOPE = contextlib.nullcontext()


class Edge:
    """Snapshot of an input's producer at record time (reference
    grad_node_info.h Edge): mutation of the Tensor afterwards (inplace ops,
    optimizer writes) must not rewire already-recorded graph edges."""

    __slots__ = ("tensor", "node", "index")

    def __init__(self, tensor: Tensor):
        self.tensor = tensor
        self.node = tensor._grad_node
        self.index = tensor._output_index


class GradNode:
    """One recorded op: maps output cotangents -> input cotangents."""

    __slots__ = (
        "name",
        "vjp_fn",
        "inputs",
        "n_outputs",
        "out_specs",
        "recompute",
        "scope",
        "_out_grads",
    )

    def __init__(self, name, vjp_fn, inputs: List[Tensor], n_outputs: int, out_specs, recompute=None):
        self.name = name
        # arrays -> arrays backward: either the residual closure from an
        # eager jax.vjp (slow path), or a kernel_cache.CachedVJP replaying a
        # compiled backward executable (fast path — applying it never traces)
        self.vjp_fn = vjp_fn
        self.inputs = [e if isinstance(e, Edge) else Edge(e) for e in inputs]
        self.n_outputs = n_outputs
        self.out_specs = out_specs  # (shape, dtype) per output for zero-fill
        self.recompute = recompute  # (fn, values, attrs, diff_idx) for create_graph
        self.scope = _active_scope()  # named_scope path of the forward op
        self._out_grads: Optional[list] = None

    def _in_scope(self):
        """The forward op's region, re-entered for its backward work."""
        return (_NO_SCOPE if self.scope is None
                else jax.named_scope(self.scope))

    def accumulate(self, index: int, grad: Tensor):
        if self._out_grads is None:
            self._out_grads = [None] * self.n_outputs
        cur = self._out_grads[index]
        if cur is None:
            self._out_grads[index] = grad
        else:
            with self._in_scope():
                self._out_grads[index] = cur + grad

    def _is_int_output(self, i: int) -> bool:
        _, dt = self.out_specs[i]
        return not jnp.issubdtype(jnp.empty((), dt).dtype, jnp.inexact)

    def _ready_outputs(self, create_graph: bool):
        outs = []
        for i in range(self.n_outputs):
            g = self._out_grads[i] if self._out_grads else None
            if g is None and not self._is_int_output(i):
                shape, dt = self.out_specs[i]
                g = Tensor(jnp.zeros(shape, dt), stop_gradient=True)
            outs.append(g)  # None stays None for integer outputs
        return outs

    def _raw_cotangent(self, i: int, g):
        """jax.vjp cotangent for output i: float0 zeros for integer outputs
        (jax's convention for non-differentiable primal outputs)."""
        import numpy as np

        shape, dt = self.out_specs[i]
        if self._is_int_output(i):
            return np.zeros(shape, jax.dtypes.float0)
        return g._value

    def run_backward(self, create_graph: bool) -> List[Optional[Tensor]]:
        gouts = self._ready_outputs(create_graph)
        if create_graph and self.recompute is not None:
            return self._run_recompute(gouts)
        enforce(self.vjp_fn is not None, f"grad node '{self.name}' was already released; "
                "pass retain_graph=True to backward() to keep it")
        cotans = tuple(self._raw_cotangent(i, g) for i, g in enumerate(gouts))
        with global_state.no_grad_guard(), self._in_scope():
            raw = self.vjp_fn(cotans if self.n_outputs > 1 else cotans[0])
        if not isinstance(raw, (tuple, list)):
            raw = (raw,)
        return [None if g is None else Tensor(g, stop_gradient=True) for g in raw]

    def _run_recompute(self, gouts: List[Tensor]) -> List[Tensor]:
        """Differentiable backward: re-run fn's VJP through the dispatcher so
        the produced grads carry their own GradNodes (double grad)."""
        from .dispatch import primitive

        fn, values, attrs, diff_idx = self.recompute
        n_diff = len(diff_idx)

        import numpy as np

        int_out = [self._is_int_output(i) for i in range(self.n_outputs)]

        def grad_op(*prims_and_gouts):
            prims = prims_and_gouts[:n_diff]
            gs = list(prims_and_gouts[n_diff:])

            def partial_fn(*diff_vals):
                full = list(values)
                for i, v in zip(diff_idx, diff_vals):
                    full[i] = v
                return fn(*full, **attrs)

            _, vjp = jax.vjp(partial_fn, *prims)
            full_gs = []
            float_cursor = 0
            for i in range(self.n_outputs):
                if int_out[i]:
                    shape, _ = self.out_specs[i]
                    full_gs.append(np.zeros(shape, jax.dtypes.float0))
                else:
                    full_gs.append(gs[float_cursor])
                    float_cursor += 1
            cotan = tuple(full_gs) if self.n_outputs > 1 else full_gs[0]
            return tuple(vjp(cotan))

        float_gouts = [g for i, g in enumerate(gouts) if not int_out[i]]
        outs = primitive(f"{self.name}_grad", grad_op, [e.tensor for e in self.inputs] + float_gouts)
        return list(outs) if isinstance(outs, tuple) else [outs]

    def release(self):
        self.vjp_fn = None
        self.recompute = None
        self._out_grads = None


def _apply_hooks(t: Tensor, g: Tensor) -> Tensor:
    if t._backward_hooks:
        for hook in t._backward_hooks:
            res = hook(g)
            if res is not None:
                g = res if isinstance(res, Tensor) else Tensor(res, stop_gradient=True)
    return g


def _count_dependencies(root_nodes) -> Dict[int, int]:
    """#times each reachable node appears as producer of another's input."""
    dep: Dict[int, int] = {}
    visited = set()
    stack = list(root_nodes)
    while stack:
        node = stack.pop()
        if id(node) in visited:
            continue
        visited.add(id(node))
        for e in node.inputs:
            prod = e.node
            if prod is not None:
                dep[id(prod)] = dep.get(id(prod), 0) + 1
                if id(prod) not in visited:
                    stack.append(prod)
    return dep


def _run_engine(roots, root_grads, retain_graph=False, accumulate_into=None, create_graph=False):
    """roots: list[Tensor]; root_grads: list[Tensor] cotangents.

    accumulate_into: optional dict id(Tensor)->Tensor|None collecting grads for
    requested tensors (paddle.grad path). If None, grads land on leaf .grad.
    """
    root_nodes = []
    for t, g in zip(roots, root_grads):
        node = t._grad_node
        g = _apply_hooks(t, g)
        if node is None:
            _sink_grad(t, g, accumulate_into, create_graph)
            continue
        node.accumulate(t._output_index, g)
        root_nodes.append(node)

    dep = _count_dependencies(root_nodes)
    queue, seen = [], set()
    for n in root_nodes:
        if id(n) not in seen and dep.get(id(n), 0) == 0:
            seen.add(id(n))
            queue.append(n)

    while queue:
        node = queue.pop()
        in_grads = node.run_backward(create_graph)
        node._out_grads = None  # never reuse cotangents across engine runs
        enforce(
            len(in_grads) == len(node.inputs),
            f"vjp of {node.name} returned {len(in_grads)} grads for {len(node.inputs)} inputs",
        )
        for e, g in zip(node.inputs, in_grads):
            t = e.tensor
            prod = e.node
            skip = g is None or t.stop_gradient
            if not skip:
                g = _apply_hooks(t, g)
                if accumulate_into is not None and id(t) in accumulate_into:
                    cur = accumulate_into[id(t)]
                    accumulate_into[id(t)] = g if cur is None else cur + g
                if prod is None and accumulate_into is None:
                    _sink_grad(t, g, accumulate_into, create_graph)
                elif prod is not None:
                    prod.accumulate(e.index, g)
            # dependency bookkeeping runs even for skipped grads, so producers
            # reachable through other live paths still get scheduled
            if prod is not None:
                dep[id(prod)] -= 1
                if dep[id(prod)] == 0:
                    queue.append(prod)
        if not retain_graph:
            node.release()


def _sink_grad(t: Tensor, g: Tensor, accumulate_into, create_graph):
    if accumulate_into is not None:
        if id(t) in accumulate_into:
            cur = accumulate_into[id(t)]
            accumulate_into[id(t)] = g if cur is None else cur + g
        return
    if t._grad is None:
        t._grad = g if create_graph else Tensor(g._value, stop_gradient=True)
    else:
        if create_graph:
            t._grad = t._grad + g
        else:
            t._grad._replace_value(t._grad._value + g._value)


def _ones_like(t: Tensor) -> Tensor:
    return Tensor(jnp.ones(t._value.shape, t._value.dtype), stop_gradient=True)


def _as_cotangent(t: Tensor, g) -> Tensor:
    if g is None:
        return _ones_like(t)
    if isinstance(g, Tensor):
        return g
    return Tensor(jnp.asarray(g), stop_gradient=True)


def backward_from(tensor: Tensor, grad_tensor=None, retain_graph=False):
    """loss.backward() entry (reference eager_functions.cc run_backward)."""
    if tensor.stop_gradient and tensor._grad_node is None:
        return
    _run_engine([tensor], [_as_cotangent(tensor, grad_tensor)], retain_graph=retain_graph)


def backward(tensors, grad_tensors=None, retain_graph=False):
    """paddle.autograd.backward on multiple roots."""
    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor):
        grad_tensors = [grad_tensors]
    gs = [_as_cotangent(t, g) for t, g in zip(tensors, grad_tensors)]
    _run_engine(list(tensors), gs, retain_graph=retain_graph)


def grad(
    outputs,
    inputs,
    grad_outputs=None,
    retain_graph=None,
    create_graph=False,
    only_inputs=True,
    allow_unused=False,
    no_grad_vars=None,
):
    """paddle.grad analog (reference eager general_grad.h partial-graph backward)."""
    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]
    if retain_graph is None:
        retain_graph = create_graph
    gs = [_as_cotangent(t, g) for t, g in zip(outputs, grad_outputs)]
    sink = {id(t): None for t in inputs}
    _run_engine(
        list(outputs), gs, retain_graph=retain_graph, accumulate_into=sink, create_graph=create_graph
    )
    results = []
    for t in inputs:
        g = sink[id(t)]
        if g is None:
            if not allow_unused:
                raise ValueError(
                    f"tensor {t.name} is unreachable from outputs (set allow_unused=True to return None)"
                )
            results.append(None)
        else:
            results.append(g)
    return results
