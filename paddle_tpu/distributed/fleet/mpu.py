"""Tensor-parallel (model-parallel) building blocks.

Reference: fleet/layers/mpu/mp_layers.py — VocabParallelEmbedding (:49),
ColumnParallelLinear (:336), RowParallelLinear (:543), ParallelCrossEntropy
(:744) built from c_identity/c_split/mp_allreduce autograd ops (mpu/mp_ops.py)
over NCCL; RNGStatesTracker (mpu/random.py:34) keeps per-rank dropout seeds.

TPU-native: a TP layer is an ordinary layer whose weight carries a
NamedSharding over the `mp` mesh axis. The forward is a plain matmul/gather;
GSPMD partitions it and inserts the identity/allreduce/allgather movements the
reference hand-codes — and under whole-step jit it fuses and overlaps them.
`gather_output=False` is expressed as a sharding constraint on the output
(kept sharded on the feature dim), so chained Column->Row pairs run without
any intermediate gather, exactly like Megatron.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core.dispatch import primitive
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn.initializer import Constant, Normal, XavierNormal
from ...nn.layer.layers import Layer
from .. import env as env_mod

_MP_AXIS = "mp"


def _mesh():
    return env_mod.get_mesh()


def _place(param: Tensor, spec: P):
    """Pin a parameter's layout on the global mesh."""
    mesh = _mesh()
    param._replace_value(jax.device_put(param._value, NamedSharding(mesh, spec)))
    param._placements = spec
    return param


def _sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop axis entries whose size does not divide the dim (XLA requires
    even shards for explicit layouts)."""
    entries = []
    for d, entry in enumerate(spec):
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for ax in axes:
            n *= mesh.shape.get(ax, 1)
        entries.append(entry if (d < len(shape) and n > 0 and shape[d] % n == 0) else None)
    return P(*entries)


def _manual_axes() -> frozenset:
    """Axes the enclosing shard_map (if any) already made Manual — a
    sharding constraint inside that region must not mention them (the
    operand is already per-shard along them)."""
    ctx = jax.sharding.get_abstract_mesh()
    if getattr(ctx, "axis_names", None):
        from jax.sharding import AxisType

        return frozenset(n for n, t in zip(ctx.axis_names, ctx.axis_types)
                         if t == AxisType.Manual)
    return frozenset()


def _strip_manual(spec: P, manual: frozenset) -> P:
    entries = []
    for entry in spec:
        if entry is None:
            entries.append(None)
            continue
        axes = tuple(a for a in (entry if isinstance(entry, tuple) else (entry,))
                     if a not in manual)
        entries.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*entries)


def _constrain(x: Tensor, spec: P) -> Tensor:
    """Sharding constraint on an activation (the c_identity/c_split analog)."""
    mesh = _mesh()
    if mesh.shape.get(_MP_AXIS, 1) == 1:
        return x
    manual = _manual_axes()
    if manual:
        spec = _strip_manual(spec, manual)
    spec = _sanitize_spec(spec, x.shape, mesh)
    sharding = NamedSharding(mesh, spec)
    if isinstance(x._value, jax.core.Tracer):
        out = primitive("sharding_constraint", lambda v: jax.lax.with_sharding_constraint(v, sharding), [x])
    else:
        out = primitive("sharding_constraint", lambda v: jax.device_put(v, sharding), [x])
    out.stop_gradient = x.stop_gradient
    return out


def _feature_spec(ndim: int, axis=_MP_AXIS):
    """last-dim sharded activation spec; batch dim rides dp."""
    entries = [None] * ndim
    entries[0] = "dp"
    entries[-1] = axis
    return P(*entries)


def _batch_spec(ndim: int):
    entries = [None] * ndim
    entries[0] = "dp"
    return P(*entries)


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dim sharded over mp (reference mp_layers.py:49).

    The reference masks out-of-range ids per rank and allreduces partial
    lookups; GSPMD derives the same exchange from the [vocab/mp, hidden]
    weight layout.
    """

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None, mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr, default_initializer=Normal(0.0, 0.02)
        )
        _place(self.weight, P(_MP_AXIS, None))

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return _constrain(out, _batch_spec(out.ndim))

    def extra_repr(self):
        return f"{self.num_embeddings}, {self.embedding_dim}, vocab-sharded over '{_MP_AXIS}'"


class ColumnParallelLinear(Layer):
    """Linear with out_features sharded over mp (reference mp_layers.py:336)."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=None,
                 gather_output=True, fuse_matmul_bias=False, mp_group=None, name=None, bias_attr=None):
        super().__init__()
        self.in_features, self.out_features = in_features, out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr, default_initializer=XavierNormal()
        )
        _place(self.weight, P(None, _MP_AXIS))
        use_bias = has_bias if has_bias is not None else (bias_attr is not False)
        if use_bias:
            self.bias = self.create_parameter([out_features], attr=bias_attr, is_bias=True)
            _place(self.bias, P(_MP_AXIS))
        else:
            self.bias = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            return _constrain(out, _batch_spec(out.ndim))
        return _constrain(out, _feature_spec(out.ndim))

    def extra_repr(self):
        return f"in={self.in_features}, out={self.out_features} (column-sharded), gather_output={self.gather_output}"


class RowParallelLinear(Layer):
    """Linear with in_features sharded over mp (reference mp_layers.py:543).

    Consumes the feature-sharded activations a ColumnParallelLinear(
    gather_output=False) produces; the partial-sum allreduce the reference
    issues (mp_allreduce) is the psum GSPMD inserts for the contracted
    sharded dim.
    """

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 input_is_parallel=False, fuse_matmul_bias=False, mp_group=None, name=None, bias_attr=None):
        super().__init__()
        self.in_features, self.out_features = in_features, out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr, default_initializer=XavierNormal()
        )
        _place(self.weight, P(_MP_AXIS, None))
        use_bias = has_bias if has_bias is not None else (bias_attr is not False)
        if use_bias:
            self.bias = self.create_parameter([out_features], attr=bias_attr, is_bias=True)
            _place(self.bias, P())
        else:
            self.bias = None

    def forward(self, x):
        if self.input_is_parallel:
            x = _constrain(x, _feature_spec(x.ndim))
        out = F.linear(x, self.weight, self.bias)
        return _constrain(out, _batch_spec(out.ndim))

    def extra_repr(self):
        return f"in={self.in_features} (row-sharded), out={self.out_features}"


class ParallelCrossEntropy(Layer):
    """Softmax cross entropy over class-sharded logits (reference
    mp_layers.py:744). The reference's two-pass max/sum allreduce is exactly
    what GSPMD emits for reductions over the sharded class dim."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        logits = _constrain(input, _feature_spec(input.ndim))
        return F.cross_entropy(logits, label, reduction="none", ignore_index=self.ignore_index)


# ----------------------------------------------------------------- RNG tracker
class RNGStatesTracker:
    """Per-scope RNG streams (reference mpu/random.py:34).

    The reference seeds each mp rank differently so dropout masks differ on
    sharded activations. Single-controller SPMD generates ONE global mask that
    is itself sharded, so cross-rank consistency is structural; the tracker
    keeps named independent streams for API parity (model_parallel_rng vs
    global seed scopes).
    """

    def __init__(self):
        self._cells = {}  # name -> Tensor holding a PRNG key (a state cell)

    def add(self, name, seed):
        import jax.random as jrandom

        if name in self._cells:
            raise ValueError(f"rng state {name} already exists")
        self._cells[name] = Tensor(jrandom.PRNGKey(seed), name=f"rng_{name}")

    def get_states_tracker(self):
        return {k: v._value for k, v in self._cells.items()}

    def set_states_tracker(self, states):
        for k, v in states.items():
            if k in self._cells:
                self._cells[k]._replace_value(v)
            else:
                self._cells[k] = Tensor(v, name=f"rng_{k}")

    def rng_state(self, name="model_parallel_rng"):
        import contextlib

        from ...base import global_state

        @contextlib.contextmanager
        def guard():
            if name not in self._cells:
                self.add(name, 2718 + len(self._cells))
            # swap the cell OBJECT: trace-safe (the stream cell becomes a
            # captured state cell under jit; no concrete keys enter traces)
            prev = global_state.swap_rng_cell(self._cells[name])
            try:
                yield
            finally:
                global_state.swap_rng_cell(prev)

        return guard()


_tracker = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _tracker


def model_parallel_random_seed(seed=None):
    global _tracker
    _tracker = RNGStatesTracker()
    _tracker.add("model_parallel_rng", seed or 2718)
