"""paddle.jit.save / paddle.jit.load.

Reference (python/paddle/jit/api.py jit.save -> translated_layer.py) exports
a static Program + params. TPU-native export: the layer's compiled forward is
serialized as a StableHLO module (jax.export) next to the state_dict; load
rebuilds a callable TranslatedLayer that runs the module via jax. An
InputSpec dim of None exports as a shared SYMBOLIC batch dim (shape
polymorphism), the serving tier's one-module-any-batch contract — the
Predictor's bucket ladder compiles per-rung specializations from it. Where
jax.export is unavailable for a program, falls back to pickling the
state_dict + re-tracing on load from the saved Layer class is NOT attempted
(matching the reference's requirement of InputSpec at save time).
"""
from __future__ import annotations

import os
import pickle

import numpy as np

import jax

from ..core.tensor import Tensor, unwrap
from ..framework import io as fio


def save(layer, path, input_spec=None, **configs):
    """Save layer params + (if input_spec given) an exported StableHLO fwd.

    configs["quantize"]: optional — "weight_only_int8" / "weight_only_int4"
    converts every Linear to int8/int4 weight storage before export
    (quantization/ptq.py::quantize_weight_only), so the exported program
    carries quantized weights and runs the fused dequant-matmul path.
    """
    quantize = configs.pop("quantize", None)
    if quantize:
        from ..quantization.ptq import quantize_weight_only

        layer = quantize_weight_only(layer, algo=quantize)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    state = layer.state_dict()
    fio.save(state, path + ".pdiparams")
    meta = {"class": type(layer).__name__, "has_program": False,
            "quantize": quantize}
    if input_spec is not None:
        from jax import export as jax_export

        from ..base import dtype as dtype_mod

        # A None dim in an InputSpec becomes a symbolic dim (jax.export
        # shape polymorphism): the exported module then serves ANY size on
        # that axis, and the serving tier warm-compiles one specialization
        # per bucket rung instead of one export per shape. Symbols are
        # assigned by RANK — the first None dim of every input shares "b"
        # (the batch axis), the second shares "s" (the sequence axis), and
        # so on — so a GPT forward exported with InputSpec([None, None])
        # carries a TWO-AXIS ladder (batch x seq) from one module, while
        # single-None exports keep the historical one-symbol contract.
        _SYM_NAMES = ("b", "s", "d2", "d3")
        # all symbols must share ONE scope: count the ranks first, then
        # mint them together in a single symbolic_shape call
        n_ranks = 0
        for s in input_spec:
            if not isinstance(s, Tensor) and hasattr(s, "shape"):
                n_ranks = max(n_ranks,
                              sum(1 for d in s.shape if d is None))
        names = [(_SYM_NAMES[r] if r < len(_SYM_NAMES) else f"d{r}")
                 for r in range(n_ranks)]
        syms = (list(jax_export.symbolic_shape(", ".join(names)))
                if names else [])
        dynamic_axes = []
        dynamic_ranks = []  # (input_idx, axis, rank) triples

        def _sym(rank):
            return syms[rank]

        def _as_shaped(s, idx):
            if isinstance(s, Tensor):
                return unwrap(s)
            if hasattr(s, "shape") and hasattr(s, "dtype"):  # InputSpec
                shape = list(s.shape)
                rank = 0
                for ax, d in enumerate(shape):
                    if d is None:
                        dynamic_axes.append((idx, ax))
                        dynamic_ranks.append((idx, ax, rank))
                        shape[ax] = _sym(rank)
                        rank += 1
                return jax.ShapeDtypeStruct(tuple(shape), dtype_mod.np_dtype(s.dtype))
            return s

        leaves = [_as_shaped(s, i) for i, s in enumerate(input_spec)]
        params = {k: v._value for k, v in state.items()}

        modes = [(l, l.training) for l in layer.sublayers(include_self=True)]

        def fwd(params, *args):
            saved = {k: t._value for k, t in state.items()}
            for k, t in state.items():
                t._value = params[k]
            try:
                layer.eval()  # export inference graph; mode restored below
                out = layer.forward(*[Tensor(a) for a in args])
                # strip Tensor wrappers: exported modules carry plain arrays
                return jax.tree_util.tree_map(
                    lambda x: x._value if isinstance(x, Tensor) else x,
                    out,
                    is_leaf=lambda x: isinstance(x, Tensor),
                )
            finally:
                for k, t in state.items():
                    t._value = saved[k]

        args_shaped = [jax.ShapeDtypeStruct(np.shape(l), np.asarray(l).dtype if not hasattr(l, "dtype") else l.dtype) for l in leaves]
        params_shaped = jax.tree_util.tree_map(lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), params)
        try:
            exported = jax_export.export(jax.jit(fwd))(params_shaped, *args_shaped)
        finally:
            for l, was_training in modes:
                l.training = was_training
        with open(path + ".pdmodel", "wb") as f:
            f.write(exported.serialize())
        meta["has_program"] = True
        meta["n_inputs"] = len(leaves)
        # symbolic dims pickle poorly and mean "any size" anyway: record None
        meta["input_shapes"] = [
            ([d if isinstance(d, int) else None for d in a.shape],
             str(a.dtype))
            for a in args_shaped]
        meta["dynamic_axes"] = dynamic_axes
        # which symbol each dynamic axis bound to: rank 0 = the batch
        # ladder, rank 1 = the sequence ladder (the two-axis bucket grid)
        meta["dynamic_ranks"] = dynamic_ranks
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump(meta, f)


class TranslatedLayer:
    """Loaded exported program (reference jit/translated_layer.py)."""

    def __init__(self, exported, params, meta):
        self._exported = exported
        self._params = params
        self._meta = meta
        self.training = False

    def __call__(self, *args):
        vals = [unwrap(a) for a in args]
        out = self._exported.call(self._params, *vals)
        return jax.tree_util.tree_map(lambda x: Tensor(x) if hasattr(x, "shape") else x, out)

    forward = __call__

    def eval(self):
        return self

    def state_dict(self):
        return {k: Tensor(v) for k, v in self._params.items()}


def load(path, **configs):
    state = fio.load(path + ".pdiparams")
    with open(path + ".pdmeta", "rb") as f:
        meta = pickle.load(f)
    if meta.get("has_program"):
        from jax import export as jax_export

        with open(path + ".pdmodel", "rb") as f:
            raw = f.read()
        exported = jax_export.deserialize(raw)
        params = {k: v._value for k, v in state.items()}
        return TranslatedLayer(exported, params, meta)
    return state
