"""SPMD region: run eager paddle code per-device over mesh axes.

This is the rebuild's analog of the reference's "each process runs the same
script" model (test/legacy_test/test_dist_base.py multi-process harness): with
a single python controller, per-rank code lives inside a `shard_map` region.
`paddle_tpu.distributed` collectives called inside the region lower to XLA
collectives (lax.psum / all_gather / ppermute / all_to_all) over the named
mesh axes — the NCCL-ring replacement (SURVEY.md §2.14 comm backend row).

Tensor is a jax pytree node, so paddle functions cross the shard_map boundary
unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from . import env as env_mod

_tls = threading.local()


def _region_stack():
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def current_region_axes() -> Optional[tuple]:
    stack = _region_stack()
    return stack[-1] if stack else None


def in_spmd_region() -> bool:
    return bool(_region_stack())


@contextlib.contextmanager
def spmd_region(axes: Sequence[str]):
    _region_stack().append(tuple(axes))
    try:
        yield
    finally:
        _region_stack().pop()


def spmd(fn=None, *, mesh=None, in_specs=None, out_specs=None, axes=None, check_vma=False):
    """Wrap ``fn`` to run per-device over the mesh (collectives enabled).

    in_specs/out_specs: PartitionSpec pytrees as in shard_map; default
    fully-replicated in, fully-replicated out. axes: which mesh axes the body
    communicates over (defaults to all mesh axes).
    """
    if fn is None:
        import functools

        return functools.partial(spmd, mesh=mesh, in_specs=in_specs, out_specs=out_specs, axes=axes, check_vma=check_vma)

    def wrapped(*args):
        from ..core.dispatch import primitive
        from ..core.tensor import Tensor

        m = mesh or env_mod.get_mesh()
        region_axes = tuple(axes) if axes is not None else tuple(m.axis_names)
        ispecs = in_specs if in_specs is not None else P()
        ospecs = out_specs if out_specs is not None else P()

        def body(*vals):
            # stop_gradient=True: no inner tape — the OUTER primitive's
            # jax.vjp differentiates through the whole shard_map region, so
            # non-differentiable collectives (pmax/pmin) stay usable in
            # forward-only paths.
            tensors = [Tensor(v, stop_gradient=True) for v in vals]
            with spmd_region(region_axes):
                out = fn(*tensors)
            if isinstance(out, (tuple, list)):
                return tuple(o._value if isinstance(o, Tensor) else o for o in out)
            return out._value if isinstance(out, Tensor) else out

        smapped = _shard_map(body, mesh=m, in_specs=ispecs, out_specs=ospecs, check_vma=check_vma)

        # route through the dispatcher so the eager tape links across the
        # shard_map boundary (jax.vjp differentiates through shard_map).
        # The engaged comm wire dtype rides along as a static attr: the
        # kernel cache keys on it, so flipping FLAGS_comm_quantize_dp_grads
        # (or an amp comm_dtype region) retraces the region instead of
        # replaying the other tier's cached executable
        from .collective_opt import engaged_comm_dtype

        def call(*vals, comm_dtype="fp32"):
            del comm_dtype  # cache-key material only; the body reads policy
            return smapped(*vals)

        return primitive("spmd_region", call, list(args),
                         attrs={"comm_dtype": engaged_comm_dtype() or "fp32"})

    return wrapped
