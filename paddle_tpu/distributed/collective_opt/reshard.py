"""Portable resharding — placement transitions as composed collectives.

``auto_parallel.api.reshard`` used to materialize every placement change
as one sharding-changing ``device_put`` and let XLA pick the movement;
for the common transitions that lowering is gather-shaped: the full
array materializes per device before the target layout is sliced back
out (arxiv 2112.01075's motivating failure). This module rewrites the
supported transitions as explicit collective sequences that keep peak
per-device residency at O(shard):

=============  ==========================  ==========================
transition     route                       per-device comm / peak
=============  ==========================  ==========================
s_to_s (i→j)   one tiled ``all_to_all``    (n-1)/n · shard  /  2·shard
r_to_s         local ``dynamic_slice``     0  /  input + shard
s_to_r         one tiled ``all_gather``    (n-1)/n · full  /  full
p_to_s (lax)   ``psum_scatter``            (n-1)/n · full  /  shard
p_to_r (lax)   ``psum``                    2(n-1)/n · full /  full
=============  ==========================  ==========================

:func:`plan_route` is the pure planner: it inspects (src placements,
dst placements, mesh, shape) and returns a :class:`ReshardRoute` with
the chosen kind plus predicted comm volume and peak residency for BOTH
the portable route and the legacy gather path — the numbers
``planner.estimate_step_cost`` and the bench rank strategies on.
:func:`apply_route` executes it through one shard_map program (memoized
per signature). Unsupported transitions (multi-dim changes, indivisible
shards, Partial sources at the eager api tier) fall back to the legacy
path with the reason recorded — ``FLAGS_comm_portable_reshard=0``
forces the legacy path for everything. The partial→shard /
partial→replicate kernels are exposed at the lax tier
(:func:`partial_to_shard`, :func:`partial_to_replicate`) for
spmd-region code, where partial values actually exist per device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

__all__ = [
    "ReshardRoute", "plan_route", "apply_route",
    "partial_to_shard", "partial_to_replicate",
]


@dataclasses.dataclass
class ReshardRoute:
    """One planned placement transition (see module docstring)."""

    kind: str                      # noop|slice|all_gather|all_to_all|fallback
    reason: str = ""               # fallback reason, "" otherwise
    axis: str = ""                 # mesh axis the transition moves over
    axis_size: int = 1
    src_dim: int = -1              # tensor dim sharded at the source
    dst_dim: int = -1              # tensor dim sharded at the target
    comm_bytes_new: float = 0.0    # per-device, portable route
    comm_bytes_old: float = 0.0    # per-device, legacy gather path
    peak_bytes_new: float = 0.0    # per-device residency, portable route
    peak_bytes_old: float = 0.0

    @property
    def supported(self) -> bool:
        return self.kind not in ("fallback",)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def plan_route(src_placements: Sequence, dst_placements: Sequence,
               mesh, shape, itemsize: int = 4) -> ReshardRoute:
    """Plan one placement transition on ``mesh`` (a ProcessMesh or any
    object with ``dim_names`` and per-axis sizes via ``shape``/
    ``get_dim_size``). Pure — no jax calls, safe in the planner."""
    from ..auto_parallel.placement_type import Partial, Replicate, Shard

    dim_names = list(getattr(mesh, "dim_names",
                             getattr(mesh, "axis_names", ())))
    full = float(_numel(shape) * itemsize)
    mesh_shape = mesh.shape  # list (ProcessMesh) or name->size (jax Mesh)
    sizes = ([mesh_shape[n] for n in dim_names]
             if isinstance(mesh_shape, dict) else list(mesh_shape))

    def axis_len(idx):
        return int(sizes[idx])

    diffs = [i for i, (s, d) in enumerate(zip(src_placements, dst_placements))
             if s != d]
    if not diffs:
        return ReshardRoute("noop")
    if len(diffs) > 1:
        return ReshardRoute("fallback", reason="multi_dim_transition")
    md = diffs[0]
    src, dst = src_placements[md], dst_placements[md]
    ax = dim_names[md] if md < len(dim_names) else str(md)
    n = axis_len(md)
    if n <= 1:
        return ReshardRoute("noop", axis=ax, axis_size=n)
    shard = full / n
    if isinstance(src, Partial):
        return ReshardRoute("fallback", reason="partial_source", axis=ax,
                            axis_size=n)
    if isinstance(dst, Partial):
        return ReshardRoute("fallback", reason="partial_target", axis=ax,
                            axis_size=n)
    ring = (n - 1) / n

    if isinstance(src, Replicate) and isinstance(dst, Shard):
        d = dst.get_dim()
        if int(shape[d]) % n != 0:
            return ReshardRoute("fallback", reason="indivisible_dim",
                                axis=ax, axis_size=n)
        return ReshardRoute(
            "slice", axis=ax, axis_size=n, dst_dim=d,
            comm_bytes_new=0.0, comm_bytes_old=0.0,
            peak_bytes_new=full + shard, peak_bytes_old=full + shard)
    if isinstance(src, Shard) and isinstance(dst, Replicate):
        i = src.get_dim()
        return ReshardRoute(
            "all_gather", axis=ax, axis_size=n, src_dim=i,
            comm_bytes_new=ring * full, comm_bytes_old=ring * full,
            peak_bytes_new=shard + full, peak_bytes_old=shard + full)
    if isinstance(src, Shard) and isinstance(dst, Shard):
        i, j = src.get_dim(), dst.get_dim()
        if i == j:
            return ReshardRoute("noop", axis=ax, axis_size=n)
        if int(shape[i]) % n != 0 or int(shape[j]) % n != 0:
            return ReshardRoute("fallback", reason="indivisible_dim",
                                axis=ax, axis_size=n)
        # portable: one tiled all_to_all over O(shard) blocks; legacy:
        # the gather path materializes the full array per device first
        return ReshardRoute(
            "all_to_all", axis=ax, axis_size=n, src_dim=i, dst_dim=j,
            comm_bytes_new=ring * shard, comm_bytes_old=ring * full,
            peak_bytes_new=2.0 * shard, peak_bytes_old=full + shard)
    return ReshardRoute("fallback", reason="unsupported_transition",
                        axis=ax, axis_size=n)


# ------------------------------------------------------------------ apply
_PROGRAMS: dict = {}
_PROGRAMS_MAX = 128


def _route_program(route: ReshardRoute, jmesh, src_spec, dst_spec,
                   shape, dtype):
    """Build (memoized) the jitted shard_map program for one route
    signature."""
    import jax
    from jax import lax

    from jax import shard_map

    try:
        key = (route.kind, route.axis, route.src_dim, route.dst_dim,
               jmesh, src_spec, dst_spec, tuple(shape), str(dtype))
        cached = _PROGRAMS.get(key)
    except TypeError:  # unhashable mesh/spec: build uncached
        key, cached = None, None
    if cached is not None:
        return cached

    ax, n = route.axis, route.axis_size

    if route.kind == "slice":
        d, chunk = route.dst_dim, int(shape[route.dst_dim]) // n

        def body(x):
            idx = lax.axis_index(ax)
            return lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=d)
    elif route.kind == "all_gather":

        def body(x):
            return lax.all_gather(x, ax, axis=route.src_dim, tiled=True)
    elif route.kind == "all_to_all":

        def body(x):
            return lax.all_to_all(x, ax, split_axis=route.dst_dim,
                                  concat_axis=route.src_dim, tiled=True)
    else:  # pragma: no cover - planner never hands these to apply
        raise ValueError(f"route kind {route.kind!r} has no program")

    prog = jax.jit(shard_map(body, mesh=jmesh, in_specs=src_spec,
                             out_specs=dst_spec, check_vma=False))
    if key is not None:
        _PROGRAMS[key] = prog
        while len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.pop(next(iter(_PROGRAMS)))
    return prog


def apply_route(value, jmesh, route: ReshardRoute, src_spec, dst_spec):
    """Execute a planned portable route on one jax array (eager tier).
    ``src_spec``/``dst_spec`` are the PartitionSpecs of the source and
    target placements over ``jmesh``."""
    prog = _route_program(route, jmesh, src_spec, dst_spec,
                          value.shape, value.dtype)
    return prog(value)


# ---------------------------------------------------------------- lax tier
def partial_to_shard(x, axis_name: str, scatter_dim: int = 0):
    """partial → shard inside an spmd region: one ``psum_scatter``
    ((n-1)/n volume) instead of psum + slice (2(n-1)/n + a dead full
    buffer). The caller's local ``x`` holds its partial term."""
    from jax import lax

    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dim,
                            tiled=True)


def partial_to_replicate(x, axis_name: str):
    """partial → replicate inside an spmd region (one psum)."""
    from jax import lax

    return lax.psum(x, axis_name)
