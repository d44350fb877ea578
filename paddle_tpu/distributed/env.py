"""Distributed environment: the TPU-native rebuild of the reference's process
bootstrap + communicator stack.

Reference (SURVEY.md §2.14):
- `init_parallel_env` (python/paddle/distributed/parallel.py:978) creates a
  TCPStore and NCCL communicators per ring;
- `HybridCommunicateGroup` (fleet/base/topology.py:189) splits the world into
  pp/mp/sep/sharding/dp process groups.

TPU-native design: there is ONE fabric object — a `jax.sharding.Mesh` over all
devices, with named axes for each parallelism dimension. "Process groups"
become mesh axes; NCCL rings become XLA collectives over ICI/DCN; the TCPStore
rendezvous becomes the JAX coordination service (`jax.distributed.initialize`).
A single python controller drives every device (SPMD), so `rank` at the python
level is the *process* index (multi-host), while per-device rank only exists
inside compiled programs (shard_map regions / GSPMD partitioning).
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

from ..base.log import get_logger

# canonical hybrid axis order, outermost first. Matches the reference's 5-D
# topology order pp->dp->sharding->sep->mp (fleet/base/topology.py:72) with
# dp outermost-adjacent so that dp+sharding ride the slower links and mp/sep
# (heaviest traffic) ride the innermost ICI.
HYBRID_AXES = ("pp", "dp", "sharding", "sep", "mp")


class ParallelEnv:
    """Singleton world description: devices, mesh, axis degrees.

    Also mirrors the reference's `ParallelEnv` (python/paddle/distributed/
    parallel.py) env-var surface: rank/world_size/device_id.
    """

    _instance: Optional["ParallelEnv"] = None

    def __init__(self):
        self.initialized = False
        self.mesh: Optional[Mesh] = None
        self.axis_degrees: Dict[str, int] = {}
        self.device_kind = "unknown"

    # ---------------------------------------------------------------- process
    @property
    def rank(self) -> int:
        return jax.process_index() if self.initialized else int(os.environ.get("PADDLE_TRAINER_ID", 0))

    @property
    def world_size(self) -> int:
        return jax.process_count() if self.initialized else int(os.environ.get("PADDLE_TRAINERS_NUM", 1))

    @property
    def local_rank(self) -> int:
        return 0

    @property
    def nranks(self) -> int:
        return self.world_size

    @property
    def device_id(self) -> int:
        return 0

    # ---------------------------------------------------------------- mesh
    def build_mesh(self, degrees: Optional[Dict[str, int]] = None, devices=None) -> Mesh:
        """Create the global device mesh.

        degrees: dict axis->size over HYBRID_AXES (missing axes get 1; one
        unspecified axis may be -1 to absorb the remaining devices; by default
        `dp` absorbs everything).
        """
        devices = list(devices if devices is not None else jax.devices())
        n = len(devices)
        degrees = dict(degrees or {})
        for ax in HYBRID_AXES:
            degrees.setdefault(ax, -1 if ax == "dp" and -1 not in degrees.values() else 1)
        fixed = int(np.prod([d for d in degrees.values() if d != -1]))
        if any(d == -1 for d in degrees.values()):
            if n % fixed != 0:
                raise ValueError(f"device count {n} not divisible by fixed degrees {degrees}")
            fill = n // fixed
            degrees = {k: (fill if v == -1 else v) for k, v in degrees.items()}
        total = int(np.prod(list(degrees.values())))
        if total != n:
            raise ValueError(f"mesh degrees {degrees} product {total} != device count {n}")
        shape = tuple(degrees[ax] for ax in HYBRID_AXES)
        arr = np.array(devices).reshape(shape)
        self.mesh = Mesh(arr, HYBRID_AXES)
        self.axis_degrees = degrees
        self.device_kind = devices[0].device_kind
        return self.mesh


def instance() -> ParallelEnv:
    if ParallelEnv._instance is None:
        ParallelEnv._instance = ParallelEnv()
    return ParallelEnv._instance


def init_parallel_env(degrees: Optional[Dict[str, int]] = None) -> ParallelEnv:
    """Initialize the distributed fabric (reference: parallel.py:978).

    Multi-host: wires `jax.distributed.initialize` from the same env contract
    the reference launcher sets (PADDLE_MASTER / PADDLE_TRAINER_ID /
    PADDLE_TRAINERS_NUM), then builds the global mesh over all hosts' devices.
    Single-host: just builds the mesh over local devices.
    """
    env = instance()
    if env.initialized:
        if degrees:
            env.build_mesh(degrees)
        return env
    master = os.environ.get("PADDLE_MASTER") or os.environ.get("MASTER_ADDR")
    nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", os.environ.get("WORLD_SIZE", 1)))
    if master and nprocs > 1 and jax.process_count() == 1:
        port = os.environ.get("MASTER_PORT")
        addr = master if (":" in master or not port) else f"{master}:{port}"
        pid = int(os.environ.get("PADDLE_TRAINER_ID", os.environ.get("RANK", 0)))
        get_logger().info("jax.distributed.initialize(%s, %d, %d)", addr, nprocs, pid)
        jax.distributed.initialize(coordinator_address=addr, num_processes=nprocs, process_id=pid)
    env.initialized = True
    env.build_mesh(degrees)
    return env


def get_mesh() -> Mesh:
    env = instance()
    if env.mesh is None:
        env.build_mesh()
    return env.mesh


def set_mesh(mesh: Mesh):
    env = instance()
    env.mesh = mesh
    env.axis_degrees = {ax: mesh.shape[ax] for ax in mesh.axis_names}


def get_rank() -> int:
    return instance().rank


def get_world_size() -> int:
    return instance().world_size


def is_initialized() -> bool:
    return instance().initialized


def barrier(group=None):
    """Block until all processes' outstanding work completes.

    Single-controller SPMD needs no explicit device barrier; multi-host sync
    rides the coordination service via a tiny psum.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("paddle_tpu_barrier")
    else:
        jax.effects_barrier()


def shard_largest_dim(value, jmesh: Mesh, axis_name: str):
    """Place `value` with its largest axis-size-divisible dim sharded over
    ``axis_name`` (replicated when no dim divides). Shared by ZeRO param/state
    sharding and pipeline stage placement."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = jmesh.shape.get(axis_name, 1)
    shape = value.shape
    best = None
    for d in range(len(shape)):
        if shape[d] % n == 0 and shape[d] >= n:
            if best is None or shape[d] > shape[best]:
                best = d
    if best is None:
        return jax.device_put(value, NamedSharding(jmesh, P()))
    spec = [None] * len(shape)
    spec[best] = axis_name
    return jax.device_put(value, NamedSharding(jmesh, P(*spec)))
