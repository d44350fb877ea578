"""Atomic rolling train-state snapshots — preemption-safe training.

The elastic path restarts workers by design, and before ISSUE 14 a
restart replayed the epoch from step 0 (ROADMAP "checkpointable loader
state"). A :class:`TrainSnapshotter` closes that gap: every
``snapshot_every`` steps ``Model.fit`` lands ONE complete, atomic
snapshot of everything the next process needs to continue the loss
stream **bit-identically**:

- the global step / epoch / next-batch **loader cursor** (the new
  ``DataLoader.iter_from`` skips back to it at the index level, no
  replayed fetches for map-style data),
- the model parameters,
- the optimizer state — zero1-aware: when the sharded update is
  attached, each rank saves only its O(shard) pieces through
  ``save_sharded_optimizer_state``, and resume onto a CHANGED dp degree
  rides the existing re-slice loader,
- the global RNG key (bit-exact — dropout streams continue, not
  restart).

Commit protocol (write to a temporary name, fsync file and directory,
rename, applied to a directory): everything writes into
``.tmp_<step>_<nonce>/``, every file is fsynced, then ONE ``os.rename``
publishes ``snap_<step>/`` and the parent directory is fsynced — a crash
(or an injected ``ckpt.write`` fault) at any point leaves the previous
snapshot intact plus an ignorable tmp dir, never a torn snapshot. ``latest()`` only
ever sees renamed (complete) snapshots. The directory is rolling:
``keep`` newest survive, older ones are pruned after each commit.
"""
from __future__ import annotations

import json
import os
import shutil
import time
import uuid
from typing import Optional

from .faults import fault_point
from .policy import RetryPolicy

__all__ = ["TrainSnapshotter", "fsync_dir"]

_SNAP_PREFIX = "snap_"
_TMP_PREFIX = ".tmp_"
_TMP_STALE_S = 3600.0
_FORMAT = "paddle_tpu_train_snap_v1"


def fsync_dir(path: str) -> None:
    """fsync a directory so a just-published rename survives power loss
    (best-effort: not every filesystem supports directory fds)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _fsync_file(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


class TrainSnapshotter:
    """Rolling atomic snapshots under one directory.

    ``save``/``restore`` are the API ``Model.fit`` drives; both are
    usable standalone (the chaos harness calls them directly). Writes
    retry under the ``ckpt.write`` :class:`~.policy.RetryPolicy` —
    a transient disk fault costs a backoff, not the snapshot."""

    def __init__(self, directory: str, keep: Optional[int] = None,
                 retry: bool = True):
        from ..base.flags import get_flag

        self.dir = str(directory)
        self.keep = int(get_flag("train_snapshot_keep")
                        if keep is None else keep)
        self._retry = (RetryPolicy("ckpt.write", max_delay_s=0.5)
                       if retry else None)

    # ------------------------------------------------------------- write
    def save(self, network=None, optimizer=None, *, step: int,
             epoch: int = 0, next_batch: int = 0,
             extra: Optional[dict] = None) -> str:
        """Land one complete snapshot for ``step``; returns its path. A
        snapshot for the same step that already committed is kept as-is
        (content-equal by construction: same step, same state)."""
        if self._retry is not None:
            return self._retry.run(self._save_once, network, optimizer,
                                   step, epoch, next_batch, extra)
        return self._save_once(network, optimizer, step, epoch,
                               next_batch, extra)

    def _save_once(self, network, optimizer, step, epoch, next_batch,
                   extra) -> str:
        final = os.path.join(self.dir, f"{_SNAP_PREFIX}{int(step):08d}")
        if os.path.isdir(final) and os.path.exists(
                os.path.join(final, "state.json")):
            return final
        os.makedirs(self.dir, exist_ok=True)
        tmp = os.path.join(
            self.dir, f"{_TMP_PREFIX}{int(step):08d}_{uuid.uuid4().hex[:8]}")
        os.makedirs(tmp)
        try:
            state = {
                "format": _FORMAT,
                "step": int(step),
                "epoch": int(epoch),
                "next_batch": int(next_batch),
                "ts_unix": time.time(),
                "zero1": False,
            }
            if extra:
                state["extra"] = extra
            if network is not None:
                # params ride the sharded writer (ISSUE 15): one piece
                # file per (tensor, shard) straight from each device's
                # shard — O(largest shard) host residency instead of a
                # full host state_dict gather — and the SAME directory is
                # directly servable (Predictor.swap_weights(<snap>/params)
                # rolls it into a live engine). The outer snapshot rename
                # is the commit; the engine's own tmp+rename inside this
                # tmp dir is redundant but harmless.
                from ..distributed.checkpoint.sharded import save_sharded

                save_sharded(network.state_dict(),
                             os.path.join(tmp, "params"))
            if optimizer is not None:
                state["zero1"] = self._save_optimizer(optimizer, tmp)
                state["opt_step"] = int(
                    getattr(optimizer, "_step_count", 0))
            # the RNG key, bit-exact: the resumed process continues the
            # same dropout/noise stream instead of restarting it
            rng = self._rng_state()
            if rng is not None:
                state["rng_seed"], state["rng_key"] = rng
            state_path = os.path.join(tmp, "state.json")
            with open(state_path, "w") as f:
                json.dump(state, f)
                f.flush()
                os.fsync(f.fileno())
            for name in os.listdir(tmp):
                _fsync_file(os.path.join(tmp, name))
            # the injected torn-write point: a crash here leaves ONLY the
            # tmp dir — the previous snapshot stays the valid latest
            fault_point("ckpt.write")
            os.rename(tmp, final)  # the atomic publish
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        fsync_dir(self.dir)
        self._prune()
        try:
            from ..observability.metrics import registry

            registry.counter(
                "reliability.snapshots",
                "rolling train-state snapshots committed by "
                "TrainSnapshotter").inc()
        except Exception:
            pass
        return final

    def _save_optimizer(self, optimizer, tmp: str) -> bool:
        from ..distributed.sharding import zero1
        from ..framework.io import save as fw_save

        prefix = os.path.join(tmp, "opt")
        if zero1.attached(optimizer) is not None:
            # O(shard) pieces per rank; resume re-slices onto any dp
            zero1.save_sharded_optimizer_state(optimizer, prefix)
            return True
        # position-stable keys (zero1's _host_key_map idiom): the plain
        # state_dict embeds auto-generated tensor names, which a fresh
        # twin model (the restarted process) does not share
        key_map = zero1._host_key_map(optimizer)
        fw_save({key_map.get(k, k): v
                 for k, v in optimizer.state_dict().items()},
                prefix + ".pdopt")
        return False

    @staticmethod
    def _rng_state():
        import numpy as np

        from ..base import global_state

        gen = global_state.default_generator
        if gen._cell is None:
            return None
        key = np.asarray(gen._cell._value)
        return int(gen._seed), key.astype(np.uint32).ravel().tolist()

    # -------------------------------------------------------------- read
    def snapshots(self) -> list:
        """Committed snapshots, oldest first: ``[(step, path), ...]``."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        for name in names:
            if not name.startswith(_SNAP_PREFIX):
                continue
            path = os.path.join(self.dir, name)
            if not os.path.exists(os.path.join(path, "state.json")):
                continue  # never happens post-rename; belt and braces
            try:
                out.append((int(name[len(_SNAP_PREFIX):]), path))
            except ValueError:
                continue
        return sorted(out)

    def latest(self) -> Optional[str]:
        snaps = self.snapshots()
        return snaps[-1][1] if snaps else None

    def restore(self, network=None, optimizer=None,
                path: Optional[str] = None) -> dict:
        """Restore the newest (or ``path``'s) snapshot into the live
        objects; returns its ``state.json`` (the loader cursor included).
        Raises ``FileNotFoundError`` when nothing complete exists."""
        import numpy as np

        from ..framework.io import load as fw_load

        if path is None:
            path = self.latest()
            if path is None:
                raise FileNotFoundError(
                    f"no complete snapshot under {self.dir!r} (tmp dirs "
                    "from interrupted saves are not restorable)")
        with open(os.path.join(path, "state.json")) as f:
            state = json.load(f)
        if state.get("format") != _FORMAT:
            raise ValueError(f"{path}: not a {_FORMAT} snapshot")
        params_dir = os.path.join(path, "params")
        params_path = os.path.join(path, "params.pdparams")
        if network is not None and os.path.isdir(params_dir):
            # sharded snapshot (ISSUE 15): pieces restore straight onto
            # each live tensor's current placement/dtype — bit-exact on
            # the fp32→fp32 round trip, loud on any missing/corrupt piece
            from ..distributed.checkpoint.sharded import load_sharded_into

            load_sharded_into(network.state_dict(), params_dir)
        elif network is not None and os.path.exists(params_path):
            network.set_state_dict(fw_load(params_path))
        if optimizer is not None:
            self._restore_optimizer(optimizer, path, state)
        if "rng_key" in state:
            self._restore_rng(state["rng_seed"],
                              np.asarray(state["rng_key"], np.uint32))
        return state

    @staticmethod
    def _restore_optimizer(optimizer, path: str, state: dict) -> None:
        from ..distributed.sharding import zero1
        from ..framework.io import load as fw_load

        prefix = os.path.join(path, "opt")
        if state.get("zero1"):
            # re-scatters (and, on a changed dp degree, re-slices) the
            # saved shard pieces onto the live topology
            zero1.load_sharded_optimizer_state(optimizer, prefix)
        elif os.path.exists(prefix + ".pdopt"):
            inverse = {v: k
                       for k, v in zero1._host_key_map(optimizer).items()}
            optimizer.set_state_dict(
                {inverse.get(k, k): v
                 for k, v in fw_load(prefix + ".pdopt").items()})

    @staticmethod
    def _restore_rng(seed: int, key) -> None:
        import jax.numpy as jnp

        from ..base import global_state

        gen = global_state.default_generator
        gen._seed = int(seed)
        cell = gen._key_cell  # force creation, then overwrite bit-exact
        cell._replace_value(jnp.asarray(key, jnp.uint32))

    # ------------------------------------------------------------- prune
    def _prune(self) -> None:
        snaps = self.snapshots()
        if self.keep > 0:
            for _step, path in snaps[:-self.keep]:
                shutil.rmtree(path, ignore_errors=True)
        now = time.time()
        try:
            names = os.listdir(self.dir)
        except OSError:
            return
        for name in names:
            if not name.startswith(_TMP_PREFIX):
                continue
            path = os.path.join(self.dir, name)
            try:
                if now - os.path.getmtime(path) > _TMP_STALE_S:
                    shutil.rmtree(path, ignore_errors=True)
            except OSError:
                pass
