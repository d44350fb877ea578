"""Distributed checkpoint save.

Reference: distributed/checkpoint/save_state_dict.py:145 — each rank writes
its local shards plus a global metadata index enabling cross-topology resume.

TPU-native: arrays are *global* jax.Arrays whose shards live per-device; each
host writes only the shards it addresses (process-local), plus the
coordinator writes metadata (shapes/dtypes/chunk index). Because the on-disk
format is the global array (chunked), loading under ANY topology is a plain
device_put — load-time reshard is structural rather than a special pass.
Orbax-style async copy: the device->host transfer runs before serialization;
fsync off the training thread.

Multi-host commit protocol (the reference's all_gather_object discipline,
jax-native): per-rank chunk indices plus a coordinator nonce are
all-gathered across hosts BEFORE any IO so the coordinator's metadata
describes every rank's chunks; chunk keys and chunked shard filenames are
rank- AND nonce-qualified so a save never overwrites the files the previous
committed metadata references; each rank acks its durable shard with a
per-save nonce file; the coordinator renames metadata.json only after every
ack for THIS save landed (then GCs superseded nonce files) — a failed
commit leaves the previous checkpoint fully intact and loadable.
"""
from __future__ import annotations

import json
import os
import threading
import time
import uuid
from typing import Dict, Optional

import numpy as np

from ...core.tensor import Tensor
from ...observability.locks import named_lock
from ...reliability.faults import fault_point
from ...reliability.snapshot import fsync_dir


def _flatten_state(state_dict, prefix=""):
    flat = {}
    for k, v in state_dict.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            flat.update(_flatten_state(v, key + "."))
        elif isinstance(v, Tensor):
            flat[key] = v
        elif v is not None and hasattr(v, "shape"):
            flat[key] = Tensor(v)
    return flat


def _gather_object(obj):
    """All-gather one small JSON-serializable object per host — the public
    collective (communication.all_gather_object), list-returning."""
    from ..communication import all_gather_object

    out: list = []
    all_gather_object(out, obj)
    return out


# Pending async writers, keyed by checkpoint path so overlapping saves into
# different directories never join (or interleave with) each other. Failed
# async commits are recorded per path and re-raised by wait_async_save.
_pending_lock = named_lock("distributed.ckpt.pending")
_pending_writers: Dict[str, list] = {}
_pending_errors: Dict[str, Exception] = {}


def save_state_dict(state_dict: Dict, path: str, process_group=None, coordinator_rank: int = 0,
                    async_save: bool = False, format: str = "auto") -> None:
    """``format="sharded"`` routes through the manifest-format engine
    (``distributed.checkpoint.sharded``): one piece file per (tensor,
    shard) written straight from each device's shard — no host-side
    full-tensor gather, O(largest shard) peak host residency, sha256
    per piece, atomic tmp+rename publish. ``load_state_dict``
    auto-detects the format on read. ``"auto"`` (default) keeps the
    legacy npz layout — it remains the multi-host commit protocol;
    the sharded engine is single-writer-per-directory by design (each
    rank points at its own directory, the TrainSnapshotter idiom)."""
    from .. import env as env_mod

    if format == "sharded":
        from .sharded import save_sharded

        if async_save:
            raise ValueError(
                "save_state_dict(format='sharded') is synchronous — the "
                "sharded engine's atomic publish has no async writer yet")
        if env_mod.get_world_size() > 1:
            # the legacy branch below IS the multi-rank commit protocol
            # (rank-qualified chunks + gathered metadata + acks); the
            # sharded engine is single-writer-per-directory — racing
            # every rank's tmp/rename dance onto one path would collide
            # or last-writer-win with partial coverage
            raise ValueError(
                "save_state_dict(format='sharded') is single-writer: in a "
                f"multi-rank job (world_size={env_mod.get_world_size()}) "
                "point each rank at its own directory (e.g. "
                "f'{path}/rank{get_rank()}', the TrainSnapshotter idiom) "
                "or use the default format's multi-host commit protocol")
        save_sharded(state_dict, path, overwrite=True)
        return
    if format not in ("auto", "legacy"):
        raise ValueError(f"unknown checkpoint format {format!r} "
                         "(expected 'auto', 'legacy' or 'sharded')")
    os.makedirs(path, exist_ok=True)
    flat = _flatten_state(state_dict)
    rank = env_mod.get_rank()
    arrays = {}
    chunked = False  # did any array write host-local chunks (true multi-host)?
    meta = {"format": "paddle_tpu_dist_ckpt_v1", "world_size": env_mod.get_world_size(), "entries": {}}
    pending = {}  # k -> [(chunk_ordinal, host_array, index), ...]
    for k, t in flat.items():
        v = t._value
        entry = {"shape": list(v.shape), "dtype": str(np.dtype(v.dtype)), "chunks": []}
        if hasattr(v, "addressable_shards") and not getattr(v, "is_fully_addressable", True):
            chunked = True
            # multi-host: each host writes only the shards it addresses, once
            # per unique device slice (replicas dedup on replica_id==0).
            # Chunk keys are assigned after the gather, once the save's nonce
            # is known — key = {k}__r{rank}c{i}_{nonce}, so neither another
            # rank's chunks nor a PREVIOUS save's chunks can collide with
            # this save's in the merged shard namespace the loader builds.
            pending[k] = [
                (i, np.asarray(sh.data),
                 [[s.start or 0, s.stop if s.stop is not None else dim]
                  for s, dim in zip(sh.index, v.shape)])
                for i, sh in enumerate(v.addressable_shards)
                if sh.replica_id == 0]
        elif rank == coordinator_rank:
            arrays[k] = np.asarray(v)  # device->host once, before any disk IO
        meta["entries"][k] = entry

    t_start = time.time()  # GC horizon: never collect files newer than this
    nonce: Optional[str] = None
    ack_ranks: list = []
    if chunked:
        # Pre-IO metadata gather (the reference's all_gather_object step):
        # the coordinator's metadata must describe EVERY rank's chunks, and
        # the gathered nonce gives all ranks this save's identity for the
        # chunk keys, shard filename and durable-shard acks below. Runs on
        # the caller thread — collectives never run on the background writer.
        payload = {
            "rank": rank,
            "chunks": {k: [[i, index] for i, _, index in cs]
                       for k, cs in pending.items()},
            "nonce": uuid.uuid4().hex if rank == coordinator_rank else None,
        }
        gathered = _gather_object(payload)
        for got in gathered:
            if got["nonce"]:
                nonce = got["nonce"]
        if nonce is None:  # degenerate: coordinator absent from the gather
            nonce = "unknown"
        for got in gathered:
            if got["rank"] == rank:
                continue
            ack_ranks.append(got["rank"])
            for k, chunks in got["chunks"].items():
                if k in meta["entries"]:
                    meta["entries"][k]["chunks"].extend(
                        {"key": f"{k}__r{got['rank']}c{i}_{nonce}",
                         "index": index} for i, index in chunks)
        for k, cs in pending.items():
            for i, data, index in cs:
                ck = f"{k}__r{rank}c{i}_{nonce}"
                arrays[ck] = data
                meta["entries"][k]["chunks"].append({"key": ck, "index": index})

    def _write():
        # Atomic commit protocol (VERDICT r3 #8; reference
        # save_state_dict.py:145's tmp-then-finalize discipline): shard data
        # lands under .tmp names, is fsynced, renamed, then acked with this
        # save's nonce; the coordinator renames metadata.json only once every
        # rank's ack for THIS save is present — a crash at any point leaves
        # either the previous complete checkpoint or an ignorable set of
        # .tmp/ack files, never readable metadata pointing at missing or
        # stale shards. Fully-addressable saves (single host, or a rank
        # checkpointing its own state into a private dir, as the elastic path
        # does) skip the wait: the coordinator's own shard already holds
        # everything its metadata references. The device→host copies happened
        # before this thread started, so the training loop may already be
        # mutating (donated) device buffers.
        # Chunked shard files are nonce-qualified too: writing shard data for
        # save N+1 must not overwrite the files save N's metadata references
        # — if this commit fails, the PREVIOUS checkpoint must stay loadable
        # with its own (unclobbered) data, not a silent mix of two steps.
        # Stale nonce-files are GC'd by the coordinator after a successful
        # commit. The single-writer non-chunked path keeps the plain name:
        # its atomic replace is already sound.
        shard_final = os.path.join(
            path, f"shard_{rank}_{nonce}.npz" if chunked else f"shard_{rank}.npz")
        if rank == coordinator_rank:
            # the loader resolves PLAIN (non-chunked) keys from this file
            # specifically, so stale same-named keys in other shard files
            # can never shadow a committed save's values
            meta["coordinator_shard"] = os.path.basename(shard_final)
        shard_tmp = shard_final + ".tmp"
        with open(shard_tmp, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        # the injected torn-write point (reliability chaos): a crash here
        # leaves ONLY the fsynced .tmp file — metadata.json still points
        # at the previous complete checkpoint
        fault_point("ckpt.write")
        os.replace(shard_tmp, shard_final)
        # fsync the DIRECTORY too (write to a temporary name, fsync the
        # file, rename, fsync the directory): the rename itself must
        # survive power loss, or a committed metadata.json can reference
        # a shard the directory forgot (ISSUE 14 satellite)
        fsync_dir(path)
        if chunked:
            # durable-shard ack for this save. No pre-write cleanup here:
            # deleting "stale" acks from save N while its coordinator is
            # still polling would fail a commit whose shards all landed —
            # superseded acks are GC'd post-commit, where it is safe.
            with open(os.path.join(path, f"ack_{rank}_{nonce}"), "w") as f:
                f.flush()
                os.fsync(f.fileno())
        if rank == coordinator_rank:
            deadline = time.monotonic() + float(
                os.environ.get("PADDLE_CKPT_COMMIT_TIMEOUT_S", "600"))
            missing = list(ack_ranks)
            while missing and time.monotonic() < deadline:
                missing = [r for r in missing if not os.path.exists(
                    os.path.join(path, f"ack_{r}_{nonce}"))]
                if missing:
                    time.sleep(0.05)
            if missing:
                raise RuntimeError(
                    f"checkpoint {path} NOT committed: no durable-shard ack "
                    f"from ranks {missing} within timeout; metadata.json left "
                    "unwritten so the previous checkpoint (if any) stays the "
                    "valid one")
            meta_final = os.path.join(path, "metadata.json")
            meta_tmp = meta_final + ".tmp"
            with open(meta_tmp, "w") as f:
                json.dump(meta, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(meta_tmp, meta_final)
            fsync_dir(path)  # the commit rename must be durable too
            # GC: nonce-qualified shards/acks from superseded saves are
            # unreferenced now that this save's metadata is committed. Runs
            # for non-chunked commits too — a single-host save into a dir
            # that previously held a chunked save must clear the stale
            # nonce-shards, or the loader's merge would let their plain keys
            # shadow the fresh ones. Only files comfortably older than THIS
            # save's start are collected: other hosts' writers chain
            # per-process, so an overlapping save N+1 may already have
            # durable files here — they are newer than t_start and must
            # survive save N's GC. The skew margin absorbs NFS server clock
            # offset and coarse mtime granularity; a file that survives one
            # GC for being too new is collected by a later save's.
            skew = float(os.environ.get("PADDLE_CKPT_GC_SKEW_S", "60"))
            for old in os.listdir(path):
                if old.endswith(".tmp"):
                    continue
                parts = (old[:-4] if old.endswith(".npz") else old).split("_")
                if (len(parts) == 3 and parts[0] in ("shard", "ack")
                        and parts[2] != nonce):
                    try:
                        full = os.path.join(path, old)
                        if os.path.getmtime(full) < t_start - skew:
                            os.remove(full)
                    except OSError:
                        pass

    def _write_retried():
        # bounded retry (ISSUE 14): a transient disk fault mid-commit is
        # replayed — safe because every piece of _write is idempotent
        # (same tmp-then-rename names, same ack file, same metadata) —
        # while a fatal error (or exhausted budget) propagates with the
        # previous checkpoint still the committed one
        from ...reliability.policy import RetryPolicy

        RetryPolicy("ckpt.write", max_delay_s=0.5).run(_write)

    if async_save:
        # Writers for the SAME path are chained: save N+1's writer first
        # joins save N's, so overlapping async saves can never interleave
        # their shard writes, acks, or GC (a later save's GC would delete
        # files an earlier in-flight commit still references). The thread is
        # started INSIDE the lock so every queued thread is joinable, and it
        # stays queued until _join_writers prunes it after completion.
        with _pending_lock:
            queue = _pending_writers.setdefault(path, [])
            prev_th = queue[-1] if queue else None

            def _guarded():
                if prev_th is not None:
                    prev_th.join()
                try:
                    _write_retried()
                except Exception as e:  # surfaced by wait_async_save
                    from ...base.log import get_logger

                    get_logger().warning(
                        "async checkpoint save to %s failed: %s", path, e)
                    with _pending_lock:
                        _pending_errors.setdefault(path, e)

            th = threading.Thread(target=_guarded, daemon=False)
            queue.append(th)
            th.start()
    else:
        # a sync save must not interleave with in-flight async writers for
        # the same path (same tmp names, and its GC would delete files an
        # uncommitted async save still references)
        _join_writers(path)
        _write_retried()


def _join_writers(path: str):
    """Join every pending writer for ``path`` (all paths when None). Threads
    stay in the queue until they are DONE — popping before the join would
    let a concurrent save chain onto nothing and interleave with a writer
    that is still running."""
    while True:
        with _pending_lock:
            if path is None:
                targets = list(_pending_writers)
            else:
                targets = [path] if path in _pending_writers else []
            th = None
            for target in targets:
                writers = _pending_writers.get(target, [])
                writers[:] = [t for t in writers if t.is_alive()]
                if writers:
                    th = writers[-1]  # the chain tail joins the whole chain
                    break
                _pending_writers.pop(target, None)
        if th is None:
            return
        th.join()


def wait_async_save(path: str = None):
    """Join pending async writers — all of them, or only those for ``path``.
    Raises the first recorded commit failure for the joined path(s)."""
    _join_writers(path)
    with _pending_lock:
        if path is None:
            errs = list(_pending_errors.values())
            _pending_errors.clear()
        else:
            err = _pending_errors.pop(path, None)
            errs = [err] if err else []
    if errs:
        raise errs[0]
