"""Cache-key derivation: environment fingerprint + per-site signatures.

A persistent executable is only replayable in an environment that would
have compiled the same bytes: the **fingerprint** pins everything the
compiled artifact implicitly depends on — jax/jaxlib versions, the XLA
backend + device kind + device count (sharded executables bind to the
topology), the python ABI, and the flags that change what the framework
stages (``use_pallas_kernels``). The fingerprint digest is folded into
every entry digest, so a toolchain upgrade or backend switch NATURALLY
misses (the old entries just become prunable garbage); the full
fingerprint is also recorded in each entry header so ``tools.cache
verify`` and the CC70x audit can explain a stale store instead of
silently re-filling it.

Per-site key material rides the caller's own signature scheme:

- ``kernel``:  the eager kernel-cache key tuple (op, code-content token,
  (shape, dtype) specs, frozen attrs — ``core/kernel_cache.py``),
  canonicalized by deterministic pickle;
- ``jit``:     the lowered StableHLO text of a ``CompiledFunction``
  entry (the functionalizer's key is process-local treedef identity, so
  the portable identity is what was actually handed to XLA);
- ``serving``: the exported module's content hash + the bucket rung's
  concrete input shapes/dtypes + the donation spec — static, derivable
  WITHOUT tracing, which is what lets a warm replica restore the whole
  ladder with ``traces_on_warm_start == 0``.
"""
from __future__ import annotations

import hashlib
import json
import pickle
import sys
from typing import Any, Optional

_FINGERPRINT_FLAGS = ("use_pallas_kernels",)

_fingerprint_memo: list = []


def _invalidate_fingerprint(_new_value=None) -> None:
    _fingerprint_memo.clear()


def _watch_fingerprint_flags() -> None:
    """A staging-relevant flag flipped via ``set_flags`` changes what the
    framework compiles, so the memoized fingerprint must re-derive —
    otherwise entries get stored under a stale fingerprint (the exact
    wrong-executable hazard CC700 polices)."""
    try:
        from ..base.flags import on_flag_change

        for name in _FINGERPRINT_FLAGS:
            on_flag_change(name, _invalidate_fingerprint)
    except Exception:
        pass


_watch_fingerprint_flags()


def fingerprint() -> dict:
    """The environment fingerprint dict (memoized — backend probing is a
    jax call; invalidated when a fingerprinted flag changes)."""
    if _fingerprint_memo:
        return _fingerprint_memo[0]
    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    device_kind = devices[0].device_kind
    n_devices = len(devices)
    flags = {}
    for name in _FINGERPRINT_FLAGS:
        try:
            from ..base.flags import get_flag

            flags[name] = get_flag(name)
        except Exception:
            flags[name] = None
    from .. import version

    fp = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": platform,
        "device_kind": device_kind,
        "n_devices": n_devices,
        "python": "%d.%d" % sys.version_info[:2],
        "framework": getattr(version, "full_version", "0"),
        "flags": flags,
    }
    _fingerprint_memo.append(fp)
    return fp


def fingerprint_digest(fp: Optional[dict] = None) -> str:
    """Stable hex digest of one fingerprint dict."""
    payload = json.dumps(fp if fp is not None else fingerprint(),
                         sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def _canonical_bytes(material: Any) -> bytes:
    """Deterministic byte serialization of one site's key material.

    bytes/str pass through; everything else goes through pickle protocol 4
    — deterministic for the value shapes the kernel-cache key holds (ints,
    strs, bytes, dtypes, type objects, nested tuples). Callers catch the
    pickle failure (a key holding an unpicklable closure simply isn't
    persistable) and skip the disk tier for that entry.
    """
    if isinstance(material, bytes):
        return material
    if isinstance(material, str):
        return material.encode()
    return pickle.dumps(material, protocol=4)


def derive_digest(site: str, material: Any,
                  fp_digest: Optional[str] = None) -> Optional[str]:
    """Content digest for one entry: sha256 over (site, fingerprint
    digest, canonical key bytes). ``None`` when the material cannot be
    canonicalized — the caller must treat that entry as unpersistable,
    never raise."""
    try:
        body = _canonical_bytes(material)
    except Exception:
        return None
    h = hashlib.sha256()
    h.update(site.encode())
    h.update(b"\0")
    h.update((fp_digest or fingerprint_digest()).encode())
    h.update(b"\0")
    h.update(body)
    return h.hexdigest()
