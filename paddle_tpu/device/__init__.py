"""Device management (reference: python/paddle/device/__init__.py set_device).

On TPU the device runtime is PJRT (the analog of the reference's
DeviceManager + custom-device C-ABI, paddle/phi/backends/device_manager.h):
jax enumerates devices; set_device picks the default placement.
"""
from __future__ import annotations

import jax

_current_device = None


def _resolve_device(device):
    if device is None:
        return get_device_object()
    if not isinstance(device, str):
        return device  # already a jax.Device
    name = device.lower()
    if ":" in name:
        kind, idx = name.split(":")
        idx = int(idx)
    else:
        kind, idx = name, 0
    if kind in ("tpu", "gpu", "cuda", "xpu"):
        pool = [d for d in jax.devices() if d.platform != "cpu"]
        if not pool:
            raise RuntimeError(
                f"device {device!r} requested but jax sees no accelerator "
                f"(jax.devices() = {jax.devices()})")
    elif kind == "cpu":
        pool = jax.devices("cpu")
    else:
        pool = jax.devices()
    if not 0 <= idx < len(pool):
        raise ValueError(
            f"device {device!r}: index {idx} out of range, "
            f"{len(pool)} such device(s) visible")
    return pool[idx]


def get_device_object():
    if _current_device is not None:
        return _current_device
    return jax.devices()[0]


def set_device(device):
    global _current_device
    _current_device = _resolve_device(device)
    return _current_device


def get_device():
    d = get_device_object()
    plat = d.platform
    if plat == "cpu":
        return "cpu"
    return f"{plat}:{d.id}"


def get_all_custom_device_type():
    return [d for d in {dd.platform for dd in jax.devices()} if d not in ("cpu", "gpu", "tpu")]


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return any(d.platform != "cpu" for d in jax.devices())


def device_count():
    return len(jax.devices())


def synchronize(device=None):
    """Block until all async dispatches complete (reference: device sync)."""
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()


class Stream:
    """Compat shim: XLA schedules streams internally; explicit streams are a no-op."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_stream(self, other):
        pass


class Event:
    def __init__(self, enable_timing=False):
        import time

        self._t = None
        self._time = time

    def record(self, stream=None):
        synchronize()
        self._t = self._time.perf_counter()

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end):
        return (end._t - self._t) * 1000.0


# ---- memory observability (reference paddle.device.cuda.max_memory_allocated
# family, paddle/phi/core/memory/stats.cc) — mapped onto PJRT memory_stats --

def _mem_stats(device=None):
    dev = get_device_object() if device is None else _resolve_device(device)
    stats = getattr(dev, "memory_stats", lambda: None)()
    return stats or {}


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on the device (PJRT ``bytes_in_use``;
    0 when the backend does not report memory stats, e.g. CPU)."""
    return int(_mem_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak bytes allocated on the device (PJRT ``peak_bytes_in_use``)."""
    return int(_mem_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool (PJRT pool stats; falls back to
    bytes_in_use when the backend has no pool accounting)."""
    s = _mem_stats(device)
    return int(s.get("pool_bytes", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    s = _mem_stats(device)
    return int(s.get("peak_pool_bytes", s.get("peak_bytes_in_use", 0)))


def memory_limit(device=None) -> int:
    """The device's usable memory budget (PJRT ``bytes_limit``)."""
    return int(_mem_stats(device).get("bytes_limit", 0))
