"""Device seconds per program region, from the scope path the chip writes
beside every operation.

The program names its regions with `jax.named_scope` (`decode/attn/kv_gather`,
`mlp`, `optimizer`, ...; the vocabulary is paddle_tpu/base/regions.py, and a
reader asks for a name by its constant there: `term`) and its Pallas kernels
with `pl.pallas_call(name=...)`. XLA keeps the path as each HLO instruction's
`op_name`, and a capture holds it as the `tf_op` stat of the operation's event
*metadata* (`jit(pure)/transpose(jvp(mlp))/dot_general:`), which
`jax.profiler.ProfileData` does not expose. So this module reads the
.xplane.pb once more, with a reader of the protobuf wire format that knows
just the six messages it needs (tsl/profiler/protobuf/xplane.proto):

    XSpace          1 planes
    XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
    XLine           2 name, 3 timestamp_ns, 4 events
    XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps
    XEventMetadata  1 id, 2 name, 5 stats
    XStat           1 metadata_id, 5 str_value, 7 ref_value (a stat_metadata id)

A region is the scope path with the `jit(...)` frames dropped, the transform
wrappers (`transpose(jvp(x))` -> `x`) opened and the trailing primitive cut;
an operation with no scope is `unscoped`. A fusion carries its root
instruction's path, so a region's seconds are those of the fusions and
kernels XLA rooted there. A capture without a single `tf_op` gives None, never 0:
the program it ran named nothing, and nothing can be said.
"""
from __future__ import annotations

import glob
import os
import re

from benchmark import harness, trace_reduce

UNSCOPED = "unscoped"

_JIT = re.compile(r"p?jit\([^()]*\)")
_WRAPPER = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\(([^()]*)\)")


def term(name: str):
    """The program's own name for a region or a kernel: the constant `name`
    of its vocabulary (paddle_tpu/base/regions.py). None where the program
    has no vocabulary (a commit before PR 27), and every reader built on it
    then returns None."""
    try:
        from paddle_tpu.base import regions
    except ImportError:
        return None
    return getattr(regions, name, None)


def region_of(tf_op: str) -> str:
    """`jit(pure)/transpose(jvp(attn/qkv))/dot_general:` -> `attn/qkv`."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    path = _JIT.sub("", path)
    while True:
        opened = _WRAPPER.sub(r"\1", path)
        if opened == path:
            break
        path = opened
    parts = [p for p in path.split("/") if p]
    return "/".join(parts[:-1]) or UNSCOPED


def holds(region: str, term: str) -> bool:
    """Whether `term` (`attn/kv_gather`, `flash_fwd`) is a whole stretch of
    the region's path."""
    return f"/{term}/" in f"/{region}/"


# ------------------------------------------------------- the wire format
def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: a varint as int, a
    length-delimited field as a memoryview, fixed-width ones as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value = bytes(buf[i:i + size])
            i += size
        else:
            raise ValueError(f"wire type {kind} is not in an xplane")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key, value = 0, b""
    for num, v in _fields(view):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _device_ops(plane):
    """One device plane -> [(start_ns, dur_ns, tf_op or None, hlo text)] of
    its `XLA Ops` line."""
    stat_names, metadata, lines = {}, {}, []
    for num, v in _fields(plane):
        if num == 3:
            lines.append(v)
        elif num == 4:
            key, value = _map_entry(v)
            metadata[key] = value
        elif num == 5:
            key, value = _map_entry(v)
            stat_names[key] = next((_text(x) for n, x in _fields(value) if n == 2), "")
    tf_op_id = next((k for k, name in stat_names.items() if name == "tf_op"), None)
    described = {}

    def describe(metadata_id):
        got = described.get(metadata_id)
        if got is None:
            name, tf_op = "", None
            for num, v in _fields(metadata.get(metadata_id, b"")):
                if num == 2:
                    name = _text(v)
                elif num == 5 and tf_op_id is not None:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op_id:
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            got = described[metadata_id] = (tf_op, name)
        return got

    ops = []
    for line in lines:
        name, t_line, events = "", 0, []
        for num, v in _fields(line):
            if num == 2:
                name = _text(v)
            elif num == 3:
                t_line = v
            elif num == 4:
                events.append(v)
        if name != "XLA Ops":
            continue
        for event in events:
            e = dict(_fields(event))
            ops.append((t_line + e.get(2, 0) / 1e3, e.get(3, 0) / 1e3)
                       + describe(e.get(1, 0)))
    return ops


_READ = {}   # path -> (mtime, ops per device)


def device_ops(path: str) -> list:
    """Per chip, the operations of the capture at `path` on the capture's own
    clock; read once per file."""
    mtime = os.path.getmtime(path)
    if _READ.get(path, (None,))[0] != mtime:
        with open(path, "rb") as f:
            space = memoryview(f.read())
        devices = []
        for num, plane in _fields(space):
            if num != 1:
                continue
            name = next((_text(v) for n, v in _fields(plane) if n == 2), "")
            if name.startswith(trace_reduce.DEVICE_PLANE):
                devices.append(_device_ops(plane))
        _READ.clear()
        _READ[path] = (mtime, devices)
    return _READ[path][1]


def capture_path() -> str:
    """The capture the traced run just wrote (harness.TraceCapture)."""
    found = glob.glob(os.path.join(harness.TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return found[0] if found else None


# ------------------------------------------------------------ the reduction
def region_seconds(trace, t0: float = None, t1: float = None, path: str = None):
    """{region: seconds} inside [t0, t1] (the traced window unless given),
    averaged over the chips, on the host's clock as `trace` has it. None where
    there is no capture or it holds no `tf_op`."""
    path = path or capture_path()
    if not path or not os.path.isfile(path):
        return None
    devices = device_ops(path)
    if not any(tf_op is not None for ops in devices for _, _, tf_op, _ in ops):
        return None
    t0 = trace.t0 if t0 is None else t0
    t1 = trace.t1 if t1 is None else t1
    regions, out = {}, {}
    for ops in devices:
        for start_ns, dur_ns, tf_op, _ in ops:
            s = start_ns / 1e9 + trace.clock_shift_s
            e = s + dur_ns / 1e9
            s, e = max(s, t0), min(e, t1)
            if e <= s:
                continue
            region = regions.get(tf_op)
            if region is None:
                region = regions[tf_op] = region_of(tf_op) if tf_op else UNSCOPED
            out[region] = out.get(region, 0.0) + (e - s) / len(devices)
    return out


def seconds_in(regions: dict, *terms: str) -> float:
    """Summed seconds of the regions whose path holds one of `terms`."""
    return sum(v for k, v in regions.items() if any(holds(k, t) for t in terms))


_LOGGED = set()


def unscoped_ops(trace, n: int = 8) -> list:
    """The operations that carry no scope, by instruction: [(seconds, count,
    category, head of the HLO text)], the longest first. They are what the
    compiler made on its own (layout and memory-space copies, expanded
    scatters), and the place to look when `unscoped` is large."""
    by_name = {}
    for ops in device_ops(capture_path()):
        for start_ns, dur_ns, tf_op, text in ops:
            if tf_op and region_of(tf_op) != UNSCOPED:
                continue
            s = max(start_ns / 1e9 + trace.clock_shift_s, trace.t0)
            e = min((start_ns + dur_ns) / 1e9 + trace.clock_shift_s, trace.t1)
            if e > s:
                got = by_name.setdefault(text.split(" = ")[0], [0.0, 0, text])
                got[0] += e - s
                got[1] += 1
    rows = sorted(by_name.values(), key=lambda r: -r[0])[:n]
    return [(sec, count, trace_reduce.category(text), text[:160]) for sec, count, text in rows]


def log_closure(trace, regions: dict) -> None:
    """Once per capture: the regions by seconds, their sum against the busy
    seconds, and what carries no scope."""
    key = (capture_path(), trace.t0, trace.t1)
    if key in _LOGGED:
        return
    _LOGGED.add(key)
    busy, named = trace_reduce.busy_seconds(trace), sum(regions.values())
    harness.log(f"regions: {named:.4f} s in {len(regions)} regions against "
                f"{busy:.4f} s busy; {UNSCOPED} {regions.get(UNSCOPED, 0.0):.4f} s = "
                f"{100.0 * regions.get(UNSCOPED, 0.0) / max(busy, 1e-12):.2f}%; top: "
                + ", ".join(f"{k} {v:.3f}" for k, v in trace_reduce.top(regions, 24)))
    for sec, count, cat, text in unscoped_ops(trace):
        harness.log(f"unscoped: {sec:.4f} s in {count} x {cat}: {text}")


def share(trace, term: str):
    """Percent of the device's busy seconds spent in regions that hold
    `term`; None where the capture names no such region, or the program has
    no such name."""
    if term is None:
        return None
    regions = region_seconds(trace)
    busy = trace_reduce.busy_seconds(trace)
    if not regions or busy <= 0:
        return None
    log_closure(trace, regions)
    spent = seconds_in(regions, term)
    return 100.0 * spent / busy if spent > 0 else None


def kernel_ms_per_layer_step(trace, facts, *kernels: str):
    """Mean device milliseconds of the named kernels per layer per step, over
    the step programs that lie whole inside the window."""
    if not trace.devices or not facts.get("layers") or None in kernels:
        return None
    steps = trace_reduce.whole_modules(trace.devices[0], trace.t0, trace.t1)
    if not steps:
        return None
    regions = region_seconds(trace, steps[0][0], steps[-1][1])
    spent = seconds_in(regions, *kernels) if regions else 0.0
    return 1e3 * spent / (facts["layers"] * len(steps)) if spent > 0 else None
