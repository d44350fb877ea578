"""MetricBuffer: keep per-step training metrics on the device.

The classic hapi loop forced ``float(loss.numpy())`` every step — a
device→host readback that stalls the async dispatch queue exactly once per
step, which on TPU serializes H2D, program dispatch and D2H
(ISSUE 5 motivation). The buffer is the non-blocking replacement: the loop
appends raw device scalars (zero host syncs), and floats materialize only
at **sync boundaries** — every ``sync_every`` steps (log frequency) and at
the epoch flush. Materialization batches all pending scalars into one
device concatenation + a single host transfer, and converts element-wise to
python floats, so the flushed values are **bit-identical** to what the
per-step ``float(...)`` loop would have produced.

Every materialization is timed and counted in
``profiler.pipeline_stats`` (``host_sync_us`` / ``host_syncs_per_step``):
the steady state issues zero.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional


def to_float(value) -> float:
    """One blocking device→host scalar read, counted as a host sync.
    The sanctioned sync point for code that *must* return a python float
    (``Model.train_batch(sync=True)``, epoch summaries)."""
    import numpy as np

    from ..profiler.pipeline import pipeline_stats

    t0 = time.perf_counter()
    v = getattr(value, "_value", value)
    out = float(np.asarray(v).reshape(-1)[0])
    dt = time.perf_counter() - t0
    pipeline_stats.add_host_sync(dt)
    from ..observability.tracing import tracer

    if tracer.enabled:
        tracer.emit("host_sync", t0, dt, track="train_loop")
    return out


class MetricBuffer:
    """Per-name ring of device scalars with boundary-only materialization.

    ``sync_every=k`` → :meth:`should_sync` is True every k-th step (the
    loop materializes there, typically to feed a progress logger);
    ``sync_every=0``/``None`` → only explicit :meth:`flush` calls sync.
    """

    def __init__(self, sync_every: Optional[int] = None):
        self.sync_every = int(sync_every or 0)
        self._pending: Dict[str, List] = {}
        self._history: Dict[str, List[float]] = {}

    # ------------------------------------------------------------- appending
    def append(self, name: str, value) -> None:
        """Record one step's metric. ``value`` may be a Tensor or a raw
        device array; it is stored as-is — no host transfer happens."""
        self._pending.setdefault(name, []).append(
            getattr(value, "_value", value))

    def latest(self, name: str):
        """The most recent recorded value, still device-resident (pending)
        or the last materialized float."""
        pend = self._pending.get(name)
        if pend:
            return pend[-1]
        hist = self._history.get(name)
        return hist[-1] if hist else None

    def last_float(self, name: str):
        """The most recent MATERIALIZED value (a python float), or None
        when nothing has synced yet — never touches the device."""
        hist = self._history.get(name)
        return hist[-1] if hist else None

    def should_sync(self, step: int) -> bool:
        """True on sync boundaries: step is 0-based and with
        ``sync_every=k`` steps 0, k, 2k, ... materialize — the same
        cadence ``ProgBarLogger`` prints on (``step % log_freq == 0``),
        so the logger always receives already-materialized floats."""
        return self.sync_every > 0 and step % self.sync_every == 0

    # --------------------------------------------------------- materializing
    def materialize(self) -> Dict[str, float]:
        """Move every pending scalar to the host (one stacked transfer per
        metric), append to the history, and return the latest float per
        metric. The conversion path (f32 device scalar → python float) is
        bit-identical to a per-step ``float(np.asarray(v))``."""
        import numpy as np

        from ..profiler.pipeline import pipeline_stats

        if not self._pending:
            return {k: v[-1] for k, v in self._history.items() if v}
        import jax.numpy as jnp

        t0 = time.perf_counter()
        out = {}
        n_values = 0
        for name, vals in self._pending.items():
            stacked = np.asarray(jnp.stack([jnp.reshape(v, ()) for v in vals]))
            floats = [float(x) for x in stacked]
            n_values += len(floats)
            self._history.setdefault(name, []).extend(floats)
            out[name] = floats[-1]
        self._pending.clear()
        dt = time.perf_counter() - t0
        pipeline_stats.add_host_sync(dt)
        from ..observability.tracing import tracer

        if tracer.enabled:
            tracer.emit("metric.flush", t0, dt, track="train_loop",
                        metrics=len(out), values=n_values)
        return out

    def flush(self) -> Dict[str, dict]:
        """Epoch boundary: materialize everything and return per-metric
        ``{"last", "mean", "values"}``, then reset the history. ``mean``
        uses the same float64 accumulation over python floats as the old
        per-step loop's ``np.mean(list_of_floats)``."""
        import numpy as np

        self.materialize()
        report = {}
        for name, vals in self._history.items():
            if not vals:
                continue
            report[name] = {"last": vals[-1],
                            "mean": float(np.mean(vals)),
                            "values": list(vals)}
        self._history.clear()
        return report
