"""Per-step input-pipeline breakdown: where does a train step's wall time go?

The async XLA dispatch model hides host→device transfer and host-side
dispatch behind device compute — but only when the loop around the compiled
step actually lets it (no per-step ``.numpy()``, batches staged ahead of
consumption). This module is the observability half of that contract: the
``DeviceLoader`` (io/device_prefetch.py), ``MetricBuffer``
(hapi/metric_buffer.py) and the hapi train loop report their waits into
one process-global :class:`PipelineStats`, whose summary holds:

- ``h2d_wait_us``   — time the consumer blocked waiting for the next
  device-resident batch (0 when prefetch keeps up: the H2D overlapped the
  previous step's compute);
- ``h2d_issue_us``  — time the prefetch worker spent issuing
  ``jax.device_put`` (the transfer cost that is being hidden);
- ``dispatch_us``   — time inside the compiled step call (enqueue + for
  synchronous backends the compute itself);
- ``host_sync_us``  / ``host_syncs_per_step`` — time and count of blocking
  device→host reads (metric materialization). The steady-state target is
  **zero per step**: syncs belong at log/epoch boundaries.
- ``overlap_ratio`` — fraction of issued H2D time the consumer never
  waited for (1.0 = transfers fully hidden).

Recording costs two ``perf_counter`` calls per event — cheap enough to
leave on; ``reset()`` starts a fresh window.
"""
from __future__ import annotations

import time

from ..observability.locks import named_lock


class PipelineStats:
    """Thread-safe accumulator for the per-step pipeline breakdown."""

    def __init__(self):
        self._lock = named_lock("profiler.pipeline_stats")
        self.reset()

    def reset(self):
        with self._lock:
            self.steps = 0
            self.h2d_wait_s = 0.0
            self.h2d_issue_s = 0.0
            self.dispatch_s = 0.0
            self.host_sync_s = 0.0
            self.host_syncs = 0

    # ------------------------------------------------------------ recording
    def add_h2d_wait(self, seconds: float):
        with self._lock:
            self.h2d_wait_s += seconds

    def add_h2d_issue(self, seconds: float):
        with self._lock:
            self.h2d_issue_s += seconds

    def add_dispatch(self, seconds: float):
        with self._lock:
            self.dispatch_s += seconds

    def add_host_sync(self, seconds: float, count: int = 1):
        with self._lock:
            self.host_sync_s += seconds
            self.host_syncs += count

    def step(self, n: int = 1):
        with self._lock:
            self.steps += n

    # ------------------------------------------------------------ reporting
    def summary(self) -> dict:
        with self._lock:
            steps = max(self.steps, 1)
            if self.h2d_issue_s > 0:
                overlap = 1.0 - min(self.h2d_wait_s / self.h2d_issue_s, 1.0)
            else:
                overlap = None
            return {
                "steps": self.steps,
                "h2d_wait_us": round(self.h2d_wait_s / steps * 1e6, 1),
                "h2d_issue_us": round(self.h2d_issue_s / steps * 1e6, 1),
                "dispatch_us": round(self.dispatch_s / steps * 1e6, 1),
                "host_sync_us": round(self.host_sync_s / steps * 1e6, 1),
                "host_syncs_per_step": round(self.host_syncs / steps, 4),
                "overlap_ratio": (round(overlap, 4)
                                  if overlap is not None else None),
            }


pipeline_stats = PipelineStats()


class ServingStats:
    """Request-phase accounting for the serving tier (paddle_tpu/serving):
    every completed request reports its enqueue→admit→dispatch→complete
    timestamps, every scheduler pass samples the queue depth, and every
    dispatched batch reports its fill. The summary holds p50/p99
    end-to-end latency, requests/sec,
    and requests/sec *within the SLO* (FLAGS_serving_slo_ms) — the
    EQuARX-style accounting discipline: a serving tier is measured in
    admitted work per second at a latency bound, not raw throughput.
    Requests recorded with a ``tenant`` additionally land in that
    tenant's own ring, and ``summary()["tenants"]`` breaks the same
    numbers down per tenant (p50/p99, queue-wait, rps, rejected) — the
    multi-tenant fairness read.

    Latency samples are kept in a bounded ring (last ``max_samples``
    requests, globally and per tenant) so percentile math never grows
    with uptime.
    """

    def __init__(self, max_samples: int = 8192):
        self._lock = named_lock("profiler.serving_stats")
        self._max_samples = int(max_samples)
        self.reset()

    def reset(self):
        with self._lock:
            self.requests = 0
            self.samples = 0
            self.rejected = 0
            self.expired = 0
            self.batches = 0
            self.padded_slots = 0
            self.batch_slots = 0
            self.queue_depth_sum = 0
            self.queue_depth_peak = 0
            self.depth_samples = 0
            self._lat = []        # (total, queue_wait, exec) seconds, ring
            self._tenants = {}    # tenant -> {"requests","samples","rejected",
            #                                  "lat": bounded ring like _lat}
            self._t_first = None
            self._t_last = None
            # decode tier (serving/decode.py): per-call prefill-vs-decode
            # latency split, emitted-token throughput, slot occupancy.
            # Every key is read by summary()["decode"] (the draft and
            # verify keys by its speculation block), so by /metrics
            # (paddle_serving_decode_*) and serving_report(); the
            # benchmark's closed-loop drivers print *_steps and
            # *_p50_ms/_p99_ms, tools/chaos reads spec_rounds.
            self._decode = {
                "prefill_steps": 0, "decode_steps": 0,
                # a call's own time, dispatch to tokens on the host, and
                # the part of it inside the dispatch: bounded rings
                "prefill_ms": [], "decode_ms": [],
                "prefill_dispatch_ms": [], "decode_dispatch_ms": [],
                # self-speculation split (ISSUE 20): draft/verify program
                # calls keyed like the other step kinds, plus per-round
                # acceptance accounting
                "draft_steps": 0, "verify_steps": 0,
                "draft_ms": [], "verify_ms": [],     # bounded rings
                "draft_dispatch_ms": [], "verify_dispatch_ms": [],
                # seconds the scheduler's thread spent inside dispatches
                # and waiting inside reads, all kinds
                "dispatch_s": 0.0, "read_wait_s": 0.0,
                "spec_rounds": 0, "spec_proposed": 0,
                "spec_accepted": 0, "spec_committed": 0,
                # program calls that sorted the vocabulary (a lane sampled)
                "sample_sort_steps": 0,
                # block-table entries of the paged decode steps, and those
                # of them that named a page with a visible column
                "pages_live": 0, "pages_table": 0,
                # reads of a call's tokens: after the next call went out
                # (the device kept working) or with nothing behind them
                # (a flush); lanes whose input token came from the device
                "reads_overlapped": 0, "reads_flushed": 0,
                "lanes_carried": 0,
                "tokens": 0, "t_first": None, "t_last": None,
                "occ_sum": 0, "occ_samples": 0, "occ_peak": 0,
                "slots": 0,
            }

    def _tenant_cell(self, tenant) -> dict:
        # caller holds the lock
        cell = self._tenants.get(tenant)
        if cell is None:
            cell = self._tenants[tenant] = {
                "requests": 0, "samples": 0, "rejected": 0, "lat": []}
        return cell

    # ------------------------------------------------------------ recording
    def record_request(self, t_enqueue: float, t_admit: float,
                       t_dispatch: float, t_complete: float, n: int = 1,
                       tenant: str = None):
        """One completed request's phase timestamps (perf_counter space);
        ``tenant`` additionally lands the sample in that tenant's own
        bounded ring for the per-tenant summary breakdown."""
        with self._lock:
            self.requests += 1
            self.samples += int(n)
            lat = (t_complete - t_enqueue, t_dispatch - t_admit,
                   t_complete - t_dispatch)
            self._ring(self._lat, lat)
            if tenant is not None:
                cell = self._tenant_cell(tenant)
                cell["requests"] += 1
                cell["samples"] += int(n)
                self._ring(cell["lat"], lat)
            if self._t_first is None:
                self._t_first = t_enqueue
            self._t_last = max(self._t_last or t_complete, t_complete)

    def record_rejected(self, n: int = 1, tenant: str = None):
        with self._lock:
            self.rejected += int(n)
            if tenant is not None:
                self._tenant_cell(tenant)["rejected"] += int(n)

    def record_expired(self, n: int = 1, tenant: str = None):
        """Requests whose queue wait outlived FLAGS_serving_request_ttl_ms
        (failed with AdmissionError reason='ttl', never executed)."""
        with self._lock:
            self.expired += int(n)
            if tenant is not None:
                cell = self._tenant_cell(tenant)
                cell["expired"] = cell.get("expired", 0) + int(n)

    def retire_tenant(self, tenant: str) -> bool:
        """Drop a tenant's stats lane (mid-traffic tenant churn): its
        ring and counters leave ``summary()["tenants"]``; the global
        aggregates keep everything it already contributed."""
        with self._lock:
            return self._tenants.pop(tenant, None) is not None

    def record_decode_step(self, kind: str, seconds: float, n_lanes: int,
                           n_tokens: int, *, t_end: float, dispatch_s: float,
                           read_wait_s: float, overlapped: bool = None,
                           lanes_carried: int = 0):
        """One decode-tier program call, recorded when its tokens were
        read: ``kind`` is ``"prefill"``, ``"decode"``, ``"draft"`` or
        ``"verify"``; ``seconds`` is the call's own time, from just before
        its dispatch to its tokens on the host at ``t_end``
        (``perf_counter``); ``n_tokens`` real tokens were emitted by
        ``n_lanes`` real lanes (pad lanes excluded — a draft call emits
        0, its round's committed tokens land on the verify call).
        ``dispatch_s`` of it passed inside the program call until it
        returned, ``read_wait_s`` waiting for the tokens: the scheduler's
        thread did nothing else meanwhile, so over the window from the
        first call's dispatch to the last one's read the two sums are
        shares that add up to at most 1. ``overlapped`` says how the
        call's tokens were read, and is left out where the read is not
        one of its own (a speculation round counts one read, with its
        verify call): the scheduler had dispatched the next call first
        (the device went on working), else it waited the read out with
        nothing queued behind it (nothing to dispatch, a drain, or a
        speculation round); ``lanes_carried`` lanes of that next call
        took their input token from the unread call's output on the
        device. The share of reads that overlapped says how often the
        host's round trip is hidden. Feeds the per-kind latency split,
        tokens/sec, ``dispatch_share`` and ``read_wait_share``."""
        with self._lock:
            cell = self._decode
            cell[f"{kind}_steps"] += 1
            self._ring(cell[f"{kind}_ms"], float(seconds) * 1e3)
            self._ring(cell[f"{kind}_dispatch_ms"], float(dispatch_s) * 1e3)
            cell["dispatch_s"] += float(dispatch_s)
            cell["read_wait_s"] += float(read_wait_s)
            if overlapped is not None:
                cell["reads_overlapped" if overlapped else "reads_flushed"] += 1
                cell["lanes_carried"] += int(lanes_carried)
            cell["tokens"] += int(n_tokens)
            if cell["t_first"] is None:
                cell["t_first"] = t_end - seconds
            cell["t_last"] = t_end

    def _ring(self, ring: list, value) -> None:
        # one more sample in a bounded ring; caller holds the lock
        ring.append(value)
        if len(ring) > self._max_samples:
            del ring[: len(ring) - self._max_samples]

    def record_sample_sort(self, calls: int):
        """``calls`` program calls of one step carried a lane with
        ``temperature > 0``, so each sorted the vocabulary in every lane
        (``PagedDecodePrograms._choose_tokens``). One minus
        ``sample_sort_steps`` over the sum of the four ``*_steps`` is
        the share of calls that chose by argmax alone."""
        with self._lock:
            self._decode["sample_sort_steps"] += int(calls)

    def record_pages(self, pages_live: int, pages_table: int):
        """One paged decode step (or speculation round) ran on a block
        table of ``pages_table`` entries (batch rung x table rung) of
        which ``pages_live`` named a page that holds a column its lane
        may see. Their ratio is the share of a dense gathered view's
        bytes that the step's attention has to read."""
        with self._lock:
            self._decode["pages_live"] += int(pages_live)
            self._decode["pages_table"] += int(pages_table)

    def record_spec_round(self, proposed: int, accepted: int,
                          committed: int):
        """One self-speculation round's acceptance accounting across its
        lanes: ``proposed`` draft tokens, ``accepted`` of them matched
        the full-model verify pass, ``committed`` tokens entered streams
        (accepted + the verify-pass bonus token per lane, clipped by
        eos/max_new). Feeds ``spec_accept_rate`` and
        ``spec_net_tokens_per_full_pass`` in the summary."""
        with self._lock:
            cell = self._decode
            cell["spec_rounds"] += 1
            cell["spec_proposed"] += int(proposed)
            cell["spec_accepted"] += int(accepted)
            cell["spec_committed"] += int(committed)

    def record_slot_occupancy(self, in_use: int, capacity: int):
        """KV slot occupancy at a step boundary (peak proves slot reuse:
        under oversubscribed traffic it reaches ``capacity`` while pool
        bytes stay constant)."""
        with self._lock:
            cell = self._decode
            cell["occ_sum"] += int(in_use)
            cell["occ_samples"] += 1
            cell["occ_peak"] = max(cell["occ_peak"], int(in_use))
            cell["slots"] = max(cell["slots"], int(capacity))

    def record_batch(self, n_samples: int, bucket: int):
        """One dispatched batch: ``n_samples`` real rows padded to
        ``bucket`` slots (fill ratio = batching efficiency)."""
        with self._lock:
            self.batches += 1
            self.batch_slots += int(bucket)
            self.padded_slots += int(bucket) - int(n_samples)

    def record_queue_depth(self, depth: int):
        with self._lock:
            self.depth_samples += 1
            self.queue_depth_sum += int(depth)
            if depth > self.queue_depth_peak:
                self.queue_depth_peak = int(depth)

    # ------------------------------------------------------------ reporting
    @staticmethod
    def _pct(sorted_vals, q):
        if not sorted_vals:
            return None
        idx = min(int(q * (len(sorted_vals) - 1) + 0.5), len(sorted_vals) - 1)
        return sorted_vals[idx]

    def summary(self, slo_ms: float = None) -> dict:
        if slo_ms is None:
            from ..base.flags import get_flag

            slo_ms = float(get_flag("serving_slo_ms"))
        with self._lock:
            total = sorted(t for t, _, _ in self._lat)
            queue_w = sorted(q for _, q, _ in self._lat)
            window = ((self._t_last - self._t_first)
                      if self._t_first is not None and self._t_last else 0.0)
            in_slo = sum(1 for t in total if t * 1e3 <= slo_ms)
            out = {
                "requests": self.requests,
                "samples": self.samples,
                "rejected": self.rejected,
                "expired": self.expired,
                "batches": self.batches,
                "slo_ms": slo_ms,
                "p50_ms": (round(self._pct(total, 0.50) * 1e3, 3)
                           if total else None),
                "p99_ms": (round(self._pct(total, 0.99) * 1e3, 3)
                           if total else None),
                "queue_wait_p50_ms": (round(self._pct(queue_w, 0.50) * 1e3, 3)
                                      if queue_w else None),
                "requests_per_sec": (round(self.requests / window, 1)
                                     if window > 0 else None),
                "samples_per_sec": (round(self.samples / window, 1)
                                    if window > 0 else None),
                "in_slo_fraction": (round(in_slo / len(total), 4)
                                    if total else None),
                "requests_per_sec_in_slo": (
                    round(self.requests * (in_slo / len(total)) / window, 1)
                    if total and window > 0 else None),
                "batch_fill": (round(1.0 - self.padded_slots
                                     / max(self.batch_slots, 1), 4)
                               if self.batches else None),
                "queue_depth_mean": (round(self.queue_depth_sum
                                           / self.depth_samples, 2)
                                     if self.depth_samples else None),
                "queue_depth_peak": self.queue_depth_peak,
                "tenants": {
                    name: self._tenant_summary(cell, window)
                    for name, cell in sorted(self._tenants.items())},
                "decode": self._decode_summary(),
            }
        return out

    def _decode_summary(self):
        """The decode tier's split (caller holds the lock): prefill vs
        decode step latency percentiles, emitted-token throughput, slot
        occupancy. None when no decode steps ran (batch-only engines)."""
        cell = self._decode
        if not cell["prefill_steps"] and not cell["decode_steps"]:
            return None
        window = ((cell["t_last"] - cell["t_first"])
                  if cell["t_first"] is not None else 0.0)
        prefill = sorted(cell["prefill_ms"])
        decode = sorted(cell["decode_ms"])
        dispatch = sorted(cell["decode_dispatch_ms"])
        prefill_dispatch = sorted(cell["prefill_dispatch_ms"])

        def pct(vals, q):
            v = self._pct(vals, q)
            return round(v, 3) if v is not None else None

        out = {
            "prefill_steps": cell["prefill_steps"],
            "decode_steps": cell["decode_steps"],
            "sample_sort_steps": cell["sample_sort_steps"],
            "pages_live": cell["pages_live"],
            "pages_table": cell["pages_table"],
            "pages_live_share": (round(cell["pages_live"]
                                       / cell["pages_table"], 4)
                                 if cell["pages_table"] else None),
            "reads_overlapped": cell["reads_overlapped"],
            "reads_flushed": cell["reads_flushed"],
            "reads_overlapped_share": (
                round(cell["reads_overlapped"]
                      / (cell["reads_overlapped"] + cell["reads_flushed"]), 4)
                if cell["reads_overlapped"] + cell["reads_flushed"] else None),
            "lanes_carried": cell["lanes_carried"],
            "prefill_p50_ms": pct(prefill, 0.50),
            "prefill_p99_ms": pct(prefill, 0.99),
            "decode_p50_ms": pct(decode, 0.50),
            "decode_p99_ms": pct(decode, 0.99),
            # a decode call's dispatch (until the program call returned),
            # and a prefill call's
            "dispatch_p50_ms": pct(dispatch, 0.50),
            "dispatch_p99_ms": pct(dispatch, 0.99),
            "prefill_dispatch_p50_ms": pct(prefill_dispatch, 0.50),
            "prefill_dispatch_p99_ms": pct(prefill_dispatch, 0.99),
            # of the scheduler thread's time: near 1 in dispatches, the
            # host sets the pace; near 1 waiting in reads, the device does
            "dispatch_share": (round(cell["dispatch_s"] / window, 4)
                               if window > 0 else None),
            "read_wait_share": (round(cell["read_wait_s"] / window, 4)
                                if window > 0 else None),
            "tokens": cell["tokens"],
            "tokens_per_sec": (round(cell["tokens"] / window, 1)
                               if window > 0 else None),
            "slot_occupancy_mean": (round(cell["occ_sum"]
                                          / cell["occ_samples"], 2)
                                    if cell["occ_samples"] else None),
            "slot_occupancy_peak": cell["occ_peak"],
            "slots": cell["slots"],
        }
        if cell["spec_rounds"]:
            draft = sorted(cell["draft_ms"])
            verify = sorted(cell["verify_ms"])
            out.update(
                spec_rounds=cell["spec_rounds"],
                spec_tokens_proposed=cell["spec_proposed"],
                spec_tokens_accepted=cell["spec_accepted"],
                spec_tokens_committed=cell["spec_committed"],
                spec_accept_rate=(
                    round(cell["spec_accepted"]
                          / max(cell["spec_proposed"], 1), 4)),
                # >1.0 is the whole point: tokens committed per FULL-model
                # program call (verify) vs the 1.0 a plain decode step gets
                spec_net_tokens_per_full_pass=(
                    round(cell["spec_committed"]
                          / max(cell["spec_rounds"], 1), 3)),
                draft_steps=cell["draft_steps"],
                verify_steps=cell["verify_steps"],
                draft_p50_ms=pct(draft, 0.50),
                verify_p50_ms=pct(verify, 0.50),
                # of which inside the dispatch, as dispatch_p50_ms is of
                # a decode call
                draft_dispatch_p50_ms=pct(
                    sorted(cell["draft_dispatch_ms"]), 0.50),
                verify_dispatch_p50_ms=pct(
                    sorted(cell["verify_dispatch_ms"]), 0.50),
            )
        return out

    def _tenant_summary(self, cell: dict, window: float) -> dict:
        """Per-tenant breakdown (caller holds the lock): latency
        percentiles, queue wait and request rate over the SAME window as
        the global summary — the multi-tenant fairness read: is one
        tenant's p99 paying for another's burst?"""
        total = sorted(t for t, _, _ in cell["lat"])
        queue_w = sorted(q for _, q, _ in cell["lat"])
        return {
            "requests": cell["requests"],
            "samples": cell["samples"],
            "rejected": cell["rejected"],
            "expired": cell.get("expired", 0),
            "p50_ms": (round(self._pct(total, 0.50) * 1e3, 3)
                       if total else None),
            "p99_ms": (round(self._pct(total, 0.99) * 1e3, 3)
                       if total else None),
            "queue_wait_p50_ms": (round(self._pct(queue_w, 0.50) * 1e3, 3)
                                  if queue_w else None),
            "requests_per_sec": (round(cell["requests"] / window, 1)
                                 if window > 0 else None),
        }


serving_stats = ServingStats()


class timed:
    """``with timed(stats.add_dispatch): step(batch)`` — records the span."""

    __slots__ = ("_sink", "_t0")

    def __init__(self, sink):
        self._sink = sink

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._sink(time.perf_counter() - self._t0)
