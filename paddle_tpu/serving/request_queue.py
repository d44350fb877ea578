"""Thread-safe request queue with per-tenant admission control.

The front door of the serving tier: client threads :meth:`RequestQueue.submit`
requests; the scheduler thread pops FIFO prefixes sized by the bucket
ladder (:func:`jit.bucketing.assemble_bucket`). Admission is decided AT
submit — a full queue or an over-quota tenant is told *now* (an
:class:`AdmissionError` carries which gate refused), not after its request
aged in a queue it could never clear. Quota is measured in SAMPLES, not
requests: a tenant streaming batch-32 requests spends its budget 32x
faster than one sending singletons.

Every request carries its phase timestamps (enqueue → admit → dispatch →
complete, ``time.perf_counter`` space; a :class:`DecodeRequest` also its
first token's); completion hands them to
``profiler.pipeline.serving_stats`` so the latency accounting rides the
same observability channel as the train-loop pipeline stats.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..observability.locks import named_condition, named_lock


class AdmissionError(RuntimeError):
    """A submit the admission controller refused: ``reason`` is ``"queue"``
    (global sample cap), ``"tenant"`` (per-tenant in-flight quota),
    ``"priority"`` (bulk tier refused to protect interactive headroom),
    ``"ttl"`` (the request expired in queue before it could be served) or
    ``"circuit"`` (the tenant's circuit breaker is open — its recent
    batches kept failing, so load is shed at the door until the breaker's
    cooldown probe succeeds)."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


class RejectedError(RuntimeError):
    """Raised by :meth:`Request.result` when the queue shut down before the
    request was served."""


_req_ids = itertools.count()


class Request:
    """One inference request: ``n`` samples stacked on each input's batch
    axis. The submitting thread blocks in :meth:`result`; the scheduler
    thread completes it."""

    __slots__ = ("id", "tenant", "inputs", "n", "seq", "t_enqueue", "t_admit",
                 "t_dispatch", "t_complete", "_event", "_outputs", "_error")

    def __init__(self, tenant: str, inputs: Sequence[np.ndarray], n: int,
                 seq: Optional[int] = None):
        self.id = next(_req_ids)
        self.tenant = tenant
        self.inputs = inputs
        self.n = int(n)
        # real length on the sequence axis (two-axis exports only): the
        # scheduler pads up to the seq rung and slices back to this
        self.seq = None if seq is None else int(seq)
        self.t_enqueue = time.perf_counter()
        self.t_admit = None
        self.t_dispatch = None
        self.t_complete = None
        self._event = threading.Event()
        self._outputs = None
        self._error = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> List[np.ndarray]:
        """Block until served; returns the output arrays (``n`` rows each)."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.id} not served in {timeout}s")
        if self._error is not None:
            raise self._error
        return self._outputs

    # scheduler side ------------------------------------------------------
    # Resolution is FIRST-RESULT-WINS: a retried batch replaying its
    # completion loop (reliability.RetryPolicy around the program call)
    # or a shutdown racing a drain must never overwrite a result a
    # client thread may already be reading. A second resolution attempt
    # is counted (`serving.duplicate_resolution` — the chaos harness
    # asserts it stays 0) and dropped.
    def _resolved_already(self) -> bool:
        if not self._event.is_set():
            return False
        from ..observability.metrics import registry

        registry.counter(
            "serving.duplicate_resolution",
            "attempts to complete/fail an already-resolved request "
            "future (must stay 0: nonzero means a retry or shutdown "
            "path double-delivered)").inc()
        return True

    def _complete(self, outputs) -> None:
        if self._resolved_already():
            return
        self.t_complete = time.perf_counter()
        self._outputs = outputs
        self._event.set()

    def _fail(self, error: BaseException) -> None:
        if self._resolved_already():
            return
        self.t_complete = time.perf_counter()
        self._error = error
        self._event.set()


class DecodeRequest(Request):
    """One autoregressive generation request: a token prompt that will
    occupy one KV slot from admission to retirement. The future resolves
    to the generated token ids (``np.int32``, greedy decode, up to
    ``max_new_tokens`` or the engine's EOS). ``n`` is 1 — admission is
    denominated in slots for the decode tier."""

    __slots__ = ("prompt", "max_new_tokens", "generated", "sent", "row",
                 "slot", "seq_rung", "cursor", "pages", "window_pages",
                 "window_from", "temperature", "top_k",
                 "top_p", "seed", "speculate", "spec_live", "spec_proposed",
                 "spec_accepted", "t_first_token")

    def __init__(self, tenant: str, prompt, max_new_tokens: int,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0,
                 speculate: bool = False):
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("decode request needs a non-empty prompt")
        super().__init__(tenant, [prompt], 1, seq=int(prompt.size))
        self.prompt = prompt
        self.max_new_tokens = max(int(max_new_tokens), 1)
        self.generated: List[int] = []
        # tokens SENT FOR: program calls dispatched that emit a token of
        # this request. The scheduler reads a call's tokens one beat late,
        # so ``len(generated) <= sent <= len(generated) + 1``; while they
        # differ the newest token is still on the device, in row ``row``
        # of the unread call's output. The write position and the PRNG
        # key index count ``sent``, not what the host has read.
        self.sent = 0
        self.row = 0
        # host time (perf_counter) at which the first entry of
        # ``generated`` reached the host (the prefill beat's stamp):
        # t_enqueue <= t_dispatch <= t_first_token <= t_complete.
        self.t_first_token: Optional[float] = None
        self.slot = None          # KV slot, assigned at admission-to-slot
        self.seq_rung = None      # prefill seq-ladder rung (scheduler set)
        self.cursor = 0           # prompt tokens already prefilled (chunked programs)
        self.pages: List[int] = []  # block table (paged pools only)
        # a second table over the same logical columns, for window layers
        # (two page lifetimes): columns ``< window_from`` were released and
        # read 0, the pad page
        self.window_pages: List[int] = []
        self.window_from = 0
        # sampling knobs ride the programs as traced DATA (never a
        # retrace); temperature 0 = greedy, the bit-exact audit mode
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        # self-speculative decoding lane policy (ISSUE 20): ``speculate``
        # is the per-request opt-in; ``spec_live`` drops to False when
        # the rolling acceptance (accepted/proposed) falls below
        # FLAGS_serving_spec_min_accept — drafts for this lane are
        # wasted work, the scheduler stops speculating once every
        # opted-in lane has disabled. The committed stream is identical
        # either way (only the tokens-per-full-pass chunking changes).
        self.speculate = bool(speculate)
        self.spec_live = bool(speculate)
        self.spec_proposed = 0
        self.spec_accepted = 0

    @property
    def position(self) -> int:
        """The next KV write position: prompt rows 0..len-1 land at
        prefill; generated token ``i`` (the input of decode step ``i+1``)
        writes at ``len + i``. Counted in tokens sent for: the newest may
        not have reached the host yet."""
        return int(self.prompt.size) + max(self.sent - 1, 0)


class AdmissionController:
    """Admission gates, all in samples: a global queued-sample cap
    (protects the scheduler's latency promise — a deeper queue than the
    executor can clear inside the SLO is better refused than served late),
    a per-tenant in-flight cap (one chatty tenant cannot starve the
    rest), and a PRIORITY gate: tenants marked ``bulk`` (:meth:`set_tier`)
    may only fill ``FLAGS_serving_bulk_queue_share`` of the global cap, so
    interactive tenants always find headroom at the door — bulk work is
    preempted at admission, not mid-execution. In-flight = admitted and
    not yet completed, so quota releases only at completion, covering
    execution occupancy too.

    The controller also owns the request TTL
    (``FLAGS_serving_request_ttl_ms`` / ``request_ttl_ms``): the queue
    expires requests whose wait exceeds it (:class:`AdmissionError`
    reason ``"ttl"``, ``serving.expired`` counter) instead of executing
    dead work whose client has long timed out."""

    #: named priority tiers (lower = more urgent); ints also accepted
    TIERS = {"interactive": 0, "bulk": 1}

    def __init__(self, max_queue: Optional[int] = None,
                 tenant_quota: Optional[int] = None,
                 request_ttl_ms: Optional[float] = None,
                 breaker_board=None):
        from ..base.flags import get_flag

        self.max_queue = int(get_flag("serving_max_queue")
                             if max_queue is None else max_queue)
        self.tenant_quota = int(get_flag("serving_tenant_quota")
                                if tenant_quota is None else tenant_quota)
        # None defers to the flag at expiry time (live-tunable)
        self._ttl_ms = request_ttl_ms
        # per-tenant circuit breakers (reliability.BreakerBoard): a
        # tenant whose batches keep failing is shed HERE, at the door,
        # instead of queueing work a broken path will fail late
        self.breaker_board = breaker_board
        self._tiers: Dict[str, int] = {}
        self._queued = 0
        self._inflight: Dict[str, int] = {}
        # own lock: try_admit runs on client threads (under the queue's
        # condition), on_complete on the scheduler thread (no queue lock) —
        # the read-modify-writes of _inflight must serialize regardless of
        # which outer lock the caller holds
        self._lock = named_lock("serving.admission")

    # ------------------------------------------------------------ tiers
    def set_tier(self, tenant: str, tier) -> None:
        """Pin ``tenant`` to a priority tier: ``"interactive"`` (0, the
        default) or ``"bulk"`` (1) — or any int, lower = more urgent."""
        with self._lock:
            self._tiers[tenant] = (self.TIERS[tier] if isinstance(tier, str)
                                   else int(tier))

    def tier_of(self, tenant: str) -> int:
        with self._lock:
            return self._tiers.get(tenant, 0)

    def ttl_s(self) -> float:
        """The live request TTL in seconds (<=0 disables)."""
        ms = self._ttl_ms
        if ms is None:
            from ..base.flags import get_flag

            ms = float(get_flag("serving_request_ttl_ms"))
        return float(ms) / 1e3

    def try_admit(self, tenant: str, n: int) -> Optional[str]:
        """None = admitted (state charged); else the refusing gate."""
        # consulted OUTSIDE self._lock: the board has its own lock and an
        # open breaker's cooldown probe must not serialize admissions
        if self.breaker_board is not None and self.breaker_board.is_open(tenant):
            return "circuit"
        with self._lock:
            if self.max_queue > 0 and self._queued + n > self.max_queue:
                return "queue"
            if self._tiers.get(tenant, 0) > 0 and self.max_queue > 0:
                from ..base.flags import get_flag

                cap = int(self.max_queue
                          * float(get_flag("serving_bulk_queue_share")))
                if self._queued + n > cap:
                    return "priority"
            if (self.tenant_quota > 0
                    and self._inflight.get(tenant, 0) + n > self.tenant_quota):
                return "tenant"
            self._queued += n
            self._inflight[tenant] = self._inflight.get(tenant, 0) + n
            return None

    def on_dispatch(self, tenant: str, n: int) -> None:
        with self._lock:
            self._queued -= n

    def on_complete(self, tenant: str, n: int) -> None:
        with self._lock:
            left = self._inflight.get(tenant, 0) - n
            if left > 0:
                self._inflight[tenant] = left
            else:
                self._inflight.pop(tenant, None)

    def inflight(self, tenant: str) -> int:
        with self._lock:
            return self._inflight.get(tenant, 0)


class RequestQueue:
    """FIFO of admitted requests + the condition variable the scheduler
    sleeps on. ``close()`` stops new submits; the scheduler keeps taking
    until the queue is drained (graceful shutdown serves everything that
    was admitted)."""

    def __init__(self, admission: Optional[AdmissionController] = None,
                 stats=None):
        self._dq: deque = deque()
        self._cond = named_condition("serving.queue")
        self.admission = admission or AdmissionController()
        self.closed = False
        if stats is None:
            from ..profiler.pipeline import serving_stats as stats
        self.stats = stats

    def __len__(self) -> int:
        with self._cond:
            return len(self._dq)

    def depth_samples(self) -> int:
        with self._cond:
            return sum(r.n for r in self._dq)

    def submit(self, request: Request) -> Request:
        """Admit + enqueue, or raise :class:`AdmissionError` /
        ``RuntimeError`` (closed). Stamps ``t_admit`` on success."""
        with self._cond:
            if self.closed:
                raise RuntimeError("serving queue is closed")
            gate = self.admission.try_admit(request.tenant, request.n)
            if gate is not None:
                self.stats.record_rejected(tenant=request.tenant)
                refusal = (
                    f"request of {request.n} samples refused by the "
                    f"'{gate}' gate (tenant={request.tenant!r}: "
                    f"{self.admission.inflight(request.tenant)} in flight, "
                    f"queue={self.admission._queued} samples)")
            else:
                request.t_admit = time.perf_counter()
                self._dq.append(request)
                self._cond.notify()
        if gate is not None:
            from ..observability.anomaly import monitor

            # rejection-burst watcher, fed OUTSIDE the condition lock: a
            # triggered verdict writes a forensic bundle, and that disk
            # I/O must never stall other tenants' submits or take_batch
            if monitor.enabled:
                monitor.on_rejected(request.tenant)
            raise AdmissionError(gate, refusal)
        return request

    def _expire_locked(self, now: float) -> None:
        """Fail every request whose queue wait exceeded the TTL (caller
        holds the condition). Requests enqueue in arrival order, so the
        overdue set is always a prefix of the deque — dead work leaves
        BEFORE batch assembly instead of occupying a program call whose
        client already timed out."""
        ttl = self.admission.ttl_s()
        if ttl <= 0:
            return
        expired = []
        while self._dq and (now - self._dq[0].t_enqueue) > ttl:
            r = self._dq.popleft()
            self.admission.on_dispatch(r.tenant, r.n)
            self.admission.on_complete(r.tenant, r.n)
            expired.append(r)
        if not expired:
            return
        from ..observability.metrics import registry

        counter = registry.counter(
            "serving.expired",
            "requests expired in queue past FLAGS_serving_request_ttl_ms "
            "(failed with AdmissionError reason='ttl', never executed)")
        for r in expired:
            wait_ms = (now - r.t_enqueue) * 1e3
            counter.inc(tenant=r.tenant)
            if hasattr(self.stats, "record_expired"):
                self.stats.record_expired(tenant=r.tenant)
            r._fail(AdmissionError(
                "ttl", f"request {r.id} expired after {wait_ms:.1f}ms in "
                       f"queue (> FLAGS_serving_request_ttl_ms = "
                       f"{self.admission.ttl_s() * 1e3:.1f}ms); dead work "
                       "is refused, not executed"))

    def take_slots(self, max_requests: int,
                   timeout: Optional[float] = None,
                   budget_fn=None) -> List[Request]:
        """Decode-scheduler side: pop up to ``max_requests`` pending
        requests in (priority tier, FIFO) order — the slot-admission path
        of the continuous-batching loop. Interactive-tier requests go
        first regardless of queue position (bulk work preempted at
        admission); within a tier FIFO order holds. TTL-overdue requests
        are expired first, never handed out. Returns ``[]`` on
        timeout/closed-empty; with ``timeout`` of 0/None it never blocks
        (the decode loop polls between steps).

        ``budget_fn(request) -> bool`` is the paged pools' admission
        gate: taking STOPS at the first request it refuses (the request
        stays queued, and nothing behind it jumps ahead — a page-budget
        wait must not become a reorder), so a request that merely has to
        wait for a retirement is never shed."""
        if max_requests <= 0:
            return []
        with self._cond:
            self._expire_locked(time.perf_counter())
            if not self._dq and timeout:
                deadline = time.perf_counter() + timeout
                while not self._dq and not self.closed:
                    rest = deadline - time.perf_counter()
                    if rest <= 0:
                        break
                    self._cond.wait(rest)
                self._expire_locked(time.perf_counter())
            if not self._dq:
                return []
            order = sorted(
                range(len(self._dq)),
                key=lambda i: (self.admission.tier_of(self._dq[i].tenant), i))
            chosen = order[:int(max_requests)]
            if budget_fn is not None:
                fits = 0
                for i in chosen:
                    if not budget_fn(self._dq[i]):
                        break
                    fits += 1
                chosen = chosen[:fits]
                if not chosen:
                    return []
            # returned in PRIORITY order (interactive lanes anchor prefill
            # grouping); the survivors keep their FIFO deque order
            taken = [self._dq[i] for i in chosen]
            chosen_set = set(chosen)
            kept = [r for i, r in enumerate(self._dq) if i not in chosen_set]
            self._dq.clear()
            self._dq.extend(kept)
            for r in taken:
                self.admission.on_dispatch(r.tenant, r.n)
            return taken

    def take_batch(self, buckets, max_total: Optional[int] = None,
                   timeout: Optional[float] = None,
                   linger: float = 0.0):
        """Scheduler side: block until requests are pending (or ``timeout``),
        then pop the FIFO prefix :func:`assemble_bucket` selects. Returns
        ``(requests, bucket)`` — or ``([], None)`` on timeout/closed-empty.

        ``buckets`` may be a ladder list or a zero-arg callable returning
        one; callables are resolved AFTER the wait, at assembly time, so a
        predictor re-laddered while the scheduler slept applies to the
        very batch that wakes it. ``max_total`` defaults to the ladder top.

        ``linger`` is the continuous-batching window: once ANY request is
        pending, wait up to that long for the rung to fill before
        dispatching a padded batch (latency spent buying fill)."""
        from ..jit.bucketing import assemble_bucket

        deadline = (time.perf_counter() + timeout) if timeout else None
        with self._cond:
            self._expire_locked(time.perf_counter())
            while not self._dq:
                if self.closed:
                    return [], None
                rest = (deadline - time.perf_counter()) if deadline else None
                if rest is not None and rest <= 0:
                    return [], None
                self._cond.wait(rest if rest is not None else 0.1)
                self._expire_locked(time.perf_counter())
            ladder = list(buckets()) if callable(buckets) else list(buckets)
            cap = (min(int(max_total), int(ladder[-1])) if max_total
                   else int(ladder[-1]))
            if linger > 0 and not self.closed:
                # a rung already full dispatches immediately; otherwise give
                # late arrivals one window to ride the same program call
                linger_until = time.perf_counter() + linger
                while (sum(r.n for r in self._dq) < cap
                       and not self.closed):
                    rest = linger_until - time.perf_counter()
                    if rest <= 0:
                        break
                    self._cond.wait(rest)
                if callable(buckets):  # re-resolve: the linger also slept
                    ladder = list(buckets())
                    cap = (min(int(max_total), int(ladder[-1])) if max_total
                           else int(ladder[-1]))
                self._expire_locked(time.perf_counter())
                if not self._dq:
                    return [], None
            try:
                k, bucket = assemble_bucket([r.n for r in self._dq], ladder,
                                            cap)
            except ValueError as e:
                # oversized head (engine.submit gates this; a live ladder
                # shrink can still race): fail ITS request, keep serving
                bad = self._dq.popleft()
                self.admission.on_dispatch(bad.tenant, bad.n)
                self.admission.on_complete(bad.tenant, bad.n)
                bad._fail(e)
                return [], None
            taken = [self._dq.popleft() for _ in range(k)]
            for r in taken:
                self.admission.on_dispatch(r.tenant, r.n)
            return taken, bucket

    def close(self) -> None:
        with self._cond:
            self.closed = True
            self._cond.notify_all()

    def fail_pending(self, error: BaseException) -> int:
        """Complete every still-queued request with ``error`` (non-drain
        shutdown). Returns how many were failed."""
        with self._cond:
            pending = list(self._dq)
            self._dq.clear()
            for r in pending:
                self.admission.on_dispatch(r.tenant, r.n)
                self.admission.on_complete(r.tenant, r.n)
                r._fail(error)
            return len(pending)
