"""Continuous bucketed batch assembly: requests → padded device batch → rows.

The scheduler is the piece between the queue and the warm-compiled
predictor program: it stacks a FIFO prefix of mixed-size requests along
the batch axis, pads the stack up to the bucket rung
(:func:`jit.bucketing.assemble_bucket` picked), runs ONE program call,
and scatters the output rows back to their requests. Re-batching is
continuous — assembly happens again between every pair of steps, so
requests that arrived while the previous batch computed ride the very
next program call.

Pure functions (:func:`stack_requests`, :func:`scatter_outputs`) do the
array work so they unit-test without threads; :class:`Scheduler` is the
one background thread that loops take → stack → execute → scatter.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..observability.tracing import _NULL_SPAN
from .request_queue import Request, RequestQueue

_TRACK = "serving.scheduler"     # the decode scheduler's beat spans
_REQUESTS = "serving.requests"   # request phases, sharing request=<id>


def stack_requests(requests: Sequence[Request], bucket: int,
                   dynamic_axes: Dict[int, int],
                   n_inputs: int,
                   seq_axes: Optional[Dict[int, int]] = None,
                   seq_bucket: Optional[int] = None) -> List[np.ndarray]:
    """Concatenate each input across requests along its batch axis and
    zero-pad up to ``bucket``. On two-axis exports each request's
    sequence axis (``seq_axes``: {input_idx: axis}) is first right-padded
    up to ``seq_bucket`` so mixed-length requests stack into one (batch,
    seq) rung. Inputs without a dynamic axis (static side inputs of a
    partially dynamic export) are per-BATCH, not per-sample — every
    batched request must carry the same value, verified bit-wise (serving
    request 1's rows with request 0's side input would be a silent
    cross-tenant data leak; a loud batch failure is the contract)."""
    stacked = []
    axes = dynamic_axes or {i: 0 for i in range(n_inputs)}
    seq_axes = seq_axes or {}
    for i in range(n_inputs):
        if i not in axes:
            head = np.asarray(requests[0].inputs[i])
            for r in requests[1:]:
                if not np.array_equal(head, np.asarray(r.inputs[i])):
                    raise ValueError(
                        f"static input {i} differs across the assembled "
                        "batch (per-batch side inputs must match bit-wise "
                        "to share one program call)")
            stacked.append(head)
            continue
        ax = axes[i]
        parts = []
        for r in requests:
            a = np.asarray(r.inputs[i])
            sax = seq_axes.get(i)
            if (sax is not None and seq_bucket is not None
                    and a.shape[sax] < seq_bucket):
                widths = [(0, 0)] * a.ndim
                widths[sax] = (0, seq_bucket - a.shape[sax])
                a = np.pad(a, widths)
            parts.append(a)
        cat = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=ax)
        short = bucket - cat.shape[ax]
        if short > 0:
            widths = [(0, 0)] * cat.ndim
            widths[ax] = (0, short)
            cat = np.pad(cat, widths)
        stacked.append(cat)
    return stacked


def fetch_outputs(outputs: Sequence) -> List[np.ndarray]:
    """ONE device fetch per assembled batch (ROADMAP serving leftover):
    start every output leaf's D2H copy asynchronously first, then gather —
    the transfers overlap on the wire instead of serializing one blocking
    ``np.asarray`` round-trip per leaf. Counted once per call in the
    ``serving.d2h_fetches`` observability counter (vs once per LEAF under
    the old path), which is the proof the batch readback stays batched."""
    from ..observability.metrics import registry

    leaves = list(outputs)
    for leaf in leaves:
        start = getattr(leaf, "copy_to_host_async", None)
        if start is not None:
            start()
    arrays = [np.asarray(leaf) for leaf in leaves]
    registry.counter(
        "serving.d2h_fetches",
        "device→host readback rounds issued by the serving scheduler "
        "(one per assembled batch, NOT one per output leaf)").inc()
    return arrays


def scatter_outputs(outputs: Sequence[np.ndarray],
                    requests: Sequence[Request],
                    seq_bucket: Optional[int] = None,
                    out_seq_axes: Optional[Dict[int, int]] = None
                    ) -> List[List[np.ndarray]]:
    """Split each output's leading axis back into per-request row blocks
    (the padding tail is dropped). Output batch axis is 0 by the serving
    export contract; on two-axis exports the seq pad is sliced back to
    each request's real length (``Request.seq``) on exactly the axes the
    export's out_avals mark symbolic (``out_seq_axes``: {leaf_idx: axis}
    from ``_BatchProgram`` — never a runtime shape guess, so a static
    axis that happens to equal the rung survives untouched)."""
    per_request: List[List[np.ndarray]] = [[] for _ in requests]
    offsets = []
    pos = 0
    for r in requests:
        offsets.append(pos)
        pos += r.n
    for idx, out in enumerate(outputs):
        arr = np.asarray(out)
        ax = (out_seq_axes or {}).get(idx)
        for j, r in enumerate(requests):
            rows = arr[offsets[j]: offsets[j] + r.n]
            if (ax is not None and seq_bucket is not None
                    and r.seq is not None and r.seq < seq_bucket
                    and rows.shape[ax] == seq_bucket):
                rows = np.take(rows, range(r.seq), axis=ax)
            per_request[j].append(rows)
    return per_request


class Scheduler:
    """The serving tier's one executor thread: continuously drains the
    queue into bucketed batches and hands them to ``execute`` (the
    engine's predictor call). Crashes in ``execute`` fail only the batch
    that triggered them — the loop survives and keeps serving.

    ``retry`` (a ``reliability.RetryPolicy``) replays a transiently
    failed program call before the fault wall gives the batch up;
    ``breakers`` (a ``reliability.BreakerBoard``) is fed per-tenant
    success/failure so a tenant whose batches keep dying flips to
    ``degraded`` and sheds at admission."""

    def __init__(self, queue: RequestQueue, execute: Callable,
                 buckets, *, max_batch: Optional[int] = None,
                 linger_s: float = 0.0, on_batch: Optional[Callable] = None,
                 retry=None, breakers=None):
        self.queue = queue
        self.execute = execute           # (requests, bucket) -> None
        # a list, or a zero-arg callable for a LIVE ladder view (the engine
        # passes the batch program's, so a re-laddered predictor takes
        # effect at the very next assembly, no scheduler restart)
        self.buckets = buckets
        self.max_batch = max_batch
        self.linger_s = float(linger_s)
        self.on_batch = on_batch         # (n_samples, bucket, depth) tap
        self.retry = retry
        self.breakers = breakers
        self._thread: Optional[threading.Thread] = None
        self._stopped = threading.Event()

    def _call(self, requests, bucket) -> None:
        if self.retry is not None:
            self.retry.run(self.execute, requests, bucket)
        else:
            self.execute(requests, bucket)

    def _record(self, requests, ok: bool) -> None:
        if self.breakers is None:
            return
        for tenant in {r.tenant for r in requests}:
            (self.breakers.record_success if ok
             else self.breakers.record_failure)(tenant)

    def start(self) -> "Scheduler":
        if self._thread is not None:
            raise RuntimeError("scheduler already started")
        self._thread = threading.Thread(target=self._loop,
                                        name="paddle-serving-scheduler",
                                        daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        from ..observability.anomaly import monitor
        from ..observability.memory import sampler
        from ..observability.tracing import tracer

        while True:
            # buckets/max pass through RAW: take_batch resolves a callable
            # ladder at assembly time, after its wait — no stale snapshot
            requests, bucket = self.queue.take_batch(
                self.buckets, self.max_batch, timeout=0.05,
                linger=self.linger_s)
            if not requests:
                if self.queue.closed and len(self.queue) == 0:
                    break
                continue
            now = time.perf_counter()
            for r in requests:
                r.t_dispatch = now
            n_samples = sum(r.n for r in requests)
            if self.on_batch is not None:
                self.on_batch(n_samples, bucket, self.queue.depth_samples())
            try:
                with tracer.span("serving.batch", track="serving.scheduler",
                                 bucket=bucket, n_samples=n_samples,
                                 n_requests=len(requests)):
                    self._call(requests, bucket)
                self._record(requests, ok=True)
            except BaseException as e:  # noqa: BLE001 — batch-scoped fault wall
                if monitor.enabled:
                    # serving-worker exception hook: capture the forensic
                    # window BEFORE the batch is failed away (the flight
                    # recorder is the only record once result() re-raises)
                    monitor.on_exception("serving.worker", e)
                self._record(requests, ok=False)
                for r in requests:
                    self.queue.admission.on_complete(r.tenant, r.n)
                    r._fail(e)
            # batch-boundary memory telemetry (sync-free by contract)
            sampler.maybe_sample("batch")
        self._stopped.set()

    def alive(self) -> bool:
        """Is the executor thread running? (the /healthz liveness probe)"""
        return self._thread is not None and self._thread.is_alive()

    def join(self, timeout: Optional[float] = None) -> bool:
        """Wait for the loop to exit (after ``queue.close()``)."""
        if self._thread is None:
            return True
        self._thread.join(timeout)
        return not self._thread.is_alive()


class DecodeScheduler:
    """The decode tier's one executor thread: true continuous batching
    over a KV slot pool. Every loop iteration is ONE program call —
    prefill OR decode — and between any two calls requests JOIN (queued →
    freed slot, priority order) and LEAVE (finished → slot released,
    future resolved). No full-batch re-assembly ever happens: running
    sequences keep their device-resident KV rows and simply appear in the
    next step's gathered lane set.

    Step policy: prefill-first. A waiting prompt joins the batch at the
    very next boundary (its compute also emits its first token), then
    decode steps serve every active lane at once. Prefill groups share
    one seq rung (anchored at the OLDEST waiting request, so rung
    grouping never starves FIFO order across rungs) and are capped at
    ``prefill_max_batch`` lanes.

    Crashes in a program call fail only the lanes that rode it — their
    slots release, the loop survives and keeps serving."""

    def __init__(self, queue: RequestQueue, programs, pool, *,
                 prefill_max_batch: int, eos_id: Optional[int] = None,
                 stats=None, on_step: Optional[Callable] = None,
                 retry=None, breakers=None):
        self.queue = queue
        self.programs = programs
        self.pool = pool
        self.prefill_max_batch = max(int(prefill_max_batch), 1)
        self.eos_id = eos_id
        self.stats = stats
        self.on_step = on_step           # (kind, lanes, rung, emitted) tap
        self.retry = retry               # replays a transient program call
        self.breakers = breakers         # per-tenant degraded accounting
        self._active: Dict[int, object] = {}    # slot -> DecodeRequest
        self._pending: List[object] = []        # slot held, prefill due
        self._step_lanes: List[object] = []     # lanes riding the current call
        self._owe_decode = False  # a prompt's chunk just ran: the lanes decode next
        self._step_extra = None   # a program's outputs beside its tokens, last call
        self.shed_count = 0
        self._beat = 0            # running index of scheduler beats
        self._beat_kind = "idle"  # what this beat ran (set by the step)
        self._trace = None        # the tracer while this beat records, else None
        self._beat_id = None      # this beat's span id (parent of request phases)
        self._thread: Optional[threading.Thread] = None
        self._stopped = threading.Event()

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "DecodeScheduler":
        if self._thread is not None:
            raise RuntimeError("decode scheduler already started")
        self._thread = threading.Thread(target=self._loop,
                                        name="paddle-serving-decode",
                                        daemon=True)
        self._thread.start()
        return self

    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def join(self, timeout: Optional[float] = None) -> bool:
        if self._thread is None:
            return True
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def active_count(self) -> int:
        """Sequences holding a slot right now (active, awaiting prefill,
        or riding the in-flight program call)."""
        seen = {id(r) for r in self._active.values()}
        seen.update(id(r) for r in self._pending)
        seen.update(id(r) for r in self._step_lanes)
        return len(seen)

    # ------------------------------------------------------------ the loop
    def _loop(self) -> None:
        from ..observability.anomaly import monitor
        from ..observability.memory import sampler

        while True:
            stepped = self._admit_and_step(monitor)
            if not stepped:
                if (self.queue.closed and len(self.queue) == 0
                        and not self._active and not self._pending):
                    break
            else:
                # step-boundary memory telemetry (sync-free by contract)
                sampler.maybe_sample("batch")
        self._stopped.set()

    def _admit_and_step(self, monitor) -> bool:
        """One scheduler beat: admit queued requests into free slots,
        then run one prefill-or-decode call. Returns False when fully
        idle (nothing admitted, nothing to step).

        Traced (ONE ``tracer.enabled`` read per beat), a beat is one
        ``serving.beat`` span (``beat`` = running index, ``kind`` =
        prefill/decode/speculate/idle) whose children tile it:
        ``serving.admit`` -> ``serving.build`` -> ``serving.decode``
        (holding ``serving.dispatch`` and ``serving.read``) ->
        ``serving.absorb``."""
        from ..observability.tracing import tracer

        self._beat += 1
        self._beat_kind = "idle"
        if not tracer.enabled:
            self._trace = self._beat_id = None
            self._admit()
            return self._step(monitor)
        self._trace = tracer
        with tracer.span("serving.beat", track=_TRACK, beat=self._beat,
                         kind="idle") as beat:
            self._beat_id = beat.id
            shed = self.shed_count
            with tracer.span("serving.admit", track=_TRACK, taken=0,
                             shed=0) as sp:
                taken = self._admit()
                if sp.id is not None:
                    sp.args.update(taken=taken, shed=self.shed_count - shed)
            stepped = self._step(monitor)
            if beat.id is not None:
                beat.args["kind"] = self._beat_kind
        return stepped

    def _admit(self) -> int:
        """Move queued requests into free slots; returns how many."""
        free = self.pool.free_count()
        if free <= 0:
            return 0
        idle = not self._active and not self._pending
        taken = self.queue.take_slots(free, timeout=0.05 if idle else 0.0)
        now = time.perf_counter()
        for r in taken:
            r.slot = self.pool.alloc()
            r.seq_rung = self._seq_rung(r)
            r.t_dispatch = now
            self._pending.append(r)
        return len(taken)

    def _step(self, monitor) -> bool:
        # prefill first, except right after a CHUNK of a prompt (programs
        # that prefill in pieces): then the decoding lanes get their beat,
        # so a prompt of many chunks never starves them for its length
        if self._pending and not (self._owe_decode and self._active):
            self._guarded(self._prefill_chunk_step
                          if getattr(self.programs, "chunked", False)
                          else self._prefill_step, monitor)
            return True
        if self._active:
            self._owe_decode = False
            self._guarded(self._decode_step, monitor)
            return True
        return False

    def _span(self, name: str, **args):
        """A child span of this beat on the scheduler's track; the shared
        no-op when the beat is not recording."""
        if self._trace is None:
            return _NULL_SPAN
        return self._trace.span(name, track=_TRACK, **args)

    def _step_span(self, kind: str, rung, lanes, **args):
        """The ``serving.decode`` span of one step. Its name and its
        ``kind``/``rung``/``lanes`` arguments are what the benchmark's
        readers filter on; ``requests`` (the lane request ids) is built
        only when recording. The paged scheduler adds ``sampling``."""
        self._beat_kind = kind
        if self._trace is None:
            return _NULL_SPAN
        return self._span("serving.decode", kind=kind, rung=rung,
                          lanes=len(lanes), requests=[r.id for r in lanes],
                          **args)

    def _call_and_read(self, program: str, call):
        """One program call, its pool commit and the host read of its
        tokens: ``serving.dispatch`` lasts until the call returns (the
        device may still be running), ``serving.read`` is the wait for
        the tokens."""
        with self._span("serving.dispatch", program=program):
            out = self._program_call(call)
        held = len(self.pool.arrays())
        self.pool.commit(*out[:held])
        toks, *more = out[held:]
        with self._span("serving.read", program=program):
            if not more:
                return np.asarray(toks)
            # what the program says of its step beside the tokens (the
            # latent family's pair counts), in the same read
            for a in more:
                a.copy_to_host_async()
            toks = np.asarray(toks)
            self._step_extra = [np.asarray(a) for a in more]
            return toks

    def _absorb_traced(self, lanes, absorb, *args, **kwargs) -> None:
        """Run one of the absorb methods under ``serving.absorb``."""
        with self._span("serving.absorb", retired=0) as sp:
            absorb(lanes, *args, **kwargs)
            if sp.id is not None:
                sp.args["retired"] = sum(1 for r in lanes if r.done())

    def _seq_rung(self, r) -> int:
        from ..jit.bucketing import bucket_for

        ladder = self.programs.seq_ladder
        # a prompt past the top rung is one that chunked programs cut
        return bucket_for(min(int(r.prompt.size), ladder[-1]), ladder)

    def _guarded(self, step, monitor) -> None:
        """Batch-scoped fault wall: a crashed program call fails exactly
        the lanes it carried (``_step_lanes``, set by the step before its
        program call) and frees their slots; pending prefills and active
        lanes that did NOT ride the call keep serving. Transient program
        faults are absorbed by the retry policy INSIDE the step (around
        the program call only — admission/absorb bookkeeping never
        replays); only a give-up reaches this wall."""
        try:
            step()
        except BaseException as e:  # noqa: BLE001 — batch-scoped fault wall
            if monitor.enabled:
                monitor.on_exception("serving.decode_worker", e)
            involved, self._step_lanes = self._step_lanes, []
            if self.breakers is not None:
                for tenant in {r.tenant for r in involved}:
                    self.breakers.record_failure(tenant)
            for r in involved:
                self._free_lane(r)
                self.queue.admission.on_complete(r.tenant, r.n)
                r._fail(e)
                self._trace_failed(r, type(e).__name__)

    # ------------------------------------------------- stamps and phases
    def _first_token(self, r, now: float) -> None:
        """The request's first token reached the host at ``now`` (always
        stamped: it is what a TTFT histogram reads). Traced, the request's
        queue and prefill phases are emitted here — so a request still
        decoding when a trace window closes has them."""
        r.t_first_token = now
        if self._trace is not None:
            self._trace_queue(r)
            self._trace.emit(
                "serving.request.prefill", r.t_dispatch, now - r.t_dispatch,
                track=_REQUESTS, parent=self._beat_id, request=r.id,
                prompt=int(r.prompt.size), seq_rung=r.seq_rung)

    def _trace_queue(self, r) -> None:
        self._trace.emit("serving.request.queue", r.t_enqueue,
                         r.t_dispatch - r.t_enqueue, track=_REQUESTS,
                         parent=self._beat_id, request=r.id, tenant=r.tenant)

    def _trace_failed(self, r, reason: str) -> None:
        """A shed or failed request closes with ``serving.request.failed``
        from the last phase boundary it reached (its queue phase first,
        if it never saw a token): a reader of first-token times learns
        from it how many requests its population lacks, and why."""
        if self._trace is None:
            return
        if r.t_first_token is None and r.t_dispatch is not None:
            self._trace_queue(r)
        t0 = r.t_first_token or r.t_dispatch or r.t_enqueue
        self._trace.emit("serving.request.failed", t0, r.t_complete - t0,
                         track=_REQUESTS, parent=self._beat_id,
                         request=r.id, reason=reason)

    def _free_lane(self, r) -> None:
        """Detach one request from its KV residency — the single cleanup
        path the fault wall and retirement share (slot pools release the
        slot; paged pools release the block table's pages)."""
        if r.slot is not None:
            self._active.pop(r.slot, None)
            self.pool.release(r.slot)
            r.slot = None

    def _program_call(self, fn):
        """One prefill/decode program call through the fault point and
        (when armed) the retry policy — the only part of a step that is
        safe to replay: it reads pool/request state and returns fresh
        buffers, mutating nothing until ``commit``/``_absorb``.

        EXCEPT under buffer donation (accelerators donate the KV pool
        args so XLA aliases in place): a failed-after-dispatch attempt
        may already have invalidated ``pool.k``/``pool.v``, and a replay
        would read deleted arrays — worse, the pool would stay poisoned
        for every later step. Donating programs therefore skip retry and
        fail straight to the fault wall (lanes fail, slots release, the
        pool keeps its last committed buffers)."""
        from ..reliability.faults import fault_point

        def attempt():
            fault_point("serving.decode_step")
            return fn()

        donates = bool(getattr(self.programs, "_donate", ()))
        if self.retry is not None and not donates:
            return self.retry.run(attempt)
        return attempt()

    # ------------------------------------------------------------- steps
    def _prefill_step(self) -> None:
        from ..jit.bucketing import bucket_for

        with self._span("serving.build", lanes=0, rung=None) as sp:
            rung = self._pending[0].seq_rung  # oldest request anchors the rung
            group = [r for r in self._pending
                     if r.seq_rung == rung][: self.prefill_max_batch]
            for r in group:
                self._pending.remove(r)
            self._step_lanes = list(group)  # the fault wall's blast radius
            b_rung = bucket_for(len(group), self.programs.prefill_batch_rungs)
            pad = self.pool.pad_slot
            tokens = np.zeros((b_rung, rung), np.int32)
            lengths = np.ones(b_rung, np.int32)
            slots = np.full(b_rung, pad, np.int32)
            for i, r in enumerate(group):
                L = int(r.prompt.size)
                tokens[i, :L] = r.prompt
                lengths[i] = L
                slots[i] = r.slot
            if sp.id is not None:
                sp.args.update(lanes=len(group), rung=(b_rung, rung))
        t0 = time.perf_counter()
        with self._step_span("prefill", (b_rung, rung), group):
            toks = self._call_and_read("prefill", lambda: self.programs.prefill(
                *self.pool.arrays(), tokens, lengths, slots))
        self._absorb_traced(group, self._absorb, toks, kind="prefill",
                            seconds=time.perf_counter() - t0,
                            rung=(b_rung, rung))

    def _prefill_chunk_step(self) -> None:
        """One chunk of the OLDEST pending request's prompt, for programs
        that prefill in pieces (``programs.chunked``: a recurrent state, or
        the pages before the cursor, carry what came before). The request
        keeps a cursor; a whole chunk takes the ladder's top rung, the
        ragged last one the smallest rung that holds it; the last one
        yields the first token. What the program is told of the lane is
        the residency's (:meth:`_chunk_args`: a state lane and whether it
        is fresh, or a block table, grown to hold the chunk first).
        Between two chunks the decoding lanes get their beat
        (:meth:`_step`)."""
        from ..jit.bucketing import bucket_for
        from ..observability.metrics import registry

        with self._span("serving.build", lanes=0, rung=None) as sp:
            r = self._pending.pop(0)  # back at the head if chunks remain
            ladder = self.programs.seq_ladder
            size, top = int(r.prompt.size), ladder[-1]
            left = size - r.cursor
            rung = top if left >= top else bucket_for(left, ladder)
            n = min(left, rung)
            last = r.cursor + n >= size
            self._step_lanes = [r]  # the fault wall's blast radius
            tokens = np.zeros((1, rung), np.int32)
            tokens[0, :n] = r.prompt[r.cursor:r.cursor + n]
            args = self._chunk_args(r, n)
            if args is None:
                # the chunk waits (for pages): the decoding lanes go first
                self._step_lanes = []
                self._owe_decode = True
                return
            if sp.id is not None:
                sp.args.update(lanes=1, rung=(1, rung))
        t0 = time.perf_counter()
        with self._step_span("prefill", (1, rung), [r], chunk=r.cursor // top,
                             chunks=-(-size // top), tokens=n) as sp:
            toks = self._call_and_read("prefill", lambda: self.programs.prefill(
                *self.pool.arrays(), tokens, np.asarray([n], np.int32), *args))
            self._note_extra(sp, n)
        r.cursor += n
        self._owe_decode = True
        registry.counter(
            "serving.prefill_chunks",
            "prefill chunks run by the decode scheduler (a prompt longer "
            "than the chunk rung takes several beats)").inc()
        if last:
            self._absorb_traced([r], self._absorb, toks, kind="prefill",
                                seconds=time.perf_counter() - t0,
                                rung=(1, rung))
        else:
            self._step_lanes = []  # the call succeeded: nothing to fail
            self._pending.insert(0, r)
            with self._span("serving.absorb", retired=0):
                if self.stats is not None:
                    self.stats.record_decode_step(
                        "prefill", time.perf_counter() - t0, 1, 0)

    def _chunk_args(self, r, n: int):
        """What a chunk's program call takes after the tokens and their
        count, for ``n`` tokens at the request's cursor: here the state
        lane, the cursor, and whether the lane is fresh (the first chunk
        tells the program to ignore the lane's old state: joining a lane
        is zeroing it). None would mean that the chunk cannot run yet."""
        return (np.asarray([r.slot], np.int32),
                np.asarray([r.cursor], np.int32),
                np.asarray([r.cursor == 0], np.int32))

    def _note_extra(self, sp, tokens: int) -> None:
        """Hand what the last program call returned beside its tokens to
        the programs (``note_step``: counters, and what the step's span
        ``sp`` says of it). Nothing for programs that return tokens only."""
        extra, self._step_extra = self._step_extra, None
        if extra:
            said = self.programs.note_step(*extra, tokens=tokens)
            if sp.id is not None:
                sp.args.update(said)

    def _decode_step(self) -> None:
        from ..jit.bucketing import bucket_for

        with self._span("serving.build", lanes=0, rung=None) as sp:
            lanes = sorted(self._active.values(), key=lambda r: r.id)
            self._step_lanes = list(lanes)  # the fault wall's blast radius
            b_rung = bucket_for(len(lanes), self.programs.decode_rungs)
            pad = self.pool.pad_slot
            tokens = np.zeros(b_rung, np.int32)
            slots = np.full(b_rung, pad, np.int32)
            positions = np.zeros(b_rung, np.int32)
            for i, r in enumerate(lanes):
                tokens[i] = r.generated[-1]
                slots[i] = r.slot
                positions[i] = r.position
            if sp.id is not None:
                sp.args.update(lanes=len(lanes), rung=b_rung)
        t0 = time.perf_counter()
        with self._step_span("decode", b_rung, lanes):
            toks = self._call_and_read("decode", lambda: self.programs.decode(
                *self.pool.arrays(), tokens, slots, positions))
        self._absorb_traced(lanes, self._absorb, toks, kind="decode",
                            seconds=time.perf_counter() - t0, rung=b_rung)

    def _absorb(self, lanes, toks, *, kind: str, seconds: float,
                rung) -> None:
        """Scatter one step's emitted tokens back to their requests,
        retire finished sequences (slot released, future resolved), keep
        the rest active for the next step."""
        self._step_lanes = []  # the call succeeded: nothing to fail
        now = time.perf_counter()  # the first-token stamp of new lanes
        if self.breakers is not None:
            for tenant in {r.tenant for r in lanes}:
                self.breakers.record_success(tenant)
        for i, r in enumerate(lanes):
            tok = int(toks[i])
            r.generated.append(tok)
            if r.t_first_token is None:
                self._first_token(r, now)
            self.pool.lengths[r.slot] = r.position
            done = (len(r.generated) >= r.max_new_tokens
                    or (self.eos_id is not None and tok == self.eos_id)
                    or r.position >= self.pool.max_seq)
            if done:
                self._retire(r)
            else:
                self._active[r.slot] = r
        if self.stats is not None:
            self.stats.record_decode_step(kind, seconds, len(lanes),
                                          len(lanes))
            self.stats.record_slot_occupancy(self.pool.in_use(),
                                             self.pool.max_slots)
        if self.on_step is not None:
            self.on_step(kind, len(lanes), rung, len(lanes))

    def _retire(self, r) -> None:
        from ..observability.anomaly import monitor

        self._free_lane(r)
        self.queue.admission.on_complete(r.tenant, r.n)
        r._complete(np.asarray(r.generated, np.int32))
        if self.stats is not None:
            self.stats.record_request(r.t_enqueue, r.t_admit, r.t_dispatch,
                                      r.t_complete, r.n, tenant=r.tenant)
        if monitor.enabled:
            monitor.on_serving_request(
                r.t_complete - r.t_enqueue, r.t_dispatch - r.t_admit,
                tenant=r.tenant)

class PagedDecodeScheduler(DecodeScheduler):
    """The decode loop over a :class:`~.kv_cache.KVPagePool`.

    Same one-program-call-per-beat shape as the slot scheduler; what
    changes is the residency model:

    - admission is gated on LANES (the batch ladder's width) and on the
      page budget — a taken request allocates ``ceil(prompt/page_size)``
      pages up front, and an allocation failure (pool pressure or an
      injected ``kv.page_alloc`` fault) sheds exactly that request with
      ``AdmissionError(reason="kv_pages")``: its pages release, every
      other lane keeps serving.
    - before each decode step, lanes crossing a page boundary grow
      their block table by one page (:meth:`_ensure_pages`) through the
      same fault site and the same single-request shed path.
    - the program call carries the batch's block tables as ONE traced
      int32 array padded to the (batch × table) rung — page maps are
      data, so churn never retraces — plus the per-lane sampling
      arguments (temperature/top-k/top-p/PRNG key pair).
    - retirement releases the request's pages; the pool's utilization
      watermark (JX334) samples live tokens against in-use pages each
      step.
    """

    def __init__(self, queue: RequestQueue, programs, pool, *,
                 max_lanes: int, prefill_max_batch: int,
                 eos_id: Optional[int] = None, stats=None,
                 on_step: Optional[Callable] = None, retry=None,
                 breakers=None, speculate_k: int = 0,
                 spec_min_accept: Optional[float] = None):
        from ..base.flags import get_flag

        super().__init__(queue, programs, pool,
                         prefill_max_batch=prefill_max_batch,
                         eos_id=eos_id, stats=stats, on_step=on_step,
                         retry=retry, breakers=breakers)
        self.max_lanes = max(int(max_lanes), 1)
        self.max_seq = int(programs.max_seq)
        # self-speculation lane policy (ISSUE 20): a beat runs one
        # draft+verify round instead of one decode step whenever the
        # master toggle is on AND any lane still speculates — opted-out
        # lanes ride the round anyway (their committed tokens come from
        # the same full-model verify pass, so their stream is identical;
        # only the chunking differs)
        self.speculate_k = max(int(speculate_k), 0)
        self.spec_min_accept = float(
            get_flag("serving_spec_min_accept")
            if spec_min_accept is None else spec_min_accept)
        self.spec_enabled = self.speculate_k > 0
        # _active is keyed by request id here (no slot identity exists)
        self._starved = set()  # lane ids waiting on a page (gate admission)

    # ---------------------------------------------------------- admission
    def _admit(self) -> int:
        free = self.max_lanes - self.active_count()
        # starved active lanes get first claim on freed pages: admitting
        # new prompts while a running lane waits for growth would steal
        # its pages and starve it forever
        if free <= 0 or self.pool.free_count() <= 0 or self._starved:
            return 0
        idle = not self._active and not self._pending
        # page-budget admission gate: a request is taken only when
        # its PROMPT pages fit the free list right now — one that
        # merely has to wait for a retirement stays queued (FIFO,
        # never shed); growth past the prompt is overcommitted by
        # design and sheds only on true mid-flight exhaustion
        # programs that prefill in pieces take their pages chunk by chunk
        # (`_chunk_args`); what the pending prompts still lack is theirs
        # already, so that a prompt admitted now finds its pages later
        chunked = getattr(self.programs, "chunked", False)
        owed = sum(max(-(-int(r.prompt.size) // self.pool.page_size)
                       - len(r.pages), 0) for r in self._pending)
        budget = [self.pool.free_count() - owed]

        def fits(r):
            need = -(-int(r.prompt.size) // self.pool.page_size)
            if need > budget[0]:
                return False
            budget[0] -= need
            return True

        taken = self.queue.take_slots(
            free, timeout=0.05 if idle else 0.0, budget_fn=fits)
        now = time.perf_counter()
        for r in taken:
            r.seq_rung = self._seq_rung(r)
            r.t_dispatch = now
            need = 0 if chunked else -(-int(r.prompt.size) // self.pool.page_size)
            try:
                r.pages = self.pool.alloc(need) if need else []
            except Exception as e:  # noqa: BLE001 — shed, don't crash
                self._shed(r, e)
                continue
            self._pending.append(r)
        return len(taken)

    def _chunk_args(self, r, n: int):
        """The lane's block table, grown first to hold the chunk's ``n``
        tokens at the cursor, the cursor, and the sampling arguments. An
        injected ``kv.page_alloc`` fault sheds the request; natural
        pressure leaves it at the head of the pending list and lets the
        decoding lanes run (a retirement frees pages); with no lane able
        to step none can come, and the request is shed."""
        from ..reliability.faults import FaultInjection

        need = -(-(r.cursor + n) // self.pool.page_size)
        try:
            if need > len(r.pages):
                r.pages.extend(self.pool.alloc(need - len(r.pages)))
        except FaultInjection as e:
            self._shed(r, e)
            return None
        except Exception as e:  # noqa: BLE001 — natural pressure: wait
            if len(self._starved) < len(self._active):
                self._pending.insert(0, r)
            else:   # no lane can step, so no retirement will come
                self._shed(r, e)
            return None
        tables = np.zeros((1, self.programs.table_rungs[-1]), np.int32)
        tables[0, :len(r.pages)] = r.pages
        return (tables, np.asarray([r.cursor], np.int32),
                *self._sample_args([r], 1))

    def _shed(self, r, cause) -> None:
        """Page-allocation failure sheds ONE request: its pages return
        to the pool (no leak — the JX333 audit stays clean), its future
        fails with ``AdmissionError(reason="kv_pages")``, and every
        other lane keeps decoding."""
        from .request_queue import AdmissionError

        self._free_lane(r)
        self.queue.admission.on_complete(r.tenant, r.n)
        if self.breakers is not None:
            self.breakers.record_failure(r.tenant)
        self.shed_count += 1
        try:
            from ..observability.metrics import registry

            registry.counter(
                "serving.kv_page_shed",
                "decode requests shed because a KV page allocation "
                "failed (pool pressure or injected kv.page_alloc "
                "fault)").inc()
        except Exception:
            pass
        r._fail(AdmissionError(
            "kv_pages",
            f"request {r.id} shed: KV page allocation failed ({cause})"))
        self._trace_failed(r, "kv_pages")

    def _free_lane(self, r) -> None:
        self._active.pop(r.id, None)
        if r.pages:
            self.pool.release(r.pages)
            r.pages = []

    def _ensure_pages(self, lanes, lookahead: int = 0):
        """Grow each lane's block table to cover its next write position
        (plus ``lookahead`` speculative positions — a draft+verify round
        writes up to k positions past the committed one, and those rows
        must land in lane-owned pages; the uncommitted suffix rolls back
        via the free-list after acceptance). The lookahead is capped at
        the last legal position — overflow writes spill to the pad page
        inside the bounded programs, never into a live page.
        Returns the lanes ready to step. An INJECTED ``kv.page_alloc``
        fault sheds its lane (the chaos contract: prove the shed path).
        Natural exhaustion is gentler: the starved lane simply sits out
        this step — it keeps its pages and retries next beat, by which
        time a retirement has usually freed some. Only when EVERY active
        lane is starved (no retirement can ever come) does the deadlock
        breaker shed the youngest starved lane, freeing its pages for
        the older ones — guaranteed progress, FIFO-fair."""
        from ..reliability.faults import FaultInjection

        ready, starved = [], []
        for r in lanes:
            last = min(int(r.position) + lookahead, self.max_seq - 1)
            need = last // self.pool.page_size + 1
            try:
                while len(r.pages) < need:
                    r.pages.extend(self.pool.alloc(1))
            except FaultInjection as e:
                self._shed(r, e)
                continue
            except Exception:  # noqa: BLE001 — natural pressure: wait
                starved.append(r)
                continue
            ready.append(r)
        if not ready and starved and not self._pending:
            victim = max(starved, key=lambda r: r.id)
            starved.remove(victim)
            self._shed(victim, RuntimeError(
                "page pool deadlocked: every active lane needs a page "
                "and none can retire"))
        self._starved = {r.id for r in starved}
        return ready

    # -------------------------------------------------------------- steps
    def _sample_args(self, lanes, b_rung: int):
        """The per-lane sampling arguments of one program call. The PRNG
        key is ``[request_seed, generated_token_index]`` — a pure
        function of the request, never of batch composition, so sampled
        streams are deterministic per seed under any join/leave order.
        Pad lanes carry temperature 0: a call whose every lane does runs
        no vocabulary sort, and a call with one sampling lane runs it
        for every lane, pads included (``_choose_tokens``)."""
        temps = np.zeros(b_rung, np.float32)
        top_ks = np.zeros(b_rung, np.int32)
        top_ps = np.ones(b_rung, np.float32)
        rkeys = np.zeros((b_rung, 2), np.uint32)
        for i, r in enumerate(lanes):
            temps[i] = r.temperature
            top_ks[i] = r.top_k
            top_ps[i] = r.top_p
            rkeys[i] = (np.uint32(r.seed & 0xFFFFFFFF),
                        np.uint32(len(r.generated)))
        return temps, top_ks, top_ps, rkeys

    def _step_span(self, kind: str, rung, lanes, **args):
        """The base class's span plus ``sampling``, the lanes of the
        step with ``temperature > 0``. If there is one, each program
        call of the step (a speculation round makes two) sorts the
        vocabulary in every lane, and the stats count those calls. A
        step of kind ``decode`` or ``speculate`` also passes
        ``pages_live`` and ``pages_table`` (:meth:`_step_inputs`)."""
        sampling = sum(1 for r in lanes if r.temperature > 0)
        if sampling and self.stats is not None:
            self.stats.record_sample_sort(2 if kind == "speculate" else 1)
        return super()._step_span(kind, rung, lanes, sampling=sampling,
                                  **args)

    def _prefill_step(self) -> None:
        from ..jit.bucketing import bucket_for

        with self._span("serving.build", lanes=0, rung=None) as sp:
            rung = self._pending[0].seq_rung  # oldest request anchors the rung
            group = [r for r in self._pending
                     if r.seq_rung == rung][: self.prefill_max_batch]
            for r in group:
                self._pending.remove(r)
            self._step_lanes = list(group)  # the fault wall's blast radius
            b_rung = bucket_for(len(group), self.programs.prefill_batch_rungs)
            t_cols = self.programs._prefill_table_cols(rung)
            tokens = np.zeros((b_rung, rung), np.int32)
            lengths = np.ones(b_rung, np.int32)
            tables = np.zeros((b_rung, t_cols), np.int32)  # 0 = pad page
            for i, r in enumerate(group):
                L = int(r.prompt.size)
                tokens[i, :L] = r.prompt
                lengths[i] = L
                tables[i, :len(r.pages)] = r.pages
            if sp.id is not None:
                sp.args.update(lanes=len(group), rung=(b_rung, rung))
        t0 = time.perf_counter()
        with self._step_span("prefill", (b_rung, rung), group):
            toks = self._call_and_read("prefill", lambda: self.programs.prefill(
                self.pool.k, self.pool.v, tokens, lengths, tables,
                *self._sample_args(group, b_rung)))
        self._absorb_traced(group, self._absorb, toks, kind="prefill",
                            seconds=time.perf_counter() - t0,
                            rung=(b_rung, rung))

    def _step_inputs(self, lookahead: int = 0):
        """The lanes ready to step and their program inputs — (lanes,
        rung, tokens, tables, positions, pages), or None when every
        active lane sits this beat out. Under ``serving.build``. The
        sampling arguments are not built here: a plain step assembles
        them inside its ``serving.decode`` span and a speculation round
        just before it, where each always did, so the span that
        ``decode_step_ms`` reads keeps measuring what it measured.

        ``pages`` is what the step's span says of its block table:
        ``pages_table`` entries (batch rung x table rung), ``pages_live``
        of them naming a page that holds a column its lane may see
        (through ``lookahead`` positions past the write position). The
        dense gathered view costs the table's bytes; the paged-attention
        kernel reads the live pages (and the pad page once a padded
        lane). The stats keep both sums."""
        from ..jit.bucketing import bucket_for

        with self._span("serving.build", lanes=0, rung=None) as sp:
            lanes = sorted(self._active.values(), key=lambda r: r.id)
            lanes = self._ensure_pages(lanes, lookahead=lookahead)
            if not lanes:
                return None
            self._step_lanes = list(lanes)  # the fault wall's blast radius
            b_rung = bucket_for(len(lanes), self.programs.decode_rungs)
            t_rung = bucket_for(max(len(r.pages) for r in lanes),
                                self.programs.table_rungs)
            tokens = np.zeros(b_rung, np.int32)
            tables = np.zeros((b_rung, t_rung), np.int32)  # 0 = pad page
            positions = np.zeros(b_rung, np.int32)
            for i, r in enumerate(lanes):
                tokens[i] = r.generated[-1]
                tables[i, :len(r.pages)] = r.pages
                positions[i] = r.position
            last = np.minimum(positions[:len(lanes)] + lookahead,
                              self.max_seq - 1)
            pages = {"pages_live": int((last // self.pool.page_size + 1).sum()),
                     "pages_table": b_rung * t_rung}
            if self.stats is not None:
                self.stats.record_pages(**pages)
            if sp.id is not None:
                sp.args.update(lanes=len(lanes), rung=(b_rung, t_rung))
        return lanes, (b_rung, t_rung), tokens, tables, positions, pages

    def _decode_step(self) -> None:
        if (self.speculate_k > 0 and self.spec_enabled
                and any(r.spec_live for r in self._active.values())):
            self._spec_round()
            return
        built = self._step_inputs()
        if built is None:
            return
        lanes, rung, tokens, tables, positions, pages = built
        t0 = time.perf_counter()
        with self._step_span("decode", rung, lanes, **pages) as sp:
            toks = self._call_and_read("decode", lambda: self.programs.decode(
                *self.pool.arrays(), tokens, tables, positions,
                *self._sample_args(lanes, rung[0])))
            self._note_extra(sp, len(lanes))
        self._absorb_traced(lanes, self._absorb, toks, kind="decode",
                            seconds=time.perf_counter() - t0, rung=rung)

    def _spec_round(self) -> None:
        """One self-speculation round (ISSUE 20): ONE draft dispatch
        proposes k tokens per lane through the truncated-layer program,
        ONE verify dispatch scores all k+1 positions with the full
        model, then the host commits each lane's longest accepted prefix
        plus the verify pass's own next token — ≥ 1 token per round,
        up to k+1, always bitwise the tokens the plain decode loop
        would have produced. Pages grown for the speculative suffix
        roll back through the pool free-list in ``_absorb_spec``."""
        k = self.speculate_k
        built = self._step_inputs(lookahead=k)
        if built is None:
            return
        lanes, rung, tokens, tables, positions, pages = built
        sample = self._sample_args(lanes, rung[0])
        with self._step_span("speculate", rung, lanes, k=k, **pages):
            t0 = time.perf_counter()
            # [b_rung, k] proposals
            drafts = self._call_and_read("draft", lambda: self.programs.draft(
                self.pool.k, self.pool.v, tokens, tables, positions,
                *sample))
            t_draft = time.perf_counter() - t0
            vin = np.zeros((len(tokens), k + 1), np.int32)
            vin[:, 0] = tokens                # last committed token at p
            vin[:, 1:] = drafts               # proposals at p+1..p+k
            t1 = time.perf_counter()
            # [b_rung, k+1] true tokens
            vtoks = self._call_and_read("verify", lambda: self.programs.verify(
                self.pool.k, self.pool.v, vin, tables, positions, *sample))
            t_verify = time.perf_counter() - t1
        self._absorb_traced(lanes, self._absorb_spec, drafts, vtoks,
                            t_draft=t_draft, t_verify=t_verify, rung=rung)

    def _absorb_spec(self, lanes, drafts, vtoks, *, t_draft: float,
                     t_verify: float, rung) -> None:
        """Acceptance + commit + rollback for one speculation round.
        Lane i's accepted prefix length m is the longest run of draft
        proposals the verify pass reproduced; verify tokens 0..m commit
        (the tokens the plain loop would emit, in order, under the same
        per-index sampling keys), stopping early at eos/max_new/max_seq
        exactly like ``_absorb``. Block-table pages past the new write
        position — grown for the speculative suffix — release back to
        the free-list: the rollback contract."""
        self._step_lanes = []  # the calls succeeded: nothing to fail
        if self.breakers is not None:
            for tenant in {r.tenant for r in lanes}:
                self.breakers.record_success(tenant)
        k = self.speculate_k
        proposed = accepted = committed = 0
        for i, r in enumerate(lanes):
            m = 0
            while m < k and int(drafts[i, m]) == int(vtoks[i, m]):
                m += 1
            r.spec_proposed += k
            r.spec_accepted += m
            proposed += k
            accepted += m
            done = False
            for j in range(m + 1):
                tok = int(vtoks[i, j])
                r.generated.append(tok)
                committed += 1
                done = (len(r.generated) >= r.max_new_tokens
                        or (self.eos_id is not None and tok == self.eos_id)
                        or r.position >= self.max_seq)
                if done:
                    break
            # rolling-acceptance lane policy: once a request has seen a
            # fair window (two full rounds' worth of proposals) and its
            # acceptance rate sits under the floor, drafting for it costs
            # more than it saves — the lane opts itself out; the batch
            # falls back to plain decode when every lane has
            if (r.spec_live and r.spec_proposed >= 2 * k
                    and r.spec_accepted
                    < self.spec_min_accept * r.spec_proposed):
                r.spec_live = False
            if done:
                self._retire(r)
            else:
                keep = int(r.position) // self.pool.page_size + 1
                if len(r.pages) > keep:  # speculative-suffix rollback
                    self.pool.release(r.pages[keep:])
                    del r.pages[keep:]
                self._active[r.id] = r
        live_tokens = sum(int(r.prompt.size) + len(r.generated)
                          for r in self._active.values())
        self.pool.note_utilization(live_tokens)
        if self.stats is not None:
            self.stats.record_decode_step("draft", t_draft, len(lanes), 0)
            self.stats.record_decode_step("verify", t_verify, len(lanes),
                                          committed)
            self.stats.record_spec_round(proposed, accepted, committed)
            self.stats.record_slot_occupancy(self.active_count(),
                                             self.max_lanes)
        if self.on_step is not None:
            self.on_step("speculate", len(lanes), rung, committed)

    def _absorb(self, lanes, toks, *, kind: str, seconds: float,
                rung) -> None:
        self._step_lanes = []  # the call succeeded: nothing to fail
        now = time.perf_counter()  # the first-token stamp of new lanes
        if self.breakers is not None:
            for tenant in {r.tenant for r in lanes}:
                self.breakers.record_success(tenant)
        for i, r in enumerate(lanes):
            tok = int(toks[i])
            r.generated.append(tok)
            if r.t_first_token is None:
                self._first_token(r, now)
            done = (len(r.generated) >= r.max_new_tokens
                    or (self.eos_id is not None and tok == self.eos_id)
                    or r.position >= self.max_seq)
            if done:
                self._retire(r)
            else:
                self._active[r.id] = r
        live_tokens = sum(int(r.prompt.size) + len(r.generated)
                          for r in self._active.values())
        self.pool.note_utilization(live_tokens)
        if self.stats is not None:
            self.stats.record_decode_step(kind, seconds, len(lanes),
                                          len(lanes))
            self.stats.record_slot_occupancy(self.active_count(),
                                             self.max_lanes)
        if self.on_step is not None:
            self.on_step(kind, len(lanes), rung, len(lanes))
