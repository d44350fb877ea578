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

import functools
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..observability.tracing import _NULL_SPAN
from .request_queue import Request, RequestQueue

_TRACK = "serving.scheduler"     # the decode scheduler's beat spans
_CALLS = "serving.calls"         # one span a program call, dispatch to read
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


class _Call:
    """One program call of the decode loop, from the beat that builds and
    dispatches it to the beat after, which reads its tokens: the lanes
    that ride it (row ``i`` of its token output is ``lanes[i]``'s), the
    call itself (``fn``, run under the fault point), and what its
    ``serving.decode`` span says of it. Dispatched, it holds its outputs,
    still on the device (``toks``, and ``extra``: what the program says of
    its step beside the tokens).

    Its stamps are always taken (``perf_counter``): ``t_dispatch`` just
    before the program call, ``t_enqueued`` when it returned (the device
    may still be running), ``t_read0`` and ``t_read`` around the wait for
    its tokens on the host. ``seq`` counts the calls this scheduler has
    enqueued on the device, in the order the device runs them, and
    ``overlapped`` says that the next call went out before this one was
    read. ``sent_by`` is the id of the ``serving.dispatch`` span that
    sent it, the parent of its ``serving.call`` span; None for a call
    dispatched with the tracer off."""

    __slots__ = ("kind", "rung", "lanes", "fn", "emits", "rows", "carried",
                 "says", "beat", "toks", "extra", "seq", "overlapped",
                 "sent_by", "t_dispatch", "t_enqueued", "t_read0", "t_read")

    def __init__(self, kind: str, rung, lanes, fn, *, emits: bool = True,
                 rows: Optional[int] = None, carried: int = 0, **says):
        self.kind = kind          # the program: prefill, decode, draft, verify
        self.rung = rung
        self.lanes = lanes
        self.fn = fn
        self.emits = emits        # False: a chunk that is not its prompt's last
        self.rows = len(lanes) if rows is None else rows  # cache rows it writes a layer
        self.carried = carried    # lanes whose input token is still on the device
        self.says = says          # the step span's arguments beside kind/rung/lanes
        self.beat = None
        self.toks = None
        self.extra = ()
        self.seq = self.sent_by = None
        self.overlapped = False
        self.t_dispatch = self.t_enqueued = self.t_read0 = self.t_read = None


class DecodeScheduler:
    """The decode tier's one executor thread: true continuous batching
    over a KV slot pool. Every loop iteration (a *beat*) dispatches ONE
    program call — prefill OR decode — and between any two calls requests
    JOIN (queued → freed slot, priority order) and LEAVE (finished → slot
    released, future resolved). No full-batch re-assembly ever happens:
    running sequences keep their device-resident KV rows and simply appear
    in the next step's gathered lane set.

    **A call's tokens are read one beat late.** A beat builds call N from
    what the host knows plus what is still on the device, dispatches it,
    and only then reads call N-1's tokens and absorbs them: the device
    finished N-1 while the host built N, and N is queued behind it, so
    the chip goes from call to call without waiting out the host's round
    trip. One call is in flight at most (``_flight``). What that takes:

    - a lane that rode N-1 and rides N takes its input token from N-1's
      output on the device (``programs.carry``: the host says which row);
    - the host counts ahead of what it has read (``DecodeRequest.sent``:
      write positions and PRNG key indices count tokens *sent for*). A
      lane whose token in flight is its last by length is not put into
      call N; it retires when that token is absorbed, a beat later;
    - ``eos`` is learned late: a lane whose token from N-1 is ``eos_id``
      has ridden N. It retires at N-1's absorb; its row of N is dropped at
      N's absorb and never emitted. What N wrote for it lies in its own
      pages (or slot, or state lane) past its last visible position, and
      the device runs calls in order, so the next owner's prefill
      overwrites it (a state lane's next owner starts ``fresh``).

    A beat reads with nothing dispatched behind the read (a *flush*) on
    three occasions only: there is nothing to dispatch (the last lanes'
    last tokens: the read does not wait for the queue's timeout), the
    loop drains for shutdown, or the next step is a speculation round,
    which compares tokens on the host and so reads its own calls.

    Step policy: prefill-first. A waiting prompt joins the batch at the
    very next boundary (its compute also emits its first token), then
    decode steps serve every active lane at once. Prefill groups share
    one seq rung (anchored at the OLDEST waiting request, so rung
    grouping never starves FIFO order across rungs) and are capped at
    ``prefill_max_batch`` lanes.

    A call that crashes at dispatch fails only the lanes that rode it —
    their slots release, the unread call before it is still absorbed for
    the lanes that survive, the loop keeps serving. An error that
    surfaces at the read fails the lanes of the call read and of the call
    queued behind it."""

    def __init__(self, queue: RequestQueue, programs, pool, *,
                 prefill_max_batch: int, eos_id: Optional[int] = None,
                 stats=None, on_step: Optional[Callable] = None,
                 retry=None, breakers=None):
        self.queue = queue
        self.programs = programs
        self.pool = pool
        self.max_seq = int(getattr(programs, "max_seq", 0) or pool.max_seq)
        self.prefill_max_batch = max(int(prefill_max_batch), 1)
        self.eos_id = eos_id
        self.stats = stats
        self.on_step = on_step           # (kind, lanes, rung, emitted) tap
        self.retry = retry               # replays a transient program call
        self.breakers = breakers         # per-tenant degraded accounting
        self._active: Dict[int, object] = {}    # lanes the next decode call takes
        self._pending: List[object] = []        # slot held, prefill due
        self._flight: Optional[_Call] = None    # dispatched, its tokens unread
        self._step_lanes: List[object] = []     # the fault wall's blast radius
        self._owe_decode = False  # a prompt's chunk just ran: the lanes decode next
        self.shed_count = 0
        self._beat = 0            # running index of scheduler beats
        self._seq = 0             # running index of calls enqueued on the device
        self._sending = False     # inside a call's ``serving.dispatch`` span
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

    def _holding(self) -> List[object]:
        """The unresolved requests that hold a slot (or pages) right now:
        decoding, awaiting prefill, or riding a call not yet absorbed."""
        flight = self._flight
        held = {id(r): r for r in (*self._active.values(), *self._pending,
                                   *self._step_lanes,
                                   *(flight.lanes if flight is not None else ()))}
        return [r for r in held.values() if not r.done()]

    def active_count(self) -> int:
        """Sequences holding a slot right now (:meth:`_holding`)."""
        return len(self._holding())

    # ------------------------------------------------------------ the loop
    def _loop(self) -> None:
        from ..observability.anomaly import monitor
        from ..observability.memory import sampler

        while True:
            stepped = self._admit_and_step(monitor)
            if not stepped:
                if (self.queue.closed and len(self.queue) == 0
                        and not self._active and not self._pending
                        and self._flight is None):
                    break
            else:
                # step-boundary memory telemetry (sync-free by contract)
                sampler.maybe_sample("batch")
        self._stopped.set()

    def _admit_and_step(self, monitor) -> bool:
        """One scheduler beat: admit queued requests into free slots,
        build and dispatch one prefill-or-decode call, then read and
        absorb the call of the beat BEFORE (one call in flight, read one
        beat late; a beat with nothing to dispatch reads at once, and a
        speculation round reads its own calls: the class docstring).
        Returns False when fully idle (nothing admitted, nothing to
        dispatch, nothing in flight).

        Traced (ONE ``tracer.enabled`` read per beat), a beat is one
        ``serving.beat`` span (``beat`` = running index, ``kind`` =
        prefill/decode/speculate/idle) whose children tile it:
        ``serving.admit`` -> ``serving.build`` -> ``serving.decode``
        (holding ``serving.dispatch`` and then ``serving.read``, which
        names the beat whose call it reads: ``of_beat``) ->
        ``serving.absorb`` (of that call). ``kind``, ``rung``, ``lanes``
        and the rest of ``serving.decode`` describe the call dispatched
        in it; a beat that only reads takes the kind of the call it
        reads, with no lanes. A call itself, which lives across two
        beats, is one ``serving.call`` span (:meth:`_read`)."""
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

    def _idle(self) -> bool:
        """Nothing to do but wait for the queue: no lane decoding, no
        prefill due, no call in flight (its read must not wait)."""
        return (not self._active and not self._pending
                and self._flight is None)

    def _admit(self) -> int:
        """Move queued requests into free slots; returns how many."""
        free = self.pool.free_count()
        if free <= 0:
            return 0
        taken = self.queue.take_slots(
            free, timeout=0.05 if self._idle() else 0.0)
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
            step = functools.partial(
                self._run, self._build_chunk
                if getattr(self.programs, "chunked", False)
                else self._build_prefill)
        elif self._active:
            self._owe_decode = False
            step = self._decode_step
        elif self._flight is not None:
            step = self._run   # nothing to dispatch: read what is in flight
        else:
            return False
        self._guarded(step, monitor)
        return True

    def _span(self, name: str, **args):
        """A child span of this beat on the scheduler's track; the shared
        no-op when the beat is not recording."""
        if self._trace is None:
            return _NULL_SPAN
        return self._trace.span(name, track=_TRACK, **args)

    def _dispatch_part(self, name: str):
        """A child of the open ``serving.dispatch`` span, for a piece of a
        dispatch that is the engine's own code (what is left of the span
        is the jitted call's argument handling and the runtime); the
        no-op outside a dispatch, where the same code runs under
        ``serving.build``."""
        if not self._sending:
            return _NULL_SPAN
        return self._span("serving.dispatch." + name)

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

    # ------------------------------------------------- the body of a beat
    def _run(self, build: Optional[Callable] = None) -> None:
        """What a beat does after admission: build call N (``build``;
        none, or one that has to wait, dispatches nothing), dispatch it,
        read call N-1, absorb N-1. The device works on N-1 while the host
        builds and dispatches N, so the read finds its tokens there, and
        N already waits behind it. With nothing to dispatch the read is a
        flush: the device goes idle after it."""
        with self._span("serving.build", lanes=0, rung=None) as sp:
            call = build() if build is not None else None
            if call is not None and sp.id is not None:
                sp.args.update(lanes=len(call.lanes), rung=call.rung)
        prev = self._flight
        if call is None and prev is None:
            return    # the build has to wait, and nothing is in flight
        # a beat that only reads takes the kind of the call it reads
        kind, rung, lanes, says = ((call.kind, call.rung, call.lanes, call.says)
                                   if call is not None else (prev.kind, None, (), {}))
        with self._step_span(kind, rung, lanes, **says) as sp:
            self._dispatch(call)
            if call is not None:
                if sp.id is not None:
                    call.says = sp.args   # as recorded: the read completes it
                self._launch(call)
            if prev is not None:
                prev.overlapped = call is not None
            toks = self._read(prev)
            self._flight = call
        self._absorb_traced(prev, toks)

    def _dispatch(self, call: Optional[_Call]) -> None:
        """``serving.dispatch``: the program call, until it returns (the
        device may still be running); then the pool commit, and the copy
        of its small outputs to the host started, so that the read a beat
        later is a wait for nothing. Its children are the engine's own
        pieces of it: ``serving.dispatch.carry`` (:meth:`_fed`) and, from
        the paged schedulers, ``serving.dispatch.sample_args``."""
        with self._span("serving.dispatch", program=call and call.kind) as sp:
            if call is None:
                return
            call.sent_by = sp.id
            self._sending = self._trace is not None
            call.t_dispatch = time.perf_counter()
            try:
                out = self._program_call(call.fn)
            finally:
                self._sending = False
            call.t_enqueued = time.perf_counter()
        self._seq += 1
        call.seq = self._seq
        held = len(self.pool.arrays())
        self.pool.commit(*out[:held])
        call.toks, *call.extra = out[held:]
        call.beat = self._beat
        for a in out[held:]:
            a.copy_to_host_async()

    def _launch(self, call: _Call) -> None:
        """The host's count of a call just dispatched: each of its lanes
        has one more token sent for, in row ``i`` of the call's output,
        and decodes on unless that token is its last by length (then it
        only waits to be absorbed). A chunk that is not its prompt's last
        sends for nothing; the request returns to the head of the pending
        list. While the call before is read, the fault wall covers both:
        an error surfacing there takes this call, queued behind it, too."""
        prev = self._flight
        self._step_lanes = call.lanes if prev is None else prev.lanes + call.lanes
        if not call.emits:
            self._pending[:0] = call.lanes
            return
        for i, r in enumerate(call.lanes):
            r.sent += 1
            r.row = i
            if self._last_by_length(r):
                self._active.pop(self._lane_key(r), None)
            else:
                self._active[self._lane_key(r)] = r

    def _read(self, call: Optional[_Call]):
        """``serving.read``: call ``call``'s tokens on the host (a wait for
        nothing when the call after it went out first), and what the
        program says of its step beside them, which completes that call's
        own ``serving.decode`` span (the tracer keeps a span's arguments
        by reference) and the programs' counters.

        Traced, the call then gets its own span, ``serving.call`` on the
        track ``serving.calls``, from its dispatch to its tokens on the
        host, under the ``serving.dispatch`` span that sent it. One span
        says all a reader needs of the call: ``seq`` (the device runs
        calls in that order), ``beat`` and ``read_beat``, ``kind``,
        ``rung``, ``lanes``, ``overlapped``, ``program`` (the
        executable's name as a device trace prints it) and ``executions``
        (program executions it enqueued: 2 when the token carry ran in
        front), ``enqueue_ms`` and ``read_wait_ms``, and what its step's
        span says of it after this read. A call dispatched before the
        tracer came on has its span too, with no parent: its stamps are
        always there, and a capture's first execution on the device is
        that call's. A call the fault wall took never gets here and has
        none."""
        with self._span("serving.read", program=call and call.kind,
                        of_beat=call and call.beat):
            if call is None:
                return None
            call.t_read0 = time.perf_counter()
            toks = np.asarray(call.toks)
            call.t_read = time.perf_counter()
            extra = [np.asarray(a) for a in call.extra]
        call.toks, call.extra = None, ()
        if extra:
            call.says.update(self.programs.note_step(*extra, tokens=call.rows))
        if self._trace is not None:
            from .decode import executable_name

            self._trace.emit(
                "serving.call", call.t_dispatch, call.t_read - call.t_dispatch,
                track=_CALLS, parent=call.sent_by,
                **{**call.says, "seq": call.seq, "beat": call.beat,
                   "read_beat": self._beat, "kind": call.kind,
                   "rung": call.rung, "lanes": len(call.lanes),
                   "overlapped": call.overlapped,
                   "program": executable_name(self.programs, call.kind),
                   "executions": 2 if call.carried else 1,
                   "enqueue_ms": 1e3 * (call.t_enqueued - call.t_dispatch),
                   "read_wait_ms": 1e3 * (call.t_read - call.t_read0)})
        return toks

    def _fed(self, tokens, carried: int):
        """A decode call's token argument: the host's array, or with
        ``carried`` lanes' tokens still on the device the programs' carry
        of it and the unread call's output. Called inside the program
        call, so inside ``serving.dispatch`` and the fault point."""
        if not carried:
            return tokens
        with self._dispatch_part("carry"):
            return self.programs.carry(self._flight.toks, tokens)

    def _token_of(self, r) -> int:
        """What a decode call is told of a lane's input token: the token,
        where the host has read it; else ``-1 - row``, its row in the
        unread call's output (``programs.carry`` resolves it)."""
        return r.generated[-1] if len(r.generated) == r.sent else -1 - r.row

    def _last_by_length(self, r) -> bool:
        """Whether the newest token sent for is the request's last
        whatever it turns out to be: by ``max_new_tokens``, or by the
        sequence's capacity."""
        return r.sent >= r.max_new_tokens or r.position >= self.max_seq

    def _absorb_traced(self, call: Optional[_Call], toks) -> None:
        """``serving.absorb`` of the call just read (none: an empty span,
        the beat's children still tile it)."""
        with self._span("serving.absorb", retired=0) as sp:
            if call is None:
                return
            retired = self._absorb(call, toks)
            if sp.id is not None:
                sp.args["retired"] = retired

    def _seq_rung(self, r) -> int:
        from ..jit.bucketing import bucket_for

        ladder = self.programs.seq_ladder
        # a prompt past the top rung is one that chunked programs cut
        return bucket_for(min(int(r.prompt.size), ladder[-1]), ladder)

    def _guarded(self, step, monitor) -> None:
        """Batch-scoped fault wall: a crashed program call fails exactly
        the lanes it carried (``_step_lanes``, set by the step before its
        program call) and frees their slots; pending prefills and active
        lanes that did NOT ride the call keep serving, and the call in
        flight before it stays in flight: the next beat absorbs it for
        the lanes that survive. Once the call is dispatched and the call
        before is being read, ``_step_lanes`` holds both calls' lanes: an
        error that surfaces at the read fails them all. Transient program
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
                for tenant in {r.tenant for r in involved if not r.done()}:
                    self.breakers.record_failure(tenant)
            for r in involved:
                if r.done():   # retired or shed since, or listed by both calls
                    continue
                if r in self._pending:
                    self._pending.remove(r)
                self._free_lane(r)
                self.queue.admission.on_complete(r.tenant, r.n)
                r._fail(e)
                self._trace_failed(r, type(e).__name__)
            flight = self._flight
            if flight is not None and all(r.done() for r in flight.lanes):
                self._flight = None   # nobody is left to read it for

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

    def _lane_key(self, r):
        """A lane's key in ``_active``: its slot."""
        return r.slot

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
        buffers, mutating nothing until ``commit``/``_launch``.

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
    def _decode_step(self) -> None:
        self._run(self._build_decode)

    def _prefill_group(self):
        """The pending requests of the next prefill call, taken off the
        pending list: those of the OLDEST one's seq rung, up to the cap.
        Returns (group, batch rung, seq rung)."""
        from ..jit.bucketing import bucket_for

        rung = self._pending[0].seq_rung  # oldest request anchors the rung
        group = [r for r in self._pending
                 if r.seq_rung == rung][: self.prefill_max_batch]
        for r in group:
            self._pending.remove(r)
        self._step_lanes = list(group)  # the fault wall's blast radius
        return group, bucket_for(len(group),
                                 self.programs.prefill_batch_rungs), rung

    def _build_prefill(self) -> _Call:
        group, b_rung, rung = self._prefill_group()
        pad = self.pool.pad_slot
        tokens = np.zeros((b_rung, rung), np.int32)
        lengths = np.ones(b_rung, np.int32)
        slots = np.full(b_rung, pad, np.int32)
        for i, r in enumerate(group):
            L = int(r.prompt.size)
            tokens[i, :L] = r.prompt
            lengths[i] = L
            slots[i] = r.slot
        return _Call("prefill", (b_rung, rung), group,
                     lambda: self.programs.prefill(
                         *self.pool.arrays(), tokens, lengths, slots))

    def _build_chunk(self) -> Optional[_Call]:
        """One chunk of the OLDEST pending request's prompt, for programs
        that prefill in pieces (``programs.chunked``: a recurrent state, or
        the pages before the cursor, carry what came before). The request
        keeps a cursor; a whole chunk takes the ladder's top rung, the
        ragged last one the smallest rung that holds it; the last one
        yields the first token (a call before it emits nothing: its lane
        returns to the head of the pending list). What the program is told
        of the lane is the residency's (:meth:`_chunk_args`: a state lane
        and whether it is fresh, or a block table, grown to hold the chunk
        first). Between two chunks the decoding lanes get their beat
        (:meth:`_step`)."""
        from ..jit.bucketing import bucket_for
        from ..observability.metrics import registry

        r = self._pending.pop(0)  # back at the head if chunks remain
        ladder = self.programs.seq_ladder
        size, top = int(r.prompt.size), ladder[-1]
        left = size - r.cursor
        rung = top if left >= top else bucket_for(left, ladder)
        n = min(left, rung)
        self._step_lanes = [r]  # the fault wall's blast radius
        self._owe_decode = True
        tokens = np.zeros((1, rung), np.int32)
        tokens[0, :n] = r.prompt[r.cursor:r.cursor + n]
        says = self._chunk_says(r, n, rung)
        args = self._chunk_args(r, n)
        if args is None:
            # the chunk waits (for pages): the decoding lanes go first
            self._step_lanes = []
            return None
        call = _Call("prefill", (1, rung), [r],
                     lambda: self.programs.prefill(
                         *self.pool.arrays(), tokens, np.asarray([n], np.int32),
                         *args),
                     emits=r.cursor + n >= size, rows=n,
                     chunk=r.cursor // top, chunks=-(-size // top), tokens=n,
                     **says)
        r.cursor += n
        registry.counter(
            "serving.prefill_chunks",
            "prefill chunks run by the decode scheduler (a prompt longer "
            "than the chunk rung takes several beats)").inc()
        return call

    def _chunk_says(self, r, n: int, rung: int) -> dict:
        """What a chunk's span says of its residency beside ``chunk``,
        ``chunks`` and ``tokens`` (``n`` of them on the ``rung`` program):
        nothing here."""
        return {}

    def _chunk_args(self, r, n: int):
        """What a chunk's program call takes after the tokens and their
        count, for ``n`` tokens at the request's cursor: here the state
        lane, the cursor, and whether the lane is fresh (the first chunk
        tells the program to ignore the lane's old state: joining a lane
        is zeroing it). None would mean that the chunk cannot run yet."""
        return (np.asarray([r.slot], np.int32),
                np.asarray([r.cursor], np.int32),
                np.asarray([r.cursor == 0], np.int32))

    def _build_decode(self) -> _Call:
        from ..jit.bucketing import bucket_for

        lanes = sorted(self._active.values(), key=lambda r: r.id)
        self._step_lanes = list(lanes)  # the fault wall's blast radius
        b_rung = bucket_for(len(lanes), self.programs.decode_rungs)
        pad = self.pool.pad_slot
        tokens = np.zeros(b_rung, np.int32)
        slots = np.full(b_rung, pad, np.int32)
        positions = np.zeros(b_rung, np.int32)
        for i, r in enumerate(lanes):
            tokens[i] = self._token_of(r)
            slots[i] = r.slot
            positions[i] = r.position
        carried = int((tokens < 0).sum())
        return _Call("decode", b_rung, lanes,
                     lambda: self.programs.decode(
                         *self.pool.arrays(), self._fed(tokens, carried),
                         slots, positions),
                     carried=carried)

    def _absorb(self, call: _Call, toks) -> int:
        """Scatter one call's emitted tokens back to their requests, one
        beat after it was dispatched: retire finished sequences (slot
        released, future resolved), the rest decode on (they may already
        ride the call after). A lane resolved since — retired at its eos a
        beat ago, shed, or failed with a later call — emits nothing.
        The stats take the call's own times, dispatch to tokens on the
        host, under its own kind. Returns how many retired."""
        self._step_lanes = []  # both calls got through: nothing to fail
        now = call.t_read  # the first-token stamp of new lanes
        lanes = [(i, r) for i, r in enumerate(call.lanes) if not r.done()]
        if self.breakers is not None:
            for tenant in {r.tenant for _, r in lanes}:
                self.breakers.record_success(tenant)
        emitted = retired = 0
        for i, r in (lanes if call.emits else ()):
            tok = int(toks[i])
            r.generated.append(tok)
            emitted += 1
            if r.t_first_token is None:
                self._first_token(r, now)
            # by length only once nothing more is sent for: known a beat ago
            if tok == self.eos_id or (len(r.generated) == r.sent
                                      and self._last_by_length(r)):
                self._retire(r)
                retired += 1
        occupancy = self._absorbed(r for _, r in lanes)
        if self.stats is not None:
            # overlapped: the call in flight now went out before this read
            self._record_call(call, emitted, overlapped=call.overlapped,
                              lanes_carried=self._flight.carried
                              if call.overlapped else 0)
            self.stats.record_slot_occupancy(*occupancy)
        if self.on_step is not None:
            self.on_step(call.kind, len(call.lanes), call.rung, emitted)
        return retired

    def _record_call(self, call: _Call, emitted: int, **read) -> None:
        """One read call in the stats, in one take of their lock: its own
        dispatch-to-read time under its own kind, the parts of it in which
        this thread was inside the dispatch and inside the read's wait,
        and (``read``) how it was read."""
        self.stats.record_decode_step(
            call.kind, call.t_read - call.t_dispatch, len(call.lanes),
            emitted, t_end=call.t_read,
            dispatch_s=call.t_enqueued - call.t_dispatch,
            read_wait_s=call.t_read - call.t_read0, **read)

    def _absorbed(self, lanes):
        """The residency's bookkeeping after an absorb of ``lanes``: the
        slot pool's per-slot row counts, as far as the host has read.
        Returns the occupancy, (in use, capacity)."""
        for r in lanes:
            if r.slot is not None:
                self.pool.lengths[r.slot] = (int(r.prompt.size)
                                             + max(len(r.generated) - 1, 0))
        return self.pool.in_use(), self.pool.max_slots

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

    Same beat as the slot scheduler (one program call dispatched, the one
    before read and absorbed); what changes is the residency model:

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
      arguments (temperature/top-k/top-p/PRNG key pair). The token is
      chosen on the device, so a sampling lane needs nothing from the
      host between steps but its key index, which counts tokens sent for.
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
        # page-budget admission gate: a request is taken only when
        # its PROMPT pages fit the free list right now — one that
        # merely has to wait for a retirement stays queued (FIFO,
        # never shed); growth past the prompt is overcommitted by
        # design and sheds only on true mid-flight exhaustion
        # programs that prefill in pieces take their pages chunk by chunk
        # (`_chunk_args`); what the pending prompts still lack is theirs
        # already, so that a prompt admitted now finds its pages later
        chunked = getattr(self.programs, "chunked", False)
        pending = [(self._page_need(r), self._page_held(r))
                   for r in self._pending]
        # by kind of page (one kind, unless the pool knows two lifetimes)
        budget = [free - sum(max(need[i] - held[i], 0) for need, held in pending)
                  for i, free in enumerate(self._page_free())]

        def fits(r):
            need = self._page_need(r)
            if any(n > b for n, b in zip(need, budget)):
                return False
            for i, n in enumerate(need):
                budget[i] -= n
            return True

        taken = self.queue.take_slots(
            free, timeout=0.05 if self._idle() else 0.0, budget_fn=fits)
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
        decoding lanes run (a retirement frees pages; one may be in
        flight); with no lane able to step none can come, and the request
        is shed."""
        from ..reliability.faults import FaultInjection

        need = -(-(r.cursor + n) // self.pool.page_size)
        try:
            self._grow(r, need)
        except FaultInjection as e:
            self._shed(r, e)
            return None
        except Exception as e:  # noqa: BLE001 — natural pressure: wait
            if (len(self._starved) < len(self._active)
                    or self._flight is not None):
                self._pending.insert(0, r)
            else:   # no lane can step, so no retirement will come
                self._shed(r, e)
            return None
        tables = self._tables([r], 1, self.programs.table_rungs[-1])
        # the call reads its table as built; what no later query sees can go
        self._trim(r, r.cursor + n)
        return (tables, np.asarray([r.cursor], np.int32),
                *self._sample_args([r], 1))

    # ------------------------------------------------- pages, by lifetime
    def _page_need(self, r) -> tuple:
        """The pages the request's prompt takes, by kind of page: one kind
        here, held for the request's life."""
        return (-(-int(r.prompt.size) // self.pool.page_size),)

    def _page_held(self, r) -> tuple:
        return (len(r.pages),)

    def _page_free(self) -> tuple:
        return (self.pool.free_count(),)

    def _columns(self, r) -> int:
        """The logical table columns the lane holds a page for."""
        return len(r.pages)

    def _grow(self, r, need: int) -> None:
        """The lane's table grown to ``need`` columns; raises what the
        pool's ``alloc`` raises, a kind of page then grown whole or not."""
        if need > len(r.pages):
            r.pages.extend(self.pool.alloc(need - len(r.pages)))

    #: whether pages go back before their request retires (:meth:`_trim`)
    _trims = False

    def _trim(self, r, position: int) -> None:
        """Give back what no query at ``position`` or later can see:
        nothing, where every page lives as long as its request."""

    def _tables(self, lanes, rows: int, cols: int):
        """The call's block tables, ``[rows, cols]``, lane ``i`` in row
        ``i``; 0 is the pad page."""
        tables = np.zeros((rows, cols), np.int32)
        for i, r in enumerate(lanes):
            tables[i, :len(r.pages)] = r.pages
        return tables

    def _pages_said(self, positions, rows: int, cols: int) -> dict:
        """What a decode step's span says of its tables: ``pages_table``
        entries (batch rung x table rung), ``pages_live`` of them naming a
        page that holds a column its lane may see (``positions``: each
        lane's last visible one). The stats keep both sums."""
        pages = {"pages_live": int((positions // self.pool.page_size + 1).sum()),
                 "pages_table": rows * cols}
        if self.stats is not None:
            self.stats.record_pages(**pages)
        return pages

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

    def _lane_key(self, r):
        return r.id

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
        lane is starved and no call is in flight (no retirement can ever
        come) does the deadlock breaker shed the youngest starved lane,
        freeing its pages for the older ones — guaranteed progress,
        FIFO-fair."""
        from ..reliability.faults import FaultInjection

        ready, starved = [], []
        for r in lanes:
            last = min(int(r.position) + lookahead, self.max_seq - 1)
            need = last // self.pool.page_size + 1
            if self._trims:
                self._trim(r, int(r.position))
            try:
                while self._columns(r) < need:
                    self._grow(r, self._columns(r) + 1)
            except FaultInjection as e:
                self._shed(r, e)
                continue
            except Exception:  # noqa: BLE001 — natural pressure: wait
                starved.append(r)
                continue
            ready.append(r)
        if (not ready and starved and not self._pending
                and self._flight is None):
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
        The index counts the tokens sent for before this call (the host
        may not have read the newest). Pad lanes carry temperature 0: a
        call whose every lane does runs no vocabulary sort, and a call
        with one sampling lane runs it for every lane, pads included
        (``_choose_tokens``). Built inside a program call it is the
        ``serving.dispatch.sample_args`` part of the dispatch."""
        with self._dispatch_part("sample_args"):
            temps = np.zeros(b_rung, np.float32)
            top_ks = np.zeros(b_rung, np.int32)
            top_ps = np.ones(b_rung, np.float32)
            rkeys = np.zeros((b_rung, 2), np.uint32)
            for i, r in enumerate(lanes):
                temps[i] = r.temperature
                top_ks[i] = r.top_k
                top_ps[i] = r.top_p
                rkeys[i] = (np.uint32(r.seed & 0xFFFFFFFF), np.uint32(r.sent))
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

    def _build_prefill(self) -> _Call:
        group, b_rung, rung = self._prefill_group()
        t_cols = self.programs._prefill_table_cols(rung)
        tokens = np.zeros((b_rung, rung), np.int32)
        lengths = np.ones(b_rung, np.int32)
        tables = np.zeros((b_rung, t_cols), np.int32)  # 0 = pad page
        for i, r in enumerate(group):
            L = int(r.prompt.size)
            tokens[i, :L] = r.prompt
            lengths[i] = L
            tables[i, :len(r.pages)] = r.pages
        return _Call("prefill", (b_rung, rung), group,
                     lambda: self.programs.prefill(
                         self.pool.k, self.pool.v, tokens, lengths, tables,
                         *self._sample_args(group, b_rung)))

    def _step_inputs(self, lookahead: int = 0):
        """The lanes ready to step and their program inputs — (lanes,
        rung, tokens, tables, positions, pages), or None when every
        active lane sits this beat out. Under ``serving.build``. The
        sampling arguments are not built here: a plain step assembles
        them inside its ``serving.decode`` span and a speculation round
        just before it, where each always did, so the span that
        ``decode_step_ms`` reads keeps measuring what it measured.

        ``tokens`` holds ``-1 - row`` for a lane whose newest token is
        still on the device (:meth:`_token_of`).

        ``pages`` is what the step's span says of its block table:
        ``pages_table`` entries (batch rung x table rung), ``pages_live``
        of them naming a page that holds a column its lane may see
        (through ``lookahead`` positions past the write position). The
        dense gathered view costs the table's bytes; the paged-attention
        kernel reads the live pages (and the pad page once a padded
        lane). The stats keep both sums."""
        from ..jit.bucketing import bucket_for

        lanes = sorted(self._active.values(), key=lambda r: r.id)
        lanes = self._ensure_pages(lanes, lookahead=lookahead)
        if not lanes:
            return None
        self._step_lanes = list(lanes)  # the fault wall's blast radius
        b_rung = bucket_for(len(lanes), self.programs.decode_rungs)
        t_rung = bucket_for(max(len(r.pages) for r in lanes),
                            self.programs.table_rungs)
        tokens = np.zeros(b_rung, np.int32)
        tables = self._tables(lanes, b_rung, t_rung)
        positions = np.zeros(b_rung, np.int32)
        for i, r in enumerate(lanes):
            tokens[i] = self._token_of(r)
            positions[i] = r.position
        last = np.minimum(positions[:len(lanes)] + lookahead,
                          self.max_seq - 1)
        pages = self._pages_said(last, b_rung, t_rung)
        return lanes, (b_rung, t_rung), tokens, tables, positions, pages

    def _build_decode(self) -> Optional[_Call]:
        built = self._step_inputs()
        if built is None:
            return None
        lanes, rung, tokens, tables, positions, pages = built
        carried = int((tokens < 0).sum())
        return _Call("decode", rung, lanes,
                     lambda: self.programs.decode(
                         *self.pool.arrays(), self._fed(tokens, carried),
                         tables, positions,
                         *self._sample_args(lanes, rung[0])),
                     carried=carried, **pages)

    def _decode_step(self) -> None:
        if (self.speculate_k > 0 and self.spec_enabled
                and any(r.spec_live for r in self._active.values())):
            # a round compares tokens on the host: what is in flight is
            # read and absorbed first, in a beat of its own
            if self._flight is not None:
                self._run()
            else:
                self._spec_round()
            return
        self._run(self._build_decode)

    def _spec_round(self) -> None:
        """One self-speculation round (ISSUE 20): ONE draft dispatch
        proposes k tokens per lane through the truncated-layer program,
        ONE verify dispatch scores all k+1 positions with the full
        model, then the host commits each lane's longest accepted prefix
        plus the verify pass's own next token — ≥ 1 token per round,
        up to k+1, always bitwise the tokens the plain decode loop
        would have produced. Pages grown for the speculative suffix
        roll back through the pool free-list in ``_absorb_spec``.

        The host compares drafts with verified tokens, so this is the one
        step that reads its own calls, each right after its dispatch;
        nothing is in flight when it starts (:meth:`_decode_step`) or
        ends. The stats count the round as one flushed read."""
        k = self.speculate_k
        with self._span("serving.build", lanes=0, rung=None) as sp:
            built = self._step_inputs(lookahead=k)
            if built is None:
                return
            lanes, rung, tokens, tables, positions, pages = built
            if sp.id is not None:
                sp.args.update(lanes=len(lanes), rung=rung)
        sample = self._sample_args(lanes, rung[0])
        with self._step_span("speculate", rung, lanes, k=k, **pages):
            draft = _Call("draft", rung, lanes, lambda: self.programs.draft(
                self.pool.k, self.pool.v, tokens, tables, positions, *sample))
            self._dispatch(draft)
            drafts = self._read(draft)        # [b_rung, k] proposals
            vin = np.zeros((len(tokens), k + 1), np.int32)
            vin[:, 0] = tokens                # last committed token at p
            vin[:, 1:] = drafts               # proposals at p+1..p+k
            verify = _Call("verify", rung, lanes, lambda: self.programs.verify(
                self.pool.k, self.pool.v, vin, tables, positions, *sample))
            self._dispatch(verify)
            vtoks = self._read(verify)        # [b_rung, k+1] true tokens
        with self._span("serving.absorb", retired=0) as sp:
            retired = self._absorb_spec(draft, verify, drafts, vtoks)
            if sp.id is not None:
                sp.args["retired"] = retired

    def _absorb_spec(self, draft: _Call, verify: _Call, drafts,
                     vtoks) -> int:
        """Acceptance + commit + rollback for one speculation round.
        Lane i's accepted prefix length m is the longest run of draft
        proposals the verify pass reproduced; verify tokens 0..m commit
        (the tokens the plain loop would emit, in order, under the same
        per-index sampling keys), stopping early at eos/max_new/max_seq
        exactly like ``_absorb``. Block-table pages past the new write
        position — grown for the speculative suffix — release back to
        the free-list: the rollback contract. Returns how many retired."""
        self._step_lanes = []  # the calls succeeded: nothing to fail
        lanes = verify.lanes
        if self.breakers is not None:
            for tenant in {r.tenant for r in lanes}:
                self.breakers.record_success(tenant)
        k = self.speculate_k
        proposed = accepted = committed = retired = 0
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
                r.sent += 1       # read as soon as sent for: nothing in flight
                committed += 1
                done = tok == self.eos_id or self._last_by_length(r)
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
                retired += 1
            else:
                keep = int(r.position) // self.pool.page_size + 1
                if len(r.pages) > keep:  # speculative-suffix rollback
                    self.pool.release(r.pages[keep:])
                    del r.pages[keep:]
                self._active[r.id] = r
        occupancy = self._absorbed(())
        if self.stats is not None:
            self._record_call(draft, 0, overlapped=False)  # one flush a round
            self._record_call(verify, committed)
            self.stats.record_spec_round(proposed, accepted, committed)
            self.stats.record_slot_occupancy(*occupancy)
        if self.on_step is not None:
            self.on_step("speculate", len(lanes), verify.rung, committed)
        return retired

    def _absorbed(self, lanes):
        """The pool's utilization watermark: the rows that the requests
        holding pages have written or sent for, against the pages in use.
        Returns the occupancy in lanes."""
        held = self._holding()
        self.pool.note_utilization(sum(int(r.prompt.size) + r.sent
                                       for r in held))
        return len(held), self.max_lanes


class WindowedDecodeScheduler(PagedDecodeScheduler):
    """The paged decode loop over a :class:`~.kv_cache.WindowedPagePools`:
    a request holds pages of TWO lifetimes. Its global-layer pages
    (``r.pages``) are the paged scheduler's, taken as the sequence grows and
    held until it retires. Its window-layer pages (``r.window_pages``, the
    same logical columns) are taken with them and given back as soon as the
    window has passed them: before a lane's decode step, and right after a
    prefill chunk's table is built (the call reads the table as built, and
    the device runs calls in order, so a page given back now is rewritten
    only by a later call). A released column reads 0, the pad page;
    ``r.window_from`` counts them. Between two program calls a lane holds at
    most ``pool.window_columns`` window pages; a prefill chunk's call holds
    the chunk's own on top of the window behind its first query.

    Admission counts both kinds. A program call takes ONE table argument
    ``[rows, 2, cols]``: ``[:, 0]`` the window table, ``[:, 1]`` the global
    one. A step's span says ``window_pages_live`` beside ``pages_live``."""

    _trims = True

    def _prefill_window_columns(self) -> int:
        """The most window pages a prompt holds at once: the window behind
        a chunk's first query (whole pages) and the chunk's own."""
        chunk = -(-self.programs.seq_ladder[-1] // self.pool.page_size)
        return self.pool.window_columns - 1 + chunk

    def _page_need(self, r) -> tuple:
        (need,) = super()._page_need(r)
        return need, min(need, self._prefill_window_columns())

    def _page_held(self, r) -> tuple:
        return len(r.pages), len(r.window_pages) - r.window_from

    def _page_free(self) -> tuple:
        return self.pool.full.free_count(), self.pool.window.free_count()

    def _columns(self, r) -> int:
        return min(len(r.pages), len(r.window_pages))

    def _grow(self, r, need: int) -> None:
        super()._grow(r, need)
        if need > len(r.window_pages):
            r.window_pages.extend(
                self.pool.window.alloc(need - len(r.window_pages)))

    def _trim(self, r, position: int) -> None:
        stop = min(self.pool.first_live_column(position), len(r.window_pages))
        if stop <= r.window_from:
            return
        from ..observability.metrics import registry

        self.pool.window.release(r.window_pages[r.window_from:stop])
        r.window_pages[r.window_from:stop] = [0] * (stop - r.window_from)
        registry.counter(
            "serving.kv_window_pages_released",
            "window-layer pages given back before their request retired: "
            "the window had passed every row of them").inc(stop - r.window_from)
        r.window_from = stop

    def _free_lane(self, r) -> None:
        if len(r.window_pages) > r.window_from:
            self.pool.window.release(r.window_pages[r.window_from:])
        r.window_pages, r.window_from = [], 0
        super()._free_lane(r)

    def _tables(self, lanes, rows: int, cols: int):
        tables = np.zeros((rows, 2, cols), np.int32)
        for i, r in enumerate(lanes):
            tables[i, 0, :len(r.window_pages)] = r.window_pages
            tables[i, 1, :len(r.pages)] = r.pages
        return tables

    def _window_live(self, first_query, last_query) -> int:
        """Window pages that hold a row some query in ``first_query ..
        last_query`` sees (one lane's, or arrays of one entry a lane)."""
        ps = self.pool.page_size
        first = np.maximum(first_query - (self.pool.window_rows - 1), 0) // ps
        return int(np.sum(last_query // ps - first + 1))

    def _pages_said(self, positions, rows: int, cols: int) -> dict:
        pages = super()._pages_said(positions, rows, cols)
        pages["window_pages_live"] = self._window_live(positions, positions)
        return pages

    def _chunk_says(self, r, n: int, rung: int) -> dict:
        """Beside the pages that hold a row the chunk's ``n`` tokens see:
        ``attn_columns_live``, the table columns the ``rung`` program's
        attention visits over all its layers (the kernel's grid is cut by
        the same ``chunk_columns``), and ``attn_columns_dense``, what whole
        blocks of top-rung keys would visit (the path off the TPU): their
        ratio is the share of a block path's key work that is real."""
        from ..ops.pallas.paged_attention import chunk_columns

        pool, ps, top = self.pool, self.pool.page_size, self.programs.seq_ladder[-1]
        last = r.cursor + n - 1
        block = r.cursor // top * top
        live = dense = 0
        for layers, window in ((pool.window.num_layers, pool.window_rows),
                               (pool.full.num_layers, None)):
            live += layers * chunk_columns(r.cursor, rung, window, ps)
            dense += layers * chunk_columns(block, top, window, top) * (top // ps)
        return {"pages_live": last // ps + 1,
                "window_pages_live": self._window_live(r.cursor, last),
                "attn_columns_live": live, "attn_columns_dense": dense}
