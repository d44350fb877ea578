"""Program calls tied to the device's executions of them.

Since the engine reads a call's tokens one beat late, a call lives across two
beats and its device work lies mostly inside the NEXT beat's spans, so no span
of a beat says what a call took. The program emits one `serving.call` span a
call read (`paddle_tpu/serving/scheduler.py:_read`): `seq` (a running index of
the calls the scheduler enqueued; the device runs them in that order), `kind`,
`rung`, `lanes`, `program` (the executable's name as `trace_reduce` keeps a
module's) and `executions` (how many program executions the call enqueued: 2
when the token carry ran in front of it), from the call's dispatch to its
tokens on the host. The capture has one event a program execution (`XLA
Modules`, `trace.devices[0].modules`, on the host's clock). `join` walks both
in order:

- a call's executions start no earlier than its dispatch and end no later
  than its read, so executions that start before a call's dispatch belong to
  calls before it. `trace_reduce` moves the device's times as late as
  causality allows, so they are never early and late by a completion
  callback's latency at most: `EARLY` and `LATE` are what the comparison
  allows for that (a fast host dispatches a call 0.25 ms after the execution
  of the call before began, so `EARLY` has to stay well under that);
- of what follows, a call takes the next execution that carries its `program`
  and the `executions - 1` right in front of it (the carry's, whose name no
  call carries); anything else in between is foreign, passed over and counted;
- where another call's program comes first, or the execution ends after the
  read, the call stays unmatched (the log says which, and why) and the walk
  goes on from where it stood.

Calls that straddle the window's edge are matched where the capture holds their
executions, and count towards seconds only, cut to the window; every count and
every per-call statistic is over the calls that lie whole inside it. A call
read in a beat that began before the tracer was switched on has no span (one
dispatched before and read after has, without a parent), so an execution at
the window's edge can be tied to nothing, and a long prefill chunk there is a
few percent of a short window's busy seconds. The busy seconds that a share
divides by are therefore those of the stretch the tied calls cover, from the
first tied execution to the last (`covered`; the log says what of the window
lies outside it), and a share is reported only where the tied seconds are
within `SLACK` of them (`Joined.covers`). A program without `serving.call`, a
ring that dropped events, or a capture without a device gives None, with a log
line, and so does every reader built on this.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from benchmark import harness, program_spans, trace_reduce

EARLY = 5e-5          # seconds an execution may seem to start before its call's dispatch
LATE = 2e-4           # seconds an execution may seem to end after its call's read
ENOUGH = 0.99         # share of the window's calls a reader needs matched
SLACK = 0.02          # of the covered busy seconds, what a share may leave untied


@dataclass
class Call:
    """One `serving.call` span and the device executions tied to it."""
    seq: int
    kind: str
    args: dict
    whole: bool                                   # inside the window, both ends
    runs: list = field(default_factory=list)      # (start, end, name), the program's own last
    inside_s: float = 0.0                         # device seconds of `runs` inside the window

    @property
    def device_s(self) -> float:
        return sum(e - s for s, e, _ in self.runs)

    @property
    def front_s(self) -> float:
        """Of `device_s`, the executions in front of the program's own."""
        return sum(e - s for s, e, _ in self.runs[:-1])


@dataclass
class Joined:
    calls: list           # matched, in order of seq; those with `whole` are the sample
    seen: int             # calls whole inside the window
    matched: int          # of them, tied to their executions
    foreign: int          # executions passed over between two calls
    matched_s: float      # device seconds of matched calls inside the window
    busy_s: float         # the first chip's busy seconds in the stretch they cover
    covered: tuple        # that stretch: (start, end), inside the window

    @property
    def usable(self) -> bool:
        return self.seen > 0 and self.matched >= ENOUGH * self.seen

    @property
    def covers(self) -> bool:
        """Whether the tied seconds are all but `SLACK` of the busy seconds:
        what a share over `busy_s` needs beside `usable`."""
        return self.busy_s > 0 and abs(self.matched_s - self.busy_s) <= SLACK * self.busy_s

    def of_kind(self, kind: str) -> list:
        return [c for c in self.calls if c.whole and c.kind == kind]

    def seconds_inside(self, kind: str) -> float:
        """Device seconds of the calls of `kind`, cut to the window."""
        return sum(c.inside_s for c in self.calls if c.kind == kind)


_dropped = []  # [(trace, events dropped)]: the ring is serialised once for it
_memo = []     # [(trace, Joined or None)]: one capture a process, four readers


def ring_dropped(trace) -> int:
    """Events the program's tracer dropped from its ring since the window
    opened; a reader of per-call or per-beat spans cannot use what is left."""
    if not (_dropped and _dropped[0][0] is trace):
        try:
            from paddle_tpu.observability.tracing import tracer
        except ImportError:
            return 0
        other = tracer.to_chrome_trace().get("otherData") or {}
        _dropped[:] = [(trace, int(other.get("dropped_events", 0)))]
    return _dropped[0][1]


def join(trace):
    """`Joined` for the window, or None (see the module's note)."""
    if _memo and _memo[0][0] is trace:
        return _memo[0][1]
    joined = _join(trace)
    _memo[:] = [(trace, joined)]
    return joined


def _join(trace):
    spans = program_spans.spans(trace, {"serving.call"})
    if not spans:
        harness.log("calls: the program emits no serving.call span; nothing to join")
        return None
    dropped = ring_dropped(trace)
    if dropped:
        harness.log(f"calls: the tracer's ring dropped {dropped} events; not joined")
        return None
    if not trace.devices:
        return None
    spans.sort(key=lambda s: s[5]["seq"])
    modules = trace.devices[0].modules
    programs = {s[5]["program"] for s in spans}
    calls, seen, foreign, j, lost = [], 0, 0, 0, []
    for _, t0, t1, _, _, args in spans:
        call = Call(args["seq"], args["kind"], args,
                    whole=t0 >= trace.t0 and t1 <= trace.t1)
        seen += call.whole
        while j < len(modules) and modules[j][0] < t0 - EARLY:
            j += 1                    # started before this call went out
        own = j
        while (own < len(modules) and modules[own][2] not in programs
               and modules[own][0] <= t1 + LATE):
            own += 1              # the carry's, or a foreign one
        first = own - (args["executions"] - 1)
        why = None
        if own == len(modules) or modules[own][0] > t1 + LATE:
            why = "no execution of a program follows its dispatch before its read"
        elif modules[own][2] != args["program"]:
            why = f"an execution of {modules[own][2]} comes first"
        elif first < j:
            why = f"{own - j} of its {args['executions']} executions are there"
        elif modules[own][1] > t1 + LATE:
            why = f"its execution ends {1e3 * (modules[own][1] - t1):.3f} ms after its read"
        if why:
            if call.whole:
                lost.append(f"seq {call.seq} ({call.kind}): {why}")
            continue
        foreign += first - j
        call.runs = modules[first:own + 1]
        call.inside_s = trace_reduce.total(trace_reduce.clip(call.runs, trace.t0, trace.t1))
        calls.append(call)
        j = own + 1
    ops = trace.devices[0].ops
    window_s = trace_reduce.total(trace_reduce.union(
        trace_reduce.clip(ops, trace.t0, trace.t1)))
    covered = ((max(calls[0].runs[0][0], trace.t0), min(calls[-1].runs[-1][1], trace.t1))
               if calls else (trace.t0, trace.t0))
    busy_s = trace_reduce.total(trace_reduce.union(trace_reduce.clip(ops, *covered)))
    matched_s = sum(c.inside_s for c in calls)
    joined = Joined(calls, seen, sum(c.whole for c in calls), foreign, matched_s, busy_s,
                    covered)
    harness.log(f"calls: {joined.matched} of {seen} in the window tied to their device "
                f"executions ({foreign} foreign executions passed over); their "
                f"{matched_s:.3f} s are {100.0 * matched_s / busy_s if busy_s else 0.0:.2f}% "
                f"of the chip's {busy_s:.3f} busy seconds in the stretch they cover, "
                f"{100.0 * matched_s / window_s if window_s else 0.0:.2f}% of the window's "
                f"{window_s:.3f} ({window_s - busy_s:.3f} s at its edges belong to calls "
                "without a span)")
    if lost:
        harness.log(f"calls: not tied, {len(lost)}: " + "; ".join(lost[:4]))
    if not joined.usable:
        harness.log(f"calls: under {100 * ENOUGH:.0f}% matched; the readers built on "
                    "the join report nothing")
    elif not joined.covers:
        harness.log(f"calls: the tied seconds are further than {100 * SLACK:.0f}% from "
                    "the busy seconds; no share over them is reported")
    return joined


def usable(trace):
    """The window's `Joined` if enough of its calls were matched, else None."""
    joined = join(trace)
    return joined if joined is not None and joined.usable else None


def ms(values, q: float) -> float:
    return 1e3 * harness.percentile(values, q)
