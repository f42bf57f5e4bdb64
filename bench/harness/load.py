"""Open-loop load through ``Engine.submit(arrival_t=due)`` and
``Engine.run(poll=...)``, the calls the HTTP front end's engine thread
makes. The poll hook submits every request that has come due, so
arrivals land between decode chunks as a front end would inject them;
each request is timed from when it was due, so a stall also counts
against the requests queued behind it."""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

from harness.traffic import Arrival


@dataclasses.dataclass
class Record:
    arrival: Arrival
    due: float                      # perf_counter time the request was due
    submit: float = 0.0
    rid: int = -1
    tok_t: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    tok_chunk: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False
    queue_wait_s: Optional[float] = None

    @property
    def in_window(self) -> bool:
        return self.arrival.segment == "window"

    @property
    def complete(self) -> bool:
        return (self.done and not self.cancelled
                and len(self.tokens) == self.arrival.max_new)


class OpenLoop:
    """Replays ``arrivals`` (offsets from the start of the load) against
    ``eng``. The window is [preroll, preroll + seconds) after the start;
    arrivals continue past it until every request due in the window has
    finished (or ``drain_s`` has passed), and then the rest is cancelled.
    ``on_poll(now, loop)`` is called first thing in every poll, when no
    device work is in flight. Where the hook itself stalls the host (it
    writes out a trace), the arrivals still to come are postponed by as
    long, so that the load does not pile up behind the stall; a traced
    run reports no end-to-end metric."""

    STALL_S = 1.0                   # a poll hook longer than this stalls

    def __init__(self, eng, arrivals: List[Arrival], preroll: float,
                 seconds: float, drain_s: float,
                 on_poll: Optional[Callable[[float, "OpenLoop"], None]]
                 = None,
                 span: Callable[[str], contextlib.AbstractContextManager]
                 = lambda name: contextlib.nullcontext()):
        self.eng, self.arrivals = eng, arrivals
        self.preroll, self.seconds, self.drain_s = preroll, seconds, drain_s
        self.on_poll = on_poll or (lambda now, loop: None)
        self.span = span
        self.records: List[Record] = []
        self.by_id: Dict[int, Record] = {}
        self.next = 0
        self.outstanding = 0
        self.window_left = sum(a.segment == "window" for a in arrivals)
        self.stopping = False
        self.w0 = self.w1 = self.t0 = 0.0

    # -- engine callbacks ----------------------------------------------------
    def _on_token(self, rid: int, tok: int) -> None:
        r = self.by_id[rid]
        r.tok_t.append(time.perf_counter())
        r.tokens.append(tok)
        # the engine's count of decode chunks so far: which chunk emitted
        # this token (a request's first token comes from its prefill)
        r.tok_chunk.append(self.eng.stats["chunks"])

    def _on_done(self, req) -> None:
        r = self.by_id[req.id]
        r.done, r.cancelled = True, req.cancelled
        r.queue_wait_s = req.queue_wait_s
        self.outstanding -= 1
        if r.in_window:
            self.window_left -= 1

    # -- the poll hook ---------------------------------------------------------
    def _submit_due(self, now: float) -> None:
        while (self.next < len(self.arrivals)
               and self.t0 + self.arrivals[self.next].t <= now):
            a = self.arrivals[self.next]
            r = Record(a, self.t0 + a.t)
            r.submit = time.perf_counter()
            r.rid = self.eng.submit(a.prompt, max_new_tokens=a.max_new,
                                    on_token=self._on_token,
                                    on_done=self._on_done, arrival_t=r.due)
            self.by_id[r.rid] = r
            self.records.append(r)
            self.outstanding += 1
            self.next += 1

    def poll(self) -> None:
        with self.span("bench.poll"):
            now = time.perf_counter()
            self.on_poll(now, self)
            stall = time.perf_counter() - now
            if stall > self.STALL_S:
                self.t0 += stall
                self.w1 += stall
                now += stall
            if self.stopping:
                return
            if (self.window_left == 0 and now >= self.w1) or (
                    now > self.w1 + self.drain_s):
                self.stopping = True
                for r in self.records:
                    if not r.done:
                        self.eng.cancel(r.rid)
                return
            self._submit_due(now)
            if self.outstanding == 0 and self.next < len(self.arrivals):
                with self.span("bench.wait_arrival"):
                    due = self.t0 + self.arrivals[self.next].t
                    time.sleep(max(0.0, due - time.perf_counter()))
                self.poll()

    def run(self) -> None:
        self.t0 = time.perf_counter()
        self.w0 = self.t0 + self.preroll
        self.w1 = self.w0 + self.seconds
        while not self.stopping:
            with self.span("bench.engine_run"):
                self.eng.run(poll=self.poll)
