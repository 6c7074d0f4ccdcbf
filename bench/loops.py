"""Open- and closed-loop drivers of ``SparqlServer.submit``/``flush``.

The server is synchronous: work happens inside ``submit`` (a bucket that
fills, or one whose oldest request has waited ``flush_ms``) and inside
``flush`` or ``PendingQuery.result``.  The drivers play the server's
event loop: between arrivals they sleep until the oldest queued
request's ``flush_ms`` deadline and then drain that request's bucket, as
the batcher's own deadline rule would on the next submit.

Every request records when it was due, when it was sent and when its
answer was back on the host; all times are ``time.perf_counter`` seconds
from the window's start.  The drivers also record the longest time the
loop spent inside one call of the server (``blocks``), where a stall of
the generator shows.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional

__all__ = ["Sent", "open_loop", "closed_loop"]

#: a request is due once the clock is within this of its due time
_SLACK = 1e-9


@dataclass
class Sent:
    template: str
    query: str
    due: float
    sent: float = 0.0
    done: Optional[float] = None
    result: object = None
    error: Optional[BaseException] = None
    ticket: object = field(default=None, repr=False)

    @property
    def latency(self) -> float:
        return self.done - self.due


class _Driver:
    def __init__(self, server, t0: float, on_done: Callable[[Sent], None],
                 note=None, clock=time.perf_counter, sleep=time.sleep,
                 blocks: Optional[dict] = None):
        self.note = note or (lambda name: contextlib.nullcontext())
        self.blocks = {} if blocks is None else blocks
        self.server = server
        self.t0 = t0
        self.on_done = on_done
        self.clock = clock
        self.sleep = sleep
        self.flush_s = server.batcher.flush_ms / 1e3
        self.outstanding: List[Sent] = []

    def now(self) -> float:
        return self.clock() - self.t0

    @contextlib.contextmanager
    def call(self, name: str):
        """A call into the server; the longest one is kept in ``blocks``."""
        t = self.now()
        with self.note(name):
            yield
        d = (self.now() - t) * 1e3
        if d > self.blocks.get("longest_ms", -1.0):
            self.blocks.update(longest_ms=d, at_s=t, call=name)

    def submit(self, r: Sent) -> None:
        r.sent = self.now()
        with self.call("bench.submit"):
            r.ticket = self.server.submit(r.query)
        self.outstanding.append(r)

    def collect(self) -> None:
        """Record every outstanding request whose answer is back."""
        now, still = self.now(), []
        for r in self.outstanding:
            if r.ticket.done():
                r.done = now
                try:
                    r.result = r.ticket.result()
                except Exception as exc:    # the request failed: counted
                    r.error = exc
                r.ticket = None
                self.on_done(r)
            else:
                still.append(r)
        self.outstanding = still

    def deadline(self) -> Optional[float]:
        """When the oldest queued request's bucket is due to drain."""
        if not self.outstanding:
            return None
        first = min(r.ticket.submitted_at for r in self.outstanding)
        return first - self.t0 + self.flush_s

    def drain_oldest(self) -> None:
        """Force the oldest request's bucket.  A request that its drained
        bucket left without an answer never gets one: it is given up as
        failed, so that the loop goes on."""
        oldest = min(self.outstanding, key=lambda r: r.ticket.submitted_at)
        try:
            with self.call("bench.drain"):
                oldest.ticket.result()
        except Exception as exc:
            oldest.error = exc              # kept if it never answers
        if not oldest.ticket.done():
            self.outstanding.remove(oldest)
            oldest.error = oldest.error or RuntimeError("never answered")
            oldest.ticket = None
            self.on_done(oldest)
        else:
            oldest.error = None
        self.collect()

    def wait_until(self, t: float) -> None:
        d = t - self.now()
        if d > 0:
            with self.note("bench.wait"):
                self.sleep(d)

    def drain_all(self) -> None:
        try:
            with self.call("bench.drain"):
                self.server.flush()
        except Exception:
            pass                            # recorded by collect()
        self.collect()


def open_loop(server, schedule, t0: float, on_done: Callable[[Sent], None],
              **opts) -> List[Sent]:
    """Send each request of ``schedule`` at its due time, whatever is
    outstanding; drain what is left after the last one."""
    drv = _Driver(server, t0, on_done, **opts)
    sent = [Sent(r.template, r.query, r.due) for r in schedule]
    i = 0
    while i < len(sent):
        while i < len(sent) and sent[i].due <= drv.now() + _SLACK:
            drv.submit(sent[i])
            i += 1
            drv.collect()
        nxt = sent[i].due if i < len(sent) else None
        dl = drv.deadline()
        if dl is not None and (nxt is None or dl < nxt):
            drv.wait_until(dl)
            drv.drain_oldest()
        elif nxt is not None:
            drv.wait_until(nxt)
    drv.drain_all()
    return sent


def closed_loop(server, source: Iterator, clients: int, seconds: float,
                t0: float, on_done: Callable[[Sent], None],
                **opts) -> List[Sent]:
    """``clients`` callers, each sending its next request as soon as its
    last answer is back, until ``seconds`` have passed; then drain."""
    sent: List[Sent] = []
    done_box: List[Sent] = []

    def finished(r: Sent) -> None:
        done_box.append(r)
        on_done(r)

    drv = _Driver(server, t0, finished, **opts)
    idle = clients
    while drv.now() < seconds:
        while idle and drv.now() < seconds:
            req = next(source)
            r = Sent(req.template, req.query, drv.now())
            sent.append(r)
            drv.submit(r)
            idle -= 1
            drv.collect()
        idle += len(done_box)
        done_box.clear()
        if idle:
            continue
        dl = drv.deadline()
        if dl is not None:
            drv.wait_until(dl)
            drv.drain_oldest()
            idle += len(done_box)
            done_box.clear()
    drv.drain_all()
    return sent
