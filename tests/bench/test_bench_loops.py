"""The open and closed loops, timed from the due time, on a fake clock,
and the percentile and rate arithmetic over a window with a stall."""

import os

import pytest

from bench import harness, loops, traffic
from benchroot import REPO


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += d


class Ticket:
    def __init__(self, at, result):
        self.submitted_at = at
        self._result = result

    def done(self):
        return True

    def result(self):
        return self._result


class Batcher:
    flush_ms = 2.0


class FakeServer:
    """Answers each request inside ``submit``, taking ``cost(query)``
    seconds of the fake clock: a one-slot synchronous server."""

    def __init__(self, clock, cost):
        self.clock, self.cost, self.batcher = clock, cost, Batcher()
        self.queries = []

    def submit(self, q):
        at = self.clock()
        self.clock.t += self.cost(q)
        self.queries.append(q)
        return Ticket(at, [q])

    def flush(self):
        return 0


def _sched(dues, slow_at=None):
    return [traffic.Request("T", "slow" if i == slow_at else f"q{i}", d)
            for i, d in enumerate(dues)]


def test_open_loop_times_from_due_and_reports_lateness():
    clock = FakeClock()
    srv = FakeServer(clock, lambda q: 1.0 if q == "slow" else 0.01)
    dues = [0.1 * i for i in range(20)]
    sent = loops.open_loop(srv, _sched(dues, slow_at=5), clock(),
                           lambda r: None, clock=clock, sleep=clock.sleep)
    assert [r.query for r in sent] == srv.queries
    before, slow, after = sent[4], sent[5], sent[6]
    assert before.sent == pytest.approx(before.due)
    assert before.latency == pytest.approx(0.01)
    assert slow.latency == pytest.approx(1.0)
    # due at 0.6 while the server was busy until 1.5: sent late, and its
    # latency counts the wait from when it was due
    assert after.sent - after.due == pytest.approx(0.9)
    assert after.latency == pytest.approx(0.9 + 0.01)
    # the queue drains: a request due after the stall is on time again
    assert sent[-1].sent == pytest.approx(sent[-1].due)


def test_open_loop_records_its_longest_call():
    """A stall inside the server is named: how long, when, in which call."""
    clock = FakeClock()
    srv = FakeServer(clock, lambda q: 1.0 if q == "slow" else 0.01)
    blocks = {}
    loops.open_loop(srv, _sched([0.1 * i for i in range(20)], slow_at=5),
                    clock(), lambda r: None, clock=clock, sleep=clock.sleep,
                    blocks=blocks)
    assert blocks["longest_ms"] == pytest.approx(1000.0)
    assert blocks["at_s"] == pytest.approx(0.5)
    assert blocks["call"] == "bench.submit"


def test_closed_loop_waits_for_each_answer():
    clock = FakeClock()
    srv = FakeServer(clock, lambda q: 0.1)
    src = iter(traffic.Request("T", f"q{i}") for i in range(1000))
    sent = loops.closed_loop(srv, src, 2, 1.0, clock(), lambda r: None,
                             clock=clock, sleep=clock.sleep)
    assert 10 <= len(sent) <= 11
    assert all(r.done is not None and r.error is None for r in sent)
    assert all(b.sent >= a.done for a, b in zip(sent, sent[1:]))


def _record(latencies, seconds, step):
    rec = harness.RunRecord(cell={}, config={}, seconds=seconds)
    for i, lat in enumerate(latencies):
        due = i * step
        rec.requests.append(loops.Sent("T", "q", due, sent=due,
                                       done=due + lat))
    return rec


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_stall_moves_p95_and_qps(loop):
    mix = traffic.Mix("m", {}, {}, loop)
    n, seconds, step = 200, 10.0, 0.05
    calm = harness.end_to_end(mix, _record([0.005] * n, seconds, step))
    # a stall from 9.4 s to 10.5 s: requests due then wait for it and
    # finish after the window closes
    lat = [0.005 if i < 188 else 10.5 - i * step + 0.005 for i in range(n)]
    stalled = harness.end_to_end(mix, _record(lat, seconds, step))
    assert calm["qps"] == pytest.approx(n / seconds)
    assert stalled["qps"] == pytest.approx(188 / seconds)
    if loop == "open":
        assert calm["latency_p95_ms"] == pytest.approx(5.0)
        assert calm["latency_p50_ms"] == pytest.approx(5.0)
        assert stalled["latency_p50_ms"] == pytest.approx(5.0)
        # 12 of 200 requests stalled: the 95th percentile lies on them
        assert stalled["latency_p95_ms"] > 500.0
    else:
        assert "latency_p95_ms" not in stalled


def test_seed_draws_constants_not_order():
    mix = traffic.load_mix(os.path.join(REPO, "bench", "traffic",
                                        "basic-open.json"))
    sizes = {k: 100 for k in ("users", "products", "reviews", "retailers",
                              "websites", "cities", "countries", "genres",
                              "categories")}
    a = traffic.open_schedule(mix, sizes, 1, 10.0)
    b = traffic.open_schedule(mix, sizes, 2**31 + 5, 10.0)
    assert len(a) == len(b) > 0 and len(a) % len(mix.templates) == 0
    assert [r.template for r in a] == [r.template for r in b]
    assert [r.due for r in a] == [r.due for r in b]
    assert a[-1].due < 10.0
    assert [r.query for r in a] != [r.query for r in b]
    assert [r.query for r in a] == \
        [r.query for r in traffic.open_schedule(mix, sizes, 1, 10.0)]


def test_heaviest_constants_match_the_most_triples():
    """Warm-up's constants: for each template, its class's constants in
    order of how many triples match the pattern that holds them."""
    import numpy as np

    mix = traffic.load_mix(os.path.join(REPO, "bench", "traffic",
                                        "basic-open.json"))
    terms = ["wsdbm:sells", "sorg:price", "wsdbm:Retailer0",
             "wsdbm:Retailer1", "wsdbm:Retailer2", "wsdbm:Product0"]
    sells, price, r0, r1, r2, p0 = range(6)
    tt = np.array([[r1, sells, p0], [r1, sells, p0], [r2, sells, p0],
                   [r0, price, p0], [r0, price, p0], [r0, price, p0]])
    qs = traffic.heaviest(mix, "L3", {"retailers": 3}, tt, terms, 2)
    assert [q.split()[4] for q in qs] == ["wsdbm:Retailer1",
                                          "wsdbm:Retailer2"]
    assert traffic.heaviest(mix, "F1", {}, tt, terms, 3) == \
        [mix.templates["F1"]]
