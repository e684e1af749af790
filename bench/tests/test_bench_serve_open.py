"""The open-loop generator on a fake clock: sends on schedule, never
waits for answers, times each request from its scheduled send, and a
stall raises the tail of every request behind it."""

from __future__ import annotations

from concurrent.futures import Future

import numpy as np

from bench.traffic import serve_open


class FakeClock:
    """A clock that moves only when the sender sleeps or the test says;
    answers due by then are delivered as it moves."""

    def __init__(self):
        self.t = 100.0
        self.pending = []

    def __call__(self):
        return self.t

    def advance_to(self, t):
        for due, f in sorted(self.pending, key=lambda p: p[0]):
            if due > t:
                break
            self.t = max(self.t, due)
            self.pending.remove((due, f))
            f.set_result(None)
        self.t = max(self.t, t)

    def sleep(self, dt):
        self.advance_to(self.t + dt)


def _serve(clock, service_s, stall_at=None, stall_s=0.0):
    """A server answering each request ``service_s`` after it arrives,
    or after a one-time stall when request ``stall_at`` arrives."""
    pending = clock.pending
    busy_until = [0.0]

    def submit(i):
        f = Future()
        start = max(clock(), busy_until[0])
        if i == stall_at:
            start += stall_s
        busy_until[0] = start + service_s
        pending.append((busy_until[0], f))
        return f

    return submit, pending


def _finish(clock, pending):
    clock.advance_to(float("inf") if not pending else
                     max(t for t, _ in pending))


def test_nearest_rank_percentile():
    vals = [1.0, 2.0, 3.0, 4.0]
    assert serve_open.percentile(vals, 0.5) == 2.0
    assert serve_open.percentile(vals, 0.95) == 4.0
    assert serve_open.percentile(list(range(1, 101)), 0.95) == 95
    assert serve_open.percentile([], 0.95) == 0.0


def test_schedule_count_is_fixed_by_rate_and_window():
    rng = np.random.default_rng(0)
    for seed in range(3):
        t = serve_open.schedule({"rate_per_s": 500}, 2.0,
                                np.random.default_rng(seed))
        assert len(t) == 1000
        assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < 2.0
    burst = {"phases": [{"rate_per_s": 1000, "seconds": 0.2},
                        {"rate_per_s": 0, "seconds": 0.6}]}
    t = serve_open.schedule(burst, 1.6, rng)
    assert len(t) == 400
    assert np.all((t % 0.8) < 0.2 + 1e-9)


def test_sender_keeps_schedule_and_records_lateness():
    clock = FakeClock()
    submit, pending = _serve(clock, 0.001)
    times = np.array([0.0, 0.01, 0.02, 0.03])
    rec = serve_open.send(times, submit, clock=clock, sleep=clock.sleep)
    np.testing.assert_allclose(rec.due, times)
    assert np.all(rec.late == 0.0)
    _finish(clock, pending)
    lat = serve_open.latencies(rec, np.ones(4, bool))
    np.testing.assert_allclose(lat, 0.001)


def test_stall_raises_the_tail_of_requests_behind_it():
    times = np.arange(100) * 0.001
    clock = FakeClock()
    submit, pending = _serve(clock, 0.0005)
    rec = serve_open.send(times, submit, clock=clock, sleep=clock.sleep)
    _finish(clock, pending)
    calm = np.sort(serve_open.latencies(rec, np.ones(100, bool)))

    clock = FakeClock()
    submit, pending = _serve(clock, 0.0005, stall_at=50, stall_s=0.05)
    rec = serve_open.send(times, submit, clock=clock, sleep=clock.sleep)
    _finish(clock, pending)
    stalled = np.sort(serve_open.latencies(rec, np.ones(100, bool)))
    assert serve_open.percentile(calm, 0.95) < 0.001
    # timed from the scheduled send, every request queued behind the stall
    # waits for it: far more than 5 % of them exceed 10 ms
    assert serve_open.percentile(stalled, 0.95) > 0.01


def test_late_sender_is_recorded_and_counted_in_latency():
    clock = FakeClock()
    submit, pending = _serve(clock, 0.001)

    def slow_submit(i):
        if i == 1:
            clock.advance_to(clock.t + 0.02)   # the sender itself stalls
        return submit(i)

    times = np.array([0.0, 0.001, 0.002, 0.003])
    rec = serve_open.send(times, slow_submit, clock=clock, sleep=clock.sleep)
    _finish(clock, pending)
    assert rec.late[2] > 0.015 and rec.late[3] > 0.015
    lat = serve_open.latencies(rec, np.ones(4, bool))
    assert lat[3] > 0.015


def test_failed_request_is_beyond_any_limit():
    rec = serve_open.Sent(due=np.zeros(2), late=np.zeros(2),
                          done=np.array([0.001, 0.002]))
    lat = serve_open.latencies(rec, np.array([True, False]))
    assert lat[0] == 0.001 and np.isinf(lat[1])
