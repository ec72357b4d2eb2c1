import time

import numpy as np

from benchmarks.harness.load import OpenLoopProducer


def test_schedule_does_not_slow_when_the_consumer_stalls():
    """The producer submits on its schedule whether or not anything
    consumes: a queue that only grows must not delay it."""
    backlog = []                       # nobody ever takes from it
    n, rate = 400, 2000.0
    due = time.time() + 0.05 + np.arange(n) / rate
    p = OpenLoopProducer(lambda ev: backlog.append(ev) or True,
                         [{"i": i} for i in range(n)], due)
    p.start()
    p.join(timeout=5.0)
    assert not p.is_alive()
    assert len(backlog) == n and np.isfinite(p.submitted).all()
    late = p.submitted - due
    assert late.min() >= 0.0
    assert np.percentile(late, 99) < 0.05      # 1 ms wake, not a backlog


def test_full_ring_is_retried_and_shows_as_lateness():
    state = {"refuse_until": time.time() + 0.15}
    taken = []

    def submit(ev):
        if time.time() < state["refuse_until"]:
            return False
        taken.append(ev)
        return True

    due = np.full(5, time.time())
    p = OpenLoopProducer(submit, [{"i": i} for i in range(5)], due)
    p.start()
    p.join(timeout=5.0)
    assert [e["i"] for e in taken] == [0, 1, 2, 3, 4]    # order kept
    assert (p.submitted - due).min() >= 0.1


def test_stop_ends_the_thread_before_its_schedule_does():
    due = time.time() + 60.0 + np.arange(3)
    p = OpenLoopProducer(lambda ev: True, [{}] * 3, due)
    p.start()
    p.stop()
    p.join(timeout=2.0)
    assert not p.is_alive() and np.isnan(p.submitted).all()
