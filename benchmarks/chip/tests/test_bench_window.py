"""Percentile and window arithmetic, and the wall clock's due-time
stamps through a stall."""
import time

import pytest

import bench
import reduce


def test_nearest_rank_percentiles():
    xs = [float(i) for i in range(1, 11)]
    assert reduce.percentile(xs, 50) == 5.0
    assert reduce.percentile(xs, 90) == 9.0
    assert reduce.percentile(xs, 100) == 10.0
    assert reduce.percentile([3.0], 90) == 3.0
    assert reduce.percentile([], 50) != reduce.percentile([], 50)  # NaN


def _r(due, first, done, gen=4):
    return dict(due=due, first=first, done=done, gen=gen)


def test_ttft_from_due_time_through_a_stall():
    """Rounds due every second; the server stalls from 2 s to 6 s.  Timed
    from when each was due, the stall shows in every round it delayed;
    a round due after the close is not counted, one that never got its
    first token is."""
    rounds = [_r(0.0, 0.1, 1.0), _r(1.0, 1.1, 2.0),
              _r(2.0, 6.1, 7.0), _r(3.0, 6.2, 7.1), _r(4.0, 6.3, 7.2),
              _r(5.0, 6.4, 12.0), _r(9.0, -1, -1), _r(11.0, 11.1, 11.5)]
    ttft, missing = reduce.ttfts(rounds, close_s=10.0)
    assert missing == 1
    assert ttft == pytest.approx([0.1, 0.1, 4.1, 3.2, 2.3, 1.4])
    assert reduce.percentile(ttft, 90) == pytest.approx(4.1)
    # rounds finished inside the window only: (done - first) / (gen - 1)
    assert reduce.tpots(rounds, 10.0) == pytest.approx(
        [0.3, 0.3, 0.3, 0.3, 0.3])


def test_tokens_in_window():
    emitted = [(0.5, 3), (9.9, 2), (10.0, 1), (10.5, 7)]
    assert reduce.tokens_in_window(emitted, 10.0) == 6


class _M:
    def __init__(self):
        self.prefill_done_t = -1.0
        self.done_t = -1.0


def test_wall_clock_stamps_due_time_when_the_loop_is_late():
    clock = bench.WallClock(bench.Spans(on=False))
    metrics = {}
    loop = bench.WindowLoop(clock, metrics, close_s=5.0, drain_s=0.0,
                            cap=None)
    stamped = []

    def submit(i):
        stamped.append(clock.now)
        metrics[i] = _M()

    for i, t in enumerate((0.0, 0.02, 0.04)):
        loop.at(t, lambda i=i: submit(i))
    time.sleep(0.1)                  # the loop is busy: a stall
    assert loop.fire_due() == 3
    assert stamped == [0.0, 0.02, 0.04]
    assert min(loop.late) >= 0.05
    assert clock.now >= 0.1          # outside a firing: the host clock


def test_loop_caps_rounds_in_flight_and_closes():
    clock = bench.WallClock(bench.Spans(on=False))
    metrics = {}
    loop = bench.WindowLoop(clock, metrics, close_s=0.2, drain_s=0.0,
                            cap=2)
    for i in range(5):
        loop.at(0.0, lambda i=i: metrics.setdefault(i, _M()))
    assert loop.fire_due() == 2
    assert loop.fire_due() == 0      # both still in flight
    metrics[0].done_t = 0.01
    assert loop.fire_due() == 1
    # the cap is full: nothing can fire before the close
    assert loop.next_time() == pytest.approx(0.2)
    # a session's next round is never held back, and holds its place
    loop.after(0.0, lambda: metrics.setdefault("next", _M()))
    assert loop.fire_due() == 1
    metrics[1].done_t = metrics[2].done_t = 0.02
    assert loop.fire_due() == 1      # one round in flight, one place free
    time.sleep(0.2)
    with pytest.raises(bench.WindowClosed):
        loop.fire_due()
    assert loop.pending == 0         # no submissions after the close


def test_loop_drains_until_first_tokens():
    clock = bench.WallClock(bench.Spans(on=False))
    metrics = {0: _M()}
    loop = bench.WindowLoop(clock, metrics, close_s=0.0, drain_s=60.0,
                            cap=None)
    assert loop.fire_due() == 0      # closed, but a first token is due
    metrics[0].prefill_done_t = 0.5
    with pytest.raises(bench.WindowClosed):
        loop.fire_due()


def test_idle_jump_sleeps_to_the_event():
    spans = bench.Spans(on=False)
    clock = bench.WallClock(spans)
    t0 = clock.now
    clock.jump_to(t0 + 0.05)
    assert clock.now >= t0 + 0.05
    assert spans.count["idle_sleep"] == 1
