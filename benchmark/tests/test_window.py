"""The window's loop and statistics, on a made-up clock."""

import pytest

from benchmark import window


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


def test_p95_is_over_all_barriers_nearest_rank():
    # 100 barriers, every 10th a checkpoint barrier of 1 s: the top tenth
    plain = [0.1] * 90
    checkpoint = [1.0 + i / 100 for i in range(10)]
    # rank ceil(0.95 * 100) = 95: the 5th smallest checkpoint barrier
    assert window.percentile(plain + checkpoint, 0.95) == checkpoint[4]
    assert window.percentile([3.0], 0.95) == 3.0
    assert window.percentile([1.0, 2.0], 0.5) == 1.0
    with pytest.raises(ValueError):
        window.percentile([], 0.95)


def test_median():
    assert window.median([]) is None
    assert window.median([3, 1, 2]) == 2
    assert window.median([4, 1, 2, 3]) == 2.5


def test_drive_stops_on_seconds_and_counts_the_straddling_barrier():
    clock = Clock()

    def barrier():
        clock.now += 0.4

    out = window.drive(barrier, seconds=1.0, max_barriers=100, clock=clock)
    # barriers start at 0, 0.4, 0.8 (inside the window) and not at 1.2
    assert out["stopped_by"] == "seconds"
    assert out["barrier_s"] == pytest.approx([0.4, 0.4, 0.4])
    assert out["elapsed_s"] == pytest.approx(1.2)


def test_drive_stops_on_max_barriers():
    clock = Clock()

    def barrier():
        clock.now += 0.1

    out = window.drive(barrier, seconds=10.0, max_barriers=7, clock=clock)
    assert out["stopped_by"] == "max_barriers"
    assert len(out["barrier_s"]) == 7
    assert out["elapsed_s"] == pytest.approx(0.7)


def test_hooks_run_outside_the_barrier_s_time():
    clock = Clock()
    seen = []

    def barrier():
        clock.now += 0.1

    def before(i, seconds_left, barrier_s):
        assert len(barrier_s) == i
        seen.append((i, seconds_left))
        clock.now += 1.0          # a profiler stopping: not barrier time

    out = window.drive(barrier, seconds=3.5, max_barriers=100, clock=clock,
                       before=before)
    # the hook runs only ahead of a barrier that runs: the fourth starts
    # 3.3 s in, inside the window, and runs after its hook
    assert out["barrier_s"] == pytest.approx([0.1, 0.1, 0.1, 0.1])
    assert [i for i, _ in seen] == [0, 1, 2, 3]
    assert [left for _, left in seen] == pytest.approx([3.5, 2.4, 1.3, 0.2])


def test_rates_are_over_the_elapsed_seconds():
    # 4 barriers of 1,000 events in 2.5 s that really elapsed
    e2e = window.end_to_end([0.5, 0.5, 0.5, 1.0], 2.5, 1000)
    assert e2e["events_per_s"] == pytest.approx(1600.0)
    assert e2e["barrier_p95_ms"] == pytest.approx(1000.0)
    with pytest.raises(ValueError):
        window.end_to_end([], 0.0, 1000)
