"""The window's arithmetic: a rate is all the work over all the time, a
percentile is over every call."""
import statistics

import numpy as np
import pytest

from benchmarks.chip import harness


class Sleeper:
    """A driver whose calls take the given (fake) durations."""

    def __init__(self, durations, clock):
        self.durations = list(durations)
        self.clock = clock

    def call(self):
        self.clock.t += self.durations.pop(0)
        return 10.0


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


def test_rate_is_all_work_over_all_time(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(harness.time, "perf_counter", clock)
    w = harness.run_window(Sleeper([1.0, 3.0, 1.0, 1.0, 9.0], clock), 6.5,
                           None)
    # calls end at 1, 4, 5, 6; the next (shortest so far 1 s) would end
    # at 7 > 6.5, so no fifth call starts
    assert w.durations == [1.0, 3.0, 1.0, 1.0]
    assert w.seconds == 6.0
    assert sum(w.work) / w.seconds == pytest.approx(40.0 / 6.0)
    # not the mean of per-call rates
    assert sum(w.work) / w.seconds != pytest.approx(
        statistics.mean(10.0 / d for d in w.durations))


def test_first_call_always_runs(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(harness.time, "perf_counter", clock)
    w = harness.run_window(Sleeper([5.0, 5.0], clock), 1.0, None)
    assert w.calls == 1 and w.seconds == 5.0


@pytest.mark.parametrize("n", [1, 2, 7, 100, 333])
def test_percentile_is_over_all_calls(n):
    xs = list(np.random.default_rng(n).exponential(size=n))
    assert harness.percentile(xs, 95) == pytest.approx(
        float(np.percentile(xs, 95)))


def test_p95_is_not_a_median_of_chunk_p95s():
    xs = [0.1] * 180 + [5.0] * 20          # one stall-heavy chunk
    chunks = [xs[i:i + 20] for i in range(0, 200, 20)]
    median_of_chunks = statistics.median(harness.percentile(c, 95)
                                         for c in chunks)
    assert harness.percentile(xs, 95) != median_of_chunks
    assert harness.percentile(xs, 95) == pytest.approx(
        float(np.percentile(xs, 95)))


def test_judge_needs_every_number_within_its_limit():
    ok = harness.judge({"a": 1e-6, "b": 0.0}, {"a": 1e-5, "b": 0.0})
    assert ok[1]
    assert not harness.judge({"a": 2e-5, "b": 0.0}, {"a": 1e-5, "b": 0.0})[1]
    assert not harness.judge({"a": float("nan"), "b": 0.0},
                             {"a": 1e-5, "b": 0.0})[1]
    assert not harness.judge({"a": 1e-6}, {"a": 1e-5, "b": 0.0})[1]
    # a number the limits do not name is not compared
    checks, ok = harness.judge({"a": 1e-6, "c": 5.0}, {"a": 1e-5})
    assert ok and set(checks) == {"a"}
    assert not harness.judge({"a": 1e-6}, {})[1]
