"""The timed window on a made-up clock: one step in flight, one stamp a
step, and a tail that shows a step that stalls."""

import pytest

from cellbench import window


class _Steps:
    """``dispatch`` for a device that takes ``times[i]`` ms for step i:
    the loss of step i is ready when the clock reads its completion."""

    def __init__(self, times_ms):
        self.times, self.now, self.done_at, self.n = times_ms, 0.0, 0.0, 0

    def clock(self):
        return self.now

    def dispatch(self):
        self.done_at += self.times[self.n % len(self.times)] / 1e3
        self.n += 1
        return _Loss(self, self.done_at)


class _Loss:
    def __init__(self, steps, ready):
        self.steps, self.ready = steps, ready

    def __float__(self):  # the wait for the step's completion
        self.steps.now = max(self.steps.now, self.ready)
        return 1.0


def test_every_step_is_stamped_and_a_periodic_stall_shows_in_the_tail():
    # every fourth step takes 80 ms, the others 40: a quarter of the
    # steps lie beyond the 90th percentile's reach
    steps = _Steps([40.0, 40.0, 40.0, 80.0])
    t0, stamps, losses = window.run_window(steps.dispatch, 2.0,
                                           clock=steps.clock)
    stats = window.summarize(t0, stamps, losses)
    assert stats["steps"] == len(stamps) == len(losses)
    # dispatched: one before t0, the counted ones, one in flight at the end
    assert steps.n == stats["steps"] + 2
    assert stats["elapsed_s"] >= 2.0
    times = window.step_times_ms(t0, stamps)
    assert len(times) == stats["steps"]
    assert sorted(set(round(t) for t in times)) == [40, 80]
    assert stats["step_ms_median"] == pytest.approx(40.0)
    assert stats["step_ms_p90"] == pytest.approx(80.0)
    assert stats["step_ms_max"] == pytest.approx(80.0)
    assert stats["failed"] == 0


def test_percentile_is_linear_between_order_statistics():
    assert window.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 90.0) == \
        pytest.approx(4.6)
    assert window.percentile([7.0], 90.0) == 7.0
    with pytest.raises(ValueError):
        window.percentile([], 90.0)
