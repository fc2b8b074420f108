"""The whole-step window rule, on a fake clock."""
import harness


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _run(step_s, seconds):
    clock = Clock()

    def step(i):
        clock.t += step_s[i]
        return i
    return harness.run_window(step, seconds, clock)


def test_window_closes_at_the_first_step_ending_past_the_length():
    t0, t1, times, out = _run([4, 4, 4, 4, 4], 10)
    assert (t0, t1) == (100.0, 112.0)
    assert times == [4, 4, 4] and out == [0, 1, 2]


def test_a_step_longer_than_the_window_is_one_whole_step():
    t0, t1, times, _ = _run([61.5, 60.0], 10)
    assert times == [61.5] and t1 - t0 == 61.5


def test_a_step_ending_exactly_at_the_length_closes_it():
    _, t1, times, _ = _run([5, 5, 5], 10)
    assert times == [5, 5] and t1 == 110.0
