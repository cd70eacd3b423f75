"""The window's arithmetic on a fake scheduler with a fake clock."""

import pytest

from benchmark.lib.window import percentile, run_window

STEP_S = 0.25
SLOTS = 4
REQUEST_TOKENS = 40          # a request lives 40 steps = 10 s


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeScheduler:
    """Every step takes ``STEP_S`` on the clock and appends one token to each
    slot's request; a finished request is replaced at once. Slot ``i`` starts
    ``i * 10`` tokens into its request, so requests end out of step."""

    def __init__(self, clock):
        self.clock = clock
        self.left = [REQUEST_TOKENS - 10 * i for i in range(SLOTS)]
        self.finished = 0

    def step(self):
        self.clock.t += STEP_S
        done = 0
        for i in range(SLOTS):
            self.left[i] -= 1
            if self.left[i] == 0:
                self.left[i] = REQUEST_TOKENS
                done += 1
        self.finished += done
        return {"out_tokens": SLOTS, "finished": done}


@pytest.mark.parametrize("warm_steps", [0, 1, 7, 9, 10, 23, 39])
def test_rate_is_the_same_wherever_the_edge_falls_in_a_request(warm_steps):
    clock = FakeClock()
    sched = FakeScheduler(clock)
    for _ in range(warm_steps):
        sched.step()
    w = run_window(sched.step, 12.0, clock)
    assert w.rate("out_tokens") == pytest.approx(SLOTS / STEP_S, rel=1e-12)
    assert w.seconds == pytest.approx(12.0)
    assert len(w.steps) == 48


def test_counting_finished_requests_over_nominal_seconds_moves_with_the_edge():
    """What PR 22 did, kept as the counter-example: work counted per finished
    request over the nominal window depends on where the edges fall."""
    rates = set()
    for warm_steps in (0, 7, 9):
        clock = FakeClock()
        sched = FakeScheduler(clock)
        for _ in range(warm_steps):
            sched.step()
        w = run_window(sched.step, 12.0, clock)
        rates.add(w.work("finished") * REQUEST_TOKENS / 12.0)
    assert len(rates) > 1


def test_window_closes_at_first_boundary_at_or_after_seconds():
    clock = FakeClock()
    sched = FakeScheduler(clock)
    w = run_window(sched.step, 1.1, clock)
    assert len(w.steps) == 5 and w.seconds == pytest.approx(1.25)
    assert w.rate("out_tokens") == pytest.approx(SLOTS / STEP_S)


def test_time_the_harness_spends_at_a_boundary_is_not_the_windows():
    clock = FakeClock()
    sched = FakeScheduler(clock)

    def profiler(elapsed, n_steps):
        if n_steps == 3:
            clock.t += 5.0            # starting a trace takes a while

    w = run_window(sched.step, 2.0, clock, profiler)
    assert w.paused == pytest.approx(5.0)
    assert max(w.step_s) == pytest.approx(STEP_S)      # the pause is no step
    assert w.seconds == pytest.approx(2.0)
    assert w.rate("out_tokens") == pytest.approx(SLOTS / STEP_S)


def test_inside_wants_both_ends_inside():
    clock = FakeClock()
    w = run_window(FakeScheduler(clock).step, 1.0, clock)
    assert w.inside(w.t_open, w.t_close)
    assert not w.inside(w.t_open - 0.01, w.t_close)
    assert not w.inside(w.t_open, None)


def test_percentile_is_a_sample():
    xs = list(range(1, 201))
    assert percentile(xs, 95) == 190
    assert percentile(xs, 50) == 100
    assert percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        percentile([], 50)
