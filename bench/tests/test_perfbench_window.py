"""The window's accounting: set-up blocks, the closing block, the roots."""
import pytest

from bench import compile_clock, window


class FakeClock:
    def __init__(self, times):
        self.times = list(times)

    def __call__(self):
        return self.times.pop(0)


def drive(win, blocks):
    for k in range(1, blocks + 1):
        if win(f"acc{k}", k):
            return k
    return None


def test_roots_count_only_blocks_completed_inside_the_window():
    # blocks complete at t = 1, 2 (warm-up), 2.5, 3.1, 3.9, 4.6 ...
    clock = FakeClock([1.0, 2.0, 2.5, 3.1, 3.9, 4.6, 5.0])
    opened = []
    win = window.StreamWindow(2, 1.8, clock=clock, on_open=opened.append)
    assert drive(win, 7) == 5  # 3.9 - 2.0 >= 1.8: the rule fires there
    assert opened == [2.0] and win.t_close == 3.9
    acct = window.account(win, [128, 128, 100, 90, 80, 70, 60])
    assert (acct.first_block, acct.last_block, acct.blocks) == (3, 5, 3)
    assert acct.roots == 100 + 90 + 80
    assert acct.seconds == pytest.approx(1.9)
    assert not acct.solve_finished
    assert win.snapshots == ["acc1", "acc2", "acc3", "acc4", "acc5"]


def test_schedule_that_runs_out_ends_the_window_with_its_last_block():
    win = window.StreamWindow(1, 100.0, clock=FakeClock([1.0, 2.0, 4.0]))
    assert drive(win, 3) is None
    acct = window.account(win, [10, 20, 5])
    assert acct.solve_finished and acct.roots == 25 and acct.seconds == 3.0


def test_window_needs_a_timed_block():
    win = window.StreamWindow(2, 1.0, clock=FakeClock([1.0, 2.0]))
    drive(win, 2)
    with pytest.raises(RuntimeError, match="no block was timed"):
        window.account(win, [1, 1])
    with pytest.raises(RuntimeError, match="never opened"):
        window.account(window.StreamWindow(3, 1.0), [])


def test_stop_rule_must_see_every_block():
    win = window.StreamWindow(1, 1.0, clock=FakeClock([1.0, 2.0]))
    win("a", 1)
    with pytest.raises(RuntimeError, match="one call per dispatch block"):
        win("b", 3)


def test_compile_clock_keeps_the_union_of_nested_spans():
    clock = compile_clock.CompileClock()
    trace, lower, backend = compile_clock.COMPILE_EVENTS
    clock.on_span(trace, start=10.0, end=14.0)
    clock.on_span(lower, start=11.0, end=12.0)  # nested in the first
    clock.on_span(backend, start=13.5, end=16.0)  # overlaps its end
    clock.on_span(backend, start=20.0, end=21.0)
    clock.on_span("/jax/other", start=0.0, end=100.0)  # not a compile event
    clock.on_event(compile_clock.CACHE_HIT_EVENT)
    clock.on_event("/jax/other")
    assert clock.seconds_between(0.0) == pytest.approx(7.0)
    assert clock.seconds_between(0.0, 15.0) == pytest.approx(6.0)
    assert clock.seconds_between(15.0) == pytest.approx(1.0)
    assert clock.count_between(12.0) == 2
    assert clock.cache_hits == 1
    assert compile_clock.union_seconds([]) == 0.0
