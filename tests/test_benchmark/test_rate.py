"""The window and the rate: whole iterations over the measured time between
two iteration boundaries, never a count over the nominal seconds."""

import pytest

from benchmark.drivers.train_main import Window
from benchmark.metrics import env_steps_per_s, iter_ms_p50, iter_ms_p95


def drive(stamps, seconds, open_at):
    stopped = []
    window = Window(seconds, open_at, stop=lambda: stopped.append(True))
    for t in stamps:
        window.on_step(t)
    assert stopped == [True]
    return window


def rate(window, num_envs=4):
    steps = (window.i_close - window.i_open) * num_envs
    return env_steps_per_s.read({"env_steps": steps, "window_s": window.window_seconds})


def test_window_opens_at_a_boundary_and_closes_at_the_first_one_late_enough():
    stamps = [0.25 * i for i in range(60)]
    w = drive(stamps, seconds=2.1, open_at=11)
    assert (w.i_open, w.i_close) == (10, 19)  # 2.25 s, nine whole iterations: not 2.1
    assert w.window_seconds == pytest.approx(2.25)
    assert rate(w) == pytest.approx(9 * 4 / 2.25)
    assert len(w.iteration_seconds) == 9


def test_one_iteration_more_or_less_does_not_move_the_rate():
    steady = [0.232 * i for i in range(200)]
    a = rate(drive(steady, seconds=20.0, open_at=5))
    b = rate(drive(steady, seconds=20.2, open_at=5))
    assert a == pytest.approx(b, rel=1e-9)


def test_a_stall_inside_the_window_lowers_the_rate_and_the_tail_not_the_median():
    steady = [0.1 * i for i in range(400)]
    stalled = [t if i < 150 else t + 3.0 for i, t in enumerate(steady)]
    clean, stall = drive(steady, 20.0, 10), drive(stalled, 20.0, 10)
    assert rate(stall) < 0.9 * rate(clean)
    runs = [{"iteration_seconds": w.iteration_seconds} for w in (clean, stall)]
    assert iter_ms_p50.read(runs[1]) == pytest.approx(iter_ms_p50.read(runs[0]), rel=1e-6)
    assert max(stall.iteration_seconds) == pytest.approx(3.1)
    assert iter_ms_p95.read({"iteration_seconds": [0.1] * 19}) is None  # no tail under 20 samples


def test_boundaries_after_the_close_are_not_counted():
    w = drive([0.5 * i for i in range(40)], seconds=3.0, open_at=3)
    assert w.i_close == w.i_open + 6 and len(w.stamps) == 40


def test_a_traced_run_traces_after_the_close_and_stops_the_main_last(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
    w = Window(1.0, 3, trace={"dir": "somewhere", "iterations": 4}, stop=lambda: calls.append(("end",)))
    for i in range(30):
        w.on_step(0.25 * i)
    # opens at boundary 3 (t=0.5), closes at t=1.5; the profiler's start-up stall falls after the close
    assert (w.i_open, w.i_close) == (2, 6) and w.window_seconds == pytest.approx(1.0)
    assert calls == [("start", "somewhere"), ("stop",), ("end",)]
    assert w.traced_iterations == 4 and len(w.iteration_seconds) == 4


def test_freed_memory_is_handed_back_once_and_before_the_stamp_that_opens_the_window(monkeypatch):
    from benchmark.drivers import train_main

    train_main.hand_back_freed_memory()  # the real one runs wherever the tests do
    w = Window(1.0, 4, stop=lambda: None)
    seen = []
    monkeypatch.setattr(train_main, "hand_back_freed_memory", lambda: seen.append((len(w.stamps), w.i_open)))
    for i in range(30):
        w.on_step(0.25 * i)
    # at the fourth boundary, with three stamps taken and the window not yet open: its time is set-up's
    assert seen == [(3, None)] and w.i_open == 3 and w.window_seconds == pytest.approx(1.0)
