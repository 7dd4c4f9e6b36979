"""The per-step-index stop decision with two threads, and the window."""

import threading

import pytest

from benchmark.lib.gate import StepGate


class Clock:
    def __init__(self):
        self.t = 0.0
        self.mu = threading.Lock()

    def __call__(self):
        with self.mu:
            return self.t

    def advance(self, dt):
        with self.mu:
            self.t += dt


def _drive(gate, workers, work):
    ran = [[] for _ in range(workers)]
    errors = []

    def loop(w):
        try:
            k = 0
            while gate.admit(k):
                work(w, k)
                ran[w].append(k)
                k += 1
        except BaseException as e:
            errors.append(e)
            gate.abort()

    threads = [threading.Thread(target=loop, args=(w,), daemon=True)
               for w in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads), "a worker hung"
    return ran, errors


def test_both_threads_run_the_same_steps_and_the_window_is_whole_steps():
    clock = Clock()
    calls = []
    gate = StepGate(2, warmup_steps=3, seconds=10.0, clock=clock,
                    on_open=lambda: calls.append(("open", clock())),
                    on_close=lambda: calls.append(("close", clock())),
                    barrier_timeout=30)
    lock = threading.Barrier(2)

    def work(w, k):
        # the two workers finish a step together; worker 0 moves the clock
        lock.wait(30)
        if w == 0:
            clock.advance(3.0)
        lock.wait(30)

    ran, errors = _drive(gate, 2, work)
    assert not errors
    # 3 warm-up steps, then steps while elapsed + one more step <= 10 s:
    # after 3 measured steps 9 s have gone and a fourth would end at 12
    assert ran[0] == ran[1] == [0, 1, 2, 3, 4, 5]
    assert gate.steps_in_window == 3
    assert gate.window_s == pytest.approx(9.0)
    assert calls == [("open", 9.0), ("close", 18.0)]   # each edge once


def test_one_decision_per_index_even_if_the_clock_passes_the_end():
    """The first to ask for step k decides; a slower thread that asks
    after the deadline still runs step k."""
    clock = Clock()
    gate = StepGate(2, warmup_steps=1, seconds=10.0, clock=clock,
                    barrier_timeout=30)
    asked = threading.Event()

    def work(w, k):
        if k == 1 and w == 0:
            clock.advance(4.0)       # fast worker: step 1 took 4 s
        if k == 1 and w == 1:
            asked.wait(30)           # slow worker: still inside step 1
            clock.advance(100.0)     # ... far past the window's end

    real_decide = gate._decide

    def decide(k):
        out = real_decide(k)
        if k == 2:
            asked.set()              # worker 0 has decided step 2 exists
        return out

    gate._decide = decide
    ran, errors = _drive(gate, 2, work)
    assert not errors
    assert ran[0] == ran[1] and 2 in ran[1]


def test_max_steps_and_at_least_one_measured_step():
    clock = Clock()
    gate = StepGate(1, warmup_steps=2, seconds=0.0, clock=clock,
                    barrier_timeout=30)
    ran, _ = _drive(gate, 1, lambda w, k: clock.advance(1.0))
    assert ran[0] == [0, 1, 2] and gate.steps_in_window == 1
    gate = StepGate(1, warmup_steps=2, seconds=1e9, max_steps=4, clock=clock,
                    barrier_timeout=30)
    ran, _ = _drive(gate, 1, lambda w, k: clock.advance(1.0))
    assert gate.steps_in_window == 4 and len(ran[0]) == 6


def test_a_dead_worker_does_not_leave_the_other_at_a_barrier():
    gate = StepGate(2, warmup_steps=1, seconds=1.0, barrier_timeout=30)

    def work(w, k):
        if w == 1:
            raise RuntimeError("worker 1 died")

    ran, errors = _drive(gate, 2, work)
    assert any(isinstance(e, RuntimeError) for e in errors)
    assert any(isinstance(e, threading.BrokenBarrierError) for e in errors)


def _lockstep(clock, step_s):
    lock = threading.Barrier(2)

    def work(w, k):
        lock.wait(30)
        if w == 0:
            clock.advance(step_s)
        lock.wait(30)
    return work


def test_the_mark_meets_once_inside_the_window_and_its_time_is_left_out():
    """After ``mark_steps`` window steps every worker is held between
    steps while ``on_mark`` runs (a trace is stopped there); what it
    takes counts neither as window nor against the pace."""
    clock = Clock()
    calls = []

    def on_mark():
        calls.append(("mark", clock()))
        clock.advance(5.0)           # stopping the trace takes 5 s

    gate = StepGate(2, warmup_steps=1, seconds=10.0, clock=clock,
                    on_close=lambda: calls.append(("close", clock())),
                    mark_steps=2, on_mark=on_mark, barrier_timeout=30)
    ran, errors = _drive(gate, 2, _lockstep(clock, 2.0))
    assert not errors
    # 1 warm-up step, 2 window steps, the mark (5 s), 3 more: 5 x 2 s fit
    # 10 s only because the mark's 5 s are not counted
    assert ran[0] == ran[1] == [0, 1, 2, 3, 4, 5]
    assert calls == [("mark", 6.0), ("close", 17.0)]
    assert gate.steps_in_window == 5 and gate.paused_s == pytest.approx(5.0)
    assert gate.window_s == pytest.approx(10.0)


def test_a_window_that_ends_before_the_mark_never_calls_it():
    clock = Clock()
    calls = []
    gate = StepGate(2, warmup_steps=1, seconds=4.0, clock=clock,
                    on_close=lambda: calls.append("close"),
                    mark_steps=2, on_mark=lambda: calls.append("mark"),
                    barrier_timeout=30)
    ran, errors = _drive(gate, 2, _lockstep(clock, 2.0))
    assert not errors
    # the step after the mark would end at 6 s: the window closes where
    # the mark would have been
    assert ran[0] == ran[1] == [0, 1, 2] and gate.steps_in_window == 2
    assert calls == ["close"] and gate.paused_s == 0.0


def test_a_worker_that_dies_lets_the_other_out_of_the_mark():
    gate = StepGate(2, warmup_steps=1, seconds=1e9, mark_steps=1,
                    barrier_timeout=30)

    def work(w, k):
        if w == 1 and k == 1:
            raise RuntimeError("worker 1 died in the first window step")

    ran, errors = _drive(gate, 2, work)
    assert any(isinstance(e, RuntimeError) for e in errors)
    assert any(isinstance(e, threading.BrokenBarrierError) for e in errors)
