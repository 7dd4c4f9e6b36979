"""Which steps exist, and the edges of the measured window.

Every worker thread asks :meth:`StepGate.admit` before each step.  The
decision for step index k is taken ONCE, by the first thread to ask, and
shared: all workers run the same number of steps, so no barrier inside
the kvstore is left waiting for a party that has stopped.  At the two
edges of the window the threads meet at a barrier of the gate's own and
the last to arrive runs the caller's ``on_open`` / ``on_close``: the
time, byte and counter snapshots are taken with every worker between
steps and no push in flight, which is what lets a byte count repeat
exactly.

``mark_steps`` puts one more such meeting inside the window, after that
many of its steps: ``on_mark`` runs with every worker between steps (a
profiler trace of the window's first steps is stopped there, at an edge
as clean as the window's own), and the time it takes is left out of
``window_s`` and of the pace the stop decision goes by.  A window that
ends before the mark never calls it.
"""

from __future__ import annotations

import threading
import time


class StepGate:
    def __init__(self, workers: int, warmup_steps: int, seconds: float,
                 max_steps=None, on_open=None, on_close=None,
                 clock=time.perf_counter, barrier_timeout: float = 900.0,
                 mark_steps=None, on_mark=None):
        self.warmup = int(warmup_steps)
        self.seconds = float(seconds)
        self.max_steps = max_steps
        self.mark_steps = mark_steps
        self._clock = clock
        self._timeout = barrier_timeout
        self._mu = threading.Lock()
        self._exists: dict = {}
        self._on_open = on_open or (lambda: None)
        self._on_close = on_close or (lambda: None)
        self._on_mark = on_mark or (lambda: None)
        self.t_open = None
        self.t_close = None
        self.paused_s = 0.0
        self._open = threading.Barrier(workers, action=self._opened)
        self._mark = threading.Barrier(workers, action=self._marked)
        self._close = threading.Barrier(workers, action=self._closed)

    # the actions run in the last thread to arrive, the others still held
    def _opened(self):
        self._on_open()
        self.t_open = self._clock()

    def _marked(self):
        t = self._clock()
        self._on_mark()
        self.paused_s += self._clock() - t

    def _closed(self):
        self.t_close = self._clock()
        self._on_close()

    def _decide(self, k: int) -> bool:
        done = k - self.warmup          # window steps the asker finished
        if done <= 0:
            return True                 # warm-up, and one measured step
        if self.max_steps is not None and done >= self.max_steps:
            return False
        elapsed = self._clock() - self.t_open - self.paused_s
        # the step exists if, at the pace so far, it ends inside the window
        return elapsed + elapsed / done <= self.seconds

    def admit(self, k: int) -> bool:
        """True if step ``k`` exists.  Blocks at the window's edges and
        at the mark."""
        with self._mu:
            if k not in self._exists:
                self._exists[k] = self._decide(k)
            exists = self._exists[k]
        if k == self.warmup:
            self._open.wait(self._timeout)
        if not exists:
            self._close.wait(self._timeout)
        elif self.mark_steps and k == self.warmup + self.mark_steps:
            self._mark.wait(self._timeout)
        return exists

    def abort(self):
        """A worker died: let the others out of the barriers."""
        self._open.abort()
        self._mark.abort()
        self._close.abort()

    @property
    def steps_in_window(self) -> int:
        with self._mu:
            last = max(k for k, e in self._exists.items() if e)
        return last + 1 - self.warmup

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open - self.paused_s
