"""Client-side record of every request a traffic loop sends, and the window.

Times are ``time.perf_counter`` seconds.  A request's latency runs from when
it was due (the open loop's schedule; the closed loop's submit) to when its
future resolved, which the future's callback records on the thread that
resolved it.
"""
from __future__ import annotations

import threading
import time

import numpy as np

__all__ = ["RequestLog", "Window"]


class Window:
    """The measured window.  ``open()`` starts it and tells every listener
    (the tracer in a traced run); ``close()`` ends it."""

    def __init__(self, seconds: float, listeners=()):
        self.seconds = float(seconds)
        self.t0 = None
        self.t1 = None
        self._listeners = list(listeners)

    def open(self) -> float:
        self.t0 = time.perf_counter()
        for fn in self._listeners:
            fn(self.t0)
        return self.t0

    @property
    def end(self) -> float:
        return self.t0 + self.seconds

    def close(self) -> None:
        self.t1 = time.perf_counter()


class RequestLog:
    """Append-only request table; ``finish`` may run on any thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self.qidx: list = []
        self.t_due: list = []
        self.t_submit: list = []
        self.t_done: list = []
        self.error: list = []
        self.ids: list = []
        self.dists: list = []
        self._pending = 0
        self._idle = threading.Condition(self._lock)

    def __len__(self) -> int:
        with self._lock:
            return len(self.qidx)

    def new(self, qidx: int, t_due: float) -> int:
        with self._lock:
            self.qidx.append(int(qidx))
            self.t_due.append(t_due)
            self.t_submit.append(np.nan)
            self.t_done.append(np.nan)
            self.error.append(None)
            self.ids.append(None)
            self.dists.append(None)
            self._pending += 1
            return len(self.qidx) - 1

    def submitted(self, i: int, t: float) -> None:
        self.t_submit[i] = t

    def finish(self, i: int, fut) -> None:
        """Done-callback body: record the time and the answer or error."""
        t = time.perf_counter()
        exc = fut.exception()
        with self._lock:
            self.t_done[i] = t
            if exc is not None:
                self.error[i] = f"{type(exc).__name__}: {exc}"
            else:
                self.ids[i], self.dists[i] = fut.result()
            self._pending -= 1
            self._idle.notify_all()

    def fail(self, i: int, exc: BaseException) -> None:
        """A request the server refused at submit."""
        with self._lock:
            self.t_done[i] = time.perf_counter()
            self.error[i] = f"{type(exc).__name__}: {exc}"
            self._pending -= 1
            self._idle.notify_all()

    def wait_all(self, timeout_s: float) -> int:
        """Wait until every request has resolved; returns how many did not
        within ``timeout_s``."""
        deadline = time.perf_counter() + timeout_s
        with self._lock:
            while self._pending:
                left = deadline - time.perf_counter()
                if left <= 0:
                    break
                self._idle.wait(left)
            return self._pending

    def arrays(self) -> dict:
        with self._lock:
            return {
                "qidx": np.asarray(self.qidx, np.int64),
                "t_due": np.asarray(self.t_due, np.float64),
                "t_submit": np.asarray(self.t_submit, np.float64),
                "t_done": np.asarray(self.t_done, np.float64),
                "ok": np.asarray([e is None and d is not None for e, d in
                                  zip(self.error, self.ids)]),
            }
