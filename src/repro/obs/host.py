"""Host-runtime witnesses: a stall sampler and the garbage collector's time.

While observability is on (``metrics.set_enabled(True)`` starts it,
``set_enabled(False)`` stops it; with ``REPRO_OBS=1`` importing
``repro.obs`` starts it), one daemon thread sleeps in steps of ``STEP_S``.
A wake-up more than ``LATE_S`` past its due time means the process, or at
least this thread's hold on the interpreter, stopped for that long:

    repro_host_stalls_total              late wake-ups
    repro_host_stall_seconds_total       their lateness, summed
    repro_host_gc_seconds_total{generation}
                                         time inside ``gc`` collections,
                                         from ``gc.callbacks``

Each stall also writes a ``repro.host_stall`` profiler annotation
(``trace.activity``) at the late wake-up, carrying ``lost_ms``: the
profiler opens an annotation only at the present moment, so the marker
ends the lost interval rather than covering it.  The counters are
registered at zero when the sampler starts, so a reader can tell "never
stalled" from "not sampled".

The GC callback runs inside the collector, possibly while the registry's
lock is held by the same thread, so it only adds to a per-generation list;
the sampler thread moves those seconds into the registry.
"""
from __future__ import annotations

import gc
import threading
import time

from . import metrics as _metrics
from . import trace as _trace

__all__ = ["STEP_S", "LATE_S", "start", "stop"]

STEP_S = 0.010
LATE_S = 0.100
_THREAD_NAME = "repro-host-sampler"
_GENERATIONS = 3


class _Sampler:
    def __init__(self):
        self._stop = threading.Event()
        self._gc_s = [0.0] * _GENERATIONS   # written by the GC callback only
        self._gc_flushed = [0.0] * _GENERATIONS
        self._gc_t0 = 0.0
        self.thread = threading.Thread(
            target=self._loop, name=_THREAD_NAME, daemon=True
        )

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._gc_s[info["generation"]] += (
                time.perf_counter() - self._gc_t0
            )

    def _flush_gc(self, reg) -> None:
        for gen in range(_GENERATIONS):
            seen = self._gc_s[gen]
            if seen != self._gc_flushed[gen]:
                reg.counter("repro_host_gc_seconds_total",
                            seen - self._gc_flushed[gen], generation=gen)
                self._gc_flushed[gen] = seen

    def start(self) -> None:
        reg = _metrics.get_registry()
        reg.counter("repro_host_stalls_total", 0.0)
        reg.counter("repro_host_stall_seconds_total", 0.0)
        for gen in range(_GENERATIONS):
            reg.counter("repro_host_gc_seconds_total", 0.0, generation=gen)
        self.thread.start()

    def _loop(self) -> None:
        reg = _metrics.get_registry()
        gc.callbacks.append(self._on_gc)
        try:
            due = time.perf_counter() + STEP_S
            while not self._stop.wait(max(due - time.perf_counter(), 0.0)):
                now = time.perf_counter()
                late = now - due
                if late > LATE_S:
                    reg.counter("repro_host_stalls_total")
                    reg.counter("repro_host_stall_seconds_total", late)
                    with _trace.activity("host_stall", lost_ms=late * 1e3):
                        pass
                self._flush_gc(reg)
                due = now + STEP_S
        finally:
            gc.callbacks.remove(self._on_gc)
            self._flush_gc(reg)


_LOCK = threading.Lock()
_SAMPLER = None


def start() -> None:
    """Start the sampler thread, if it is not running."""
    global _SAMPLER
    with _LOCK:
        if _SAMPLER is None:
            _SAMPLER = _Sampler()
            _SAMPLER.start()


def stop() -> None:
    """Stop the sampler thread and wait for it, if it is running."""
    global _SAMPLER
    with _LOCK:
        s, _SAMPLER = _SAMPLER, None
        if s is not None:
            s._stop.set()
            s.thread.join()
