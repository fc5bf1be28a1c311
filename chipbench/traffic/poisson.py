"""Open loop: Poisson arrivals at a fixed rate, whatever the server does.

Arrival times and the query of each arrival are drawn from the seed before
the window opens; the loop sleeps until each is due and sends it, late if it
fell behind.  Every request is timed from when it was due, so a stall also
counts against the requests that queued behind it.  How late the loop sent
is recorded per request (``t_submit - t_due``).

Parameters: ``rate_qps``.  A request is in the window, and counts as
attempted, when it was due inside it; the window lasts its nominal length.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["drive", "measured", "arrivals"]


def arrivals(rate_qps: float, seconds: float, n_pool: int, seed: int):
    """(offsets in seconds from the window's start, pool indices)."""
    rng = np.random.default_rng(seed)
    n_max = int(rate_qps * seconds + 10 * np.sqrt(rate_qps * seconds) + 10)
    t = np.cumsum(rng.exponential(1.0 / rate_qps, size=n_max))
    t = t[t < seconds]
    return t, rng.integers(0, n_pool, size=len(t))


def drive(server, queries, params, *, window, seed, log, errors):
    offsets, qidx = arrivals(float(params["rate_qps"]), window.seconds,
                             len(queries), seed)
    t0 = window.open()
    for off, q in zip(offsets, qidx):
        due = t0 + off
        delay = due - time.perf_counter()
        if delay > 0:
            with TraceAnnotation("bench.sleep"):
                time.sleep(delay)
        i = log.new(q, due)
        with TraceAnnotation("bench.submit"):
            try:
                fut = server.submit(queries[q])
            except errors as e:
                log.fail(i, e)
                continue
        log.submitted(i, time.perf_counter())
        fut.add_done_callback(lambda f, i=i: log.finish(i, f))
    delay = window.end - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    window.close()


def measured(arr, window):
    """(requests due inside the window: every one sent, its length)."""
    due = arr["t_due"]
    return (due >= window.t0) & (due < window.end), window.seconds
