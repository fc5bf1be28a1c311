"""Closed loop: a fixed number of requests outstanding at all times.

Each completion sends the next query.  ``VectorServer`` holds up to three
batches past its admission queue (one running, one handed off, one prepared
and waiting for the hand-off), so with ``outstanding`` at four times its
``max_batch`` a full batch is always queued when the batcher drains; with
fewer, a drain that finds the sender still refilling forms a smaller batch.
Queries cycle through the pool in an order drawn from the seed.  Before the
window opens the loop runs until ``warm_in`` requests have completed, so the
window starts with the pipeline full.

Parameters: ``outstanding``, ``warm_in``.

Completions come in bursts, one per batch, so a count over a fixed span of
time moves in steps of a whole batch.  The measured span therefore starts at
the first completion at or after the window opens and ends at the first
completion at or after its nominal end: it holds whole batches, and its
length is the time they took.  A request is in the window, and counts as
attempted, when it completes inside that span.
"""
from __future__ import annotations

import queue
import time

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["drive", "measured"]


def drive(server, queries, params, *, window, seed, log, errors):
    order = np.random.default_rng(seed).permutation(len(queries))
    done: "queue.SimpleQueue[int]" = queue.SimpleQueue()

    def send():
        n = len(log)
        i = log.new(order[n % len(order)], time.perf_counter())
        with TraceAnnotation("bench.submit"):
            try:
                fut = server.submit(queries[log.qidx[i]])
            except errors as e:
                log.fail(i, e)
                done.put(i)
                return
        log.submitted(i, log.t_due[i])
        fut.add_done_callback(lambda f, i=i: (log.finish(i, f), done.put(i)))

    def wait():
        with TraceAnnotation("bench.wait"):
            return done.get(timeout=120)

    for _ in range(int(params["outstanding"])):
        send()
    for _ in range(int(params["warm_in"])):
        wait()
        send()
    window.open()
    while True:
        wait()
        if time.perf_counter() >= window.end:
            break
        send()
    window.close()


def measured(arr, window):
    """(requests in the measured span, its length in seconds)."""
    t = arr["t_done"]

    def first_at_or_after(x):
        later = t[t >= x]       # NaN (never resolved) compares false
        if not later.size:
            raise RuntimeError(f"no request completed after {x}")
        return later.min()

    a, b = first_at_or_after(window.t0), first_at_or_after(window.end)
    return (t >= a) & (t < b), float(b - a)
