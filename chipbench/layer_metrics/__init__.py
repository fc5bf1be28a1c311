"""Per-layer metric readers, one module per metric name (``plan_ms.bulk``
is ``plan_ms.bulk.py``), and what they share.  A reader returns None when
its run holds nothing for it to read."""


def mean_span_ms(run, name: str):
    """Mean duration of the ``name`` span over the window's served batches
    (``repro.obs.trace``, host clock)."""
    ms = run.span_ms(name)
    return sum(ms) / len(ms) if ms else None


def device_idle_pct(run):
    """Share of the profiled window in which no operation ran on the
    device."""
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
