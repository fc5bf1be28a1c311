"""Time the host lost to stalls over the profiled seconds of the window:
the growth of ``repro_host_stall_seconds_total`` (a sampler thread's
wake-ups more than 100 ms late, ``repro.obs.host``) between the counter
snapshots taken inside the profile, after ``start_trace`` and before
``stop_trace``, in ms.  0.0 when the counter is there and never grew; None
when the run was not traced or the program does not keep it."""

COUNTER = "repro_host_stall_seconds_total"


def read(run):
    if COUNTER not in (run.traced or {}):
        return None
    return 1e3 * sum(run.traced[COUNTER].values())
