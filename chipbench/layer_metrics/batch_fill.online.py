"""Queries per executed batch over the window, from the server's counters
(``repro_serve_queries_total`` / ``repro_serve_batches_total``)."""


def read(run):
    batches = run.counter("repro_serve_batches_total")
    if not batches:
        return None
    return run.counter("repro_serve_queries_total") / batches
