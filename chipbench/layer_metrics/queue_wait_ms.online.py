"""Admission wait per batch: mean ``queue`` span (the batch's first
enqueue to the start of its execution), in ms."""
from chipbench.layer_metrics import mean_span_ms


def read(run):
    return mean_span_ms(run, "queue")
