"""Admission wait per batch: mean ``admit`` span (the batch's first
enqueue to the batcher's drain returning: the wait for the batcher, plus
the flush window), in ms."""
from chipbench.layer_metrics import mean_span_ms


def read(run):
    return mean_span_ms(run, "admit")
