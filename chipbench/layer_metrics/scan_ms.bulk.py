"""Executor time per batch: mean ``scan`` span, host clock, device fence
included, in ms."""
from chipbench.layer_metrics import mean_span_ms


def read(run):
    return mean_span_ms(run, "scan")
