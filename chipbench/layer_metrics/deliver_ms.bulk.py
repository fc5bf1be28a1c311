"""Result delivery per batch: mean ``deliver`` span (the executor copying
each query's answer and resolving its future, whose done-callbacks run on
that thread), in ms."""
from chipbench.layer_metrics import mean_span_ms


def read(run):
    return mean_span_ms(run, "deliver")
