"""Host planning per batch: mean ``plan`` span (``plan_search`` +
``prepare_execute`` on the batcher thread), in ms."""
from chipbench.layer_metrics import mean_span_ms


def read(run):
    return mean_span_ms(run, "plan")
