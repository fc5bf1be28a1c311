"""Hand-off wait per batch: mean ``handoff`` span (the end of planning to
the executor's start: the batcher blocked on the depth-1 hand-off queue,
then the batch in it), in ms."""
from chipbench.layer_metrics import mean_span_ms


def read(run):
    return mean_span_ms(run, "handoff")
