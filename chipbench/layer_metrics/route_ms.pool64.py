"""Device routing per batch: mean ``route`` span, in ms: the host time to
dispatch the centroid ranking and the per-query probe mask
(``IVFIndex.probe_mask``) from the fused executor, not fenced.  None when
no batch routed: no ``nprobe`` stated, or a program without device
routing."""
from chipbench.layer_metrics import mean_span_ms


def read(run):
    return mean_span_ms(run, "route")
