"""Share of the profiled window in which the device ran no operation,
from the profiler trace, in %."""
from chipbench.layer_metrics import device_idle_pct


def read(run):
    return device_idle_pct(run)
