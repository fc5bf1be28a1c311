"""Share of its roofline that the batched scan kernel
(``kernels/batched_matmul.py`` ``_bmm_quant_kernel``) reaches, in %.

Measured: the device time of the kernel's events in the profiled window,
and how many there were; its events are the ``tpu_custom_call`` named
after ``batched_distance_quant_pallas``, the jitted function that launches
it.  Least time per call: the larger of 2*B*C*D operations at the bf16
peak and the tile, queries and distances at the HBM peak
(``work.bmm_quant_call``), B being the batch's shape bucket, taken
from the server's per-bucket batch counters over the same seconds, and C, D
the store's tile shape.
"""
from chipbench.work import bmm_quant_call, least_time_s

KERNEL = "batched_distance_quant_pallas"


def is_kernel(text: str) -> bool:
    name = text.split(" = ", 1)[0]
    return KERNEL in name and "tpu_custom_call" in text


def read(run):
    if run.trace is None or not run.traced:
        return None
    calls, seconds = run.trace.op_seconds(is_kernel)
    buckets: dict = {}
    for key, v in run.traced.get("repro_serve_batches_total", {}).items():
        b = int(dict(p.split("=", 1) for p in key.split(","))["bucket"])
        buckets[b] = buckets.get(b, 0.0) + v
    total = sum(buckets.values())
    if not calls or seconds <= 0 or not total:
        return None
    st, peaks = run.store, run.peaks()
    least = sum(
        share / total * least_time_s(
            *bmm_quant_call(b, st["C"], st["D"], st["value_bytes"]), peaks
        )[0]
        for b, share in buckets.items()
    )
    return 100.0 * least * calls / seconds
