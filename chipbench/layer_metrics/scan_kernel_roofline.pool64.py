"""Share of its roofline that the batched scan kernel
(``_bmm_quant_kernel``, launched as ``batched_distance_quant_pallas``)
reaches in the routed union scan, in %.  The routed scan calls the same
kernel once per partition it reads, on the same (B, D) query block and
(D, C) tile, so each call's least time is the bulk cells'
(``work.bmm_quant_call``) and the reading is the bulk reader's, over the
kernel's events in this cell's profile."""
from chipbench.harness import load_module

read = load_module("layer_metrics/scan_kernel_roofline.bulk.py").read
