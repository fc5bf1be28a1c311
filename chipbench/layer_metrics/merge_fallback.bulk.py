"""Share of the fused-batch batches whose slot-wise top-k certificate
failed, so that the batch re-ran its scan with the per-step merge, in %,
from the executor's counter over the window
(``repro_slot_topk_batches_total{outcome="certified"|"fallback"}``).
None when the program counted no such batch or keeps no such counter."""

COUNTER = "repro_slot_topk_batches_total"


def read(run):
    by_outcome: dict = {}
    for key, v in (run.counters or {}).get(COUNTER, {}).items():
        outcome = dict(p.split("=", 1) for p in key.split(","))["outcome"]
        by_outcome[outcome] = by_outcome.get(outcome, 0.0) + v
    total = sum(by_outcome.values())
    if not total:
        return None
    return 100.0 * by_outcome.get("fallback", 0.0) / total
