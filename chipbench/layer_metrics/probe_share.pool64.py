"""Share of the store's partitions that the routed batches scanned, in %:
the partitions some query of a batch probes over the store's, summed over
the window's batches, from the executors' counter
``repro_ivf_probe_partitions_total{set="scanned"|"store"}``.  None when the
program counted no routed batch or keeps no such counter."""

COUNTER = "repro_ivf_probe_partitions_total"


def read(run):
    by_set: dict = {}
    for key, v in (run.counters or {}).get(COUNTER, {}).items():
        which = dict(p.split("=", 1) for p in key.split(","))["set"]
        by_set[which] = by_set.get(which, 0.0) + v
    store = by_set.get("store", 0.0)
    if not store:
        return None
    return 100.0 * by_set.get("scanned", 0.0) / store
