#!/usr/bin/env python3
"""Bring-up smoke test: ``VectorServer`` end to end on a TPU.

    python chip_smoke.py [--seed 0] [--n 500000]     # one chip
    python chip_smoke.py --chips 4                   # routed_bucket on 4 chips

One chip: a 500,000 x 1536 clustered corpus (the VECTOR(1536) text-embedding
width) is served by an IVF + ADSampling engine with an int8 scan mirror
through ``VectorServer``: single queries (``fused-scan``), a burst
(``fused-batch``), the multi-resolution cascade (``cascade-batch`` and
``cascade-scan``) and an insert.  Every plan must run the Pallas kernels,
return the same ids as the ``kernel="jnp"`` bodies on the same chip (up to
printed f32 ties), and mint no executable after ``warmup()``; the cascade
and a flat BOND engine must reach recall@10 = 1.0 against the NumPy ground
truth.  ``--chips 4`` builds the same corpus on a 4-device ``data`` mesh,
serves a burst through ``routed_bucket`` and compares it with the same
engine searched on one device and with the ground truth, and nothing else.

Any failed check raises, so the exit code is non-zero.  Phase times, compile
counts and device memory are printed as information.  The last line of
stdout is the JSON device record, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DIM = 1536                  # VECTOR(1536): OpenAI text-embedding width
N_VECTORS = 500_000
N_QUERIES = 64
K = 10
CASCADE = ("proj32:int8", "int4", "f32")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    print(f"[phase] {name} ...", flush=True)
    yield
    print(f"[phase] {name}: ok in {time.perf_counter() - t0:.3f} s",
          flush=True)


def device_record(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d.platform == "tpu", f"no TPU: JAX platform is {d.platform!r}")
    check(len(devs) >= n_chips, f"{n_chips} chips asked, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind, "count": n_chips}


def _proc_kb(path: str, key: str) -> str:
    try:
        with open(path) as f:
            for line in f:
                if line.startswith(key + ":"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return "n/a"


def print_memory(label: str, devices) -> None:
    print(f"[memory] {label} host: rss={_proc_kb('/proc/self/status', 'VmRSS')}"
          f" available={_proc_kb('/proc/meminfo', 'MemAvailable')}",
          flush=True)
    for d in devices:
        st = d.memory_stats() or {}
        print(f"[memory] {label} {d}: bytes_in_use="
              f"{st.get('bytes_in_use', 'n/a')} peak_bytes_in_use="
              f"{st.get('peak_bytes_in_use', 'n/a')}", flush=True)


def make_corpus(n: int, dim: int, seed: int):
    """Clustered corpus + queries from the seed, and the exact top-K of
    every query by the NumPy reference (queries split over threads; each
    query's answer does not depend on the split)."""
    import numpy as np

    from repro.data.synthetic import ground_truth, make_dataset

    with phase(f"data n={n} D={dim} queries={N_QUERIES} seed={seed}"):
        X, Q = make_dataset(n, dim, "clustered", n_queries=N_QUERIES,
                            seed=seed)
    with phase("ground truth (numpy, exact)"):
        parts = np.array_split(np.arange(len(Q)), min(len(Q), 8))
        with ThreadPoolExecutor(len(parts)) as ex:
            outs = list(ex.map(
                lambda idx: ground_truth(X, Q[idx], K, chunk=16384), parts
            ))
        gt_ids = np.concatenate([o[0] for o in outs])
    return X, Q, gt_ids


def recall(ids, gt_ids) -> float:
    from repro.data.synthetic import recall_at_k

    return float(recall_at_k(ids, gt_ids))


def same_ids(name, got, want, rtol=1e-5):
    """Equal id sets per query, except ties: an id in one answer only must
    sit within f32 rounding of the other answer's k-th distance."""
    import numpy as np

    (gi, gd), (wi, wd) = got, want
    gi, gd, wi, wd = (np.atleast_2d(np.asarray(a)) for a in (gi, gd, wi, wd))
    ties = 0
    for qi in range(len(gi)):
        only_g = set(gi[qi].tolist()) - set(wi[qi].tolist())
        only_w = set(wi[qi].tolist()) - set(gi[qi].tolist())
        if not only_g and not only_w:
            continue
        for ids, d, other_d, extra in ((gi, gd, wd, only_g),
                                       (wi, wd, gd, only_w)):
            kth = float(np.max(other_d[qi]))
            for x in extra:
                dx = float(d[qi][list(ids[qi]).index(x)])
                check(abs(dx - kth) <= rtol * abs(kth),
                      f"{name}: query {qi} id {x} at distance {dx} is not a "
                      f"tie with the k-th distance {kth}")
        ties += 1
        print(f"[tie] {name}: query {qi} differs only by f32 ties "
              f"{sorted(only_g)} vs {sorted(only_w)}", flush=True)
    print(f"[check] {name}: ids equal for {len(gi) - ties}/{len(gi)} "
          f"queries, {ties} f32 ties", flush=True)


def executors_run(snapshot_before, snapshot_after) -> dict:
    """{executor: batches} served between two metrics snapshots."""
    def counts(snap):
        out = {}
        series = snap["counters"].get("repro_serve_batches_total", {})
        for labels, v in series.items():
            ex = dict(kv.split("=", 1) for kv in labels.split(","))
            out[ex["executor"]] = out.get(ex["executor"], 0) + v
        return out

    a, b = counts(snapshot_before), counts(snapshot_after)
    return {ex: b[ex] - a.get(ex, 0) for ex in b if b[ex] > a.get(ex, 0)}


def serve_phase(server, eng, name, queries, spec, expect, pallas=True):
    """Submit ``queries`` at once; assert only ``expect`` executors ran,
    each planned with the Pallas kernels where ``pallas``.  Returns
    (ids, dists)."""
    import numpy as np

    before = eng.metrics()
    futs = [server.submit(q, spec) for q in queries]
    out = [f.result() for f in futs]
    ran = executors_run(before, eng.metrics())
    print(f"[serve] {name}: {len(queries)} queries, batches per executor "
          f"{ran}", flush=True)
    check(set(ran) <= set(expect) and set(expect) & set(ran),
          f"{name}: expected executors {expect}, ran {ran}")
    for b in sorted({1, len(queries)}) if pallas else ():
        plan = eng.plan(queries[:b], spec)
        check("kernel=pallas" in plan.reason,
              f"{name}: plan for B={b} does not run Pallas: {plan.reason}")
    return (np.stack([o[0] for o in out]), np.stack([o[1] for o in out]))


def run_one_chip(n: int, dim: int, seed: int, kernel: str = "auto") -> None:
    import jax
    import numpy as np

    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.obs import metrics
    from repro.serve.vector import VectorServer, jit_compile_count

    metrics.set_enabled(True)
    X, Q, gt_ids = make_corpus(n, dim, seed)
    dev0 = jax.devices()[:1]
    print_memory("after data", dev0)

    from repro.core.pruners import make_adsampling

    rotation = make_adsampling(dim, seed=seed)
    with phase("adsampling rotation of the corpus (host numpy, set-up)"):
        # the same matmul the build runs, timed alone
        rotation.preprocess(X)
    with phase("build IVF engine (adsampling, scan_dtype=int8)"):
        eng = VectorSearchEngine.build(
            X, index="ivf", pruner="adsampling", scan_dtype="int8",
            kernel=kernel, seed=seed,
        )
        # no local reference to the store: the first insert replaces it,
        # and HBM holds only one f32 copy of this corpus
        print(f"[info] nlist={eng.ivf.nlist} "
              f"partitions={eng.store.num_partitions} "
              f"capacity={eng.store.capacity}", flush=True)
    print_memory("after IVF build", dev0)

    spec = eng.spec.replace(k=K)
    cspec = spec.replace(cascade=CASCADE)
    with VectorServer(eng, spec=spec, max_batch=N_QUERIES) as server:
        with phase("warmup (default + cascade specs)"):
            c0 = jit_compile_count()
            warm = server.warmup(specs=[cspec])
            print(f"[info] warmup compiles={jit_compile_count() - c0} "
                  f"buckets={warm}", flush=True)
        print_memory("after warmup", dev0)

        singles = Q[:4]
        with phase("single submits -> fused-scan"):
            one = [server.search(q) for q in singles]
            one = (np.stack([o[0] for o in one]),
                   np.stack([o[1] for o in one]))
        with phase("burst -> fused-batch"):
            burst = serve_phase(server, eng, "burst", Q, spec,
                                ["fused-batch", "fused-scan"])
        with phase("cascade burst -> cascade-batch"):
            cburst = serve_phase(server, eng, "cascade burst", Q, cspec,
                                 ["cascade-batch", "cascade-scan"])
        with phase("cascade single -> cascade-scan"):
            csingle = serve_phase(server, eng, "cascade single", Q[:1],
                                  cspec, ["cascade-scan"])
        for b in (1, len(Q)):
            reason = eng.plan(Q[:b], spec).reason
            check("kernel=pallas" in reason, f"B={b}: {reason}")
        n_new = server.jit_compiles_since_warmup()
        print(f"[check] compiles since warmup: {n_new}", flush=True)
        check(n_new == 0, f"{n_new} executables minted after warmup")

        with phase("same queries through the jnp bodies"):
            jspec, jcspec = spec.replace(kernel="jnp"), \
                cspec.replace(kernel="jnp")
            ref_one = [eng.search(q, jspec) for q in singles]
            same_ids("fused-scan vs jnp", one,
                     (np.stack([r.ids for r in ref_one]),
                      np.stack([r.dists for r in ref_one])))
            r = eng.search(Q, jspec)
            check(r.plan.executor == "fused-batch", r.plan.reason)
            same_ids("fused-batch vs jnp", burst, (r.ids, r.dists))
            r = eng.search(Q, jcspec)
            check(r.plan.executor == "cascade-batch", r.plan.reason)
            same_ids("cascade-batch vs jnp", cburst, (r.ids, r.dists))
            r = eng.search(Q[0], jcspec)
            check(r.plan.executor == "cascade-scan", r.plan.reason)
            same_ids("cascade-scan vs jnp", csingle, (r.ids, r.dists))

        rc = recall(cburst[0], gt_ids)
        print(f"[check] cascade recall@{K} = {rc}", flush=True)
        check(rc == 1.0, f"exact-safe cascade recall@{K} = {rc} < 1.0")

        with phase("insert, then search the new rows"):
            rng = np.random.default_rng(seed + 1)
            X_new = (Q[:4] + rng.normal(0, 1e-3, Q[:4].shape)).astype(
                np.float32)
            new_ids = server.insert(X_new).result()
            got = [server.search(x)[0][0] for x in X_new]
            print(f"[info] inserted ids {list(new_ids)} -> top-1 {got}",
                  flush=True)
            check(list(got) == list(new_ids),
                  f"inserted rows not returned: {got} vs {list(new_ids)}")
    print_memory("after IVF phases", dev0)
    del eng, server

    with phase("flat BOND engine, exact batch"):
        bond = VectorSearchEngine.build(X, pruner="bond", kernel=kernel)
        res = bond.search(Q, SearchSpec(k=K, kernel=kernel))
        print(f"[info] executor={res.plan.executor} "
              f"reason={res.plan.reason}", flush=True)
        check("kernel=pallas" in res.plan.reason, res.plan.reason)
        rb = recall(res.ids, gt_ids)
        print(f"[check] BOND flat recall@{K} = {rb}", flush=True)
        check(rb == 1.0, f"BOND flat recall@{K} = {rb} < 1.0")
    print_memory("after BOND", dev0)


def run_four_chips(n: int, dim: int, seed: int) -> None:
    import jax

    from repro.core.engine import VectorSearchEngine
    from repro.obs import metrics
    from repro.serve.vector import VectorServer

    metrics.set_enabled(True)
    X, Q, gt_ids = make_corpus(n, dim, seed)
    devs = jax.devices()[:4]
    mesh = jax.make_mesh((4,), ("data",), devices=devs)
    with phase("build IVF engine on a 4-device data mesh"):
        eng = VectorSearchEngine.build(
            X, index="ivf", pruner="adsampling", scan_dtype="int8",
            seed=seed, mesh=mesh,
        )
    # every bucket probed: the routed answer is then exact, so it must
    # equal the one-device search and the ground truth
    spec = eng.spec.replace(k=K, nprobe=eng.ivf.nlist)
    with VectorServer(eng, spec=spec, max_batch=N_QUERIES) as server:
        with phase("warmup"):
            server.warmup()
        with phase("burst -> routed_bucket"):
            # the shard-local scans are XLA bodies, no Pallas kernel
            routed = serve_phase(server, eng, "routed burst", Q, spec,
                                 ["routed_bucket"], pallas=False)
    print_memory("after routed burst", devs)
    with phase("same burst on one device"):
        single = dataclasses.replace(eng, mesh=None).search(Q, spec)
        print(f"[info] one-device executor={single.plan.executor}",
              flush=True)
        same_ids("routed_bucket vs one device", routed,
                 (single.ids, single.dists))
    rr = recall(routed[0], gt_ids)
    print(f"[check] routed recall@{K} = {rr}", flush=True)
    check(rr == 1.0, f"routed_bucket recall@{K} = {rr} < 1.0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=N_VECTORS,
                    help="corpus size (the width stays 1536)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    record = device_record(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[info] compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.n, DIM, args.seed)
    else:
        run_one_chip(args.n, DIM, args.seed)
    print(f"[info] total {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": record}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
