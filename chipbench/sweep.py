#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the highest offered rate the server
sustains, found once on the chip and written into the cell's traffic file.

    python chipbench/sweep.py --workload openai1536.online --seed 5 \
        --rates 600,900,1200,1500,1800,2100 --seconds 10

One process builds the cell's engine and server as a run does, then offers
each rate in turn (Poisson arrivals from the seed) for ``--seconds``.  Per
rate it prints one JSON line: the rate offered and completed, the requests
outstanding at the middle and at the end of the window, rejections, and
the latency median and 99th percentile.  The knee is the highest rate at
which the window ends with no rejection and no backlog that grew over its
second half by more than one batch.  Executables made while a rate ran are
counted too: there should be none.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def outstanding_at(arr, t: float) -> int:
    import numpy as np

    sent = arr["t_submit"] <= t
    open_ = ~(arr["t_done"] <= t)   # NaN (never resolved) counts as open
    return int(np.sum(sent & open_))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="queries/s, comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import numpy as np

    from chipbench import harness
    from chipbench.requests import RequestLog, Window
    from repro.core.engine import VectorSearchEngine
    from repro.serve.batcher import ServeError
    from repro.serve.vector import VectorServer

    harness.require_chips(1)
    harness.enable_compile_cache()
    _, config, traffic = harness.cell_parts(harness.load_benchmark(),
                                            args.workload)
    loop = harness.load_module(f"traffic/{traffic['loop']}.py")
    data = config["data"]
    gen = harness.load_module(f"generators/{data['generator']}.py")
    X, Q = gen.generate(data["seed"], args.seed, int(config["n"]),
                        int(config["dim"]), int(traffic["query_pool"]), data)
    X, Q = np.asarray(X), np.asarray(Q)
    eng = VectorSearchEngine.build(X, **config["build"])
    del X
    with VectorServer(eng, spec=eng.spec.replace(k=int(config["check"]["k"])),
                      **config["server"]) as server, \
            harness.CompileCounter() as cc:
        server.warmup()
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            c0 = cc.read()[0]
            log = RequestLog()
            window = Window(args.seconds)
            loop.drive(server, Q, {**traffic, "rate_qps": rate},
                       window=window, seed=args.seed + i, log=log,
                       errors=(ServeError,))
            arr = log.arrays()
            mid = outstanding_at(arr, window.t0 + args.seconds / 2)
            end = outstanding_at(arr, window.end)
            log.wait_all(harness.LATE_WAIT_S)
            arr = log.arrays()
            lat = np.sort(arr["t_done"] - arr["t_due"]) * 1e3
            rejected = int(np.sum(~arr["ok"]))
            done = int(np.sum(arr["t_done"] < window.end))
            knee_ok = rejected == 0 and end - mid <= server.max_batch
            print(json.dumps({
                "rate_qps": rate,
                "offered_qps": len(lat) / args.seconds,
                "completed_qps": done / args.seconds,
                "outstanding_mid": mid, "outstanding_end": end,
                "rejected": rejected,
                "p50_ms": float(np.percentile(lat, 50)),
                "p99_ms": float(np.percentile(lat, 99)),
                "sustained": knee_ok,
                "executables_made": cc.read()[0] - c0,
            }), flush=True)
            time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
