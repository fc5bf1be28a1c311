#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, on the chip.

    python chipbench/control.py --workload <cell> --seeds 11,12,13

For each seed: the cell's corpus and query pool at its own size, as a run
makes them; as many queries from the pool as a run checks, drawn from the
seed; and in the program's place the plain reference computed in bfloat16,
one precision step below the float32 the configuration states
(``references/<name>.py`` ``control_answers``).  Its answers go through the
same comparison as a run's, against the same limits, and every one of its
numbers is printed: one JSON line per seed.  The control has to come out
not correct; the smallest reading of each number over the seeds is the
upper reading its limit is set below.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control_once(config: dict, traffic: dict, seed: int) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from chipbench import compare, harness

    data, check = config["data"], config["check"]
    gen = harness.load_module(f"generators/{data['generator']}.py")
    ref = harness.load_module(f"references/{check['reference']}.py")
    pool = int(traffic["query_pool"])
    X, Q = gen.generate(data["seed"], seed, int(config["n"]),
                        int(config["dim"]), pool, data)
    rng = np.random.default_rng([seed, 0xC0FFEE])
    pick = rng.integers(0, pool, size=int(check["sample"]))
    Qs = jnp.asarray(np.asarray(Q)[pick])
    k = int(check["k"])
    ids, dists = ref.control_answers(X, Qs, k)
    numbers, correct = compare.compare(ref, X, Qs, ids, dists, k,
                                       check["limits"])
    return {"seed": seed, "correct": correct, "check": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    from chipbench import harness

    harness.require_chips(1)
    harness.enable_compile_cache()
    _, config, traffic = harness.cell_parts(harness.load_benchmark(),
                                            args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = control_once(config, traffic, seed)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
