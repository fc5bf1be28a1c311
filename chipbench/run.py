#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics and the profiled
window's device record and breakdown.  Set-up lines and the compared
numbers, each with its limit, go to standard error, the compared numbers
last; the last line of standard output is the result as one JSON object.
Exits 3, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for; 2 when the program or the benchmark's files are missing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
        from chipbench import harness
        bench = harness.load_benchmark(ROOT)
        harness.cell_parts(bench, args.workload)
    except (ImportError, OSError, KeyError) as e:
        print(f"chipbench: cannot set up {args.workload!r}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 2
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               bench=bench)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
