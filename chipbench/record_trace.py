#!/usr/bin/env python3
"""Record a small profiler trace of the served batched scan on the chip.

    python chipbench/record_trace.py --out <dir>

A 20,000 x 1536 corpus behind ``VectorServer`` (the benchmark's
configuration otherwise), 64-query batches for 0.15 s under the
profiler, inside the ``bench.trace_window`` annotation the benchmark itself
uses.  Writes ``<dir>/served_scan.xplane.pb.gz`` (the test data of
``tests/test_tracing.py``) and prints the trace's planes, lines and busiest
device operations with their stats.
"""
import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--n", type=int, default=20000)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import harness
    from chipbench.tracing import WINDOW_ANNOTATION, load_profile
    from repro.core.engine import VectorSearchEngine
    from repro.serve.vector import VectorServer

    harness.require_chips(1)
    config = json.loads((harness.BENCH_DIR / "configs/openai1536.json")
                        .read_text())
    gen = harness.load_module("generators/clustered_unit.py")
    X, Q = gen.generate(7, 7, args.n, config["dim"], 128, config["data"])
    eng = VectorSearchEngine.build(np.asarray(X), **config["build"])
    Q = np.asarray(Q)
    tmp = tempfile.mkdtemp(prefix="chipbench-record-")
    with VectorServer(eng, spec=eng.spec.replace(k=10),
                      **config["server"]) as server:
        server.warmup(buckets=[64])
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            t_end = time.perf_counter() + 0.15
            while time.perf_counter() < t_end:
                futs = [server.submit(q) for q in Q]
                for f in futs:
                    f.result()
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "served_scan.xplane.pb.gz")
    with open(path, "rb") as f, gzip.open(dst, "wb") as g:
        g.write(f.read())
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {dst}: {os.path.getsize(dst)} bytes")

    pd = load_profile(dst)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: lines "
              f"{[(ln.name, sum(1 for _ in ln.events)) for ln in lines]}")
        if not plane.name.startswith("/device:"):
            continue
        for ln in lines:
            dur, seen = Counter(), {}
            for ev in ln.events:
                dur[ev.name] += ev.duration_ns
                seen.setdefault(ev.name, dict(ev.stats))
            for name, ns in dur.most_common(8):
                stats = {k: str(v)[:160] for k, v in seen[name].items()}
                print(f"  {ln.name!r} {name!r} {ns / 1e6:.3f} ms {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
