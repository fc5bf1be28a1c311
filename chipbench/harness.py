"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything that belongs to a configuration, a traffic mix or a metric is
found by its name in ``BENCHMARK.json``:

- ``configs/<config>.json``: the deployment (data, engine, server, check);
- ``generators/<data.generator>.py``: makes its corpus on the device;
- ``references/<check.reference>.py``: its plain reference;
- ``traffic/<traffic>.json``: the mix, run by ``traffic/<loop>.py``;
- ``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py``: one reader
  per metric, ``read(run) -> float | None`` (None: nothing to read, and the
  metric is left out of the result line).

A run: make the configuration's corpus and, from the seed, the query pool
on the device, build the engine and the server, warm every pow2 batch
bucket (all of that is set-up), then drive the server for the window.
With ``trace`` the metrics registry and the query spans are on, and a few
seconds in the middle of the window are profiled.  After the window every request is
awaited, the device's memory peak read, the program freed, and a sample of
the answers compared with the reference.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from . import compare as _compare
from .requests import RequestLog, Window
from .tracing import WINDOW_ANNOTATION, reduce_trace

__all__ = ["NoChip", "load_benchmark", "cell_parts", "metrics_for",
           "load_module", "run_cell", "BENCH_DIR", "ROOT"]

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: how long after the window opens the profiler starts, and for how long
TRACE_OFFSET_S = 1.0
TRACE_SECONDS = 3.0
#: how long past the window's close a request may still come back
LATE_WAIT_S = 60.0


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------- lookup
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _json(rel: str) -> dict:
    path = BENCH_DIR / rel
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    return json.loads(path.read_text())


def cell_parts(bench: dict, workload: str):
    """(cell entry, configuration, traffic mix) of ``workload``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have {sorted(cells)})")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = _json(f"traffic/{cell['traffic']}.json")
    return cell, config, traffic


def metrics_for(bench: dict, workload: str) -> tuple[list, list]:
    """The cell's end-to-end metrics and its per-layer metrics."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def load_module(rel: str):
    """The module at ``chipbench/<rel>`` (names may hold dots)."""
    path = BENCH_DIR / rel
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(
        "chipbench._by_name." + rel.replace("/", ".").removesuffix(".py"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- device
def require_chips(n: int):
    """The first device, when JAX finds at least ``n`` TPU chips."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                     "this benchmark measures the chip only")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return devs


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # every executable, however quick to compile, is kept, so a run after
    # the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included) and
    persistent-cache hits, from JAX's monitoring events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        self.compile_s = 0.0
        self._lock = threading.Lock()

    def _on_duration(self, event, duration, **_):
        if event == self._COMPILE:
            with self._lock:
                self.compiles += 1
                self.compile_s += duration

    def _on_event(self, event, **_):
        if event == self._HIT:
            with self._lock:
                self.hits += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False

    def read(self) -> tuple[int, int, float]:
        with self._lock:
            return self.compiles, self.hits, self.compile_s


# ------------------------------------------------------------- the run
class Run:
    """What the metric readers read.  Times are ``perf_counter`` seconds;
    ``in_window`` and ``window_s`` are the traffic loop's measured span."""

    def __init__(self, **kw):
        self.spans = None       # served batches' QueryTraces in the window
        self.counters = None    # counter deltas over the window
        self.traced = None      # counter deltas over the profiled part
        self.trace = None       # tracing.TraceSummary of the profiled part
        self.__dict__.update(kw)

    def span_ms(self, name: str) -> list:
        out = []
        for tr in self.spans or ():
            s = tr.find(name)
            if s is not None:
                out.append(s.duration_s * 1e3)
        return out

    def counter(self, name: str) -> float:
        """A counter's increase over the window, summed over its labels."""
        return sum((self.counters or {}).get(name, {}).values())

    def peaks(self) -> dict:
        from .work import load_peaks

        return load_peaks(self.device_kind)


def _counter_delta(a: dict, b: dict) -> dict:
    out = {}
    for name, series in b["counters"].items():
        before = a["counters"].get(name, {})
        out[name] = {k: v - before.get(k, 0.0) for k, v in series.items()
                     if v - before.get(k, 0.0)}
    return out


class _Observer:
    """A traced run's instruments: the metrics registry and query spans
    over the window, the profiler over a few seconds inside it."""

    def __init__(self, seconds: float):
        from repro.obs import metrics, trace

        self._metrics, self._tracer = metrics, trace.get_tracer()
        self.offset = TRACE_OFFSET_S if seconds >= 2 * TRACE_OFFSET_S else 0.0
        self.length = min(TRACE_SECONDS, seconds - self.offset)
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.spans: dict = {}
        self.traced = None
        self._open = threading.Event()
        self._stop = threading.Event()
        self._snap0 = None
        self._error = None
        self._threads = [
            threading.Thread(target=self._collect, name="bench-spans",
                             daemon=True),
            threading.Thread(target=self._profile, name="bench-profiler",
                             daemon=True),
        ]

    def start(self):
        self._metrics.set_enabled(True)
        self._tracer.clear()
        for t in self._threads:
            t.start()

    def on_open(self, t0: float):
        self._snap0 = self._metrics.get_registry().snapshot()
        self._open.set()

    def _collect(self):
        while not self._stop.wait(0.25):
            self._take()

    def _take(self):
        for tr in self._tracer.traces():
            self.spans.setdefault(tr.trace_id, tr)

    def _profile(self):
        import jax

        try:
            self._open.wait()
            if self._stop.wait(self.offset):
                return
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                    a = self._metrics.get_registry().snapshot()
                    time.sleep(self.length)
                    b = self._metrics.get_registry().snapshot()
            finally:
                jax.profiler.stop_trace()
            self.traced = _counter_delta(a, b)
        except Exception as e:  # reported with the run, never swallowed
            self._error = e

    def finish(self, t0: float, t1: float):
        """(spans in the window, counter deltas over it)."""
        self._stop.set()
        for t in self._threads:
            t.join()
        if self._error is not None:
            raise self._error
        self._take()
        snap1 = self._metrics.get_registry().snapshot()
        self._metrics.set_enabled(False)
        spans = [tr for tr in self.spans.values()
                 if tr.attrs.get("served") and t0 <= tr.t0 < t1]
        return spans, _counter_delta(self._snap0, snap1)

    def summary(self):
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        try:
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, found {files}")
            return reduce_trace(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: dict | None = None,
             parts: tuple | None = None, require_tpu: bool = True) -> dict:
    """One run; returns the result line as a dict.  ``parts`` replaces
    (cell, config, traffic) from ``BENCHMARK.json`` (tests use tiny ones);
    ``require_tpu=False`` skips the look for a chip (tests only)."""
    bench = bench if bench is not None else load_benchmark()
    cell, config, traffic = parts or cell_parts(bench, workload)
    e2e, layer = metrics_for(bench, cell["name"])
    chips = int(cell.get("chips", 1))

    import jax

    devs = require_chips(chips) if require_tpu else jax.devices()
    dev0 = devs[0]
    _say(f"[device] platform={dev0.platform} kind={dev0.device_kind} "
         f"count={chips}")
    _say(f"[setup] compile cache {enable_compile_cache()}")

    from repro.core.engine import VectorSearchEngine
    from repro.obs import metrics as obs_metrics
    from repro.serve.batcher import ServeError
    from repro.serve.vector import VectorServer

    obs_metrics.set_enabled(False)
    data, check = config["data"], config["check"]
    gen = load_module(f"generators/{data['generator']}.py")
    loop = load_module(f"traffic/{traffic['loop']}.py")
    n, dim = int(config["n"]), int(config["dim"])
    pool = int(traffic["query_pool"])

    with CompileCounter() as cc:
        t = time.perf_counter()
        X, Q = gen.generate(data["seed"], seed, n, dim, pool, data)
        X_host, Q_host = np.asarray(X), np.asarray(Q)
        del X, Q
        _say(f"[setup] data {n} x {dim} + {pool} queries on the device, "
             f"copied to the host: {time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        eng = VectorSearchEngine.build(X_host, **config["build"])
        del X_host
        st = eng.store
        P, D, C = (int(s) for s in st.data.shape)
        _say(f"[setup] build: {time.perf_counter() - t:.3f} s, "
             f"{P} partitions x {C} slots, D = {D}")
        server = VectorServer(eng, spec=eng.spec.replace(k=int(check["k"])),
                              **config["server"])
        try:
            t = time.perf_counter()
            # every pow2 bucket up to max_batch: a drain can form any size
            warmed = server.warmup()
            c, h, cs = cc.read()
            _say(f"[setup] warmup {warmed}: {time.perf_counter() - t:.3f} s; "
                 f"set-up made {c} executables ({h} from the persistent "
                 f"cache), {cs:.3f} s compiling")
            obs = _Observer(seconds) if trace else None
            window = Window(seconds, [obs.on_open] if obs else [])
            if obs:
                obs.start()
            log = RequestLog()
            loop.drive(server, Q_host, traffic, window=window, seed=seed,
                       log=log, errors=(ServeError,))
            c_open = cc.read()[0]
            unresolved = log.wait_all(LATE_WAIT_S)
            t_wait_end = time.perf_counter()
            c_win = cc.read()[0] - c
            _say(f"[window] {seconds} s; executables made in the window and "
                 f"the wait after it: {c_win} ({c_open - c} before close)")
        finally:
            server.close(drain=True)
    mem = (dev0.memory_stats() or {}).get("peak_bytes_in_use", 0)

    spans = counters = summary = None
    if obs:
        spans, counters = obs.finish(window.t0, window.t1)
    run = Run(
        cell=cell, config=config, traffic=traffic, seconds=seconds,
        seed=seed, setup_s=window.t0 - t_start, window=window,
        log=log.arrays(), in_window=None, window_s=None, latency_ms=None,
        store={"P": P, "D": D, "C": C,
               "value_bytes": {"int8": 1, "int4": 0.5, "bf16": 2,
                               "f32": 4}[eng.spec.scan_dtype]},
        device_kind=dev0.device_kind, spans=spans, counters=counters,
    )
    arr = run.log
    run.in_window, run.window_s = loop.measured(arr, window)
    late_end = np.where(arr["ok"], arr["t_done"], t_wait_end)
    run.latency_ms = (late_end - arr["t_due"])[run.in_window] * 1e3
    lateness = (arr["t_submit"] - arr["t_due"])[run.in_window] * 1e3
    lateness = lateness[np.isfinite(lateness)]
    if lateness.size:
        _say(f"[window] sender lateness: mean {lateness.mean():.4f} ms, "
             f"p99 {np.percentile(lateness, 99):.4f} ms, "
             f"max {lateness.max():.4f} ms")

    # the program is freed before the reference runs on the device
    answers = [(log.ids[i], log.dists[i]) for i in range(len(log.qidx))]
    del server, eng, st, log
    gc.collect()

    if obs:
        run.traced = obs.traced
        summary = obs.summary()
        run.trace = summary

    attempted = int(run.in_window.sum())
    failed = int((run.in_window & ~arr["ok"]).sum())
    _say(f"[window] {run.window_s:.6f} s measured; {attempted} requests "
         f"attempted, {failed} failed or refused")
    t = time.perf_counter()
    checked, correct = _check(gen, config, seed, pool, arr, run.in_window,
                              answers, unresolved)
    _say(f"[check] reference and comparison: {time.perf_counter() - t:.3f} s")

    metrics = {}
    for m, kind in [(m, "end_to_end") for m in (e2e if not trace else [])] + \
                   [(m, "layer_metrics") for m in (layer if trace else [])]:
        value = load_module(f"{kind}/{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": chips, "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(),
                            "idle_gaps": summary.top_gaps()}
    out["check"] = checked
    for name, v in checked.items():
        _say(f"[check] {name} = {v['value']!r} (limit {v['limit']!r})")
    return out


def _check(gen, config, seed, pool, arr, in_window, answers, unresolved):
    """Compare a sample of the window's answers, drawn from the seed, with
    the reference.  A request that never came back fails the run."""
    import jax.numpy as jnp

    data, check = config["data"], config["check"]
    k = int(check["k"])
    ok = np.flatnonzero(in_window & arr["ok"])
    rng = np.random.default_rng([int(seed) % 2**63, 0xC0FFEE])
    pick = np.sort(rng.choice(ok, size=min(int(check["sample"]), len(ok)),
                              replace=False))
    ref = load_module(f"references/{check['reference']}.py")
    X, Q = gen.generate(data["seed"], seed, int(config["n"]),
                        int(config["dim"]), pool, data)
    ids = np.stack([answers[i][0] for i in pick]) if len(pick) else \
        np.zeros((0, k), np.int64)
    dists = np.stack([answers[i][1] for i in pick]) if len(pick) else \
        np.zeros((0, k), np.float32)
    Qs = jnp.asarray(np.asarray(Q)[arr["qidx"][pick]])
    numbers, correct = _compare.compare(ref, X, Qs, ids, dists, k,
                                        check["limits"])
    numbers["unanswered"] = {"value": float(unresolved), "limit": 0.0}
    return numbers, correct and unresolved == 0
