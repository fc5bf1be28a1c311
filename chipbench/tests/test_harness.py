"""The harness: lookup by name, the run's refusals, and whole runs on the
CPU at a tiny size with the timed path sound and broken."""
import copy
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_every_cell_finds_its_files_by_name(bench):
    for cell in bench["workloads"]:
        c, config, traffic = harness.cell_parts(bench, cell["name"])
        assert c is cell
        harness.load_module(f"generators/{config['data']['generator']}.py")
        harness.load_module(f"references/{config['check']['reference']}.py")
        loop = harness.load_module(f"traffic/{traffic['loop']}.py")
        assert callable(loop.drive) and callable(loop.measured)
        e2e, layer = harness.metrics_for(bench, cell["name"])
        assert "setup_s" in {m["name"] for m in e2e}
        assert len(e2e) >= 2 and layer
        for m, kind in [(m, "end_to_end") for m in e2e] + \
                       [(m, "layer_metrics") for m in layer]:
            assert callable(harness.load_module(
                f"{kind}/{m['name']}.py").read)


def test_metrics_for_follows_workloads_and_moves():
    bench = {
        "end_to_end": [{"name": "qps", "workloads": ["a"]},
                       {"name": "setup_s"}],
        "per_layer": [{"name": "x.a", "moves": "qps", "workloads": ["a"]},
                      {"name": "y", "moves": "qps"},
                      {"name": "z", "moves": "p99_ms"}],
    }
    e2e, layer = harness.metrics_for(bench, "a")
    assert [m["name"] for m in e2e] == ["qps", "setup_s"]
    assert [m["name"] for m in layer] == ["x.a", "y"]
    e2e, layer = harness.metrics_for(bench, "b")
    assert [m["name"] for m in e2e] == ["setup_s"] and layer == []


def test_closed_loop_measures_whole_batches():
    """Bursts of 4 completions every 0.5 s: the span runs from the first
    completion at or after the opening to the first at or after the nominal
    end, so it holds whole bursts and reads the burst rate exactly."""
    from types import SimpleNamespace

    closed = harness.load_module("traffic/closed.py")
    bursts = np.arange(10) * 0.5
    t = (bursts[:, None] + np.arange(4) * 1e-4).ravel()
    t = np.append(t, np.nan)            # a request that never came back
    for t0 in (0.2, 0.3, 0.45):
        window = SimpleNamespace(t0=t0, end=t0 + 3.0)
        mask, seconds = closed.measured({"t_done": t}, window)
        assert mask.sum() == 4 * 6 and seconds == pytest.approx(3.0)
        assert mask.sum() / seconds == pytest.approx(8.0)


def test_benchmark_file_keeps_to_its_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all(m["moves"] in {e["name"] for e in bench["end_to_end"]}
               for m in bench["per_layer"])
    assert layers
    for conf in bench["configs"]:
        config = json.loads((ROOT / conf["file"]).read_text())
        assert sorted(config["reduced"]) == sorted(conf["reduced"])


def _run_py(cwd, env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "openai1536.bulk",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run_py(ROOT)
    assert p.returncode == 3, p.stderr
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, env={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout == ""


# ------------------------------------------------- whole runs on the CPU
def tiny_parts(bench, workload):
    cell, config, traffic = copy.deepcopy(harness.cell_parts(bench, workload))
    config.update(n=3000, dim=64)
    config["data"]["n_clusters"] = 8
    config["check"]["sample"] = 32
    traffic["query_pool"] = 256
    if traffic["loop"] == "poisson":
        traffic["rate_qps"] = 200.0
    return cell, config, traffic


def tiny_run(bench, workload, seed=2**35 + 11):
    return harness.run_cell(
        workload, seed, 1.0, False, t_start=time.perf_counter(),
        bench=bench, parts=tiny_parts(bench, workload), require_tpu=False,
    )


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


@pytest.mark.parametrize("workload", ["openai1536.bulk", "openai1536.online"])
def test_sound_run_is_correct(bench, no_cache, workload):
    out = tiny_run(bench, workload)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    e2e, _ = harness.metrics_for(bench, workload)
    assert set(out["metrics"]) == {m["name"] for m in e2e}
    assert list(out)[-1] == "check"


def _broken(fault):
    """Wrap the fused executors so the timed path answers wrongly."""
    from repro.core import plan

    def wrap(fn):
        def run(store, pruner, Q, spec, **kw):
            ids, dists = fn(store, pruner, Q, spec, **kw)
            ids, dists = np.array(ids), np.array(dists)
            if fault == "answer_altered":       # one id of each answer
                ids[:, 0] = (ids[:, 0] + 1) % store.ids.size
            elif fault == "half_batch_left_out":  # lanes B/2.. get lane 0's
                h = max(len(ids) // 2, 1)
                ids[h:], dists[h:] = ids[0], dists[0]
            return ids, dists
        return run

    return {name: wrap(plan._EXECUTORS[name])
            for name in ("fused-batch", "fused-scan")}


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("workload", ["openai1536.bulk", "openai1536.online"])
def test_broken_timed_path_is_not_correct(bench, no_cache, monkeypatch,
                                          fault, workload):
    from repro.core import plan

    for name, fn in _broken(fault).items():
        monkeypatch.setitem(plan._EXECUTORS, name, fn)
    out = tiny_run(bench, workload)
    assert not out["correct"], out["check"]
