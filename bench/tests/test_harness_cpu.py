"""Rehearsal of a run on the CPU: the tiny cell goes through the harness's
window loop (server, client thread, counters, reference check) for a few
seconds; the measuring entry refuses a device that is not a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import cells
import run

TINY_SECONDS = 3


def test_window_accounting(tiny_cell, capsys):
    dev, devs, _ = run.require_chip(tiny_cell, platform=None)
    peaks = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}
    args = types.SimpleNamespace(seed=2**32 + 99, seconds=TINY_SECONDS,
                                 trace=0)
    assert run.measure_cell(tiny_cell, args, dev, devs, peaks) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    res = json.loads(line)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    assert names == {m["name"] for m in tiny_cell.metrics("end_to_end")}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["positions"] > 0


def test_traced_run(tiny_cell, capsys):
    """--trace 1: per-layer metrics only, the device's busy and traced
    seconds, and a breakdown of at most ten entries per list."""
    dev, devs, _ = run.require_chip(tiny_cell, platform=None)
    peaks = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}
    args = types.SimpleNamespace(seed=7, seconds=TINY_SECONDS, trace=1)
    assert run.measure_cell(tiny_cell, args, dev, devs, peaks) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    per_layer = {m["name"] for m in tiny_cell.metrics("per_layer")}
    assert res["metrics"] and set(res["metrics"]) <= per_layer
    assert "router.occupancy" in res["metrics"]
    assert 0 < res["metrics"]["router.occupancy"]["value"] <= 100
    d = res["device"]
    assert 0 < d["busy_s"] <= d["window_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(res["breakdown"][key]) <= 10


def test_window_counts_match_records(tiny_cell):
    """attempted counts the requests sent in the window; every one of them
    either finished ok, is still in flight, or failed — none lost."""
    import measure
    dev, _, _ = run.require_chip(tiny_cell, platform=None)
    out = run.serve_window(tiny_cell, types.SimpleNamespace(
        seed=5, seconds=TINY_SECONDS, trace=0), dev)
    recs = out["records"]
    due = [r for r in recs if out["w0"] <= r.due < out["w1"]]
    e2e = measure.window_metrics(recs, out["w0"], out["w1"])
    assert e2e["attempted"] == len(due) > 0
    assert all(r.status in ("ok", None) for r in due)
    assert e2e["failed"] == 0
    # a closed loop: one request in flight per client at any time
    assert sum(r.status is None for r in recs) <= 4
    assert e2e["tokens"] == sum(
        sum(out["w0"] <= t < out["w1"] for t in r.times) for r in recs)


def test_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(cells.HERE, "run.py"),
                        "--workload", "qwen3-dscim1.batch", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env,
                       cwd=str(tmp_path), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copytree(cells.HERE, tmp_path / "bench")
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-dscim1.batch", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True,
                       text=True, cwd=str(tmp_path), timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
