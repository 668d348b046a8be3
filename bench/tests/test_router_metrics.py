"""The Router's per-layer metrics: ``router.host_ms`` on hand-made host
spans, and the trace's and the counters' metrics in a traced run of the
tiny cell."""
from __future__ import annotations

import json
import os
import types

import cells
import run

trace = cells.load_module(os.path.join(cells.HERE, "trace.py"),
                          "bench_trace")
MS = 1_000_000                          # ns


def _ctx(host, t0=0, t1=100 * MS):
    return types.SimpleNamespace(
        summary=trace.Summary([], {}, host, [], t0, t1))


def test_host_ms_reads_rounds_less_waits(tiny_cell):
    host_ms = tiny_cell.metric_reader("router.host_ms")
    main, other = "main", "worker"
    host = [
        # three round starts: two intervals of 30 and 40 ms
        (10 * MS, 25 * MS, main, "router.round"),
        (40 * MS, 35 * MS, main, "router.round"),
        (80 * MS, 10 * MS, main, "router.round"),
        # waits: 5 + 12 ms in the first interval, 20 ms in the second
        (12 * MS, 5 * MS, main, "router.wait_done"),
        (20 * MS, 12 * MS, main, "router.wait_tokens"),
        (45 * MS, 20 * MS, main, "router.wait_tokens"),
        # not waits of the round's thread, or not waits at all
        (50 * MS, 10 * MS, other, "router.wait_tokens"),
        (15 * MS, 20 * MS, main, "router.segment"),
        # a round that starts after the traced span
        (120 * MS, 5 * MS, main, "router.round"),
    ]
    got = host_ms.read(_ctx(host))
    assert abs(got - ((30 - 17) + (40 - 20)) / 2) < 1e-9
    # a wait that straddles a round start counts on both sides
    host.append((78 * MS, 4 * MS, main, "router.wait_done"))
    got = host_ms.read(_ctx(host))
    assert abs(got - ((30 - 17) + (40 - 22)) / 2) < 1e-9


def test_host_ms_silent_without_round_spans(tiny_cell):
    """A program without the Router's spans reads nothing, and one round
    holds no interval."""
    host_ms = tiny_cell.metric_reader("router.host_ms")
    assert host_ms.read(_ctx([(0, MS, "main", "PjitFunction(f)")])) is None
    assert host_ms.read(_ctx([(0, MS, "main", "router.round")])) is None


def test_traced_run_reports_router_metrics(tiny_cell, capsys):
    dev, devs, _ = run.require_chip(tiny_cell, platform=None)
    peaks = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}
    args = types.SimpleNamespace(seed=2**31 + 11, seconds=3, trace=1)
    assert run.measure_cell(tiny_cell, args, dev, devs, peaks) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["router.host_ms"]["value"] > 0
    assert res["metrics"]["router.host_ms"]["unit"] == "ms"
    # the counters of Router.stats() at the span's ends reach their readers
    shares = [res["metrics"][f"router.{k}_slot_share"]["value"]
              for k in ("empty", "prefill")]
    occ = res["metrics"]["router.occupancy"]["value"]
    assert all(0 <= v <= 100 for v in shares) and sum(shares) + occ <= 100
    assert res["metrics"]["router.queue_wait_ms"]["value"] >= 0
