#!/usr/bin/env python3
"""Readings that the limits in ``checks/`` are set from (not part of a
benchmark run).

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ... [--control 3]

For each seed, in one process: serve the cell's traffic for a window of
``--seconds`` exactly as ``run.py`` does, free the program, then run the
plain reference over the sample of finished requests that ``run.py``
compares, and print the widest gap of a served token below the
reference's best.  For the first ``--control`` seeds also the control:
the reference one precision step lower (references/), whose own first
token at each checked position is read in the reference the same way.
One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells                                      # noqa: E402
import check                                      # noqa: E402
import measure                                    # noqa: E402
import run                                        # noqa: E402


def readings(cell, seed: int, records, w1: float, control: bool) -> dict:
    chk, cfg = cell.check, cell.config
    sample = check.select(records, w1, seed, chk["tokens"], chk["rows"],
                          chk["want_rows"])
    if not sample:
        return {"seed": seed, "requests": 0}
    params = cell.weights(seed)
    mod = check.reference_module(cfg)
    packed = check.pack(sample, chk["tokens"], chk["rows"])
    alt = None
    if control:
        alt = check.control_tokens(mod.build(cfg, control=True), params,
                                   packed)
    g, ga = check.gaps(mod.build(cfg), params, packed, alt)
    out = {"seed": seed, "requests": len(sample), "positions": len(g),
           "longest": max(len(r.tokens) for r in sample), **_stats(g, "")}
    if control:
        out.update(_stats(ga, "ctrl_"))
    return out


def _stats(g, pre: str) -> dict:
    import numpy as np
    miss = g[g > 0]
    return {f"{pre}max_gap": float(g.max()), f"{pre}mean_gap": float(g.mean()),
            f"{pre}agree": float((g == 0).mean()),
            f"{pre}q90_gap": float(np.quantile(g, 0.9)),
            f"{pre}miss_mean_gap": float(miss.mean()) if len(miss) else 0.0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="run the control on this many of the seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell = cells.Cell(args.workload)
    try:
        device, devs, peaks = run.require_chip(cell)
    except run.NoChip as e:
        run.log(f"refusing to run: {e}")
        return 3
    from repro.launch.compile_cache import use_compilation_cache
    use_compilation_cache()
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        a = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        out = run.serve_window(cell, a, device)
        r = readings(cell, seed, out["records"], out["w1"],
                     i < args.control)
        r["setup_s"] = out["setup_s"]
        r["tokens_per_10s"] = measure.token_bins(out["records"], out["t0"],
                                                 out["w1"], 10.0)
        r["wall_s"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
