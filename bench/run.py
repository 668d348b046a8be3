#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a workload of ``BENCHMARK.json``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/mixes/``).  The run serves
the configuration's model through the program's ``Router`` behind its
``HttpFrontend`` on 127.0.0.1 and drives it with the mix from a client
thread (``load.py``):

1. refuse anything but a TPU listed in ``peaks.json`` (exit 3, no result);
2. make the weights on the device from ``--seed`` (the configuration's
   module under ``architectures/``);
3. build the Router from the configuration file;
4. warm up the cell's programs with one request (one ``extend`` chunk and
   one decode ``segment``) — process start to here is ``setup_s``;
5. start the traffic, let it ramp for the mix's ``ramp_s``;
6. measure for ``--seconds`` (``--trace 1``: profile the window's first
   ``trace_s`` seconds instead and report the per-layer metrics);
7. close the window, read the peak device memory, free the program's
   state, run the plain reference over a sample of the finished requests
   (``check.py``) and print one JSON line.

The numbers compared for ``correct`` are printed with their limits as the
last lines of standard error and under ``checks`` in the result line.
"""
from __future__ import annotations

import os
import time


def _process_start() -> float:
    """The process's start on the ``time.perf_counter`` clock."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return now - (up - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_START = _process_start()

import argparse                                   # noqa: E402
import asyncio                                    # noqa: E402
import dataclasses                                # noqa: E402
import gc                                         # noqa: E402
import json                                       # noqa: E402
import shutil                                     # noqa: E402
import socket                                     # noqa: E402
import sys                                        # noqa: E402
import tempfile                                   # noqa: E402
import types                                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import cells                                      # noqa: E402
import check                                      # noqa: E402
import load                                       # noqa: E402
import measure                                    # noqa: E402


# the warm-up request compiles every program on a cell's first run
WARMUP_TIMEOUT_S = 900


class NoChip(Exception):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_chip(cell, platform: str | None = "tpu"):
    """The device this run measures on: a TPU in ``peaks.json``, with as
    many chips as the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if platform is not None and d.platform != platform:
        raise NoChip(f"JAX found no TPU (platform {d.platform!r})")
    if len(devs) < cell.workload["chips"]:
        raise NoChip(f"the cell needs {cell.workload['chips']} chips, JAX "
                     f"sees {len(devs)}")
    try:
        peaks = cell.peaks(d.device_kind)
    except KeyError as e:
        if platform is not None:
            raise NoChip(str(e)) from None
        peaks = None
    return d, devs, peaks


def program_config(cfg: dict):
    """The program's ArchConfig for a configuration file, checked against
    every key of the file's ``model`` block that names a field or property
    of ArchConfig."""
    from repro.configs import get_arch
    arch = dataclasses.replace(get_arch(cfg["arch"]),
                               dscim=cfg["dscim"]["spec"],
                               **cfg.get("program", {}))
    names = {f.name for f in dataclasses.fields(arch)} | {
        k for k, v in vars(type(arch)).items() if isinstance(v, property)}
    bad = {k: (getattr(arch, k), v) for k, v in cfg["model"].items()
           if k in names and getattr(arch, k) != v}
    if bad:
        raise ValueError(f"program arch {cfg['arch']!r} departs from the "
                         f"configuration file: {bad}")
    return arch


def build_router(cfg: dict, arch, params):
    from repro.runtime.router import Router
    s = cfg["serve"]
    if s.get("watchdog", "off") != "off":
        raise ValueError("the benchmark serves with the watchdog off")
    return Router(arch, params, slots=s["slots"], seg_len=s["seg_len"],
                  kv=s["kv"], page_size=s["page_size"],
                  buckets=tuple(s["buckets"]), chunk_len=s["chunk_len"],
                  max_prompt=s["max_prompt"], max_new_cap=s["max_new_cap"],
                  max_queue=s["max_queue"], paged_attn=s["paged_attn"],
                  log=lambda m: None)


def counters(router, st: dict) -> dict:
    """Segments run and decode-slot steps that served a live request, from
    the Router's ``stats()`` ``st`` (``occupancy`` is live over all slot
    steps)."""
    total = st["segments"] * router.seg_len * router.slots
    return {"segments": st["segments"], "live": st["occupancy"] * total}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def _stop(task) -> None:
    task.cancel()
    try:
        await task
    except (asyncio.CancelledError, Exception):    # noqa: BLE001
        pass


async def session(router, cell, plan, args, device) -> dict:
    """Serve, warm up, ramp, measure; returns what the window saw."""
    from repro.launch.server import HttpFrontend
    mix = cell.mix
    port = _free_port()
    listening = asyncio.Event()
    front = HttpFrontend(router, "127.0.0.1", port,
                         log=lambda m: listening.set()
                         if "listening" in m else None)
    server = asyncio.create_task(front.serve())
    await asyncio.wait({server, asyncio.create_task(listening.wait())},
                       return_when=asyncio.FIRST_COMPLETED)
    if server.done():
        server.result()
    # warm-up: one chunk of prompt and one decode segment compile (or are
    # read from the persistent cache) before anything is timed
    rec = load.Record(plan["warmup"], 2, time.perf_counter())
    await asyncio.wait_for(load._request(port, rec), WARMUP_TIMEOUT_S)
    if rec.status != "ok":
        raise RuntimeError(f"warm-up request ended {rec.status!r}")
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    w0 = t0 + float(mix["ramp_s"])
    w1 = w0 + float(args.seconds)
    trace = {}

    async def tracer():
        """Profile from the first round boundary after ``w0`` for
        ``trace_s``, reading the Router's stats at the same boundaries."""
        import jax
        tracemod = cells.load_module(os.path.join(HERE, "trace.py"),
                                     "bench_trace")
        await asyncio.sleep(max(0.0, w0 - time.perf_counter()))
        trace["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
        s0 = router.stats()
        c0 = counters(router, s0)
        tracemod.start(trace["dir"])
        trace["t0"], trace["c0"], trace["stats0"] = \
            time.perf_counter(), c0, s0
        await asyncio.sleep(max(0.0, min(float(mix["trace_s"]),
                                         w1 - time.perf_counter())))
        trace["t1"], s1 = time.perf_counter(), router.stats()
        trace["c1"], trace["stats1"] = counters(router, s1), s1
        # collecting the trace holds the serving loop for tens of seconds;
        # from a thread of its own, racing that loop for the GIL, it took
        # minutes.  Nothing after the span is reported in a traced run.
        jax.profiler.stop_trace()
        log(f"profiler stopped {time.perf_counter() - trace['t1']:.1f} s "
            "after the traced span")

    client = load.LoadClient(port, plan, t0, w1)
    client.start()
    tr = asyncio.create_task(tracer()) if args.trace else None
    await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
    if tr is not None:
        await tr
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    # stop the traffic and the engine; requests in flight are dropped
    await asyncio.get_running_loop().run_in_executor(None, client.close)
    await _stop(router._engine_task)
    await _stop(server)
    if front._server is not None:
        front._server.close()
    return {"setup_s": setup_s, "t0": t0, "w0": w0, "w1": w1,
            "records": client.records, "errors": client.errors,
            "peak": peak, "trace": trace}


def window_work(records, a: float, b: float, c0: dict, c1: dict,
                seg_len: int, extend_calls: int) -> dict:
    """What the model computed in ``[a, b)``: decode steps and tokens from
    the Router's counters, prompt rows and every position's context from
    the requests the window served."""
    ctx = {}
    prefill_rows = emits = 0
    for r in records:
        S = len(r.prompt)
        for i, t in enumerate(r.times):
            if not a <= t < b:
                continue
            if i == 0:
                emits += 1
                prefill_rows += S
                for c in range(1, S + 1):
                    ctx[c] = ctx.get(c, 0) + 1
            else:
                ctx[S + i] = ctx.get(S + i, 0) + 1
    return {"decode_steps": (c1["segments"] - c0["segments"]) * seg_len,
            "decoded": c1["live"] - c0["live"],
            "prefill_rows": prefill_rows, "emits": emits,
            "extend_calls": extend_calls,
            "contexts": sorted(ctx.items())}


def per_layer(cell, out, peaks) -> tuple:
    """Reduce the trace and read every per-layer metric of the cell."""
    tracemod = cells.load_module(os.path.join(HERE, "trace.py"),
                                 "bench_trace")
    tr = out["trace"]
    try:
        summary = tracemod.reduce(tr["dir"])
    finally:
        shutil.rmtree(tr["dir"], ignore_errors=True)
    serve = cell.config["serve"]
    work = window_work(out["records"], tr["t0"], tr["t1"], tr["c0"],
                       tr["c1"], serve["seg_len"],
                       summary.module_calls("jit_extend")[0])
    window_s = tr["t1"] - tr["t0"]
    traced = measure.window_metrics(out["records"], tr["t0"], tr["t1"])
    log(f"inside the traced span: {traced['output_tok_s']:.3f} tokens/s")
    ctx = types.SimpleNamespace(
        summary=summary, window_s=window_s, work=work, peaks=peaks,
        model=cell.config["model"], serve=serve,
        kernel_work=cell.kernel_work, architecture=cell.architecture(),
        stats0=tr["stats0"], stats1=tr["stats1"])
    metrics = {}
    for m in cell.metrics("per_layer"):
        v = cell.metric_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"busy_s": summary.busy_seconds(),
           "window_s": summary.window_seconds()}
    log(f"traced {window_s:.3f} s (host), {dev['window_s']:.3f} s (trace); "
        f"work {dict((k, v) for k, v in work.items() if k != 'contexts')}")
    return metrics, dev, summary.breakdown()


def compare(cell, seed: int, records, w1: float) -> dict:
    """The reference over a sample of finished requests (check.py)."""
    chk, cfg = cell.check, cell.config
    gap = {"value": None, "limit": chk["mean_logit_gap"]}
    sample = check.select(records, w1, seed, chk["tokens"], chk["rows"],
                          chk["want_rows"])
    if not sample:
        return {"mean_logit_gap": gap, "requests": 0, "positions": 0}
    params = cell.weights(seed)
    packed = check.pack(sample, chk["tokens"], chk["rows"])
    t = time.perf_counter()
    g, _ = check.gaps(check.reference_module(cfg).build(cfg), params, packed)
    log(f"reference over {len(sample)} requests, {len(g)} served tokens: "
        f"{time.perf_counter() - t:.1f} s")
    gap["value"] = float(g.mean())
    return {"mean_logit_gap": gap, "requests": len(sample),
            "positions": int(len(g)), "agree": float((g == 0).mean())}


def serve_window(cell, args, device) -> dict:
    """Set up, serve the cell's traffic and measure one window; the
    program's state is freed when this returns."""
    cfg = cell.config
    arch = program_config(cfg)
    params = cell.weights(args.seed)
    router = build_router(cfg, arch, params)
    del params
    plan = cell.generator().make(cell.mix, seed=args.seed,
                                 vocab=cfg["model"]["vocab"],
                                 slots=cfg["serve"]["slots"])
    plan["warmup"] = plan_warmup(cfg, args.seed)
    out = asyncio.run(session(router, cell, plan, args, device))
    del router
    gc.collect()
    import jax
    log(f"after the window: {sum(a.nbytes for a in jax.live_arrays())} "
        "bytes of arrays still live")
    return out


def measure_cell(cell, args, device, devs, peaks) -> int:
    from repro.launch.compile_cache import use_compilation_cache
    log(f"{cell.name}: {device.device_kind} x{len(devs)}, compilation "
        f"cache {use_compilation_cache()}")
    out = serve_window(cell, args, device)
    metrics, dev_extra, breakdown = {}, {}, None
    if args.trace:
        metrics, dev_extra, breakdown = per_layer(cell, out, peaks)
    e2e = measure.window_metrics(out["records"], out["w0"], out["w1"])
    log(f"window: {json.dumps(e2e)}; setup {out['setup_s']:.3f} s; "
        f"client errors {out['errors'][:3]}")
    log("tokens per 10 s from the end of set-up: "
        f"{measure.token_bins(out['records'], out['t0'], out['w1'], 10.0)}")
    if not args.trace:
        for m in cell.metrics("end_to_end"):
            v = out["setup_s"] if m["name"] == "setup_s" else e2e[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = compare(cell, args.seed, out["records"], out["w1"])
    gap = checks["mean_logit_gap"]
    correct = gap["value"] is not None and gap["limit"] is not None \
        and gap["value"] <= gap["limit"]
    result = {"correct": bool(correct), "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics,
              "device": {"platform": device.platform,
                         "kind": device.device_kind, "count": len(devs),
                         "memory_peak_bytes": out["peak"], **dev_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    log(f"checked {checks['requests']} requests, {checks['positions']} "
        f"served tokens")
    log(f"mean_logit_gap {gap['value']} limit {gap['limit']}")
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log("no src/repro beside bench/: run from a checkout of the "
            "repository")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = cells.Cell(args.workload)
    try:
        device, devs, peaks = require_chip(cell)
    except NoChip as e:
        log(f"refusing to run: {e}")
        return 3
    return measure_cell(cell, args, device, devs, peaks)


def plan_warmup(cfg: dict, seed: int):
    """The warm-up prompt: less than one chunk, so that one extend call
    emits and one segment decodes."""
    import numpy as np
    rng = np.random.default_rng([seed, 0x3A53])
    n = max(1, cfg["serve"]["chunk_len"] // 2)
    return rng.integers(0, cfg["model"]["vocab"], n, dtype=np.int32)


if __name__ == "__main__":
    sys.exit(main())
