#!/usr/bin/env python3
"""Chip smoke test: serve Qwen3-0.6B at its published widths on one TPU.

Drives the serving main path once, in one process, through the entry points
a user calls: a ``Router`` (runtime/router.py) behind the ``HttpFrontend``
(launch/server.py) on 127.0.0.1, answering POST /v1/generate.  The DS-CIM
leg serves ``--dscim kernel:dscim1:256`` (the fused DS-CIM MVM kernel) with
the int8 paged KV cache read by the paged-attention kernel and the accuracy
watchdog, calibrated on the served model, probing every segment against
the exact-int8 twin; a float leg (``--dscim off``) serves the same
prompts.  Weights are random, drawn from ``--seed``.

It fails (exit 1) unless:

* on the chip, the fused MVM at 1024 -> 3072 matches
  ``DSCIMLinear(mode="lut")`` and the paged kernel matches the jnp
  reference read, to the tolerances of the interpret-mode parity tests;
* the compiled decode segment holds both kernels as TPU custom calls;
* every request ends ``ok`` with its full token count, with no failover
  replay, watchdog trip or ladder escalation.

``--chips 4`` runs only the multi-chip path: the DS-CIM leg under a
``model=4`` mesh against the same leg on one device, tokens bitwise equal
and logits within 1e-5.

Times printed are chip set-up figures (compile, first token, wall time of a
few requests), not benchmark metrics.  The last line of output is the
result, ``{"ok": true, "device": {...}}``.  Without a TPU, or outside a
checkout of the repository, it exits non-zero and prints no result.

Usage, from the repository root:

    python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import socket
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen3-0.6b"
DSCIM = "kernel:dscim1:256"
PROMPT_LENS = (64, 128, 300, 512)     # one request each
NEW_TOKENS = 16
SLOTS = 4
SEG_LEN = 4
CHUNK_LEN = 128                       # chunked-prefill tokens per round
PAGE_SIZE = 8                         # the serving default


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------------
# HTTP client (stdlib asyncio streams, same event loop as the server)
# --------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _dechunk(raw: bytes) -> bytes:
    out, i = b"", 0
    while True:
        j = raw.index(b"\r\n", i)
        n = int(raw[i:j], 16)
        if n == 0:
            return out
        out += raw[j + 2:j + 2 + n]
        i = j + 2 + n + 2


async def _generate(port: int, prompt, max_new: int) -> dict:
    """POST /v1/generate; returns tokens, final status and the seconds to
    the first streamed token."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new": max_new}).encode()
    t0 = time.perf_counter()
    writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                 b"Content-Type: application/json\r\n"
                 + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    code = int(head.split(b" ", 2)[1])
    first = await reader.read(1)
    ttft = time.perf_counter() - t0
    raw = first + await reader.read()
    writer.close()
    await writer.wait_closed()
    check(code == 200, f"HTTP {code}: {raw[:200]!r}")
    tokens, status = [], None
    for line in _dechunk(raw).decode().splitlines():
        ev = json.loads(line)
        if "token" in ev:
            tokens.append(ev["token"])
        elif ev.get("restart"):
            tokens.clear()
        else:
            status = ev.get("status")
    return {"tokens": tokens, "status": status, "ttft_s": ttft}


async def _serve_over_http(router, prompts, max_new: int) -> list:
    from repro.launch.server import HttpFrontend
    port = _free_port()
    listening = asyncio.Event()

    def log(msg):
        if "listening" in msg:
            listening.set()

    front = HttpFrontend(router, "127.0.0.1", port, log=log)
    server = asyncio.create_task(front.serve())
    waiter = asyncio.create_task(listening.wait())
    await asyncio.wait({server, waiter}, return_when=asyncio.FIRST_COMPLETED)
    if server.done():
        server.result()                # re-raise a bind/start failure
    results = await asyncio.gather(
        *(_generate(port, p, max_new) for p in prompts))
    front.request_drain()
    await server
    return results


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def _build(seed: int):
    import jax

    from repro.configs import get_arch
    from repro.models import get_model
    cfg = get_arch(ARCH)               # published widths: reduced is off
    t0 = time.perf_counter()
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    say(f"built {ARCH} at full width: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {n} parameters from seed {seed} "
        f"({time.perf_counter() - t0:.1f} s)")
    return cfg, params


def _prompts(cfg, seed: int) -> list:
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
            for n in PROMPT_LENS]


def check_kernels_on_chip(seed: int) -> None:
    """Both kernels against their references at full width, on the chip.
    The references' f32 contractions run at full f32 precision, so the
    tolerances are those of the interpret-mode parity tests."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dscim_layer import DSCIMLinear
    from repro.core.seed_search import calibrated_config
    from repro.kernels.dscim_fused import dscim_fused_mvm
    from repro.kernels.paged_attention import paged_attention_decode
    from repro.layers.attention import _paged_read_jnp

    rng = np.random.default_rng(seed)
    # 8 rows; then the serving shapes: 4 decode slots of one row each, and
    # one 128-token prefill chunk
    for variant, L, lead in (("dscim1", 256, (8,)), ("dscim2", 64, (8,)),
                             ("dscim1", 256, (4, 1)),
                             ("dscim1", 256, (1, 128))):
        cfg = calibrated_config(variant, L, "paper")
        x = jnp.asarray(rng.normal(0, 1, lead + (1024,)), jnp.float32)
        w = jnp.asarray(rng.normal(0, 1, (1024, 3072)), jnp.float32)
        t0 = time.perf_counter()
        got = np.asarray(dscim_fused_mvm(x, w, cfg, group_k=128))
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("float32"):
            want = np.asarray(DSCIMLinear(cfg, mode="lut", group_k=128)(x, w))
        scale = max(float(np.abs(want).max()), 1.0)
        err = float(np.abs(got - want).max())
        bound = 2e-5 * scale + 2e-5 * np.abs(want)
        say(f"fused MVM {variant}/L{L} {lead}x1024x3072 vs lut: max abs err "
            f"{err:.3e} (scale {scale:.3e}; first call incl. compile "
            f"{dt:.2f} s)")
        check(bool(np.all(np.abs(got - want) <= bound)),
              f"fused MVM {variant}/L{L} differs from lut by {err:.3e}")

    B, KV, R, HD, ps, MP = 8, 8, 2, 128, PAGE_SIZE, 64
    P = B * MP + 2
    kp = jnp.asarray(rng.integers(-127, 128, (P, ps, KV, HD)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (P, ps, KV, HD)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (P, KV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (P, KV)), jnp.float32)
    table = jnp.asarray(rng.permutation(P)[:B * MP].reshape(B, MP), jnp.int32)
    pos = jnp.asarray(rng.integers(0, MP * ps, (B,)), jnp.int32)
    kt = jnp.asarray(rng.normal(0, 1, (B, ps, KV, HD)), jnp.bfloat16)
    vt = jnp.asarray(rng.normal(0, 1, (B, ps, KV, HD)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(0, 1, (B, KV, R, HD)), jnp.float32)
    t0 = time.perf_counter()
    got = np.asarray(paged_attention_decode(q, kp, vp, ks, vs, kt, vt,
                                            table, pos))
    dt = time.perf_counter() - t0
    view = {"k_pages": kp, "v_pages": vp, "k_scale": ks, "v_scale": vs,
            "page_table": table, "pos": pos}
    with jax.default_matmul_precision("float32"):
        want = np.asarray(_paged_read_jnp(q, view, kt, vt))
    err = float(np.abs(got - want).max())
    say(f"paged attention B{B} KV{KV} n_rep{R} HD{HD} ps{ps} MP{MP} vs jnp "
        f"reference: max abs err {err:.3e} (first call incl. compile "
        f"{dt:.2f} s)")
    check(err <= 1e-6, f"paged kernel differs from jnp reference by {err:.3e}")


def check_segment_kernels(cfg_ds, router) -> None:
    """The compiled decode segment holds both Pallas kernels as TPU custom
    calls — nothing runs in interpret mode or falls back to the jnp read."""
    from repro.kernels import dscim_fused, paged_attention
    from repro.launch.steps import init_serve_state, make_segment_fn
    segment = make_segment_fn(cfg_ds, None, SEG_LEN, eos_id=-1,
                              sample="greedy", paged_attn="kernel")
    state = init_serve_state(cfg_ds, SLOTS, router.capacity, kv="int8",
                             page_size=PAGE_SIZE)
    t0 = time.perf_counter()
    text = segment.lower(router.params, state).compile().as_text()
    say(f"decode segment compiled ahead of serving in "
        f"{time.perf_counter() - t0:.1f} s")
    for name in (dscim_fused.KERNEL_NAME, paged_attention.KERNEL_NAME):
        hits = [ln for ln in text.splitlines()
                if "tpu_custom_call" in ln and name in ln]
        say(f"segment program: {len(hits)} tpu_custom_call op(s) of {name}")
        check(bool(hits), f"{name} is not a TPU custom call in the segment")


def make_router(cfg, params, *, par=None, monitor=None):
    """A Router for the smoke's prompts (all admissions chunked); returns
    it with the seconds its construction (weight preparation) took."""
    from repro.runtime.router import Router
    t0 = time.perf_counter()
    router = Router(cfg, params, slots=SLOTS, seg_len=SEG_LEN, kv="int8",
                    page_size=PAGE_SIZE, buckets=(), chunk_len=CHUNK_LEN,
                    max_prompt=max(PROMPT_LENS), max_new_cap=NEW_TOKENS,
                    max_queue=len(PROMPT_LENS), paged_attn="kernel", par=par,
                    monitor=monitor, log=say)
    return router, time.perf_counter() - t0


def run_leg(tag: str, router, prompts, t_build: float, monitor=None) -> list:
    """Serve the prompts through the router behind the HTTP front end;
    checks statuses, token counts and the fault-tolerance counters."""
    t0 = time.perf_counter()
    results = asyncio.run(_serve_over_http(router, prompts, NEW_TOKENS))
    wall = time.perf_counter() - t0
    stats = router.stats()
    n_tok = sum(len(r["tokens"]) for r in results)
    say(f"{tag}: {len(results)} requests, {n_tok} tokens, statuses "
        f"{[r['status'] for r in results]}; router build {t_build:.1f} s, "
        f"first token after {min(r['ttft_s'] for r in results):.1f} s "
        f"(compiles included), all done after {wall:.1f} s")
    counters = stats["counters"]
    say(f"{tag}: replays {stats['replays']}, quarantined "
        f"{counters['quarantined']}, degraded {counters['degraded']}, "
        f"watchdog trips {monitor.n_trips if monitor else 'off'}, "
        f"pages live after drain "
        f"{stats['pages']['live_pages']}")
    for n, r in zip(PROMPT_LENS, results):
        check(r["status"] == "ok", f"{tag}: {n}-token prompt ended "
              f"{r['status']!r}")
        check(len(r["tokens"]) == NEW_TOKENS, f"{tag}: {n}-token prompt "
              f"got {len(r['tokens'])} of {NEW_TOKENS} tokens")
    check(stats["replays"] == 0, f"{tag}: {stats['replays']} replays")
    check(counters["quarantined"] == 0 and counters["degraded"] == 0,
          f"{tag}: ladder escalations {counters}")
    if monitor is not None:
        check(monitor.n_trips == 0, f"{tag}: {monitor.n_trips} watchdog "
              f"trips (relative drift {monitor.history})")
    check(stats["pages"]["live_pages"] == 0, f"{tag}: pages leaked")
    return [r["tokens"] for r in results]


def one_chip(seed: int) -> None:
    import dataclasses

    import numpy as np

    from repro.runtime.serving import watchdog_for_spec
    check_kernels_on_chip(seed)
    cfg, params = _build(seed)
    cfg_ds = dataclasses.replace(cfg, dscim=DSCIM)
    prompts = _prompts(cfg, seed)
    t0 = time.perf_counter()
    monitor = watchdog_for_spec(DSCIM, cfg_ds, params, probe_every=1)
    say(f"watchdog calibrated on the served model in "
        f"{time.perf_counter() - t0:.1f} s: relative logit drift threshold "
        f"{monitor.rel_threshold:.4f} (3x the healthy drift)")
    router, t_build = make_router(cfg_ds, params, monitor=monitor)
    check_segment_kernels(cfg_ds, router)
    run_leg(f"dscim leg ({DSCIM})", router, prompts, t_build, monitor)
    check(monitor.n_probes > 0, "the watchdog never probed")
    rels = np.concatenate(monitor.history)
    rels = rels[np.isfinite(rels)]
    say(f"watchdog: {monitor.n_probes} probes, slot drift {rels.min():.4f} "
        f"to {rels.max():.4f}")
    del router
    router, t_build = make_router(cfg, params)
    run_leg("float leg (--dscim off)", router, prompts, t_build)


def four_chips(seed: int) -> None:
    """The model=4 mesh against one device: served tokens bitwise equal,
    per-step logits within 1e-5."""
    import dataclasses

    import numpy as np

    from repro.launch.mesh import parallel_ctx_from_spec
    from repro.launch.serve import serve_batch
    cfg, params = _build(seed)
    cfg_ds = dataclasses.replace(cfg, dscim=DSCIM)
    prompts = _prompts(cfg, seed)
    par = parallel_ctx_from_spec("model=4")
    toks = {}
    for tag, p in (("one device", None), ("mesh model=4", par)):
        router, t_build = make_router(cfg_ds, params, par=p)
        toks[tag] = run_leg(f"dscim leg, {tag}", router, prompts, t_build)
        del router
    # report both comparisons before checking either
    diverge = {}
    for n, a, b in zip(PROMPT_LENS, toks["one device"], toks["mesh model=4"]):
        if a != b:
            diverge[n] = next(i for i, (x, y) in enumerate(zip(a, b))
                              if x != y)
    say(f"served tokens, mesh model=4 vs one device: "
        + (f"first divergence (prompt length: token index) {diverge}"
           if diverge else "bitwise equal"))
    batch = np.stack([p[:PROMPT_LENS[0]] for p in prompts])
    out = {}
    for tag, p in (("one device", None), ("mesh model=4", par)):
        t0 = time.perf_counter()
        out[tag] = serve_batch(cfg_ds, params, batch, NEW_TOKENS, par=p,
                               trace_logits=True, kv="int8",
                               page_size=PAGE_SIZE, paged_attn="kernel")
        say(f"serve_batch logit trace, {tag}: "
            f"{time.perf_counter() - t0:.1f} s (compile included)")
    (t1, l1), (t4, l4) = out["one device"], out["mesh model=4"]
    diffs = [float(np.abs(a - b).max()) for a, b in zip(l1, l4)]
    say(f"logit trace ({len(l1)} steps x {batch.shape[0]} rows), max abs "
        f"diff mesh vs one device per step: "
        f"{' '.join(f'{d:.3e}' for d in diffs)}; tokens equal "
        f"{bool(np.array_equal(t1, t4))}")
    check(not diverge, f"served tokens differ under the mesh: {diverge}")
    check(np.array_equal(t1, t4), "serve_batch tokens differ under the mesh")
    check(max(diffs) <= 1e-5, f"logits differ by {max(diffs):.3e} under "
          "the mesh")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip (default); 4: only "
                         "the model=4 mesh path against one device")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and operands")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: no src/repro next to this script; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); refusing to run on it",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import use_compilation_cache
    say(f"device {devices[0].device_kind} x{len(devices)}; compilation "
        f"cache at {use_compilation_cache()}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = devices[0].memory_stats() or {}
    say(f"total {time.perf_counter() - t0:.1f} s; device 0 peak memory "
        f"{stats.get('peak_bytes_in_use', 'n/a')} bytes (set-up figures, "
        "not benchmark metrics)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
