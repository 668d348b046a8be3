"""Tiny tile autotuner for the DS-CIM Pallas kernels.

Sweeps a small candidate list of tile shapes per (kernel kind, shape, cfg)
key, times each candidate on shared synthetic operands of the requested
shape, and caches the winner — in memory always, on disk when
``REPRO_AUTOTUNE_CACHE`` points at a JSON file (so serving processes
inherit tuned tiles across restarts), and from the **checked-in serving
cache** ``autotune_cache.json`` next to this module, which ships winners
for the DS-CIM decode serving shapes (skinny-M GEMV tiles, B on the batch
grid axis) so cold-start serving never re-tunes them.  Lookup order:
memory -> env-pointed cache -> packaged cache; only the env-pointed file
is ever written.

Deliberately simple: a handful of curated candidates beats an exhaustive
sweep for these kernels (the tile space is tiny — MXU-aligned bm/bn, the
pad-free bm=M decode tiles, and a couple of contraction sub-tile sizes),
and timing happens at most once per key per process.
"""
from __future__ import annotations

import json
import os
import time

import jax

__all__ = ["best", "fused_tiles", "mvm_tiles", "paged_attn_tiles", "clear",
           "DEFAULT_CACHE"]

_CACHE: dict[str, tuple] = {}
_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
# checked-in winners for the serving shapes (benchmarks/autotune_serving.py
# regenerates it; keys embed shape/cfg/bits/backend so stale entries can
# never match a different geometry)
DEFAULT_CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "autotune_cache.json")


def clear():
    _CACHE.clear()


def _disk_path() -> str | None:
    return os.environ.get(_CACHE_ENV) or None


def _read_json(path: str) -> dict:
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            pass
    return {}


def _load_disk() -> dict:
    """Merged on-disk caches: packaged serving defaults first, the
    env-pointed (writable) cache overriding them."""
    data: dict = {}
    for path in (DEFAULT_CACHE, _disk_path()):
        data.update(_read_json(path))
    return data


def _save_disk(key: str, val: tuple):
    path = _disk_path()
    if not path:
        return
    # read back only the env-pointed file itself — merging the packaged
    # cache in would freeze its current entries there, where they'd shadow
    # future updates to the checked-in winners
    data = _read_json(path)
    data[key] = list(val)
    try:
        with open(path, "w") as f:
            json.dump(data, f, indent=0, sort_keys=True)
    except OSError:
        pass


def _time_once(fn, n: int = 2, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best_t = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best_t = min(best_t, time.perf_counter() - t0)
    return best_t


def best(key: str, candidates, bench) -> tuple:
    """Return the cached winner for ``key`` or sweep ``candidates``.

    ``bench(cand)`` must return a zero-arg callable running the kernel with
    that candidate; candidates that fail to trace/launch are skipped.
    """
    if key in _CACHE:
        return _CACHE[key]
    disk = _load_disk()
    if key in disk:
        win = tuple(disk[key])
        _CACHE[key] = win
        return win
    win, win_t = None, float("inf")
    for cand in candidates:
        try:
            t = _time_once(bench(cand))
        except Exception:  # noqa: BLE001 — bad tile shape for this geometry
            continue
        if t < win_t:
            win, win_t = tuple(cand), t
    if win is None:
        raise ValueError(f"autotune: no viable candidate for {key}")
    _CACHE[key] = win
    _save_disk(key, win)
    return win


# --------------------------------------------------------------------------
# kernel-specific entry points
# --------------------------------------------------------------------------

def _mxu_opts(dim: int):
    """Tile options for an MXU-aligned axis of extent ``dim``."""
    up8 = -(-dim // 8) * 8
    return sorted({min(128, up8), min(64, up8), min(256, up8)})


def fused_tiles(shape, cfg, g: int, *, interpret: bool,
                bits: str = "bfloat16"):
    """(bm, bn) winner for dscim_fused_mvm on (B, M, K, N) operands (the
    K step is always one whole quantization window).

    Decode serving shapes (M <= 16 — the skinny GEMV regime, batch riding
    the batch grid axis) get their own candidate set: the pad-free bm=M
    tile plus the 8/16-row aligned ones (candidates that fail to launch on
    a backend — e.g. sub-sublane tiles on TPU — are skipped by ``best``)."""
    import jax.numpy as jnp
    import numpy as np

    from .dscim_fused import dscim_fused_mvm

    B, M, K, N = shape
    key = f"fused/{cfg.name}/k{cfg.k}L{cfg.length}t{cfg.trunc}/" \
          f"{B}x{M}x{K}x{N}/g{g}/{bits}/{'cpu' if interpret else 'tpu'}"
    if M <= 16:
        bms = sorted({M, -(-M // 8) * 8, 16})
    else:
        bms = _mxu_opts(M)[:2]
    cands = [(bm, bn) for bm in bms for bn in _mxu_opts(N)[:2]]
    # one shared operand set for all candidates (shape, not data, matters)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (B, M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (K, N)), jnp.float32)

    def bench(cand):
        bm, bn = cand
        return lambda: dscim_fused_mvm(
            x, w, cfg, group_k=(g if g != K else None), bm=bm, bn=bn,
            bits=bits, interpret=interpret)

    return best(key, cands, bench)


def paged_attn_tiles(shape, page_size: int, *, interpret: bool):
    """(gh, qp) winner for the paged-attention decode kernel on a
    (B, KV, n_rep, HD) query against ``page_size``-token int8 pages.

    ``gh`` (kv heads per grid cell — the GQA head-grouping knob: gh > 1
    amortizes one page DMA across head groups sharing the page bytes) and
    ``qp`` (q rows per cell: pad-free n_rep, or n_rep rounded up to the
    8-row sublane tile).  The page count is deliberately NOT part of the
    key — the winning cell shape is a per-page property, and decode MP
    grows with capacity; candidates are swept at a fixed representative
    walk length.  The checked-in cache ships winners for the decode
    serving shapes at page_size in {4, 8, 16}."""
    import jax.numpy as jnp
    import numpy as np

    from .paged_attention import chip_legal_ghs, paged_attention_decode

    B, KV, R, HD = shape
    key = f"paged_attn/B{B}/kv{KV}r{R}hd{HD}/ps{page_size}/" \
          f"{'cpu' if interpret else 'tpu'}"
    # the chip refuses page blocks of other group sizes
    ghs = sorted({g for g in (1, 2, 4, KV) if KV % g == 0}) if interpret \
        else chip_legal_ghs(KV)
    qps = sorted({R, -(-R // 8) * 8})
    cands = [(gh, qp) for gh in ghs for qp in qps]
    MP = 4                               # representative decode page walk
    P = B * MP
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (B, KV, R, HD)), jnp.float32)
    kp = jnp.asarray(rng.integers(-127, 128, (P, page_size, KV, HD)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (P, page_size, KV, HD)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (P, KV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (P, KV)), jnp.float32)
    kt = jnp.asarray(rng.normal(0, 1, (B, page_size, KV, HD)), jnp.bfloat16)
    vt = jnp.asarray(rng.normal(0, 1, (B, page_size, KV, HD)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(P).reshape(B, MP), jnp.int32)
    pos = jnp.full((B,), MP * page_size - 2, jnp.int32)

    def bench(cand):
        gh, qp = cand
        return lambda: paged_attention_decode(
            q, kp, vp, ks, vs, kt, vt, table, pos, gh=gh, qp=qp,
            interpret=interpret)

    return best(key, cands, bench)


def mvm_tiles(shape, cfg, *, interpret: bool):
    """(bm, bn, bk, bl) winner for ops.dscim_mvm on (M, K, N) operands."""
    import jax.numpy as jnp
    import numpy as np

    M, K, N = shape
    key = f"mvm/{cfg.name}/k{cfg.k}L{cfg.length}t{cfg.trunc}/" \
          f"{M}x{K}x{N}/{'cpu' if interpret else 'tpu'}"
    bls = [bl for bl in (64, 128, 256) if bl <= cfg.length
           and cfg.length % bl == 0] or [cfg.length]
    cands = [(bm, bn, bk, bl)
             for bm in _mxu_opts(M)[:2] for bn in _mxu_opts(N)[:2]
             for bk in (8, 16) for bl in bls[:2]]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-128, 128, (M, K)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (K, N)), jnp.int8)

    def bench(cand):
        from .ops import dscim_mvm
        bm, bn, bk, bl = cand
        return lambda: dscim_mvm(x, w, cfg, bm=bm, bn=bn, bk=bk, bl=bl,
                                 interpret=interpret)

    return best(key, cands, bench)
