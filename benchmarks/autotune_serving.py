"""Regenerate the checked-in fused-kernel autotune cache for the DS-CIM
serving decode shapes (src/repro/kernels/autotune_cache.json).

Covers the skinny-M GEMV tiles the serving hot path hits — the per-token
decode matmuls of the reduced serve configs (M=1, request batch riding the
batch grid axis: MLP gate/up/down, LM head, and the '+attn' projections)
plus the decode-shape microbench GEMVs (M in {1, 8, 16}) — for the two
calibrated macro variants the serve/bench paths use (DS-CIM1/L256,
DS-CIM2/L64) — and, since ISSUE 5, the **paged-attention decode cells**
(kernels/paged_attention.py ``(gh, qp)`` winners: GQA head grouping x
padded q rows) for the serving KV geometry at page_size in {4, 8, 16}.
With the cache checked in, cold-start serving with ``--tune`` (or
``REPRO_DSCIM_TUNE=1``) is a dictionary lookup, never a re-tune; unlisted
shapes still sweep once and land in the ``REPRO_AUTOTUNE_CACHE``-pointed
file if set.

Run from the repo root:  PYTHONPATH=src python -m benchmarks.autotune_serving
Only re-time the paged-attention keys (fused winners kept):
                 PYTHONPATH=src python -m benchmarks.autotune_serving --paged-only
"""
from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# write winners straight into the packaged cache
os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
    REPO, "src", "repro", "kernels", "autotune_cache.json")

# (K, N) of the per-token serving matmuls for the reduced serve configs
# (d_model=64, d_ff=96, vocab_padded=128, 4x16 q heads / 2x16 kv heads):
# MLP gate/up + down, LM head, attention q/o and k/v projections.
SERVE_KN = ((64, 96), (96, 64), (64, 128), (64, 64), (64, 32))
# mesh serving: inside dscim_fused_mvm_sharded's shard_map each device
# tunes for its *local* N = N/nshard — cover the --mesh model={4,8} sizes
# the tests/CI use, so mesh cold starts are lookups too
MESH_NSHARD = (4, 8)
SERVE_BATCHES = (1, 4, 8)          # request batch = the batch grid axis, M=1
BENCH_SHAPES = ((1, 1, 512, 128), (1, 8, 512, 128), (1, 16, 512, 128))
GROUP_K = 128                      # DSCIMLinear serving default granularity

# paged-attention decode cells: (B, KV, n_rep, HD) of the reduced serve
# config (qwen3-0.6b: 2 kv heads x 2-way GQA x hd 16) at the request
# batches serving/CI hit (incl. the DP-sharded locals B/dp) x the
# supported page sizes
PAGED_QSHAPES = tuple((b, 2, 2, 16) for b in (1, 2, 3, 4, 8))
PAGED_PAGE_SIZES = (4, 8, 16)


def serve_kn() -> list:
    """Full-N pairs plus their model-sharded local-N variants (deduped)."""
    kn = set(SERVE_KN)
    for (k, n) in SERVE_KN:
        for s in MESH_NSHARD:
            if n % s == 0:
                kn.add((k, n // s))
    return sorted(kn)


def tune_paged(autotune) -> int:
    """Time the paged-attention cell candidates for the serving shapes."""
    n = 0
    for (B, KV, R, HD) in PAGED_QSHAPES:
        for ps in PAGED_PAGE_SIZES:
            t0 = time.time()
            win = autotune.paged_attn_tiles((B, KV, R, HD), ps,
                                            interpret=True)
            print(f"paged_attn B{B} kv{KV}r{R}hd{HD} ps{ps} -> gh,qp={win} "
                  f"({time.time() - t0:.1f}s)", flush=True)
            n += 1
    return n


def _drop_paged_keys(path: str) -> None:
    """--paged-only re-times the paged winners without touching the fused
    ones: strip just the paged_attn/* keys so ``best`` re-sweeps them
    (DEFAULT_CACHE is the very file being written)."""
    import json
    if not os.path.exists(path):
        return
    with open(path) as f:
        data = json.load(f)
    data = {k: v for k, v in data.items() if not k.startswith("paged_attn/")}
    with open(path, "w") as f:
        json.dump(data, f, indent=0, sort_keys=True)


def main(argv=None):
    from repro.core.seed_search import calibrated_config
    from repro.kernels import autotune

    argv = sys.argv[1:] if argv is None else argv
    if "--paged-only" in argv:
        _drop_paged_keys(autotune.DEFAULT_CACHE)
        autotune.clear()
        n = tune_paged(autotune)
        print(f"# {n} paged keys -> {os.environ['REPRO_AUTOTUNE_CACHE']}")
        return 0

    # a *re*generation must re-time: drop the existing packaged winners
    # first, or autotune.best would read them back (DEFAULT_CACHE is the
    # very file being written) and never sweep the current candidate sets
    if os.path.exists(autotune.DEFAULT_CACHE):
        os.remove(autotune.DEFAULT_CACHE)
    autotune.clear()

    shapes = [(b, 1, k, n) for b in SERVE_BATCHES for (k, n) in serve_kn()]
    shapes += list(BENCH_SHAPES)
    rows = []
    for variant, length in (("dscim1", 256), ("dscim2", 64)):
        cfg = calibrated_config(variant, length, "paper")
        for (B, M, K, N) in shapes:
            # g of the prepared serve weight: prepare_linear_weight pads K
            # up to a whole number of group_k windows, so g is always 128
            t0 = time.time()
            win = autotune.fused_tiles((B, M, K, N), cfg, GROUP_K,
                                       interpret=True, bits="float32")
            rows.append((variant, length, B, M, K, N, win,
                         time.time() - t0))
            print(f"{variant}/L{length} B{B} {M}x{K}x{N} -> bm,bn={win} "
                  f"({rows[-1][-1]:.1f}s)", flush=True)
    nrows = len(rows) + tune_paged(autotune)
    print(f"# {nrows} keys -> {os.environ['REPRO_AUTOTUNE_CACHE']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
