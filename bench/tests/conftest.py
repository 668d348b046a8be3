"""Shared fixtures: a tiny configuration (reduced widths, bfloat16 compute
as the real configurations state, the Pallas kernels interpreted on the
CPU) installed as a cell of a temporary copy of the benchmark, so that
tests drive the harness as a run does."""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"n_layers": 2, "d_model": 128, "n_heads": 2, "n_kv": 1,
              "head_dim": 128, "d_ff": 256, "vocab": 512,
              "vocab_padded": 512, "norm": "rmsnorm", "norm_eps": 1e-06,
              "qk_norm": True, "rope_theta": 1000000.0,
              "tie_embeddings": True}


def tiny_config() -> dict:
    with open(os.path.join(BENCH, "configs", "qwen3-0.6b.dscim1.json")) as f:
        real = json.load(f)
    m = TINY_MODEL
    return {
        "name": "tiny", "arch": "qwen3-0.6b", "source": "test",
        "reduced": [], "reference": "dense_decoder",
        "architecture": "dense_tied",
        "program": {k: m[k] for k in ("n_layers", "d_model", "n_heads",
                                      "n_kv", "head_dim", "d_ff", "vocab")}
        | {"vocab_pad_mult": 256, "compute_dtype": "bfloat16",
           "cache_dtype": "bfloat16", "q_chunk": 8, "kv_chunk": 8,
           "remat": False},
        "model": copy.deepcopy(m),
        "precision": {"compute": "bfloat16"},
        "dscim": real["dscim"],
        "init": real["init"],
        "serve": {"kv": "int8", "page_size": 8, "slots": 4, "seg_len": 2,
                  "chunk_len": 16, "buckets": [], "max_prompt": 64,
                  "max_new_cap": 16, "max_queue": 16, "paged_attn": "kernel",
                  "watchdog": "off"},
    }


TINY_MIX = {"generator": "closed", "size_seed": 5, "clients": "slots",
            "per_client": 30, "stagger_tokens": 4,
            "prompt": {"median": 24, "sigma": 0.6, "min": 4, "max": 60},
            "output": {"median": 6, "sigma": 0.6, "min": 2, "max": 16},
            "ramp_s": 1, "trace_s": 2}

# the tiny cell's limit, set as a cell's is: on the CPU sound runs read a
# mean gap of 0.0007 to 0.0089 over twelve seeds, the control 0.103 to 0.174
TINY_CHECK = {"tokens": 512, "rows": 128, "want_rows": 64,
              "mean_logit_gap": 0.04}


def install(root: str, name: str, config: dict, mix: dict, check: dict,
            traffic: str = "tinyload") -> None:
    """Copy the benchmark into ``root`` and add a cell ``<name>.load``."""
    bench = os.path.join(root, "bench")
    if not os.path.exists(bench):
        shutil.copytree(BENCH, bench,
                        ignore=shutil.ignore_patterns("tests", "__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(bench, "configs", f"{name}.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "mixes", f"{traffic}.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "checks", f"{name}.load.json"), "w") as f:
        json.dump(check, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": name, "source": "test",
                         "file": f"bench/configs/{name}.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": f"{name}.load", "config": name,
                           "traffic": traffic, "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(f"{name}.load")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_root"))
    install(root, "tiny", tiny_config(), TINY_MIX, TINY_CHECK)
    return root


@pytest.fixture(scope="session")
def tiny_cell(tiny_root):
    import cells
    return cells.Cell("tiny.load", root=tiny_root,
                      bench_dir=os.path.join(tiny_root, "bench"))
