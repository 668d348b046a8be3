"""Adding a configuration, its parameter layout, a mix, a per-layer metric
and a kernel's work count is adding files: the harness finds them by name,
no code edit."""
from __future__ import annotations

import json
import os
import shutil

import cells
import conftest


def test_new_files_are_found(tmp_path):
    root = str(tmp_path)
    cfg = conftest.tiny_config()
    cfg["name"] = "tiny2"
    mix = dict(conftest.TINY_MIX, generator="open_bursty", rate=3.0,
               burst_p=0.5, horizon_s=60)
    conftest.install(root, "tiny2", cfg, mix, conftest.TINY_CHECK,
                     traffic="newmix")
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "metrics", "router.new_share.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    with open(os.path.join(bench, "work", "some_new_kernel.py"), "w") as f:
        f.write("PEAK = 'bf16_flops'\n\ndef work(n):\n    return 2 * n, n\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["per_layer"].append({"name": "router.new_share", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "router", "moves": "output_tok_s",
                           "workloads": ["tiny2.load"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    cell = cells.Cell("tiny2.load", root=root, bench_dir=bench)
    assert cell.config["name"] == "tiny2"
    assert cell.mix["generator"] == "open_bursty"
    plan = cell.generator().make(cell.mix, seed=1, vocab=512, slots=4)
    assert plan["kind"] == "open" and plan["requests"]
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "router.new_share" in names
    assert cell.metric_reader("router.new_share").read(None) == 42.0
    assert cell.kernel_work("some_new_kernel").work(3) == (6, 3)
    assert cell.kernel_work("no_such_kernel") is None
    # a cell of the real benchmark is untouched by the new files
    other = cells.Cell("qwen3-dscim1.batch", root=root, bench_dir=bench)
    assert "router.new_share" not in [m["name"] for m in
                                      other.metrics("per_layer")]


# A configuration with a parameter layout of its own: the dense layout's
# arrays from another key, and one more product per layer in its work
TWIN_ARCH = '''
import os

import cells

dense = cells.load_module(os.path.join(os.path.dirname(__file__),
                                       "dense_tied.py"), "twin_dense")
EXTRA = (128, 384)


def make(model, init, seed):
    return dense.make(model, init, seed + 1)


def position_products(ctx):
    return dense.position_products(ctx) + [(ctx.model["n_layers"], [EXTRA])]


def attention_ops(ctx, context):
    return dense.attention_ops(ctx, context)


def calls(ctx):
    return dense.calls(ctx)
'''

# a per-layer metric that reads a Router.stats() counter at the span's end
SUBMITTED = '''
SEEN = []


def read(ctx):
    SEEN.append(ctx)
    return float(ctx.stats1["submitted"])
'''


def _hashes(root: str) -> dict:
    import hashlib
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _extends(old, new) -> bool:
    """``new`` holds all of ``old``: the same scalars, every key, and every
    list's entries first, in order."""
    if isinstance(old, dict):
        return isinstance(new, dict) and all(
            k in new and _extends(v, new[k]) for k, v in old.items())
    if isinstance(old, list):
        return isinstance(new, list) and len(new) >= len(old) and all(
            _extends(a, b) for a, b in zip(old, new))
    return old == new


def test_new_model_by_new_files_alone(tmp_path, monkeypatch, capsys):
    """A cell whose configuration names a parameter layout of its own and
    a counter metric: the layout's weights are served, ``mfu.step`` counts
    its products, the counter is reported, and no file that was there
    before changes (``BENCHMARK.json`` only gains entries)."""
    import sys
    import types

    import jax
    import numpy as np

    import run
    root = str(tmp_path)
    bench = os.path.join(root, "bench")
    shutil.copytree(cells.HERE, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), root)
    before = _hashes(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench_before = json.load(f)

    cfg = conftest.tiny_config()
    cfg["name"], cfg["architecture"] = "twin", "dense_twin"
    conftest.install(root, "twin", cfg, conftest.TINY_MIX,
                     conftest.TINY_CHECK)
    with open(os.path.join(bench, "architectures", "dense_twin.py"),
              "w") as f:
        f.write(TWIN_ARCH)
    with open(os.path.join(bench, "metrics", "router.test_submitted.py"),
              "w") as f:
        f.write(SUBMITTED)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["per_layer"].append({"name": "router.test_submitted", "unit": "1",
                           "better": "higher", "source": "program_counter",
                           "layer": "router", "moves": "output_tok_s",
                           "workloads": ["twin.load"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    cell = cells.Cell("twin.load", root=root, bench_dir=bench)
    served = []
    real_build = run.build_router

    def spy(cfg, arch, params):
        served.append(jax.tree.map(np.asarray, params))
        return real_build(cfg, arch, params)

    monkeypatch.setattr(run, "build_router", spy)
    dev, devs, _ = run.require_chip(cell, platform=None)
    peaks = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}
    seed = 2**32 + 17
    args = types.SimpleNamespace(seed=seed, seconds=3, trace=1)
    assert run.measure_cell(cell, args, dev, devs, peaks) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True

    # the layout's weights were served, not the dense layout's
    twin = sys.modules["bench_architecture_dense_twin"]
    dense = twin.dense
    got = served[0]["layers"]["mlp"]["w_up"]
    want = twin.make(cfg["model"], cfg["init"], seed)["layers"]["mlp"]
    assert np.array_equal(got, np.asarray(want["w_up"]))
    other = dense.make(cfg["model"], cfg["init"], seed)["layers"]["mlp"]
    assert not np.array_equal(got, np.asarray(other["w_up"]))

    # the counter reached its reader, and mfu.step counted the products
    seen = sys.modules["bench_metric_router.test_submitted"].SEEN
    assert len(seen) == 1
    ctx = seen[0]
    assert res["metrics"]["router.test_submitted"]["value"] == \
        ctx.stats1["submitted"] > 0

    def mfu(arch):
        w = ctx.work
        params = sum(t * sum(K * N for K, N in p)
                     for t, p in arch.position_products(ctx))
        attn = sum(k * arch.attention_ops(ctx, c) for c, k in w["contexts"])
        ops = 2 * params * (w["prefill_rows"] + w["decoded"]) + attn
        return 100.0 * ops / (ctx.window_s * peaks["bf16_flops"])

    got = res["metrics"]["mfu.step"]["value"]
    assert got == mfu(twin) and got > mfu(dense)

    # nothing that was there before changed
    after = _hashes(root)
    changed = [p for p, h in before.items()
               if p != "BENCHMARK.json" and after.get(p) != h]
    assert not changed
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        assert _extends(bench_before, json.load(f))
