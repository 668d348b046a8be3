"""Adding a configuration, a mix, a per-layer metric and a kernel's work
count is adding files: the harness finds them by name, no code edit."""
from __future__ import annotations

import json
import os

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
