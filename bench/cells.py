"""Finds a cell's files by the names in ``BENCHMARK.json``: the
configuration (``configs/``), its parameter layout (``architectures/``,
by the file's ``architecture`` key), the traffic mix (``mixes/``) and its
generator (``generators/``), the comparison's limits (``checks/``), the
per-layer metric readers (``metrics/``), the kernels' work counts
(``work/``) and the device peaks (``peaks.json``).  Adding any of them is
adding a file; nothing here names one."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, workload: str, root: str = ROOT,
                 bench_dir: str = HERE):
        self.bench = _json(os.path.join(root, "BENCHMARK.json"))
        self.dir = bench_dir
        ws = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in ws:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(ws)}")
        self.workload = ws[workload]
        cs = {c["name"]: c for c in self.bench["configs"]}
        entry = cs[self.workload["config"]]
        self.config = _json(os.path.join(root, entry["file"]))
        self.mix = _json(os.path.join(bench_dir, "mixes",
                                      f"{self.workload['traffic']}.json"))
        self.check = _json(os.path.join(bench_dir, "checks",
                                        f"{workload}.json"))
        self.peaks_table = _json(os.path.join(bench_dir, "peaks.json"))

    @property
    def name(self) -> str:
        return self.workload["name"]

    def architecture(self):
        """The configuration's parameter layout,
        ``architectures/<architecture>.py``: ``make(model, init, seed)``
        gives the weights in the program's layout; ``position_products``,
        ``attention_ops`` and ``calls`` give the work the per-layer readers
        count (``architectures/dense_tied.py`` documents each)."""
        name = self.config["architecture"]
        return load_module(os.path.join(self.dir, "architectures",
                                        f"{name}.py"),
                           f"bench_architecture_{name}")

    def weights(self, seed: int):
        """The configuration's weights for ``seed``."""
        cfg = self.config
        return self.architecture().make(cfg["model"], cfg["init"], seed)

    def generator(self):
        kind = self.mix["generator"]
        return load_module(os.path.join(self.dir, "generators",
                                        f"{kind}.py"),
                           f"bench_generator_{kind}")

    def peaks(self, device_kind: str) -> dict:
        try:
            return self.peaks_table["devices"][device_kind]
        except KeyError:
            raise KeyError(f"device kind {device_kind!r} has no peaks in "
                           "peaks.json") from None

    def metrics(self, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or self.name in m["workloads"]]

    def metric_reader(self, name: str):
        return load_module(os.path.join(self.dir, "metrics", f"{name}.py"),
                           f"bench_metric_{name}")

    def kernel_work(self, kernel: str):
        path = os.path.join(self.dir, "work", f"{kernel}.py")
        if not os.path.exists(path):
            return None
        return load_module(path, f"bench_work_{kernel}")
