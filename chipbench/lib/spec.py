"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

A configuration is ``chipbench/configs/<config>.json``, a traffic mix
``chipbench/traffic/<traffic>.json`` and a metric
``chipbench/metrics/<metric>.py``.  Adding any of them is adding a file
and an entry; no code here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

__all__ = ["Bench", "load_bench"]

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class Bench:
    def __init__(self, data: dict, root: str = ROOT):
        self.data = data
        self.root = root
        self.bench_dir = os.path.join(root, "chipbench")

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r}; have "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r}")

    def mix_path(self, traffic: str) -> str:
        return os.path.join(self.bench_dir, "traffic", f"{traffic}.json")

    def metrics_for(self, cell: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics (untraced run) or per-layer
        metrics (traced run), in ``BENCHMARK.json`` order."""
        group = self.data["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``chipbench/metrics/<metric>.py``.
        A metric split by cell kind (``latency_p50_ms.decode``) is read by
        its base's file unless it has one of its own."""
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        if not os.path.exists(path):
            base = metric.split(".", 1)[0]
            path = os.path.join(self.bench_dir, "metrics", f"{base}.py")
        modname = "chipbench_metric_" + re.sub(r"\W", "_", metric)
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def load_bench(root: str = ROOT) -> Bench:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return Bench(json.load(f), root)
