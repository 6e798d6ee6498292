"""Everything a run needs, found by name: the cell in ``BENCHMARK.json``,
its configuration file (published keys of any family that the translation
takes, ``driver.arch_config``: dense GQA, MLA, MoE), its traffic mix
``traffic/<mix>.json``, its correctness limits ``limits/<cell>.json`` and
the reader of each per-layer metric ``metrics/<metric>.py``. Adding a
configuration, a cell, a mix or a metric adds files and entries; no file
here changes."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``base`` (the
    benchmark's directory) and ``root`` (the checkout)."""

    def __init__(self, root: str = ROOT, base: str = HERE):
        self.root, self.base = root, base
        self.doc = _json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return _json(os.path.join(self.base, "traffic", name + ".json"))

    def limits(self, cell: str) -> Dict:
        return _json(os.path.join(self.base, "limits", cell + ".json"))

    def metrics(self, kind: str, cell: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics ``cell`` reports."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py`` under ``base``,
        or else under the benchmark's own directory."""
        path = os.path.join(self.base, "metrics", metric + ".py")
        if not os.path.exists(path):
            path = os.path.join(HERE, "metrics", metric + ".py")
        mod_name = "perfbench.metrics." + metric \
            if path.startswith(HERE + os.sep + "metrics") \
            else "_bench_metric_" + metric
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
