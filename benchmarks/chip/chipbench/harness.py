"""Finding a cell's files by name and putting its result line together.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

* the configuration: the ``file`` of its ``configs`` entry;
* the traffic mix: ``benchmarks/chip/traffic/<traffic>.json``, whose
  ``job`` key names the job kind, ``benchmarks/chip/jobs/<job>.py``;
* the limits of its correctness check: ``benchmarks/chip/limits/<cell>.json``;
* each per-layer metric's reader: ``benchmarks/chip/metrics/<metric>.py``.

So a later cell, configuration, traffic mix or metric is added by adding
files, never by editing one.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
REL = os.path.relpath(HERE, ROOT)


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration, traffic,
    limits and metrics, read from the checkout rooted at ``root``."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = read_json(os.path.join(root, "BENCHMARK.json"))
        found = [w for w in self.bench["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.entry = found[0]
        self.name = name
        conf = next(c for c in self.bench["configs"]
                    if c["name"] == self.entry["config"])
        self.config = read_json(os.path.join(root, conf["file"]))
        self.traffic = read_json(self.path("traffic",
                                           self.entry["traffic"] + ".json"))
        self.limits = read_json(self.path("limits", name + ".json"))
        self.chips = int(self.entry["chips"])

    def path(self, *parts) -> str:
        return os.path.join(self.root, REL, *parts)

    def job_module(self):
        kind = self.traffic["job"]
        return load_module(self.path("jobs", kind + ".py"),
                           f"chipbench_job_{kind}")

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    def per_layer(self) -> list:
        """Per-layer metrics whose ``workloads`` list this cell."""
        return [m for m in self.bench["per_layer"]
                if self.name in m["workloads"]]

    def reader(self, metric: str):
        return load_module(self.path("metrics", metric + ".py"),
                           "chipbench_metric_" + metric.replace(".", "_"))


def read_layers(cell: Cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer():
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(result: dict, checks: dict) -> None:
    """The compared numbers as the last lines on stderr; the result as the
    last line on stdout, with the numbers compared under its last key."""
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
