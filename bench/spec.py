"""What one run of one cell is: found by name from ``BENCHMARK.json``.

Each configuration, traffic mix, cell and per-layer metric is a file of
its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the sizes as run (the configuration entry's
  ``file``), with the registry arch they are served through and the
  name of their plain reference under ``reference/``;
* ``traffic/<traffic>.json``: the mix, read by ``traffic_gen``;
* ``cells/<workload>.json``: what the cell sets besides its mix: the
  pool (slots, ``max_seq``), the offered rate and the
  correctness check's sample and limit;
* ``metrics/<metric>.py``: one ``read(record)`` per metric.

Adding a configuration, a mix, a cell or a metric takes new files and
new entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[dict], float | None]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_metric_reader(bench_dir: str, name: str) -> Callable:
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"metric {name!r}: no reader at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, workload: str) -> bool:
    cells = entry.get("workloads")
    return cells is None or workload in cells


def load_cell(root: str, workload: str, bench_dir: str | None = None
              ) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with every
    file it names loaded.  ``bench_dir`` holds ``traffic/``, ``cells/``
    and ``metrics/`` (default: this directory)."""
    bench_dir = bench_dir or BENCH_DIR
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    cell = load_json(os.path.join(bench_dir, "cells", f"{workload}.json"))

    def metrics(kind: str) -> list[Metric]:
        return [Metric(m["name"], m["unit"],
                       load_metric_reader(bench_dir, m["name"]))
                for m in bench[kind] if _applies(m, workload)]

    return Cell(workload, int(w["chips"]), config, traffic, cell,
                metrics("end_to_end"), metrics("per_layer"))
