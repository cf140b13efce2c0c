"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix; each metric names its
reader. The harness looks each up as a file of its own:

* configuration: the ``file`` of its ``configs`` entry;
* traffic mix: ``chipbench/traffic/<traffic>.json``;
* per-layer metric: ``chipbench/metrics/<name>.py``, with ``read(ctx)``.

Adding a cell is adding such files and entries; no file here changes.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list           # metric entries of BENCHMARK.json
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(benchmark.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``chipbench/metrics/<name>.py``."""
    return importlib.import_module(f"chipbench.metrics.{name}").read
