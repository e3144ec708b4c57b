"""Resolve a cell of ``BENCHMARK.json`` into its configuration and traffic.

A cell names a configuration file and a traffic file; both are found by
name, so a new cell needs only new files and one entry.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    end_to_end: tuple     # metric entries this cell reports with --trace 0
    per_layer: tuple      # metric entries this cell reports with --trace 1


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics.

    Raises ``KeyError`` for a cell the benchmark does not declare."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    here = root / HERE.name
    config = json.loads((here / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads(
        (here / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    moved = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"]
                  if _reports(m, name) and m["moves"] in moved)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)
