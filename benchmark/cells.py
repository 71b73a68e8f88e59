"""Finding a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds an entry to the manifest and files beside these, and
edits nothing.
"""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def _reported(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, manifest: dict | None = None, root: str = ROOT,
              traffic_dir: str | None = None) -> dict:
    """The cell ``name``: its manifest entry, configuration, traffic mix,
    job module and the names of the metrics it reports."""
    manifest = manifest or load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r}; the manifest has "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _load(os.path.join(root, entry["file"]))
    traffic = _load(os.path.join(traffic_dir or os.path.join(HERE, "traffic"),
                                 cell["traffic"] + ".json"))
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "job": importlib.import_module(f"benchmark.jobs.{config['job']}"),
        "end_to_end": [m["name"] for m in manifest["end_to_end"]
                       if _reported(m, name)],
        "per_layer": [m["name"] for m in manifest["per_layer"]
                      if _reported(m, name)],
    }


def read_layer_metric(name: str, run: dict):
    """``layer_metrics/<name>.json`` names the reader module (its own name
    when it gives none) and the reader's arguments. A reader that finds
    nothing to read returns None."""
    spec = _load(os.path.join(HERE, "layer_metrics", name + ".json"))
    reader = importlib.import_module(
        "benchmark.layer_metrics." + spec.get("reader", name).replace("-", "_"))
    value = reader.read(run, **spec.get("args", {}))
    return None if value is None else {"value": value, "unit": spec["unit"]}
