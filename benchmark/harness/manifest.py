"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell ``<name>`` is ``workloads/<name>.json`` (its traffic: mode, sizes,
rate, what is checked), its configuration ``configs/<config>.json`` (the
deployment: query, keys, partitions, guarantees) and, for every per-layer
metric that lists the cell, a reader ``metrics/<metric>.py`` with a
``read(obs)`` function.  A later PR adds a cell, a configuration or a
per-layer metric by adding files and manifest entries and edits nothing
here; the tests prove it with files that exist only under their fixtures.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: dict[str, str]  # metric name -> unit
    per_layer: dict[str, str]
    readers: dict  # per-layer metric name -> read(obs)


def _lists(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(path: str):
    """The ``read`` function of a reader module, loaded by path (metric
    names hold dots, so they are no module names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_cell(name: str, manifest_path: str | None = None,
              bench_dir: str | None = None) -> Cell:
    manifest_path = manifest_path or os.path.join(ROOT, "BENCHMARK.json")
    bench_dir = bench_dir or BENCH_DIR
    manifest = _load_json(manifest_path)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(
            f"no workload {name!r} in {manifest_path}: "
            f"{[w['name'] for w in manifest['workloads']]}"
        )
    traffic = _load_json(os.path.join(bench_dir, "workloads", name + ".json"))
    config = _load_json(
        os.path.join(bench_dir, "configs", entry["config"] + ".json")
    )
    for key, want in (("config", entry["config"]), ("traffic", entry["traffic"])):
        if traffic.get(key) != want:
            raise ValueError(
                f"workloads/{name}.json says {key} {traffic.get(key)!r}, "
                f"the manifest {want!r}"
            )
    end_to_end = {
        m["name"]: m["unit"] for m in manifest["end_to_end"] if _lists(m, name)
    }
    per_layer = {
        m["name"]: m["unit"] for m in manifest["per_layer"] if _lists(m, name)
    }
    readers = {}
    for m in per_layer:
        # a tree of fixtures may bring readers of its own beside the real ones
        own = os.path.join(bench_dir, "metrics", m + ".py")
        readers[m] = load_reader(
            own if os.path.exists(own)
            else os.path.join(BENCH_DIR, "metrics", m + ".py")
        )
    return Cell(name, int(entry["chips"]), config, traffic, end_to_end,
                per_layer, readers)
