"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell ``<name>`` is ``workloads/<name>.json`` (its traffic: mode, sizes,
rate, what is checked), its configuration ``configs/<config>.json`` (the
deployment: query, keys, partitions, guarantees), the deployment module
the configuration names under ``module`` (its records, its stream and its
reference: ``deployments/emit_measurements.py`` says what one holds) and,
for every per-layer metric that lists the cell, a reader
``metrics/<metric>.py`` with a ``read(obs)`` function.  A later PR adds a
cell, a configuration, a deployment or a per-layer metric by adding files
and manifest entries and edits nothing here; the tests prove it with files
that exist only under their fixtures.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

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
    #: the configuration's deployment module (records, stream, reference)
    deployment: types.ModuleType
    #: the per-layer metrics whose reader needs the profiler's trace and
    #: nothing else: a traced line always carries them (the others where
    #: their reader found a number, else the line names them as not read)
    from_trace: frozenset[str]


#: what a reader sees of a run whose trace is sound and whose program counts
#: nothing: a ``device_trace`` reader that finds its number here needs no
#: counter (``device_idle_share``), one that does not (``device_step_ms``:
#: busy seconds over the program's ``device_steps``) is a ratio of the two
TRACE_ALONE = {
    "window_s": 1.0, "counters": {}, "feeder": {}, "windows_delivered": 0,
    "compiles": 0, "trace": {"busy_s": 0.5, "window_s": 1.0},
}


def _lists(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, prefix: str) -> types.ModuleType:
    """A module of the benchmark's, loaded by path (metric names hold dots,
    so they are no module names; a tree of fixtures is no package)."""
    spec = importlib.util.spec_from_file_location(
        prefix + os.path.basename(path)[:-3].replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(path: str):
    """The ``read`` function of a reader module, loaded by path."""
    return load_module(path, "bench_metric_").read


def _own_or_real(own: str, real: str) -> str:
    """A tree of fixtures may bring files of its own beside the real ones."""
    return own if os.path.exists(own) else real


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
    readers = {
        m: load_reader(_own_or_real(
            os.path.join(bench_dir, "metrics", m + ".py"),
            os.path.join(BENCH_DIR, "metrics", m + ".py"),
        ))
        for m in per_layer
    }
    # the module's path is relative to the root of the manifest's tree
    deployment = load_module(_own_or_real(
        os.path.join(os.path.dirname(os.path.abspath(manifest_path)),
                     config["module"]),
        os.path.join(ROOT, config["module"]),
    ), "bench_deployment_")
    from_trace = frozenset(
        m["name"] for m in manifest["per_layer"]
        if m["name"] in per_layer and m["source"] == "device_trace"
        and readers[m["name"]](TRACE_ALONE) is not None
    )
    return Cell(name, int(entry["chips"]), config, traffic, end_to_end,
                per_layer, readers, deployment, from_trace)
