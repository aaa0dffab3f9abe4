"""Two trees of the benchmark for the tests that state the manifest's rules:
the real one, and one entered by new files and entries alone.

The entered tree is built in a temporary directory from the real one: a
copy of ``benchmark/`` and of the reader cases, ``fixtures/entering/``
laid over them (a configuration, a cell, a deployment module and a
per-layer metric that exist nowhere else), and ``BENCHMARK.json`` with
``fixtures/entering/entries.json`` appended the way a later PR appends:
its configurations, cells and per-layer metrics at the end of their lists,
and each new one-chip cell at the end of the end-to-end metrics it names
and of every per-layer metric that lists every one-chip cell.  A rule test
takes the ``tree`` fixture and holds on both."""

import dataclasses
import json
import os
import shutil

import pytest

from benchmark.harness import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ENTERING = os.path.join(HERE, "fixtures", "entering")
CASES = os.path.join("tests", "benchmark_tests", "reader_cases")


@dataclasses.dataclass(frozen=True)
class Tree:
    root: str

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.root, "BENCHMARK.json")

    @property
    def bench_dir(self) -> str:
        return os.path.join(self.root, "benchmark")

    @property
    def bench(self) -> dict:
        with open(self.manifest_path) as f:
            return json.load(f)

    def load_cell(self, name: str) -> manifest.Cell:
        return manifest.load_cell(name, self.manifest_path, self.bench_dir)

    def cells(self, chips: int | None = None) -> list[str]:
        """The cells' names in manifest order, of ``chips`` chips if given."""
        return [w["name"] for w in self.bench["workloads"]
                if chips is None or w["chips"] == chips]

    def cases(self) -> dict:
        """``{metric: (obs, want)}`` of the reader cases kept as files, one
        a metric, under ``tests/benchmark_tests/reader_cases/``."""
        d = os.path.join(self.root, CASES)
        out = {}
        for f in sorted(os.listdir(d)) if os.path.isdir(d) else []:
            with open(os.path.join(d, f)) as fh:
                case = json.load(fh)
            out[f[:-len(".json")]] = (case["obs"], case["want"])
        return out


def enter(dst: str) -> Tree:
    """The real tree with ``fixtures/entering`` entered into it, at ``dst``."""
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(manifest.BENCH_DIR, os.path.join(dst, "benchmark"),
                    ignore=ignore)
    if os.path.isdir(os.path.join(manifest.ROOT, CASES)):
        shutil.copytree(os.path.join(manifest.ROOT, CASES),
                        os.path.join(dst, CASES))
    shutil.copytree(ENTERING, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "entries.json"), dirs_exist_ok=True)
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ENTERING, "entries.json")) as f:
        entries = json.load(f)
    one_chip = {w["name"] for w in bench["workloads"] if w["chips"] == 1}
    for w in entries["workloads"]:
        assert w["chips"] == 1, "only one-chip cells enter this way"
        for m in bench["end_to_end"]:
            if m["name"] in entries["end_to_end"]:
                m["workloads"].append(w["name"])
        for m in bench["per_layer"]:
            if one_chip <= set(m["workloads"]):
                m["workloads"].append(w["name"])
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += entries[key]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return Tree(dst)


@pytest.fixture(scope="session")
def entered_tree(tmp_path_factory) -> Tree:
    return enter(str(tmp_path_factory.mktemp("entered")))


@pytest.fixture(scope="session", params=["real", "entered"])
def tree(request) -> Tree:
    if request.param == "real":
        return Tree(manifest.ROOT)
    return request.getfixturevalue("entered_tree")
