"""``EngineConfig`` holds no option that outlived its reader, and the
options that were removed are refused by name."""

import ast
import dataclasses
import functools
from pathlib import Path

import numpy as np
import pytest

from denormalized_tpu import Context, col
from denormalized_tpu.api import functions as F
from denormalized_tpu.api.context import EngineConfig
from denormalized_tpu.common.errors import PlanError
from denormalized_tpu.sources.memory import MemorySource

PACKAGE = Path(__file__).resolve().parent.parent / "denormalized_tpu"
FIELDS = [f.name for f in dataclasses.fields(EngineConfig)]


def _name(node) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _is_config(node) -> bool:
    name = _name(node).lower()
    return "config" in name or "cfg" in name


@functools.cache
def _config_reads() -> dict[str, list[str]]:
    """Attribute name -> modules that load it from something named like a
    config (``self.config.x``, ``cfg.x``, ``getattr(config, "x", ...)``),
    over the package without ``api/context.py`` (the definition)."""
    reads: dict[str, list[str]] = {}
    for path in sorted(PACKAGE.rglob("*.py")):
        if path == PACKAGE / "api" / "context.py":
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            attr = None
            if isinstance(node, ast.Attribute) and isinstance(
                node.ctx, ast.Load
            ):
                if _is_config(node.value):
                    attr = node.attr
            elif (
                isinstance(node, ast.Call)
                and _name(node.func) == "getattr"
                and len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and _is_config(node.args[0])
            ):
                attr = node.args[1].value
            if attr is not None:
                reads.setdefault(attr, []).append(path.name)
    return reads


@pytest.mark.parametrize("field", FIELDS)
def test_every_engine_config_field_is_read(field):
    """An option nothing reads is a promise the engine does not keep: it
    goes with the last code that read it."""
    assert field in _config_reads(), (
        f"EngineConfig.{field} is read by no module under denormalized_tpu/ "
        "other than api/context.py"
    )


def _window_query(config, make_batch):
    t0 = 1_700_000_000_000
    batch = make_batch(
        np.arange(t0, t0 + 2000, 10, dtype=np.int64),
        np.array(["a", "b"] * 100, dtype=object),
        np.ones(200),
    )
    return (
        Context(config)
        .from_source(
            MemorySource.from_batches(
                [batch], timestamp_column="occurred_at_ms"
            )
        )
        .window(["sensor_name"], [F.count(col("reading")).alias("c")], 1000)
        .collect()
    )


def _pallas_dense(make_batch):
    _window_query(EngineConfig(device_strategy="pallas_dense"), make_batch)


def _emission_compaction(make_batch):
    EngineConfig().set("emission_compaction", True)


@pytest.mark.parametrize(
    "use, error",
    [(_pallas_dense, ValueError), (_emission_compaction, PlanError)],
    ids=["device_strategy=pallas_dense", "emission_compaction"],
)
def test_removed_option_is_refused(make_batch, use, error):
    """PR 29 removed both after chip pairs in which neither beat the
    default (PERF.md, Findings): a job that still names one fails at its
    first use, not silently on another path."""
    assert _window_query(EngineConfig(), make_batch).num_rows > 0
    with pytest.raises(error, match="pallas_dense|emission_compaction"):
        use(make_batch)
