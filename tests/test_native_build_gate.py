"""Native-build smoke gate: every C++ component must COMPILE on this
image, loudly.

PR 1 found the JSON parser had never compiled here (a gcc-10 libstdc++
gap) while every caller silently caught the build failure and ran the
~30x-slower pure-Python fallback — for five rounds.  This gate makes
that failure mode structurally impossible: it compiles every
``denormalized_tpu/native/*.cpp`` from source with the same flags the
production loader uses, into a scratch directory, and fails the suite
with the compiler's stderr on any error.  A second check drives the real
``build.load()`` path so the ctypes modules are known loadable, not just
compilable."""

import shutil
import subprocess
import sysconfig
from pathlib import Path

import pytest

NATIVE = Path(__file__).resolve().parent.parent / "denormalized_tpu" / "native"

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None,
    reason="no compiler — the pure-Python fallbacks cover this environment",
)

_PY_INC = sysconfig.get_paths()["include"]

# every ctypes-loaded module and its production extra flags (mirrors the
# call sites: sources/kafka.py loads kafka_client with -lz; state/lsm.py
# builds lsmkv with the base flags; pyassemble needs the Python headers —
# the interner's optional -DINTERN_HAVE_PYTHON build is exercised by its
# own loader check below)
_MODULES = {
    "json_parser": [],
    "avro_parser": [],
    "interner": [],
    "partial_agg": [],
    "sketch_update": [],
    "kafka_client": ["-lz"],
    "lsmkv": [],
    "pyassemble": [f"-I{_PY_INC}"],
}

# the production loader's warning surface, made FATAL here: the gate is
# where warning-cleanliness is enforced (build.py keeps warnings
# non-fatal so a future compiler's new diagnostics can't brick first-use
# builds in production — the gate catches them in CI instead)
from denormalized_tpu.native.build import WARN_FLAGS

_BASE_FLAGS = ["-O2", "-shared", "-fPIC", "-std=c++17", *WARN_FLAGS,
               "-Werror"]


def test_all_native_sources_enumerated():
    """A new .cpp dropped into native/ must be added to the gate (or the
    gate is silently incomplete) — native_test.cpp is the standalone test
    binary, compiled end-to-end by test_native_sanitizers."""
    on_disk = {p.stem for p in NATIVE.glob("*.cpp")} - {"native_test"}
    assert on_disk == set(_MODULES), (
        f"native modules on disk {sorted(on_disk)} != gated "
        f"{sorted(_MODULES)} — extend _MODULES in this test"
    )


@pytest.mark.parametrize("name", sorted(_MODULES))
def test_native_module_compiles(tmp_path, name):
    src = NATIVE / f"{name}.cpp"
    out = tmp_path / f"{name}.so"
    proc = subprocess.run(
        ["g++", *_BASE_FLAGS, str(src), "-o", str(out), *_MODULES[name]],
        capture_output=True,
        text=True,
        cwd=NATIVE,
        timeout=300,
    )
    assert proc.returncode == 0, (
        f"{name}.cpp does not compile on this image — every caller would "
        f"silently run its Python fallback:\n{proc.stderr[-3000:]}"
    )
    assert out.exists() and out.stat().st_size > 0


def test_native_parsers_load_through_production_path():
    """The real build-on-first-use loaders must return a usable library —
    compilation alone doesn't prove the srchash/stamp machinery and the
    ctypes signature setup work."""
    from denormalized_tpu.formats._native_parser_base import _pyassemble
    from denormalized_tpu.formats.native_avro import _lib as avro_lib
    from denormalized_tpu.formats.native_json import _lib as json_lib

    jl = json_lib()
    assert hasattr(jl, "jp_create_tree")
    al = avro_lib()
    assert hasattr(al, "ap_create_tree")
    # this image has Python headers, so the C row assembler must engage
    # (elsewhere it may legitimately be None — the wrapper then uses the
    # generated-comprehension reassembly)
    assert _pyassemble() is not None
