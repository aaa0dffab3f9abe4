"""Build and run the native C++ test binary under sanitizers — coverage
the reference lacks entirely (SURVEY.md §5):

- AddressSanitizer + UndefinedBehaviorSanitizer: memory safety over the
  parser/LSM/codec surfaces (untrusted broker bytes included);
- ThreadSanitizer: the threaded hammers in native_test.cpp (concurrent
  kafka_client produce/fetch against a loopback mini-broker, lsmkv
  put/get/flush from 4 threads, concurrent TLS-API init) — the engine
  calls these components from prefetch worker threads with the GIL
  released, so races here are real races;
- a plain optimized build, because the hammers are also ordinary
  correctness tests.

Each flavor skips cleanly — with the toolchain's own error recorded in
the skip reason — when this g++ can't produce a working binary for it
(e.g. no libtsan on the image).
"""

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

NATIVE = Path(__file__).resolve().parent.parent / "denormalized_tpu" / "native"

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None,
    reason="no compiler — the pure-Python fallbacks cover this environment",
)

FLAVORS = {
    "asan": ["-fsanitize=address,undefined", "-fno-sanitize-recover=all"],
    "tsan": ["-fsanitize=thread"],
    "plain": ["-O2"],
}


def _probe_sanitizer(tmp_path: Path, flags: list[str]) -> str | None:
    """Can this toolchain build AND run a trivial binary with ``flags``?
    Returns the failure detail (recorded in the skip reason) or None.
    Runtime is probed too: some images ship the compiler support but not
    the sanitizer runtime libraries."""
    src = tmp_path / "probe.cpp"
    src.write_text("int main() { return 0; }\n")
    exe = tmp_path / "probe"
    build = subprocess.run(
        ["g++", "-std=c++17", *flags, str(src), "-o", str(exe)],
        capture_output=True, text=True, timeout=120,
    )
    if build.returncode != 0:
        return f"probe build failed: {build.stderr[-300:]}"
    run = subprocess.run(
        [str(exe)], capture_output=True, text=True, timeout=60
    )
    if run.returncode != 0:
        return f"probe run failed: {run.stderr[-300:]}"
    return None


@pytest.mark.parametrize("flavor", sorted(FLAVORS))
def test_native_components(tmp_path, flavor):
    flags = FLAVORS[flavor]
    if flavor != "plain":
        why = _probe_sanitizer(tmp_path, flags)
        if why is not None:
            pytest.skip(f"toolchain lacks {flavor}: {why}")
    exe = tmp_path / "native_test"
    build = subprocess.run(
        # -ldl: the kafka client dlopens OpenSSL; glibc < 2.34 keeps
        # dlopen/dlsym in libdl (newer glibc folded them into libc, where
        # the flag is a harmless no-op).  -lpthread likewise for the
        # hammer threads on older glibc.
        ["g++", "-std=c++17", "-g", *flags,
         str(NATIVE / "native_test.cpp"), "-o", str(exe),
         "-lz", "-ldl", "-lpthread"],
        capture_output=True,
        text=True,
        cwd=NATIVE,
    )
    assert build.returncode == 0, build.stderr[-2000:]
    run = subprocess.run(
        [str(exe), str(tmp_path / "lsm")],
        capture_output=True,
        text=True,
        timeout=280,
    )
    sys.stderr.write(run.stderr[-1000:])
    assert run.returncode == 0, (run.stdout[-500:], run.stderr[-2000:])
    assert "ALL NATIVE TESTS PASSED" in run.stdout
    # the hammers must actually have run in every flavor — a refactor
    # that drops them from main() would silently gut the TSan coverage
    for marker in ("lsm hammer ok", "kafka hammer ok",
                   "interner hammer ok", "sketch hammer ok"):
        assert marker in run.stdout, run.stdout[-500:]


def test_tsan_build_flavor(tmp_path):
    """The ``sanitize="thread"`` flavor in native/build.py produces a
    distinctly-named, distinctly-stamped artifact (lsmkv.tsan.so) beside
    the production lsmkv.so, and the artifact is genuinely dlopen-able
    with the TSan runtime preloaded (the harness usage it exists for)."""
    why = _probe_sanitizer(tmp_path, ["-fsanitize=thread"])
    if why is not None:
        pytest.skip(f"toolchain lacks tsan: {why}")
    from denormalized_tpu.native import build

    with pytest.raises(ValueError, match="unknown sanitize kind"):
        build.compile("lsmkv", sanitize="bogus")
    so = build.compile("lsmkv", sanitize="thread")
    assert so == NATIVE / "lsmkv.tsan.so"
    assert so.exists() and so.stat().st_size > 0
    stamp = NATIVE / "lsmkv.tsan.so.srchash"
    assert stamp.exists()
    # flavored stamp differs from the plain one (different flags hash)
    plain_stamp = NATIVE / "lsmkv.so.srchash"
    if plain_stamp.exists():
        assert stamp.read_text() != plain_stamp.read_text()
    # second call is a cache hit (stamp matches — no recompile)
    assert build.compile("lsmkv", sanitize="thread") == so

    libtsan = subprocess.run(
        ["g++", "-print-file-name=libtsan.so"],
        capture_output=True, text=True,
    ).stdout.strip()
    if not libtsan or "/" not in libtsan:
        pytest.skip("g++ cannot locate libtsan.so for preload")
    snippet = (
        "import ctypes\n"
        f"lib = ctypes.CDLL({str(so)!r})\n"
        "lib.lsm_open.restype = ctypes.c_void_p\n"
        "lib.lsm_open.argtypes = [ctypes.c_char_p]\n"
        "lib.lsm_close.argtypes = [ctypes.c_void_p]\n"
        f"h = lib.lsm_open({str(tmp_path / 'flv').encode()!r})\n"
        "assert h\n"
        "lib.lsm_close(h)\n"
        "print('FLAVOR_OK')\n"
    )
    run = subprocess.run(
        [sys.executable, "-c", snippet],
        capture_output=True, text=True,
        env={"LD_PRELOAD": libtsan, "PATH": "/usr/bin:/bin",
             "TSAN_OPTIONS": "report_bugs=0:exitcode=0"},
        timeout=120,
    )
    assert "FLAVOR_OK" in run.stdout, (run.stdout, run.stderr[-1500:])
