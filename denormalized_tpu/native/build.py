"""Build-on-first-use for the native (C++) components: compiles
``<name>.cpp`` beside this file into ``<name>.so`` with g++ and loads it
with ctypes.  The rebuild trigger is a content hash of the source recorded
in a sidecar file — NOT mtimes, which a fresh git checkout resets to the
same instant for source and any stray binary, silently shipping a stale
build.  Raises on failure — callers decide whether a pure-Python fallback
exists."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path

_DIR = Path(__file__).resolve().parent
_LOCK = threading.Lock()
_CACHE: dict[str, ctypes.CDLL] = {}

#: warning surface every native build compiles under.  The gate test
#: (tests/test_native_build_gate.py) compiles with these PLUS -Werror,
#: so the committed tree is warning-clean; the production build keeps
#: them non-fatal (a future compiler inventing a new warning must not
#: take the engine down at first use).
WARN_FLAGS = ["-Wall", "-Wextra", "-Wshadow", "-Wconversion"]

#: ``sanitize=`` kinds -> compile/link flags.  ``thread`` is what
#: tests/test_native_sanitizers.py uses for the TSan hammer coverage;
#: address covers the single-thread memory-safety runs.
SANITIZE_FLAGS = {
    "thread": ["-fsanitize=thread", "-g"],
    "address": ["-fsanitize=address,undefined", "-g"],
}


def _flavor_suffix(sanitize: str | None) -> str:
    flavor = {"thread": ".tsan", "address": ".asan"}.get(sanitize or "", "")
    if sanitize and not flavor:
        raise ValueError(
            f"unknown sanitize kind {sanitize!r} "
            f"(expected one of {sorted(SANITIZE_FLAGS)})"
        )
    return flavor


def compile(
    name: str,
    extra_flags: list[str] | None = None,
    *,
    sanitize: str | None = None,
) -> Path:
    """Compile ``<name>.cpp`` (if stale) and return the .so path WITHOUT
    dlopen'ing it.  ``sanitize="thread"|"address"`` builds a
    separately-named, separately-stamped flavor (``<name>.tsan.so`` /
    ``<name>.asan.so``) with the matching ``-fsanitize=`` flags — those
    artifacts can only be dlopen'd with the sanitizer runtime preloaded
    (LD_PRELOAD=libtsan.so...), which is exactly why this step is split
    from :func:`load`: the sanitizer test harness compiles flavors here
    and loads them in a preloaded subprocess, while production loads
    stay unflavored.  Callers must hold no assumption about which thread
    builds first: the compile is serialized under the module lock."""
    flavor = _flavor_suffix(sanitize)
    with _LOCK:
        return _compile_locked(name, flavor, extra_flags, sanitize)


def _compile_locked(
    name: str, flavor: str, extra_flags, sanitize: str | None
) -> Path:
    src = _DIR / f"{name}.cpp"
    so = _DIR / f"{name}{flavor}.so"
    stamp = _DIR / f"{name}{flavor}.so.srchash"
    # local quoted includes participate in the rebuild hash — a header
    # edit must rebuild every .so that inlines it; the scan follows
    # the quoted-include closure recursively
    def hash_with_includes(path: Path, seen: set) -> bytes:
        if path in seen or not path.exists():
            return b""
        seen.add(path)
        data = path.read_bytes()
        out = data
        for line in data.splitlines():
            line = line.strip().replace(b'#include"', b'#include "')
            if line.startswith(b'#include "'):
                out += hash_with_includes(
                    _DIR / line.split(b'"')[1].decode(), seen
                )
        return out

    build_flags = (
        WARN_FLAGS
        + (SANITIZE_FLAGS[sanitize] if sanitize else [])
        + (extra_flags or [])
    )
    want = hashlib.sha256(
        hash_with_includes(src, set())
        + repr(sorted(build_flags)).encode()
    ).hexdigest()
    have = stamp.read_text().strip() if stamp.exists() else ""
    if not so.exists() or have != want:
        # the module lock is per process: several processes of a fresh
        # checkout (test workers, cluster workers) build at once, so each
        # links into a file of its own and renames it into place — a
        # loader never maps a library another process is still writing
        tmp = _DIR / f".{name}{flavor}.{os.getpid()}.tmp.so"
        cmd = [
            "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
            str(src), "-o", str(tmp),
        ] + build_flags
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            tmp.unlink(missing_ok=True)
            raise RuntimeError(
                f"native build of {name} failed:\n{proc.stderr[-2000:]}"
            )
        os.replace(tmp, so)
        stamp.write_text(want)
    return so


def load(
    name: str,
    extra_flags: list[str] | None = None,
    *,
    pydll: bool = False,
) -> ctypes.CDLL:
    """``pydll=True`` loads through :class:`ctypes.PyDLL` (calls keep the
    GIL) — REQUIRED for libraries that touch the CPython API
    (pyassemble.cpp): a plain-CDLL handle to such a library would release
    the GIL around calls that manipulate PyObjects and crash the
    interpreter.  The cache keys on the loader kind so a PyDLL library
    can never be served a previously-cached CDLL handle or vice versa."""
    key = f"{name}|pydll" if pydll else name
    with _LOCK:
        if key in _CACHE:
            return _CACHE[key]
        so = _compile_locked(name, "", extra_flags, None)
        lib = (ctypes.PyDLL if pydll else ctypes.CDLL)(str(so))
        _CACHE[key] = lib
        return lib
