"""Host-side group-key interning: values → dense int32 group ids.

The TPU analog of DataFusion's ``GroupValues`` hash-interning table, which the
reference drives inside ``GroupedAggWindowFrame::group_aggregate_batch``
(grouped_window_agg_stream.rs:501-537): group keys are interned to dense
indices so accumulators can be flat vectors.  Here the dense id doubles as the
row index into the device-resident ``(windows, groups)`` state buffers, so
interning is the bridge between host strings and HBM tensors.

String keys take the native table (``native/interner.cpp``): a
``StringColumn`` interns straight off its offsets + bytes in one foreign call
a batch, an object array through the PyObject lane.  Numeric keys and
environments without a compiler stay in Python: one ``np.unique`` a batch, and
only first-seen values take the dict path.  Ids are dense, int32 and in
first-seen order on every path.
"""

from __future__ import annotations

import numpy as np


def _load_native():
    lib = _load_native_lib()
    if lib is None:
        return None, None
    try:
        import ctypes

        from denormalized_tpu.native.build import _DIR

        if getattr(lib, "_intern_pyobjects", None) is None:
            # the PyObject fast path keeps the GIL → must go through PyDLL
            # (same .so, second handle)
            pylib = ctypes.PyDLL(str(_DIR / "interner.so"))
            pylib.intern_pyobjects.restype = ctypes.c_int
            pylib.intern_pyobjects.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,  # PyObject** (the object array's data)
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_int32),
            ]
            pylib.intern_py_release.argtypes = [ctypes.c_void_p]
            lib._intern_pyobjects = pylib.intern_pyobjects
            lib._intern_py_release = pylib.intern_py_release
        return lib, lib._intern_pyobjects
    except Exception:  # dnzlint: allow(broad-except) the PyObject fast path is optional (needs -DINTERN_HAVE_PYTHON + headers); the byte-key path below covers interning either way
        return lib, None


def _load_native_lib():
    try:
        import ctypes
        import sysconfig

        from denormalized_tpu.native.build import load

        try:
            inc = sysconfig.get_paths()["include"]
            lib = load(
                "interner", [f"-I{inc}", "-DINTERN_HAVE_PYTHON"]
            )
        except Exception:  # dnzlint: allow(broad-except) retried immediately as the plain (headerless) build — only THAT failure is terminal below
            # no Python headers: plain build without the PyObject path
            lib = load("interner")
        if not getattr(lib, "_in_configured", False):
            lib.intern_create.restype = ctypes.c_void_p
            lib.intern_destroy.argtypes = [ctypes.c_void_p]
            lib.intern_count.restype = ctypes.c_uint64
            lib.intern_count.argtypes = [ctypes.c_void_p]
            lib.intern_key.restype = ctypes.c_uint32
            lib.intern_key.argtypes = [
                ctypes.c_void_p,
                ctypes.c_uint64,
                ctypes.c_char_p,
                ctypes.c_uint32,
            ]
            lib.intern_keys_range.restype = ctypes.c_int64
            lib.intern_keys_range.argtypes = [
                ctypes.c_void_p,
                ctypes.c_uint64,
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
            ]
            # offsets+bytes lane (StringColumn)
            lib.intern_offsets.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,  # utf-8 byte buffer
                ctypes.c_void_p,  # u64 offsets, n + 1
                ctypes.c_void_p,  # validity (u8) or NULL
                ctypes.c_uint64,
                ctypes.c_void_p,  # int32 ids out, n
            ]
            lib.intern_stats.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_uint64),  # out[3]
            ]
            lib.intern_free.argtypes = [ctypes.c_void_p]
            lib._in_configured = True
        return lib
    except Exception as e:  # dnzlint: allow(broad-except) dict-based interning is the designed fallback on no-compiler boxes; logged so the downgrade is visible, gated by test_native_build_gate where g++ exists
        from denormalized_tpu.runtime.tracing import logger

        logger.warning(
            "native interner unavailable (%s: %s) — dict-based interning "
            "takes over (slower at high key cardinality)",
            type(e).__name__, e,
        )
        return None


# canonical dict key for float NaN (nan != nan, so NaN itself can never be
# found again in a dict); all NaNs intern to one id — the SQL
# GROUP-BY-NULL convention, and what np.unique already does within a batch
_NAN_KEY = ("__nan__",)


class ColumnInterner:
    """value -> id for one column.

    String columns take the native table: a ``StringColumn`` hands over its
    offsets + bytes, an object column its ``PyObject*`` slots — no per-row
    Python work at steady state.  Numeric columns and environments without
    a compiler use the np.unique+dict fallback.
    """

    def __init__(self) -> None:
        self._to_id: dict = {}
        self._values: list = []
        self._lib, self._py_intern = _load_native()
        self._h = self._lib.intern_create() if self._lib else None
        self._native_active = False
        # object-array mirror of _values and how much of it is filled
        self._values_arr: np.ndarray | None = None
        self._values_arr_n = 0
        # numeric fast-path mirror: known keys sorted + their ids, valid
        # only while _num_mirror_n == len(_values) (any dict-path or
        # restore mutation invalidates it → lazily rebuilt)
        self._num_sorted: np.ndarray | None = None
        self._num_ids: np.ndarray | None = None
        self._num_by_id: np.ndarray | None = None  # dense id → key
        self._num_mirror_n = -1
        # the fast path syncs _to_id lazily (suffix-only, see
        # _sync_to_id) — the NaN id is tracked directly so the NaN tail
        # never forces a sync
        self._nan_id: int | None = None
        self._to_id_synced = 0  # dict-synced prefix of _values

    def __del__(self):
        if getattr(self, "_h", None) and self._lib:
            rel = getattr(self._lib, "_intern_py_release", None)
            if rel is not None:
                rel(self._h)  # drop the pointer cache's INCREF pins
            self._lib.intern_destroy(self._h)
            self._h = None

    def __len__(self) -> int:
        if self._native_active:
            # authoritative count straight from the native table — the
            # Python value mirror is synced LAZILY (only when emission or a
            # checkpoint needs the actual strings)
            return int(self._lib.intern_count(self._h))
        if self._num_by_id is not None:
            # numeric fast path: the dense key array is authoritative,
            # the Python list lags until _flush_values
            return max(len(self._values), len(self._num_by_id))
        return len(self._values)

    def _sync_native_values(self) -> None:
        """Extend the Python-side value mirror with newly interned keys —
        ONE bulk ctypes call per batch fetching every new key's bytes, so
        emission-time keys_of() is plain list indexing even at 100k+
        cardinality."""
        import ctypes

        n_now = int(self._lib.intern_count(self._h))
        values = self._values
        start = len(values)
        if n_now <= start:
            return
        bptr = ctypes.POINTER(ctypes.c_uint8)()
        optr = ctypes.POINTER(ctypes.c_uint64)()
        n = self._lib.intern_keys_range(
            self._h, start, n_now, ctypes.byref(bptr), ctypes.byref(optr)
        )
        try:
            offs = np.ctypeslib.as_array(optr, shape=(n + 1,))
            raw = ctypes.string_at(bptr, int(offs[-1])) if offs[-1] else b""
            for i in range(n):
                piece = raw[offs[i] : offs[i + 1]]
                # 0xFF is the dedicated NULL-key byte (see interner.cpp)
                values.append(
                    None
                    if piece == b"\xff"
                    else piece.decode("utf-8", errors="replace")
                )
        finally:
            self._lib.intern_free(bptr)
            self._lib.intern_free(optr)

    def intern_array(self, arr: np.ndarray) -> np.ndarray:
        """Key normalization note: fixed-width numpy string storage cannot
        represent trailing NUL characters, so keys differing only in
        trailing ``'\\x00'`` intern to one id — consistently in BOTH the
        native and fallback paths."""
        import ctypes

        from denormalized_tpu.common.columns import StringColumn

        if isinstance(arr, StringColumn):
            # columnar lane: intern straight off offsets+bytes — no
            # Python str is ever created for a key on this path.  Null
            # slots intern the 0xFF NULL key, the same id the PyObject
            # lane gives None, so a column mixing columnar and legacy
            # batches groups identically.
            if self._h is not None:
                return self._intern_string_column(arr)
            arr = arr.as_object()  # no native lib: dict fallback below
        if arr.dtype.kind in "ifbM":
            # numeric key column: unique per batch, dict on uniques only
            uniq, inv = np.unique(arr, return_inverse=True)
            if arr.dtype.kind in "if":
                # int/float columns take the sorted-mirror fast path: one
                # searchsorted per batch, Python only for first-seen keys
                # (bulk).  At 1M-distinct approx_top_k cardinalities the
                # per-unique dict loop below was 70% of the sketch lane's
                # wall time (ISSUE 18 approx_scale profile).
                out = self._intern_numeric_uniques(uniq)
                if out is not None:
                    return out[inv]
            uniq = uniq.tolist()
        elif self._h is not None and self._py_intern is not None:
            # PyObject fast path: the C side reads each slot's CPython-cached
            # UTF-8 bytes directly — no fixed-width conversion, no new
            # Python objects, no per-batch value sync (lazy, at emission)
            obj = arr if arr.dtype == object else arr.astype(object)
            obj = np.ascontiguousarray(obj)
            n = len(obj)
            ids = np.empty(n, dtype=np.int32)
            rc = self._py_intern(
                self._h,
                obj.ctypes.data,
                n,
                ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            )
            if rc != 0:  # pragma: no cover - PyDLL re-raises pending errors
                raise RuntimeError("native interning failed")
            self._native_active = True
            return ids
        else:
            # fallback dict interning with the SAME value identity rules as
            # the native PyObject path, so results never depend on build
            # flavor: None is its own key, non-string objects normalize via
            # str(), trailing NULs strip like the native arena padding.
            # (There is deliberately NO third fixed-width-buffer path: a
            # str()-based one merged None with 'None'.)
            ids = np.empty(len(arr), dtype=np.int32)
            to_id = self._sync_to_id()
            values = self._values
            for i, v in enumerate(arr.tolist()):
                if v is None:
                    pass
                elif isinstance(v, str):
                    v = v.rstrip("\x00")
                else:
                    v = str(v)
                j = to_id.get(v)
                if j is None:
                    j = len(values)
                    to_id[v] = j
                    values.append(v)
                ids[i] = j
            self._to_id_synced = len(values)
            return ids
        ids = np.empty(len(uniq), dtype=np.int32)
        to_id = self._sync_to_id()
        values = self._values
        for i, v in enumerate(uniq):
            # NaN needs a canonical dict key: np.unique collapses NaNs
            # WITHIN a batch, but nan != nan so a plain dict lookup would
            # mint a fresh id every batch — grouping would then depend on
            # batch boundaries (review-found, pinned by
            # test_nan_group_keys_form_one_session cross-batch case)
            key = _NAN_KEY if isinstance(v, float) and v != v else v
            j = to_id.get(key)
            if j is None:
                j = len(values)
                to_id[key] = j
                values.append(v)
                if key is _NAN_KEY:
                    self._nan_id = j
            ids[i] = j
        self._to_id_synced = len(values)
        return ids[inv]

    def _rebuild_num_mirror(self, dtype) -> bool:
        """(Re)build the sorted numeric-key mirror from the value list —
        covers first use, checkpoint restore, and any dict-path mutation.
        Returns False (mirror stays invalid) when the stored values can't
        round-trip through ``dtype`` unambiguously: non-numeric entries,
        or cast collisions (two distinct dict keys landing on one
        ``dtype`` value — e.g. ints beyond 2**53 under float64); those
        columns keep the per-unique dict loop, which has no such limits."""
        vals = self._values
        try:
            karr = np.asarray(vals, dtype=dtype)
        except (ValueError, TypeError, OverflowError):
            return False
        ids = np.arange(len(vals), dtype=np.int32)
        if karr.dtype.kind == "f":
            ok = karr == karr  # NaN lives in the dict under _NAN_KEY
            karr, ids = karr[ok], ids[ok]
        order = np.argsort(karr, kind="stable")
        skarr, sids = karr[order], ids[order]
        if len(skarr) and bool(np.any(skarr[1:] == skarr[:-1])):
            return False  # cast collision → ambiguous lookup
        self._num_sorted = skarr
        self._num_ids = sids
        # dense id-ordered key array (NaN included): value_of gathers
        # straight from it, so streaming never materializes Python floats
        self._num_by_id = np.asarray(vals, dtype=dtype)
        self._num_mirror_n = len(vals)
        return True

    def _intern_numeric_uniques(self, uniq: np.ndarray) -> np.ndarray | None:
        """Vectorized id lookup for one batch's sorted unique numeric
        keys; assigns first-seen ids in ``uniq`` order — exactly the old
        per-unique loop's order, so interning is bit-identical either
        way.  New keys land ONLY in numpy structures (the sorted mirror
        + the dense id-ordered ``_num_by_id``); the Python value list
        and key dict lag behind and are suffix-synced lazily
        (``_flush_values`` / ``_sync_to_id``) the moment a checkpoint,
        restore, or dict-path batch needs them.  Returns None to fall
        back to the per-unique dict loop."""
        n = len(uniq)
        ids_u = np.empty(n, dtype=np.int32)
        # np.unique sorts NaN to the tail (and collapses it); it can't go
        # through searchsorted — resolve via the canonical sentinel
        nan_tail = 0
        if uniq.dtype.kind == "f" and n and uniq[-1] != uniq[-1]:
            # count, don't assume 1: np.unique only collapses NaNs on
            # numpy builds with equal_nan — all of them sort to the tail
            nan_tail = int(np.count_nonzero(np.isnan(uniq)))
        core = uniq[: n - nan_tail]
        nb = self._num_by_id
        total = len(nb) if nb is not None else len(self._values)
        if self._num_mirror_n != total or (
            self._num_sorted is not None
            and self._num_sorted.dtype != core.dtype
        ):
            self._flush_values()
            if not self._rebuild_num_mirror(core.dtype):
                return None
            nb = self._num_by_id
        skeys, sids = self._num_sorted, self._num_ids
        pos = np.searchsorted(skeys, core)
        safe = np.minimum(pos, max(len(skeys) - 1, 0))
        if len(skeys):
            found = (pos < len(skeys)) & (skeys[safe] == core)
        else:
            found = np.zeros(len(core), dtype=bool)
        ids_core = np.where(found, sids[safe] if len(skeys) else 0, -1)
        miss = np.flatnonzero(~found)
        if len(miss):
            new_keys = core[miss]
            start = len(nb)
            new_ids = np.arange(
                start, start + len(new_keys), dtype=np.int32
            )
            nb = np.concatenate([nb, new_keys])
            self._num_by_id = nb
            ids_core[miss] = new_ids
            # merge the (sorted) new keys into the sorted mirror with two
            # boolean scatters — one pass, vs np.insert's two generic
            # fancy-index passes (measurable at 100k+ new keys/run)
            ins = np.searchsorted(skeys, new_keys)
            m = len(skeys) + len(new_keys)
            pos_new = ins + np.arange(len(new_keys))
            old_mask = np.ones(m, dtype=bool)
            old_mask[pos_new] = False
            merged_k = np.empty(m, dtype=skeys.dtype)
            merged_i = np.empty(m, dtype=sids.dtype)
            merged_k[pos_new] = new_keys
            merged_k[old_mask] = skeys
            merged_i[pos_new] = new_ids
            merged_i[old_mask] = sids
            self._num_sorted = merged_k
            self._num_ids = merged_i
            self._num_mirror_n = len(nb)
        ids_u[: n - nan_tail] = ids_core
        if nan_tail:
            j = self._nan_id
            if j is None:
                # a dict-path batch may have minted the sentinel before
                # this column ever hit the fast path
                j = self._sync_to_id().get(_NAN_KEY)
            if j is None:
                j = len(nb)
                self._to_id[_NAN_KEY] = j
                self._num_by_id = np.concatenate(
                    [nb, np.asarray([uniq[-1]], dtype=nb.dtype)]
                )
                # NaN never enters the SORTED mirror (it can't be
                # searched) but it does hold an id slot
                self._num_mirror_n = len(self._num_by_id)
            self._nan_id = j
            ids_u[n - nan_tail :] = j
        return ids_u

    def _flush_values(self) -> None:
        """Materialize the Python value list from the dense numeric key
        array — called lazily at checkpoint / restore / dict-path
        boundaries, never per streaming batch."""
        nb = self._num_by_id
        if nb is not None and len(nb) > len(self._values):
            self._values.extend(nb[len(self._values) :].tolist())

    def _sync_to_id(self) -> dict:
        """Suffix-sync the key dict with the value list.  The numeric
        fast path appends values WITHOUT dict entries (the sorted mirror
        is its lookup structure); any path that still needs the dict
        calls this first.  The un-synced keys are exactly the suffix the
        fast path appended — O(new), not O(all); tracked by an explicit
        prefix counter (``len(to_id)`` can't serve: the fast path's NaN
        sentinel lands in the dict ahead of un-synced values)."""
        self._flush_values()
        to_id, values = self._to_id, self._values
        n = self._to_id_synced
        if n < len(values):
            for i in range(n, len(values)):
                v = values[i]
                to_id[
                    _NAN_KEY if isinstance(v, float) and v != v else v
                ] = i
            self._to_id_synced = len(values)
        return to_id

    def _intern_string_column(self, col) -> np.ndarray:
        """offsets+bytes native intern (pinned hot path: one foreign call
        per batch, no per-row Python)."""
        n = len(col)
        ids = np.empty(n, dtype=np.int32)
        if n == 0:
            return ids
        # int64 offsets are never negative: the native side reads the same
        # buffer as u64, no copy
        offsets = np.ascontiguousarray(col.offsets, dtype=np.int64)
        data = np.ascontiguousarray(col.data)
        # a contiguous copy, where one is made, has to outlive the call
        validity = (
            None if col.validity is None
            else np.ascontiguousarray(col.validity, dtype=np.bool_)
        )
        self._lib.intern_offsets(
            self._h,
            data.ctypes.data if data.size else 0,
            offsets.ctypes.data,
            0 if validity is None else validity.ctypes.data,
            n,
            ids.ctypes.data,
        )
        self._native_active = True
        return ids

    def native_stats(self) -> tuple[int, int, int]:
        """The native table's tallies since this interner was made: rows
        interned, slots visited beyond a row's first (collision pressure),
        rows whose key was too long for a slot and paid the arena compare.
        Zeros where no native table runs (numeric keys, no compiler)."""
        if self._h is None:
            return (0, 0, 0)
        import ctypes

        out = (ctypes.c_uint64 * 3)()
        self._lib.intern_stats(self._h, out)
        return (int(out[0]), int(out[1]), int(out[2]))

    def key_mirror(self) -> np.ndarray | None:
        """The filled prefix of the object-array mirror of the native
        table's keys, synced with every key interned so far: id ``i``'s key
        is ``mirror[i]``.  None where no key went through the native table.

        The mirror grows by doubling and takes the new keys alone, so a
        call costs the keys that appeared since the last one.  An entry
        below the filled length is never written again: a doubling copies
        into a new array and leaves the old one as it was, so a prefix
        handed out reads the same keys for as long as anyone holds it."""
        if not self._native_active:
            return None
        self._sync_native_values()
        n = len(self._values)
        arr, have = self._values_arr, self._values_arr_n
        if arr is None or have > n:
            arr, have = np.empty(n, dtype=object), 0
        elif len(arr) < n:
            arr = np.empty(max(n, 2 * len(arr)), dtype=object)
            arr[:have] = self._values_arr[:have]
        arr[have:n] = self._values[have:n]
        self._values_arr, self._values_arr_n = arr, n
        return arr[:n]

    def value_of(self, ids: np.ndarray) -> np.ndarray:
        mirror = self.key_mirror()
        if mirror is not None:
            # fancy-index the object-array mirror: C-speed gather even for
            # 100k-group emissions
            return mirror[np.asarray(ids)]
        nb = self._num_by_id
        if nb is not None and len(nb) > len(self._values):
            # numeric fast path with an un-flushed suffix: gather from
            # the dense key array, then box ONLY the requested ids to
            # Python scalars (tolist) — emission asks for a handful of
            # ids, never the whole key space
            sel = nb[np.asarray(ids, dtype=np.int64)]
            out = np.empty(len(sel), dtype=object)
            out[:] = sel.tolist()
            return out
        values = self._values
        out = np.empty(len(ids), dtype=object)
        for i, j in enumerate(ids.tolist()):
            out[i] = values[j]
        return out

    # -- snapshot/restore support ---------------------------------------
    def all_values(self) -> list:
        if self._native_active:
            self._sync_native_values()
        self._flush_values()
        return list(self._values)

    def load_values(self, vals: list) -> None:
        """Re-seed with an ordered value list (ids must match positions)."""
        if (
            self._h is not None
            and vals
            and all(isinstance(v, str) or v is None for v in vals)
        ):
            # string column → native table re-seed (also re-syncs _values)
            ids = self.intern_array(np.array(vals, dtype=object))
            assert ids.tolist() == list(range(len(vals))), "restore order"
        else:
            # numeric (or no-native) columns live in the dict; NaN values
            # re-key through the canonical NaN sentinel exactly like
            # intern_array, or post-restore batches would re-mint NaN ids
            self._values = list(vals)
            self._to_id = {
                (_NAN_KEY if isinstance(v, float) and v != v else v): i
                for i, v in enumerate(self._values)
            }
            self._to_id_synced = len(self._values)
            self._nan_id = self._to_id.get(_NAN_KEY)
            self._num_mirror_n = -1  # mirror re-derives from the new list
            self._num_by_id = None


def format_key_tuple(vals) -> str:
    """Canonical display string for one composite key — the ONE
    formatting rule every hot-key label uses (engine interners and the
    reference oracle's seq-id map must render identically or
    differential hot-key comparisons break)."""
    return (
        str(vals[0]) if len(vals) == 1
        else "(" + ", ".join(str(v) for v in vals) + ")"
    )


def display_keys(interner, gids) -> list:
    """Best-effort display strings for dense gids, None for released or
    out-of-range ids — the state observatory's hot-key resolution (a
    heavy-hitter sketch can briefly hold a gid the recycling interner
    already released; that key's state is gone, so rendering the raw
    gid is the honest answer)."""
    gl = np.asarray(gids, dtype=np.int64)
    out: list = [None] * len(gl)
    rows = interner._gid_rows
    ok = [
        i for i, g in enumerate(gl.tolist())
        if 0 <= g < len(rows) and rows[g] is not None
    ]
    if not ok:
        return out
    cols = interner.keys_of(gl[ok])
    for j, i in enumerate(ok):
        out[i] = format_key_tuple([c[j] for c in cols])
    return out


def interner_accounting(interner) -> dict:
    """Free-list / id-space accounting shared by both interner classes
    (the state observatory's key-capacity view): live ids, total dense
    id space, and the recycling free-list depth (0 for the
    non-recycling :class:`GroupInterner`)."""
    free = len(getattr(interner, "_free", ()))
    return {
        "live_keys": len(interner),
        "key_capacity": getattr(
            interner, "capacity", len(interner._gid_rows)
        ),
        "free_gids": free,
    }


def _dedup_rows(per_col: list[np.ndarray]) -> tuple[list[tuple], np.ndarray]:
    """Shared composite-key dedup: per-column id arrays → (unique row
    tuples, inverse indices).  2 columns pack into one int64 for a 1-D
    unique (much faster than np.unique(axis=0)'s void-view row sort);
    single source of truth for GroupInterner AND RecyclingGroupInterner so
    the packing can never diverge between them."""
    if len(per_col) == 2:
        packed = (per_col[0].astype(np.int64) << 32) | per_col[1].astype(
            np.int64
        )
        uniq, inv = np.unique(packed, return_inverse=True)
        rows = [(int(p >> 32), int(p & 0xFFFFFFFF)) for p in uniq.tolist()]
    else:
        stacked = np.stack(per_col, axis=1)
        uniq_rows, inv = np.unique(stacked, axis=0, return_inverse=True)
        rows = list(map(tuple, uniq_rows.tolist()))
    return rows, inv


#: keys of :meth:`GroupInterner.stats`, in the order of
#: :meth:`ColumnInterner.native_stats`
INTERN_STATS = ("intern_rows", "intern_extra_probes", "intern_overflow_rows")


class GroupInterner:
    """Composite (multi-column) key -> dense group id.

    Per-column ids are packed row-wise and the row-tuples interned, so the
    reverse map can reconstruct every key column for emission.
    """

    def __init__(self, num_columns: int) -> None:
        self.num_columns = num_columns
        self._col_interners = [ColumnInterner() for _ in range(num_columns)]
        self._tuple_to_gid: dict = {}
        # per group id, the tuple of per-column value ids
        self._gid_rows: list[tuple] = []

    def __len__(self) -> int:
        return len(self._gid_rows)

    def intern(self, key_columns: list[np.ndarray]) -> np.ndarray:
        assert len(key_columns) == self.num_columns
        per_col = [
            it.intern_array(c) for it, c in zip(self._col_interners, key_columns)
        ]
        if self.num_columns == 1:
            # single-column fast path: the column interner assigns dense ids
            # in first-seen order, which is exactly the group-id order —
            # no row-dedup needed at all
            cids = per_col[0]
            n_known = len(self._gid_rows)
            n_now = len(self._col_interners[0])
            if n_now > n_known:
                # zip() of one range yields the (i,) 1-tuples at C speed —
                # the genexpr version was measurable at 100k+ new ids/batch
                # (the approx_top_k value-interning profile, ISSUE 18)
                self._gid_rows.extend(zip(range(n_known, n_now)))
            return cids
        rows, inv = _dedup_rows(per_col)
        gids_for_uniq = np.empty(len(rows), dtype=np.int32)
        for i, row in enumerate(rows):
            g = self._tuple_to_gid.get(row)
            if g is None:
                g = len(self._gid_rows)
                self._tuple_to_gid[row] = g
                self._gid_rows.append(row)
            gids_for_uniq[i] = g
        return gids_for_uniq[inv]

    def stats(self) -> dict[str, int]:
        """The key columns' native tallies, summed (docs/observability.md,
        Spans: a row counts once per string key column)."""
        per_col = [it.native_stats() for it in self._col_interners]
        return dict(zip(INTERN_STATS, map(sum, zip(*per_col))))

    def key_dictionary(self) -> np.ndarray | None:
        """The values a group id indexes, where the key is one column in
        the native table: group ``g``'s key is ``dictionary[g]`` for every
        group interned so far, and stays so (``ColumnInterner.key_mirror``).
        None for several key columns, or keys the native table does not
        hold (numbers, no compiler)."""
        if self.num_columns != 1:
            return None
        return self._col_interners[0].key_mirror()

    def keys_of(self, gids: np.ndarray) -> list[np.ndarray]:
        """Reconstruct each key column's values for the given group ids."""
        if self.num_columns == 1:
            # group id == column id (see intern's single-column fast path)
            return [self._col_interners[0].value_of(gids)]
        rows = np.array([self._gid_rows[g] for g in gids.tolist()], dtype=np.int64)
        if len(gids) == 0:
            rows = rows.reshape(0, self.num_columns)
        return [
            it.value_of(rows[:, c])
            for c, it in enumerate(self._col_interners)
        ]

    # -- checkpoint support ---------------------------------------------
    def snapshot(self) -> dict:
        return {
            "columns": [it.all_values() for it in self._col_interners],
            "rows": self._gid_rows,
        }

    @classmethod
    def restore(cls, snap: dict) -> "GroupInterner":
        g = cls(len(snap["columns"]))
        for it, vals in zip(g._col_interners, snap["columns"]):
            it.load_values(list(vals))
        g._gid_rows = [tuple(r) for r in snap["rows"]]
        g._tuple_to_gid = {r: i for i, r in enumerate(g._gid_rows)}
        return g


class RecyclingGroupInterner:
    """Composite key -> dense group id WITH gid recycling.

    Same ``intern``/``keys_of`` contract as :class:`GroupInterner`, plus
    ``release(gids)``: a released gid goes onto a free list and is handed
    to the next first-seen key, so the dense-id space stays proportional
    to the number of LIVE keys rather than all keys ever seen.  Built for
    the session operator, whose key population churns (a key with no open
    session holds no state and its id can be reused); the window and join
    interners keep gids forever because their ids index device buffers.

    Two deliberate deviations from GroupInterner:

    - no single-column ``cid == gid`` fast path — recycling breaks that
      identity, so every shape goes through the packed-row dedup (still
      O(uniques-per-batch) Python, the same bound as the multi-column
      paths);
    - per-COLUMN value ids (inside ColumnInterner) are never recycled:
      they deduplicate values, and the composite-key cross product — the
      thing that actually explodes at high key churn — is what the free
      list caps.
    """

    def __init__(self, num_columns: int) -> None:
        self.num_columns = num_columns
        self._col_interners = [ColumnInterner() for _ in range(num_columns)]
        self._row_to_gid: dict = {}
        # per gid: tuple of per-column value ids, or None when freed
        self._gid_rows: list[tuple | None] = []
        self._free: list[int] = []

    def __len__(self) -> int:
        """Number of LIVE (unreleased) keys."""
        return len(self._gid_rows) - len(self._free)

    @property
    def capacity(self) -> int:
        """Dense-id space size (live + free) — sizes gid-indexed arrays."""
        return len(self._gid_rows)

    def intern(self, key_columns: list[np.ndarray]) -> np.ndarray:
        assert len(key_columns) == self.num_columns
        from denormalized_tpu.common.columns import as_key_column

        per_col = [
            it.intern_array(as_key_column(c))
            for it, c in zip(self._col_interners, key_columns)
        ]
        if self.num_columns == 1:
            # no cid==gid fast path here (recycling breaks the identity),
            # but the dedup is still a single 1-D unique
            uniq, inv = np.unique(per_col[0].astype(np.int64),
                                  return_inverse=True)
            rows = [(int(c),) for c in uniq.tolist()]
        else:
            rows, inv = _dedup_rows(per_col)
        gids_for_uniq = np.empty(len(rows), dtype=np.int32)
        row_to_gid = self._row_to_gid
        gid_rows = self._gid_rows
        free = self._free
        for i, row in enumerate(rows):
            g = row_to_gid.get(row)
            if g is None:
                if free:
                    g = free.pop()
                    gid_rows[g] = row
                else:
                    g = len(gid_rows)
                    gid_rows.append(row)
                row_to_gid[row] = g
            gids_for_uniq[i] = g
        return gids_for_uniq[inv]

    def release(self, gids) -> None:
        """Return gids to the free list (idempotent per gid).  The caller
        guarantees no state remains keyed by a released gid."""
        gid_rows = self._gid_rows
        for g in np.asarray(gids).tolist():
            row = gid_rows[g]
            if row is None:
                continue  # already free
            del self._row_to_gid[row]
            gid_rows[g] = None
            self._free.append(g)

    def keys_of(self, gids: np.ndarray) -> list[np.ndarray]:
        """Reconstruct each key column's values for the given LIVE gids."""
        rows = np.array(
            [self._gid_rows[g] for g in np.asarray(gids).tolist()],
            dtype=np.int64,
        )
        if len(rows) == 0:
            rows = rows.reshape(0, self.num_columns)
        return [
            it.value_of(rows[:, c])
            for c, it in enumerate(self._col_interners)
        ]
