"""ctypes binding to the native C++ loader (native/loader.cc).

The shared library is built lazily with `make -C native` on first use;
where the toolchain, the build or the dlopen fails, a RuntimeWarning
carries the reason and callers take the Python/pandas parser
(`read_edge_file` handles the dispatch).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings

import numpy as np

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libgrape_tpu_native.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("GRAPE_TPU_NO_NATIVE"):
            return None
        src = os.path.join(_NATIVE_DIR, "loader.cc")
        stale = not os.path.exists(_SO_PATH) or (
            os.path.exists(src)
            and os.path.getmtime(src) > os.path.getmtime(_SO_PATH)
        )
        if stale:
            # a stale .so silently loses every symbol group added since
            # it was built (make is incremental, so this is cheap)
            from libgrape_lite_tpu import obs

            try:
                # set-up phase: g++ on loader.cc, first time in a checkout
                with obs.tracer().span("native.build"):
                    subprocess.run(
                        ["make", "-C", _NATIVE_DIR],
                        check=True, capture_output=True, text=True,
                        timeout=120,
                    )
            except (OSError, subprocess.SubprocessError) as e:
                why = f"({e})\n{getattr(e, 'stderr', None) or ''}"
                if not os.path.exists(_SO_PATH):
                    warnings.warn(
                        "native library build failed; file loads take "
                        f"the slower Python parsers {why}",
                        RuntimeWarning,
                    )
                    return None
                warnings.warn(
                    "native library rebuild failed; loading the stale "
                    f"{_SO_PATH} — newer symbol groups (and their "
                    f"speedups) may be unavailable {why}",
                    RuntimeWarning,
                )
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            warnings.warn(
                f"native library {_SO_PATH} did not load ({e}); file "
                "loads take the slower Python parsers",
                RuntimeWarning,
            )
            return None
        lib.gl_parse.restype = ctypes.c_void_p
        lib.gl_parse.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ]
        lib.gl_num_rows.restype = ctypes.c_int64
        lib.gl_num_rows.argtypes = [ctypes.c_void_p]
        for name in ("gl_col0", "gl_col1"):
            fn = getattr(lib, name)
            fn.restype = ctypes.POINTER(ctypes.c_int64)
            fn.argtypes = [ctypes.c_void_p]
        lib.gl_colw.restype = ctypes.POINTER(ctypes.c_double)
        lib.gl_colw.argtypes = [ctypes.c_void_p]
        lib.gl_all_weighted.restype = ctypes.c_int
        lib.gl_all_weighted.argtypes = [ctypes.c_void_p]
        lib.gl_free.restype = None
        lib.gl_free.argtypes = [ctypes.c_void_p]
        try:
            # a stale prebuilt .so may predate gl_sort_edges: degrade to
            # parser-only rather than crashing every native call
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            lib.gl_sort_edges.restype = None
            lib.gl_sort_edges.argtypes = [
                i64p, i64p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                i64p, i64p, ctypes.c_void_p, i64p,
            ]
            lib._gl_has_sort = True
        except AttributeError:
            lib._gl_has_sort = False
        try:
            # vertex-map acceleration (id table + MPH), added round 2
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            lib.gl_ht_build.restype = ctypes.c_void_p
            lib.gl_ht_build.argtypes = [i64p, ctypes.c_int64]
            lib.gl_ht_insert.restype = None
            lib.gl_ht_insert.argtypes = [
                ctypes.c_void_p, i64p, ctypes.c_int64, ctypes.c_void_p,
            ]
            lib.gl_ht_lookup.restype = None
            lib.gl_ht_lookup.argtypes = [
                ctypes.c_void_p, i64p, ctypes.c_int64, i64p,
            ]
            lib.gl_ht_size.restype = ctypes.c_int64
            lib.gl_ht_size.argtypes = [ctypes.c_void_p]
            lib.gl_ht_oids.restype = None
            lib.gl_ht_oids.argtypes = [ctypes.c_void_p, i64p]
            lib.gl_ht_free.restype = None
            lib.gl_ht_free.argtypes = [ctypes.c_void_p]
            lib.gl_mph_build.restype = ctypes.c_void_p
            lib.gl_mph_build.argtypes = [i64p, ctypes.c_int64]
            lib.gl_mph_pos.restype = None
            lib.gl_mph_pos.argtypes = [
                ctypes.c_void_p, i64p, ctypes.c_int64, i64p,
            ]
            lib.gl_mph_bits.restype = ctypes.c_double
            lib.gl_mph_bits.argtypes = [ctypes.c_void_p]
            lib.gl_mph_free.restype = None
            lib.gl_mph_free.argtypes = [ctypes.c_void_p]
            lib._gl_has_vm = True
        except AttributeError:
            lib._gl_has_vm = False
        try:
            # varint decode (fragment-cache wire format), added round 4
            u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
            u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
            lib.gl_varint_count.restype = ctypes.c_int64
            lib.gl_varint_count.argtypes = [u8p, ctypes.c_int64]
            lib.gl_varint_decode.restype = ctypes.c_int64
            lib.gl_varint_decode.argtypes = [
                u8p, ctypes.c_int64, u64p, ctypes.c_int64, ctypes.c_int,
            ]
            lib.gl_varint_size.restype = ctypes.c_int64
            lib.gl_varint_size.argtypes = [
                u64p, ctypes.c_int64, ctypes.c_int,
            ]
            lib.gl_varint_encode.restype = ctypes.c_int64
            lib.gl_varint_encode.argtypes = [
                u64p, ctypes.c_int64, u8p, ctypes.c_int64, ctypes.c_int,
            ]
            lib._gl_has_varint = True
        except AttributeError:
            lib._gl_has_varint = False
        try:
            # float byte-plane transpose (garc weight streams), round 5
            u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
            lib.gl_byte_split.restype = None
            lib.gl_byte_split.argtypes = [
                u8p, ctypes.c_int64, ctypes.c_int, u8p,
            ]
            lib.gl_byte_join.restype = None
            lib.gl_byte_join.argtypes = [
                u8p, ctypes.c_int64, ctypes.c_int, u8p,
            ]
            lib._gl_has_bytesplit = True
        except AttributeError:
            lib._gl_has_bytesplit = False
        _lib = lib
        return _lib


def byte_split(a: np.ndarray) -> np.ndarray:
    """[n] itemsize-wide array -> [itemsize, n] uint8 planes (native
    transpose when available; numpy reshape fallback)."""
    n, itemsize = len(a), a.dtype.itemsize
    flat = np.ascontiguousarray(a).view(np.uint8).reshape(-1)
    lib = _load()
    if lib is not None and getattr(lib, "_gl_has_bytesplit", False) and n:
        out = np.empty(itemsize * n, dtype=np.uint8)
        lib.gl_byte_split(flat, n, itemsize, out)
        return out.reshape(itemsize, n)
    return flat.reshape(n, itemsize).T.copy()


def byte_join(planes: np.ndarray, dtype) -> np.ndarray:
    """Inverse of byte_split: [itemsize, n] uint8 planes -> [n] dtype."""
    itemsize, n = planes.shape
    assert np.dtype(dtype).itemsize == itemsize
    lib = _load()
    if lib is not None and getattr(lib, "_gl_has_bytesplit", False) and n:
        out = np.empty(itemsize * n, dtype=np.uint8)
        lib.gl_byte_join(np.ascontiguousarray(planes).reshape(-1), n,
                         itemsize, out)
        return out.view(dtype)
    return np.ascontiguousarray(planes.T).reshape(-1).view(dtype)


def varint_encode_native(vals: np.ndarray, delta: bool) -> bytes | None:
    """Native LEB128 (optionally delta) encode; None when unavailable."""
    lib = _load()
    if lib is None or not getattr(lib, "_gl_has_varint", False):
        return None
    v = np.ascontiguousarray(vals, dtype=np.uint64)
    if len(v) == 0:
        return b""
    size = lib.gl_varint_size(v, len(v), 1 if delta else 0)
    out = np.empty(size, dtype=np.uint8)
    got = lib.gl_varint_encode(v, len(v), out, size, 1 if delta else 0)
    if got != size:
        return None
    return out.tobytes()


def varint_decode_native(buf: bytes, delta: bool) -> np.ndarray | None:
    """Native LEB128 (optionally delta-accumulated) decode; None when
    the library is unavailable (callers fall back to numpy)."""
    lib = _load()
    if lib is None or not getattr(lib, "_gl_has_varint", False):
        return None
    b = np.frombuffer(buf, dtype=np.uint8)
    if len(b) == 0:
        return np.zeros(0, dtype=np.uint64)
    n = lib.gl_varint_count(b, len(b))
    out = np.empty(n, dtype=np.uint64)
    got = lib.gl_varint_decode(b, len(b), out, n, 1 if delta else 0)
    if got != n:
        # gl_varint_decode returns -1 or the exact count, so this is
        # unambiguously a truncated/overlong stream — the numpy
        # fallback would silently drop the trailing value instead
        raise ValueError(
            f"corrupt varint stream: decoded {got} of {n} values"
        )
    return out


def _as_i64(a) -> np.ndarray | None:
    """Contiguous int64 view of an integer array; None for non-integer
    oid dtypes (string-keyed graphs keep the Python paths)."""
    arr = np.asarray(a)
    if not np.issubdtype(arr.dtype, np.integer):
        return None
    return np.ascontiguousarray(arr, dtype=np.int64)


class NativeIdTable:
    """Open-addressing oid->lid table (native IdTable; the reference
    `IdIndexer`, grape/graph/id_indexer.h)."""

    def __init__(self, lib, handle):
        self._lib = lib
        self._h = handle

    @classmethod
    def build(cls, oids: np.ndarray) -> "NativeIdTable | None":
        lib = _load()
        if lib is None or not getattr(lib, "_gl_has_vm", False):
            return None
        o = _as_i64(oids)
        if o is None:
            return None
        h = lib.gl_ht_build(o, len(o))
        return cls(lib, h) if h else None

    def insert(self, oids: np.ndarray) -> np.ndarray:
        """Arrival-order setdefault; returns the lid of each input.
        Raises TypeError for non-integer oids (callers that allow mixed
        dtypes must check before inserting)."""
        o = _as_i64(oids)
        if o is None:
            raise TypeError("NativeIdTable.insert: non-integer oids")
        out = np.empty(len(o), dtype=np.int64)
        self._lib.gl_ht_insert(self._h, o, len(o), out.ctypes.data)
        return out

    def lookup(self, oids: np.ndarray) -> np.ndarray:
        o = _as_i64(oids)
        if o is None:
            # a non-integer query can never be in an int64 table
            return np.full(len(np.asarray(oids)), -1, dtype=np.int64)
        out = np.empty(len(o), dtype=np.int64)
        self._lib.gl_ht_lookup(self._h, o, len(o), out)
        return out

    def size(self) -> int:
        return int(self._lib.gl_ht_size(self._h))

    def oids(self) -> np.ndarray:
        out = np.empty(self.size(), dtype=np.int64)
        self._lib.gl_ht_oids(self._h, out)
        return out

    def __del__(self):
        h, self._h = self._h, None
        if h and self._lib is not None:
            self._lib.gl_ht_free(h)


class NativeMph:
    """Minimal perfect hash over int64 keys (native PTHash-style build;
    the reference `pthash_idxer.h` + thirdparty/pthash)."""

    def __init__(self, lib, handle, n):
        self._lib = lib
        self._h = handle
        self._n = n

    @classmethod
    def build(cls, keys: np.ndarray) -> "NativeMph | None":
        lib = _load()
        if lib is None or not getattr(lib, "_gl_has_vm", False):
            return None
        k = _as_i64(keys)
        if k is None or len(k) == 0:
            return None
        h = lib.gl_mph_build(k, len(k))
        return cls(lib, h, len(k)) if h else None

    def positions(self, keys: np.ndarray) -> np.ndarray:
        """[0, n) position per key; arbitrary for unknown keys (callers
        verify against their lid->oid array)."""
        k = _as_i64(keys)
        out = np.empty(len(k), dtype=np.int64)
        self._lib.gl_mph_pos(self._h, k, len(k), out)
        return out

    def bits_per_key(self) -> float:
        return float(self._lib.gl_mph_bits(self._h))

    def __del__(self):
        h, self._h = self._h, None
        if h and self._lib is not None:
            self._lib.gl_mph_free(h)


def available() -> bool:
    return _load() is not None


def sort_edges_native(src, nbr, w, num_rows: int, num_cols: int):
    """Stable (src, nbr) counting sort + indptr via the C++ helper;
    returns (src_sorted, nbr_sorted, w_sorted|None, indptr) or None when
    the native library is unavailable."""
    lib = _load()
    if lib is None or not getattr(lib, "_gl_has_sort", False):
        return None
    src64 = np.ascontiguousarray(src, dtype=np.int64)
    nbr64 = np.ascontiguousarray(nbr, dtype=np.int64)
    n = len(src64)
    if n:
        # the C counting sort indexes raw ids — validate here so an
        # upstream bug raises instead of corrupting the heap
        if int(src64.min()) < 0 or int(src64.max()) >= num_rows:
            raise ValueError("sort_edges_native: src id out of range")
        if int(nbr64.min()) < 0 or int(nbr64.max()) >= num_cols:
            raise ValueError("sort_edges_native: nbr id out of range")
    w64 = None if w is None else np.ascontiguousarray(w, dtype=np.float64)
    out_src = np.empty(n, dtype=np.int64)
    out_nbr = np.empty(n, dtype=np.int64)
    out_w = np.empty(n, dtype=np.float64) if w is not None else None
    indptr = np.empty(num_rows + 1, dtype=np.int64)
    lib.gl_sort_edges(
        src64, nbr64,
        w64.ctypes.data if w64 is not None else None,
        n, num_rows, num_cols,
        out_src, out_nbr,
        out_w.ctypes.data if out_w is not None else None,
        indptr,
    )
    return out_src, out_nbr, out_w, indptr


def parse_file_native(path: str, ncols: int, weighted: bool):
    """Returns (col0 int64, col1 int64 | None, w float64 | None) or None
    when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    handle = lib.gl_parse(path.encode(), ncols, int(weighted), 0)
    if not handle:
        raise FileNotFoundError(path)
    try:
        n = lib.gl_num_rows(handle)
        if n == 0:  # empty vectors return NULL data pointers
            return (
                np.zeros(0, np.int64),
                np.zeros(0, np.int64) if ncols >= 2 else None,
                np.zeros(0, np.float64) if weighted else None,
            )
        c0 = np.ctypeslib.as_array(lib.gl_col0(handle), shape=(n,)).copy()
        c1 = (
            np.ctypeslib.as_array(lib.gl_col1(handle), shape=(n,)).copy()
            if ncols >= 2
            else None
        )
        w = None
        if weighted:
            # all-rows-weighted or the file has no weight column — in the
            # latter case behave like the python parser (w = None)
            if lib.gl_all_weighted(handle):
                w = np.ctypeslib.as_array(lib.gl_colw(handle), shape=(n,)).copy()
    finally:
        lib.gl_free(handle)
    return c0, c1, w
