"""Native (C++) host runtime for cylon_tpu, loaded over ctypes.

The reference's host-side runtime is native C++ (Arrow CSV reader over mmap,
io/arrow_io.cpp:33-61; row-wise CSV writer, table.cpp:244-253). Here the
equivalent lives in ``csv.cpp``, compiled on first use with the in-image g++
(no pybind11 in the image — plain C ABI + ctypes). If the toolchain is
missing the callers fall back to pyarrow/pandas paths.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "csv.cpp")
_SRC_RT = os.path.join(_HERE, "runtime.cpp")
_SRC_CAPI = os.path.join(_HERE, "capi.cpp")


def _src_hash(*paths: str) -> str:
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:12]


def _asan() -> bool:
    """CYLON_TPU_NATIVE_ASAN=1 compiles the native libs with
    AddressSanitizer — the analog of the reference's Debug build
    (-fsanitize=address, cpp/CMakeLists.txt:57). Loading the instrumented
    .so additionally requires libasan to be LD_PRELOADed (see get_lib)."""
    from ..utils import envgate as _envgate

    return _envgate.NATIVE_ASAN.get() == "1"


def _asan_runtime_loaded() -> bool:
    try:
        with open("/proc/self/maps") as f:
            m = f.read()
        return "libasan" in m or "libclang_rt.asan" in m
    except OSError:
        return False


def _so_path() -> str:
    # the source hash is in the filename: glibc dlopen caches by pathname, so
    # a rebuild after a source edit must land at a NEW path to actually map
    # fresh symbols in-process; ASAN variants get their own name
    tag = "-asan" if _asan() else ""
    return os.path.join(
        _HERE, f"_cylon_native-{_src_hash(_SRC, _SRC_RT)}{tag}.so"
    )


def _so_capi_path() -> str:
    tag = "-asan" if _asan() else ""
    return os.path.join(_HERE, f"_cylon_capi-{_src_hash(_SRC_CAPI)}{tag}.so")

_lock = threading.Lock()
_lib_handle = None
_load_failed = False

# ColType tags (must match csv.cpp)
CT_INT64, CT_FLOAT64, CT_BOOL, CT_STRING = 0, 1, 2, 3


def _prune_stale(keep: str, prefix: str) -> None:
    """Unlink hash-named siblings from earlier source versions (each rebuild
    lands at a new path — see _so_path — and would otherwise accumulate).
    ASAN and plain variants are pruned independently."""
    import glob

    keep_asan = keep.endswith("-asan.so")
    for old in glob.glob(os.path.join(_HERE, f"{prefix}-*.so")):
        if old != keep and old.endswith("-asan.so") == keep_asan:
            try:
                os.unlink(old)
            except OSError:
                pass


def _compile(cmd: list, so: str, prefix: str) -> bool:
    """Run ``cmd + ["-o", <tmp>]`` and move the result to ``so``.

    The temporary name is this process's own: several processes (pytest
    workers, ranks of one job) may find the library missing at once, and a
    shared name lets one process rename the file another is still linking.
    ``os.replace`` is atomic and every builder produces the same bytes (the
    path carries the source hash), so whoever lands last changes nothing;
    a build that fails after another process placed the library is a
    success too."""
    tmp = f"{so}.{os.getpid()}.tmp"
    if _asan():
        asan = ["-fsanitize=address", "-fno-omit-frame-pointer", "-g"]
        cmd = cmd[:1] + asan + cmd[1:]
    try:
        subprocess.run(
            cmd + ["-o", tmp], check=True, capture_output=True, timeout=300
        )
        os.replace(tmp, so)
    except (subprocess.CalledProcessError, FileNotFoundError,
            subprocess.TimeoutExpired):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return os.path.exists(so)
    _prune_stale(so, prefix)
    return True


def _build(so: str) -> bool:
    cmd = [
        "g++", "-std=c++20", "-O3", "-fPIC", "-shared", "-pthread",
        _SRC, _SRC_RT,
    ]
    return _compile(cmd, so, "_cylon_native")


def build_capi() -> Optional[str]:
    """Compile the C-ABI binding library (capi.cpp — the Java/JNI-binding
    analog) against the current interpreter. Returns the .so path or None."""
    import sysconfig

    so = _so_capi_path()
    if os.path.exists(so):
        return so
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or sysconfig.get_python_version()
    cmd = [
        "g++", "-std=c++20", "-O2", "-fPIC", "-shared", "-pthread",
        f"-I{inc}", _SRC_CAPI, f"-L{libdir}", f"-lpython{ver}",
    ]
    return so if _compile(cmd, so, "_cylon_capi") else None


def _bind(lib):
    c = ctypes
    lib.ct_csv_read.restype = c.c_void_p
    lib.ct_csv_read.argtypes = [c.c_char_p, c.c_char, c.c_int32, c.c_int32, c.c_int32]
    lib.ct_csv_error.restype = c.c_char_p
    lib.ct_csv_error.argtypes = [c.c_void_p]
    lib.ct_csv_nrows.restype = c.c_int64
    lib.ct_csv_nrows.argtypes = [c.c_void_p]
    lib.ct_csv_ncols.restype = c.c_int32
    lib.ct_csv_ncols.argtypes = [c.c_void_p]
    lib.ct_csv_colname.restype = c.c_char_p
    lib.ct_csv_colname.argtypes = [c.c_void_p, c.c_int32]
    lib.ct_csv_coltype.restype = c.c_int32
    lib.ct_csv_coltype.argtypes = [c.c_void_p, c.c_int32]
    for name, ty in [
        ("ct_csv_data_i64", c.POINTER(c.c_int64)),
        ("ct_csv_data_f64", c.POINTER(c.c_double)),
        ("ct_csv_data_bool", c.POINTER(c.c_uint8)),
        ("ct_csv_data_codes", c.POINTER(c.c_int32)),
        ("ct_csv_valid", c.POINTER(c.c_uint8)),
    ]:
        fn = getattr(lib, name)
        fn.restype = ty
        fn.argtypes = [c.c_void_p, c.c_int32]
    lib.ct_csv_dict_size.restype = c.c_int32
    lib.ct_csv_dict_size.argtypes = [c.c_void_p, c.c_int32]
    lib.ct_csv_dict.restype = c.POINTER(c.c_char_p)
    lib.ct_csv_dict.argtypes = [c.c_void_p, c.c_int32]
    lib.ct_csv_free.restype = None
    lib.ct_csv_free.argtypes = [c.c_void_p]
    lib.ct_csv_write.restype = c.c_int32
    lib.ct_csv_write.argtypes = [
        c.c_char_p, c.c_char, c.c_int64, c.c_int32,
        c.POINTER(c.c_char_p), c.POINTER(c.c_int32),
        c.POINTER(c.c_void_p), c.POINTER(c.c_void_p), c.POINTER(c.c_void_p),
    ]
    # runtime.cpp: pool + murmur3
    lib.ct_pool_create.restype = c.c_void_p
    lib.ct_pool_create.argtypes = [c.c_int64]
    lib.ct_pool_alloc.restype = c.c_void_p
    lib.ct_pool_alloc.argtypes = [c.c_void_p, c.c_int64]
    for name in ("ct_pool_in_use", "ct_pool_peak", "ct_pool_reserved", "ct_pool_allocs"):
        fn = getattr(lib, name)
        fn.restype = c.c_int64
        fn.argtypes = [c.c_void_p]
    lib.ct_pool_reset.restype = None
    lib.ct_pool_reset.argtypes = [c.c_void_p]
    lib.ct_pool_destroy.restype = None
    lib.ct_pool_destroy.argtypes = [c.c_void_p]
    lib.ct_murmur3_32.restype = c.c_uint32
    lib.ct_murmur3_32.argtypes = [c.c_void_p, c.c_int64, c.c_uint32]
    lib.ct_murmur3_batch.restype = None
    lib.ct_murmur3_batch.argtypes = [
        c.c_char_p, c.POINTER(c.c_int64), c.c_int64, c.c_uint32,
        c.POINTER(c.c_uint32),
    ]
    lib.ct_dict_union_u32.restype = c.c_int64
    lib.ct_dict_union_u32.argtypes = [
        c.c_void_p, c.c_int64, c.c_int32,
        c.c_void_p, c.c_int64, c.c_int32,
        c.c_void_p, c.c_int32,
        c.POINTER(c.c_int32), c.POINTER(c.c_int32),
    ]
    return lib


def get_lib():
    """The loaded native library, building it if needed; None if unavailable."""
    global _lib_handle, _load_failed
    if _lib_handle is not None or _load_failed:
        return _lib_handle
    with _lock:
        if _lib_handle is not None or _load_failed:
            return _lib_handle
        from ..utils import envgate as _envgate

        if _envgate.NO_NATIVE.raw():
            _load_failed = True
            return None
        if _asan() and not _asan_runtime_loaded():
            # CDLL of an ASAN-instrumented .so ABORTS the process ("ASan
            # runtime does not come first in initial library list") — it is
            # not a catchable error, so refuse up front unless libasan was
            # LD_PRELOADed (build.sh --asan --test does this)
            import warnings

            warnings.warn(
                "CYLON_TPU_NATIVE_ASAN=1 but libasan is not preloaded; "
                "run under LD_PRELOAD=$(g++ -print-file-name=libasan.so). "
                "Falling back to the pure-Python paths.",
                stacklevel=2,
            )
            _load_failed = True
            return None
        try:
            # hash-named .so: a source edit changes the path, so there is no
            # stale-mtime case and no dlopen-same-path staleness
            so = _so_path()
            if not os.path.exists(so) and not _build(so):
                _load_failed = True
                return None
            _lib_handle = _bind(ctypes.CDLL(so))
        except (OSError, AttributeError):
            _lib_handle = None
            _load_failed = True
            return None
    return _lib_handle


def get_lib_if_loaded():
    """The library handle only if already loaded — never triggers a g++
    build (keeps compile latency off the join/groupby hot path)."""
    return _lib_handle


def available() -> bool:
    return get_lib() is not None


class MemoryPool:
    """Arena allocator for host staging buffers (reference memory-pool
    analog, ctx/memory_pool.hpp:69). ``alloc_array`` returns a numpy view
    into pool memory — valid until ``reset``/``close``."""

    def __init__(self, block_bytes: int = 1 << 20):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.ct_pool_create(block_bytes)

    def alloc_array(self, shape, dtype) -> np.ndarray:
        dt = np.dtype(dtype)
        n = int(np.prod(shape)) * dt.itemsize
        ptr = self._lib.ct_pool_alloc(self._h, max(n, 1))
        buf = (ctypes.c_char * max(n, 1)).from_address(ptr)
        # the view's base chain (array -> ctypes buf -> pool) keeps the pool
        # alive while any allocation is referenced; reset()/close() are the
        # explicit arena-invalidation points (documented contract)
        buf._pool = self
        return np.frombuffer(buf, dtype=dt, count=int(np.prod(shape))).reshape(shape)

    def reset(self) -> None:
        self._lib.ct_pool_reset(self._h)

    @property
    def bytes_in_use(self) -> int:
        return self._lib.ct_pool_in_use(self._h)

    @property
    def bytes_peak(self) -> int:
        return self._lib.ct_pool_peak(self._h)

    @property
    def bytes_reserved(self) -> int:
        return self._lib.ct_pool_reserved(self._h)

    @property
    def alloc_count(self) -> int:
        return self._lib.ct_pool_allocs(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.ct_pool_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


_M32 = 0xFFFFFFFF


def _murmur3_32_py(data: bytes, seed: int = 0) -> int:
    """Pure-python MurmurHash3_x86_32, bit-identical to runtime.cpp's
    ct_murmur3_32. Both implementations MUST agree: in a multi-host mesh the
    hash decides shuffle routing, so a host without the native build has to
    produce the same lanes as one with it."""
    c1, c2 = 0xCC9E2D51, 0x1B873593
    h = seed
    nblocks = len(data) // 4
    for i in range(nblocks):
        k = int.from_bytes(data[4 * i: 4 * i + 4], "little")
        k = (k * c1) & _M32
        k = ((k << 15) | (k >> 17)) & _M32
        k = (k * c2) & _M32
        h ^= k
        h = ((h << 13) | (h >> 19)) & _M32
        h = (h * 5 + 0xE6546B64) & _M32
    tail = data[4 * nblocks:]
    k = 0
    if len(tail) >= 3:
        k ^= tail[2] << 16
    if len(tail) >= 2:
        k ^= tail[1] << 8
    if len(tail) >= 1:
        k ^= tail[0]
        k = (k * c1) & _M32
        k = ((k << 15) | (k >> 17)) & _M32
        k = (k * c2) & _M32
        h ^= k
    h ^= len(data)
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M32
    return h ^ (h >> 16)


def murmur3_strings(values: np.ndarray, seed: int = 0) -> np.ndarray:
    """MurmurHash3_x86_32 of each string's UTF-8 bytes (reference
    util/murmur3.cpp). Uses the native batch only when the library is
    ALREADY loaded (no g++ build on the join/groupby hot path); the python
    fallback is bit-identical, so shuffle routing agrees across processes
    regardless of which path each one took."""
    enc = [str(s).encode("utf-8") for s in values]
    lib = get_lib_if_loaded()
    if lib is not None:
        offsets = np.zeros(len(enc) + 1, np.int64)
        np.cumsum([len(b) for b in enc], out=offsets[1:])
        blob = b"".join(enc)
        out = np.empty(len(enc), np.uint32)
        lib.ct_murmur3_batch(
            blob, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(enc), seed, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        )
        return out
    return np.array([_murmur3_32_py(b, seed) for b in enc], np.uint32)


class NativeColumn:
    """One parsed column: numpy data (+valid mask, +sorted dictionary)."""

    __slots__ = ("name", "ctype", "data", "valid", "dictionary")

    def __init__(self, name, ctype, data, valid, dictionary):
        self.name = name
        self.ctype = ctype
        self.data = data
        self.valid = valid
        self.dictionary = dictionary


def read_csv(
    path: str,
    delimiter: str = ",",
    skip_rows: int = 0,
    has_header: bool = True,
    num_threads: int = 0,
) -> List[NativeColumn]:
    """Parse a CSV file with the native codec. Raises on parse error."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native CSV codec unavailable")
    h = lib.ct_csv_read(
        path.encode(), delimiter.encode(), skip_rows, int(has_header), num_threads
    )
    try:
        err = lib.ct_csv_error(h)
        if err:
            raise ValueError(f"native csv read failed: {err.decode()}")
        nrows = lib.ct_csv_nrows(h)
        ncols = lib.ct_csv_ncols(h)
        out: List[NativeColumn] = []
        for i in range(ncols):
            name = lib.ct_csv_colname(h, i).decode()
            ctype = lib.ct_csv_coltype(h, i)
            if ctype == CT_INT64:
                src, dt = lib.ct_csv_data_i64(h, i), np.int64
            elif ctype == CT_FLOAT64:
                src, dt = lib.ct_csv_data_f64(h, i), np.float64
            elif ctype == CT_BOOL:
                src, dt = lib.ct_csv_data_bool(h, i), np.uint8
            else:
                src, dt = lib.ct_csv_data_codes(h, i), np.int32
            data = np.ctypeslib.as_array(src, shape=(nrows,)).copy() if nrows else np.empty(0, dt)
            if ctype == CT_BOOL:
                data = data.astype(bool)
            vptr = lib.ct_csv_valid(h, i)
            valid = (
                np.ctypeslib.as_array(vptr, shape=(nrows,)).astype(bool).copy()
                if vptr and nrows
                else None
            )
            dictionary = None
            if ctype == CT_STRING:
                dsz = lib.ct_csv_dict_size(h, i)
                dptr = lib.ct_csv_dict(h, i)
                dictionary = np.array(
                    [dptr[j].decode() for j in range(dsz)], dtype=str
                ) if dsz else np.array([], dtype=str)
            out.append(NativeColumn(name, ctype, data, valid, dictionary))
        return out
    finally:
        lib.ct_csv_free(h)


def write_csv(
    path: str,
    names: List[str],
    columns: List[Tuple[int, np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]],
    delimiter: str = ",",
) -> None:
    """Write columns to CSV. Each column: (ctype, data, valid, dictionary)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native CSV codec unavailable")
    ncols = len(names)
    nrows = len(columns[0][1]) if ncols else 0
    c_names = (ctypes.c_char_p * ncols)(*[n.encode() for n in names])
    c_types = (ctypes.c_int32 * ncols)(*[c[0] for c in columns])
    keep = []  # keep numpy buffers + dict arrays alive
    c_data = (ctypes.c_void_p * ncols)()
    c_valid = (ctypes.c_void_p * ncols)()
    c_dicts = (ctypes.c_void_p * ncols)()
    for i, (ctype, data, valid, dictionary) in enumerate(columns):
        want = {CT_INT64: np.int64, CT_FLOAT64: np.float64,
                CT_BOOL: np.uint8, CT_STRING: np.int32}[ctype]
        arr = np.ascontiguousarray(data, dtype=want)
        keep.append(arr)
        c_data[i] = arr.ctypes.data_as(ctypes.c_void_p)
        if valid is not None:
            v = np.ascontiguousarray(valid, dtype=np.uint8)
            keep.append(v)
            c_valid[i] = v.ctypes.data_as(ctypes.c_void_p)
        if ctype == CT_STRING:
            entries = [str(s).encode() for s in (dictionary if dictionary is not None else [])]
            darr = (ctypes.c_char_p * max(len(entries), 1))(*entries)
            keep.append(darr)
            c_dicts[i] = ctypes.cast(darr, ctypes.c_void_p)
    rc = lib.ct_csv_write(
        path.encode(), delimiter.encode(), nrows, ncols,
        c_names, c_types, c_data,
        ctypes.cast(c_valid, ctypes.POINTER(ctypes.c_void_p)),
        ctypes.cast(c_dicts, ctypes.POINTER(ctypes.c_void_p)),
    )
    if rc != 0:
        raise IOError(f"native csv write failed (rc={rc})")


def dict_union(a: np.ndarray, b: np.ndarray):
    """Merge-union of two SORTED unique numpy unicode arrays via the native
    two-pointer merge (runtime.cpp ct_dict_union_u32): O(Da+Db) vs
    np.union1d's concat + full sort. Returns (union, map_a, map_b) or None
    when the native lib is unavailable / dtypes aren't plain native-order
    'U' (the C merge compares raw UCS4 words, so a byteswapped '>U' array
    would be ordered by its swapped bytes — fall back to numpy instead)."""
    if a.dtype.kind != "U" or b.dtype.kind != "U":
        return None
    if any(
        d.byteorder not in ("=", "|")
        and d.byteorder != ("<" if sys.byteorder == "little" else ">")
        for d in (a.dtype, b.dtype)
    ):
        return None
    # small unions: never trigger a first-use g++ build on the join hot
    # path (the murmur3_strings convention); big unions amortize the
    # one-time build against np.union1d's O(n log n) host sort
    lib = (
        get_lib_if_loaded() if len(a) + len(b) < 100_000 else get_lib()
    )
    if lib is None:
        return None
    da, db = len(a), len(b)
    wa = max(a.dtype.itemsize // 4, 1)
    wb = max(b.dtype.itemsize // 4, 1)
    wu = max(wa, wb)
    a_c = np.ascontiguousarray(a)
    b_c = np.ascontiguousarray(b)
    out = np.zeros(max(da + db, 1), dtype=f"<U{wu}")
    map_a = np.empty(max(da, 1), np.int32)
    map_b = np.empty(max(db, 1), np.int32)
    n = lib.ct_dict_union_u32(
        a_c.ctypes.data_as(ctypes.c_void_p), da, wa,
        b_c.ctypes.data_as(ctypes.c_void_p), db, wb,
        out.ctypes.data_as(ctypes.c_void_p), wu,
        map_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        map_b.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    union = out[:n]
    if n < 0.9 * (da + db):
        # a view would pin the full (da+db)-slot buffer for the lifetime of
        # the unified dictionary; copy when the slack is material
        union = union.copy()
    return union, map_a[:da], map_b[:db]
