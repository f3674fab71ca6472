"""ctypes loader for the native CPU core (sheep_tpu/core/csrc).

pybind11 is not available in this environment, so the C++ core exposes a
plain C ABI over caller-allocated numpy buffers. The library is built
lazily with make on first use; failure to build leaves the ``cpu`` backend
unregistered (callers fall back to ``pure``).

The build is keyed, not timed: ``libsheep_core.so.key`` next to the
library holds a hash of the committed sources (``sheep_core.cpp``,
``Makefile``) and of the host CPU (the Makefile compiles with
``-march=native``). A library whose key differs — other sources, or
built on another CPU and copied here — is rebuilt before it is loaded,
never loaded as it is.
"""

from __future__ import annotations

import ctypes
import fcntl
import functools
import hashlib
import os
import platform
import subprocess
from typing import Optional

import numpy as np

_CSRC = os.path.join(os.path.dirname(__file__), "csrc")
_SO = os.path.join(_CSRC, "libsheep_core.so")
_lib: Optional[ctypes.CDLL] = None

_i64p = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
_u32p = np.ctypeslib.ndpointer(dtype=np.uint32, flags="C_CONTIGUOUS")
_f64p = np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS")


class _f64p_or_null(_f64p):
    """float64 ndpointer that also accepts None (passed as NULL) — for
    C functions whose array argument is optional, e.g. unit weights."""

    @classmethod
    def from_param(cls, obj):
        if obj is None:
            return None
        return _f64p.from_param(obj)


def _host_cpu() -> str:
    """What ``-march=native`` compiles for: machine, CPU model, flags."""
    ident = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features",
                           "CPU part") and line not in ident:
                    ident.append(line)
                if line.strip() == "":
                    break  # the first processor block says it all
    except OSError:
        ident.append(platform.processor())
    return "".join(ident)


def build_key() -> str:
    """Hash of the committed sources plus the host CPU."""
    h = hashlib.sha256()
    for name in ("sheep_core.cpp", "Makefile"):
        with open(os.path.join(_CSRC, name), "rb") as f:
            h.update(f.read())
    h.update(_host_cpu().encode())
    return h.hexdigest()


def _build() -> None:
    key_path = _SO + ".key"
    key = build_key()

    def fresh() -> bool:
        try:
            with open(key_path) as f:
                return f.read().strip() == key and os.path.exists(_SO)
        except OSError:
            return False

    if fresh():
        return
    # one builder at a time (test workers, sheepd + clients): the loser
    # of the lock finds the winner's library fresh and loads it
    with open(os.path.join(_CSRC, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if fresh():
            return
        tmp = f"libsheep_core.so.{os.getpid()}.tmp"
        try:
            subprocess.run(["make", "-B", "-C", _CSRC, f"TARGET={tmp}"],
                           check=True, capture_output=True, text=True)
            os.replace(os.path.join(_CSRC, tmp), _SO)
        finally:
            if os.path.exists(os.path.join(_CSRC, tmp)):
                os.unlink(os.path.join(_CSRC, tmp))
        with open(key_path, "w") as f:
            f.write(key + "\n")


def load() -> ctypes.CDLL:
    """Build if needed and load the native library (cached)."""
    global _lib
    if _lib is not None:
        return _lib
    _build()
    lib = ctypes.CDLL(_SO)

    lib.sheep_core_abi_version.restype = ctypes.c_int64
    if lib.sheep_core_abi_version() != 1:
        raise RuntimeError("libsheep_core ABI mismatch; run make clean")

    c_i64 = ctypes.c_int64
    lib.sheep_degrees.argtypes = [_i64p, c_i64, c_i64, _i64p]
    lib.sheep_elim_order.argtypes = [_i64p, c_i64, _i64p]
    lib.sheep_build_elim_tree.argtypes = [_i64p, c_i64, _i64p, c_i64, _i64p]
    lib.sheep_merge_trees.argtypes = [_i64p, _i64p, _i64p, c_i64]
    lib.sheep_tree_split.argtypes = [_i64p, _i64p, _f64p_or_null, c_i64,
                                     c_i64, ctypes.c_double, _i32p]
    lib.sheep_score_chunk.argtypes = [_i64p, c_i64, _i32p, c_i64,
                                      ctypes.POINTER(c_i64), ctypes.POINTER(c_i64)]
    lib.sheep_cut_pairs.argtypes = [_i64p, c_i64, _i32p, c_i64, c_i64, _i64p]
    lib.sheep_cut_pairs.restype = c_i64
    lib.sheep_parse_text.argtypes = [ctypes.c_char_p, c_i64, _i64p, c_i64,
                                     ctypes.POINTER(c_i64)]
    lib.sheep_parse_text.restype = c_i64
    lib.sheep_rmat_hash_range.argtypes = [c_i64, c_i64, c_i64, _u32p, _u32p,
                                          ctypes.c_uint32, ctypes.c_uint32,
                                          ctypes.c_uint32, _i64p]
    if hasattr(lib, "sheep_sbm_hash_range"):
        # round-4 symbol; a pre-round-4 .so (stale build) simply keeps
        # the numpy path (generators.sbm_hash_range checks this hasattr)
        lib.sheep_sbm_hash_range.argtypes = [c_i64, c_i64, _u32p, _u32p,
                                             ctypes.c_uint32, c_i64, c_i64,
                                             _i64p]
    _lib = lib
    return lib


def available() -> bool:
    try:
        load()
        return True
    except Exception:
        return False


# ---------------------------------------------------------------- wrappers

def _timed(fn):
    """Each call opens profiler span ``sheep/native:<fn>`` and adds its
    wall to ``t_native_s`` of the current stats scope (``obs``)."""
    from sheep_tpu import obs

    name = "native:" + fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with obs.timed(name, "t_native_s"):
            return fn(*args, **kwargs)
    return wrapper


def _edges64(edges: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(edges).reshape(-1, 2), dtype=np.int64)


@_timed
def degrees(edges: np.ndarray, n: int, out: Optional[np.ndarray] = None) -> np.ndarray:
    lib = load()
    e = _edges64(edges)
    if out is None:
        out = np.zeros(n, dtype=np.int64)
    lib.sheep_degrees(e, len(e), n, out)
    return out


@_timed
def elim_order(deg: np.ndarray) -> np.ndarray:
    lib = load()
    d = np.ascontiguousarray(deg, dtype=np.int64)
    pos = np.empty(len(d), dtype=np.int64)
    lib.sheep_elim_order(d, len(d), pos)
    return pos


@_timed
def build_elim_tree(edges: np.ndarray, pos: np.ndarray,
                    parent: Optional[np.ndarray] = None) -> np.ndarray:
    lib = load()
    e = _edges64(edges)
    p = np.ascontiguousarray(pos, dtype=np.int64)
    if parent is None:
        parent = np.full(len(p), -1, dtype=np.int64)
    else:
        parent = np.ascontiguousarray(parent, dtype=np.int64)
    lib.sheep_build_elim_tree(e, len(e), p, len(p), parent)
    return parent


@_timed
def merge_trees(parent: np.ndarray, other: np.ndarray, pos: np.ndarray) -> np.ndarray:
    lib = load()
    parent = np.ascontiguousarray(parent, dtype=np.int64)
    lib.sheep_merge_trees(parent, np.ascontiguousarray(other, dtype=np.int64),
                          np.ascontiguousarray(pos, dtype=np.int64), len(parent))
    return parent


@_timed
def tree_split(parent: np.ndarray, pos: np.ndarray, k: int,
               weights: Optional[np.ndarray] = None, alpha: float = 1.0) -> np.ndarray:
    lib = load()
    n = len(parent)
    # weights=None -> NULL: the C side treats it as unit weights without
    # either side materializing an O(n) ones array (8 GB at n = 2^30)
    w = None if weights is None \
        else np.ascontiguousarray(weights, dtype=np.float64)
    assign = np.empty(n, dtype=np.int32)
    lib.sheep_tree_split(
        np.ascontiguousarray(parent, dtype=np.int64),
        np.ascontiguousarray(pos, dtype=np.int64),
        w, n, k, alpha, assign)
    return assign


def score_chunk(edges: np.ndarray, assign: np.ndarray, n: int):
    lib = load()
    e = _edges64(edges)
    cut = ctypes.c_int64(0)
    total = ctypes.c_int64(0)
    lib.sheep_score_chunk(e, len(e), np.ascontiguousarray(assign, dtype=np.int32),
                          n, ctypes.byref(cut), ctypes.byref(total))
    return cut.value, total.value


def cut_pairs(edges: np.ndarray, assign: np.ndarray, n: int, k: int) -> np.ndarray:
    lib = load()
    e = _edges64(edges)
    out = np.empty(2 * len(e), dtype=np.int64)
    cnt = lib.sheep_cut_pairs(e, len(e), np.ascontiguousarray(assign, dtype=np.int32),
                              n, k, out)
    return out[:cnt]


def parse_text(data: bytes, max_edges: Optional[int] = None):
    """Parse complete 'u v' lines from a byte block -> (edges, bytes_consumed)."""
    lib = load()
    cap = max_edges if max_edges is not None else len(data) // 3 + 1
    out = np.empty((cap, 2), dtype=np.int64)
    consumed = ctypes.c_int64(0)
    cnt = lib.sheep_parse_text(data, len(data), out.reshape(-1), cap,
                               ctypes.byref(consumed))
    return out[:cnt].copy(), consumed.value


def rmat_hash_range(scale: int, start: int, count: int,
                    keys, keys2, thresholds) -> np.ndarray:
    """Native twin of generators._rmat_hash_uv over an edge-index range
    (bit-identical; asserted by tests/test_rmat_hash.py). ``keys``/
    ``keys2`` are the premixed per-level uint32 constants, ``thresholds``
    the (t_u, t_v0, t_v1) quadrant cutoffs."""
    lib = load()
    out = np.empty((count, 2), dtype=np.int64)
    lib.sheep_rmat_hash_range(
        scale, start, count,
        np.ascontiguousarray(keys, dtype=np.uint32),
        np.ascontiguousarray(keys2, dtype=np.uint32),
        int(thresholds[0]), int(thresholds[1]), int(thresholds[2]), out)
    return out


def sbm_hash_range(start: int, count: int, keys, keys2, t_out: int,
                   n_blocks: int, block_bits: int) -> np.ndarray:
    """Native twin of generators._sbm_hash_uv over an edge-index range
    (bit-identical; asserted by tests/test_sbm.py)."""
    lib = load()
    out = np.empty((count, 2), dtype=np.int64)
    lib.sheep_sbm_hash_range(
        start, count,
        np.ascontiguousarray(keys, dtype=np.uint32),
        np.ascontiguousarray(keys2, dtype=np.uint32),
        int(t_out), int(n_blocks), int(block_bits), out)
    return out


def has_sbm_hash() -> bool:
    try:
        return hasattr(load(), "sheep_sbm_hash_range")
    except Exception:
        return False
