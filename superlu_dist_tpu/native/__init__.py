"""ctypes seam to the native host-analysis library (slu_host.cpp).

The reference's host analysis is C (SRC/etree.c, symbfact.c, mc64ad_dist.c,
get_perm_c.c); ours is C++ compiled on first use with the toolchain baked
into the image.  The built library is named by a hash of the committed
source (``_slu_host-<sha>.so``), so a library built from any other
source is never loaded.  Python implementations remain the
specification and the fallback for library callers: every entry point
here degrades when the compiler is unavailable, and the test suite
cross-checks native vs Python output.  :func:`require` is the strict
entry for callers that must not degrade (chip_smoke.py): it raises with
the compiler's own error.

Set SLU_TPU_NO_NATIVE=1 to force the Python fallbacks.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from superlu_dist_tpu.utils.lockwatch import make_lock

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "slu_host.cpp")

_lock = make_lock("native._lock")
_lib = None
_tried = False
_error = None              # why the last load failed (None: not failed)

_I64 = ctypes.POINTER(ctypes.c_int64)
_F64 = ctypes.POINTER(ctypes.c_double)


def lib_path() -> str:
    """The library built from the current source: its name carries the
    first 16 hex digits of the source's sha256."""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"_slu_host-{digest}.so")


def _build(path: str) -> str:
    """Compile the shared library to ``path`` (``lib_path()``) unless it
    exists; return the path.  Raises ``subprocess.CalledProcessError``
    (with the compiler's stderr) or ``OSError`` when it cannot be
    built."""
    if os.path.exists(path):
        return path
    # per-process tmp name: concurrent first-use builds (pytest workers,
    # bench + tests) must not interleave writes; os.replace is atomic
    # (-lrt: shm_open lives in librt on glibc < 2.34; a no-op stub on
    # newer glibc, so linking it unconditionally is safe)
    tmp = f"{path}.{os.getpid()}.tmp"
    subprocess.run(
        ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
         "-o", tmp, _SRC, "-lrt"],
        check=True, capture_output=True, text=True, timeout=300)
    os.replace(tmp, path)
    return path


def _load():
    global _lib, _tried, _error
    if _lib is not None or _tried:
        return _lib
    path = lib_path()            # reads the source: outside the lock
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _error = None
        from superlu_dist_tpu.utils.options import env_flag
        if env_flag("SLU_TPU_NO_NATIVE"):
            _error = "SLU_TPU_NO_NATIVE is set"
            return None
        try:
            lib = ctypes.CDLL(_build(path))
            lib.slu_etree.argtypes = [ctypes.c_int64, _I64, _I64, _I64]
            lib.slu_postorder.argtypes = [ctypes.c_int64, _I64, _I64]
            # (slu_symbolic — the serial alias — stays exported for the C
            # ABI but Python always calls the _mt entry, which dispatches
            # serial at nthreads=1)
            lib.slu_symbolic_mt.restype = ctypes.c_int64
            lib.slu_symbolic_mt.argtypes = [
                ctypes.c_int64, _I64, _I64, _I64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, _I64, _I64, _I64, _I64,
                _I64, ctypes.POINTER(_I64)]
            lib.slu_free_i64.argtypes = [_I64]
            lib.slu_amalgamate.restype = ctypes.c_int64
            lib.slu_amalgamate.argtypes = [
                ctypes.c_int64, ctypes.c_int64, _I64, _I64, _I64,
                ctypes.c_double, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_double, _I64, _I64, _I64, _I64, _I64,
                ctypes.POINTER(_I64)]
            lib.slu_mc64.restype = ctypes.c_int
            lib.slu_mc64.argtypes = [ctypes.c_int64, _I64, _I64, _F64,
                                     _I64, _F64, _F64]
            lib.slu_mlnd.argtypes = [ctypes.c_int64, _I64, _I64,
                                     ctypes.c_int64, ctypes.c_uint64, _I64]
            lib.slu_mlnd_mt.argtypes = [ctypes.c_int64, _I64, _I64,
                                        ctypes.c_int64, ctypes.c_uint64,
                                        ctypes.c_int64, _I64]
            lib.slu_positions.argtypes = [ctypes.c_int64, _I64, _I64, _I64,
                                          _I64, _I64, _I64, _I64, _I64]
            lib.slu_awpm.restype = ctypes.c_int
            lib.slu_awpm.argtypes = [ctypes.c_int64, _I64, _I64, _F64, _I64]
            lib.slu_mmd.argtypes = [ctypes.c_int64, _I64, _I64, _I64]
            lib.slu_colamd.argtypes = [ctypes.c_int64, ctypes.c_int64,
                                       _I64, _I64, _I64]
            lib.slu_tree_attach.restype = ctypes.c_void_p
            lib.slu_tree_attach.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64]
            lib.slu_tree_detach.argtypes = [ctypes.c_void_p,
                                            ctypes.c_char_p, ctypes.c_int64]
            lib.slu_tree_bcast.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                           _F64, ctypes.c_int64]
            lib.slu_tree_reduce_sum.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int64, _F64,
                                                ctypes.c_int64]
            # bounded-wait collective legs + failure-detector surface
            # (ISSUE 8): timed variants return 0 ok / 1+rank on timeout;
            # pid + heartbeat slots feed the Python-side liveness poll;
            # post/peek are the wait-free ".ftx" agreement board
            lib.slu_tree_bcast_tw.restype = ctypes.c_int64
            lib.slu_tree_bcast_tw.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, _F64, ctypes.c_int64,
                ctypes.c_double]
            lib.slu_tree_reduce_sum_tw.restype = ctypes.c_int64
            lib.slu_tree_reduce_sum_tw.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, _F64, ctypes.c_int64,
                ctypes.c_double]
            lib.slu_tree_set_pid.argtypes = [ctypes.c_void_p,
                                             ctypes.c_int64]
            lib.slu_tree_get_pid.restype = ctypes.c_int64
            lib.slu_tree_get_pid.argtypes = [ctypes.c_void_p,
                                             ctypes.c_int64]
            lib.slu_tree_heartbeat.argtypes = [ctypes.c_void_p]
            lib.slu_tree_get_heartbeat.restype = ctypes.c_int64
            lib.slu_tree_get_heartbeat.argtypes = [ctypes.c_void_p,
                                                   ctypes.c_int64]
            lib.slu_tree_post.restype = ctypes.c_int64
            lib.slu_tree_post.argtypes = [ctypes.c_void_p, _F64,
                                          ctypes.c_int64]
            lib.slu_tree_peek.restype = ctypes.c_int64
            lib.slu_tree_peek.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                          _F64, ctypes.c_int64]
            lib.slu_ata_pattern.restype = ctypes.c_int64
            lib.slu_ata_pattern.argtypes = [
                ctypes.c_int64, ctypes.c_int64, _I64, _I64, ctypes.c_int64,
                _I64, ctypes.POINTER(_I64)]
            _lib = lib
        except subprocess.CalledProcessError as e:
            _error = f"g++ failed ({e.returncode}): {e.stderr.strip()}"
        except Exception as e:
            _error = f"{type(e).__name__}: {e}"
        return _lib


def available() -> bool:
    return _load() is not None


def require():
    """The loaded library, or RuntimeError naming why it could not be
    built or loaded — for callers that must run the native analysis."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native host library unavailable: {_error}")
    return lib


def _as_i64(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.int64)


def _ptr_i64(a: np.ndarray):
    return a.ctypes.data_as(_I64)


def _ptr_f64(a: np.ndarray):
    return a.ctypes.data_as(_F64)


def etree(n: int, indptr: np.ndarray, indices: np.ndarray):
    """Native etree; returns parent array or None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    indptr = _as_i64(indptr)
    indices = _as_i64(indices)
    parent = np.empty(n, dtype=np.int64)
    lib.slu_etree(n, _ptr_i64(indptr), _ptr_i64(indices), _ptr_i64(parent))
    return parent


def postorder(parent: np.ndarray):
    lib = _load()
    if lib is None:
        return None
    parent = _as_i64(parent)
    n = len(parent)
    post = np.empty(n, dtype=np.int64)
    lib.slu_postorder(n, _ptr_i64(parent), _ptr_i64(post))
    return post


def symbolic(n: int, indptr, indices, parent, relax: int, max_supernode: int,
             nthreads: int = 1):
    """Native supernodal symbolic (nthreads > 1 => the symbfact_dist
    analog, subtree-to-worker threads).  Returns (sn_start, col_to_sn,
    sn_parent, sn_level, rows_ptr, rows_data) or None."""
    lib = _load()
    if lib is None:
        return None
    indptr = _as_i64(indptr)
    indices = _as_i64(indices)
    parent = _as_i64(parent)
    sn_start = np.empty(n + 1, dtype=np.int64)
    col_to_sn = np.empty(n, dtype=np.int64)
    sn_parent = np.empty(n, dtype=np.int64)
    sn_level = np.empty(n, dtype=np.int64)
    rows_ptr = np.empty(n + 1, dtype=np.int64)
    rows_data_p = _I64()
    # slu_symbolic_mt with nthreads=1 IS the serial path (symbolic_impl
    # dispatches internally), so one call site serves both
    ns = lib.slu_symbolic_mt(n, _ptr_i64(indptr), _ptr_i64(indices),
                             _ptr_i64(parent), relax, max_supernode,
                             max(nthreads, 1), _ptr_i64(sn_start),
                             _ptr_i64(col_to_sn), _ptr_i64(sn_parent),
                             _ptr_i64(sn_level), _ptr_i64(rows_ptr),
                             ctypes.byref(rows_data_p))
    if ns < 0:
        return None
    total = int(rows_ptr[ns])
    rows_data = np.ctypeslib.as_array(rows_data_p, shape=(max(total, 1),))[
        :total].copy()
    lib.slu_free_i64(rows_data_p)
    return (sn_start[:ns + 1].copy(), col_to_sn, sn_parent[:ns].copy(),
            sn_level[:ns].copy(), rows_ptr[:ns + 1].copy(), rows_data)


def amalgamate(n: int, sn_start, rows_ptr, rows_data, tol: float,
               max_width: int, narrow: int, hard_tol: float):
    """Native fill-tolerant supernode amalgamation (twin of
    symbfact.amalgamate_supernodes).  Takes/returns structures in the
    `symbolic` output protocol; returns (sn_start, col_to_sn, sn_parent,
    sn_level, rows_ptr, rows_data) or None."""
    lib = _load()
    if lib is None:
        return None
    sn_start = _as_i64(sn_start)
    rows_ptr = _as_i64(rows_ptr)
    rows_data = _as_i64(rows_data)
    ns = len(sn_start) - 1
    o_sn_start = np.empty(n + 1, dtype=np.int64)
    o_col_to_sn = np.empty(n, dtype=np.int64)
    o_sn_parent = np.empty(max(ns, 1), dtype=np.int64)
    o_sn_level = np.empty(max(ns, 1), dtype=np.int64)
    o_rows_ptr = np.empty(n + 1, dtype=np.int64)
    o_rows_data_p = _I64()
    k = lib.slu_amalgamate(n, ns, _ptr_i64(sn_start), _ptr_i64(rows_ptr),
                           _ptr_i64(rows_data), float(tol), int(max_width),
                           int(narrow), float(hard_tol),
                           _ptr_i64(o_sn_start), _ptr_i64(o_col_to_sn),
                           _ptr_i64(o_sn_parent), _ptr_i64(o_sn_level),
                           _ptr_i64(o_rows_ptr),
                           ctypes.byref(o_rows_data_p))
    if k < 0:
        return None
    total = int(o_rows_ptr[k])
    out_rows = np.ctypeslib.as_array(o_rows_data_p,
                                     shape=(max(total, 1),))[:total].copy()
    lib.slu_free_i64(o_rows_data_p)
    return (o_sn_start[:k + 1].copy(), o_col_to_sn,
            o_sn_parent[:k].copy(), o_sn_level[:k].copy(),
            o_rows_ptr[:k + 1].copy(), out_rows)


def mc64(n: int, indptr, indices, absval):
    """Native MC64 job=5.  Returns (col_match, u, v) or None if unavailable.
    Raises ValueError on structural singularity."""
    lib = _load()
    if lib is None:
        return None
    indptr = _as_i64(indptr)
    indices = _as_i64(indices)
    absval = np.ascontiguousarray(absval, dtype=np.float64)
    col_match = np.empty(n, dtype=np.int64)
    u = np.empty(n, dtype=np.float64)
    v = np.empty(n, dtype=np.float64)
    rc = lib.slu_mc64(n, _ptr_i64(indptr), _ptr_i64(indices),
                      _ptr_f64(absval), _ptr_i64(col_match), _ptr_f64(u),
                      _ptr_f64(v))
    if rc != 0:
        raise ValueError("structurally singular")
    return col_match, u, v


def positions(s_arr, x_arr, first, last, snW, rows_ptr, rows_data):
    """Batched front-position queries (plan building); None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    s_arr = _as_i64(s_arr)
    x_arr = _as_i64(x_arr)
    first = _as_i64(first)
    last = _as_i64(last)
    snW = _as_i64(snW)
    rows_ptr = _as_i64(rows_ptr)
    rows_data = _as_i64(rows_data)
    pos = np.empty(len(s_arr), dtype=np.int64)
    lib.slu_positions(len(s_arr), _ptr_i64(s_arr), _ptr_i64(x_arr),
                      _ptr_i64(first), _ptr_i64(last), _ptr_i64(snW),
                      _ptr_i64(rows_ptr), _ptr_i64(rows_data), _ptr_i64(pos))
    return pos


def mmd(n: int, indptr, indices):
    """Exact-external-degree minimum-degree ordering; None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    indptr = _as_i64(indptr)
    indices = _as_i64(indices)
    order = np.empty(n, dtype=np.int64)
    lib.slu_mmd(n, _ptr_i64(indptr), _ptr_i64(indices), _ptr_i64(order))
    return order


def awpm(n: int, indptr, indices, absval):
    """Approximate-weight perfect matching (HWPM analog); None if
    unavailable.  Raises ValueError on structural singularity."""
    lib = _load()
    if lib is None:
        return None
    indptr = _as_i64(indptr)
    indices = _as_i64(indices)
    absval = np.ascontiguousarray(absval, dtype=np.float64)
    col_match = np.empty(n, dtype=np.int64)
    rc = lib.slu_awpm(n, _ptr_i64(indptr), _ptr_i64(indices),
                      _ptr_f64(absval), _ptr_i64(col_match))
    if rc != 0:
        raise ValueError("structurally singular")
    return col_match


def colamd(n_rows: int, n_cols: int, indptr, indices):
    """COLAMD-class approximate column MD ordering; None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    indptr = _as_i64(indptr)
    indices = _as_i64(indices)
    order = np.empty(n_cols, dtype=np.int64)
    lib.slu_colamd(n_rows, n_cols, _ptr_i64(indptr), _ptr_i64(indices),
                   _ptr_i64(order))
    return order


def ata_pattern(n_rows: int, n_cols: int, indptr, indices,
                dense_row: int = 0):
    """Symmetric adjacency of AᵀA (getata_dist analog); None if
    unavailable.  dense_row > 0 drops rows longer than that."""
    lib = _load()
    if lib is None:
        return None
    indptr = _as_i64(indptr)
    indices = _as_i64(indices)
    out_ptr = np.empty(n_cols + 1, dtype=np.int64)
    buf = _I64()
    total = int(lib.slu_ata_pattern(n_rows, n_cols, _ptr_i64(indptr),
                                    _ptr_i64(indices), dense_row,
                                    _ptr_i64(out_ptr), ctypes.byref(buf)))
    try:
        out_idx = np.ctypeslib.as_array(buf, shape=(max(total, 1),))[
            :total].copy()
    finally:
        lib.slu_free_i64(buf)
    return out_ptr, out_idx


def mlnd(n: int, indptr, indices, leaf_size: int = 96, seed: int = 1,
         nthreads: int | None = None):
    """Native multilevel nested dissection; returns order or None.

    nthreads > 1 (or SLU_TPU_ND_THREADS) maps independent separator
    subtrees onto threads — the parallel-ordering capability analog of
    the reference's ParMETIS path (SRC/get_perm_c_parmetis.c:104,255:
    separator tree built by 2^q processes).  The result is deterministic
    for a given (seed, leaf_size) regardless of nthreads: every subtree
    derives its RNG stream from its tree path, not from thread timing.
    """
    lib = _load()
    if lib is None:
        return None
    if nthreads is None:
        from superlu_dist_tpu.utils.options import env_int
        nthreads = env_int("SLU_TPU_ND_THREADS")
    indptr = _as_i64(indptr)
    indices = _as_i64(indices)
    order = np.empty(n, dtype=np.int64)
    lib.slu_mlnd_mt(n, _ptr_i64(indptr), _ptr_i64(indices), leaf_size, seed,
                    max(int(nthreads), 1), _ptr_i64(order))
    return order
