"""Loader for the native (C++/OpenMP) setup kernels.

The solve phase is pure XLA; the setup phase's hot host passes (strength
filtering, greedy aggregation) have native implementations in
``csrc/setup_kernels.cpp``, compiled on first use with the toolchain baked
into the image and loaded over ctypes (no pybind11 dependency). Falls back
to the vectorized numpy implementations when no compiler is available —
every caller treats this module as optional.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_LIB = None
_LOCK = threading.Lock()
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "csrc", "setup_kernels.cpp")
_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_native_cache")


def _build() -> str:
    """Path of the library built from the current source: the file name
    carries a hash of ``setup_kernels.cpp``, so a copied tree (whose
    mtimes say nothing) rebuilds exactly when the source changed."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    os.makedirs(_CACHE_DIR, exist_ok=True)
    so = os.path.join(_CACHE_DIR, "libamgcl_tpu_native-%s.so" % digest)
    if os.path.exists(so):
        return so
    tmp = so + ".tmp%d" % os.getpid()
    cmd = ["g++", "-O3", "-fopenmp", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, _SRC]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so)
    for name in os.listdir(_CACHE_DIR):      # builds of older sources
        old = os.path.join(_CACHE_DIR, name)
        if name.endswith(".so") and old != so:
            try:
                os.remove(old)
            except OSError:
                pass
    return so


def lib():
    """The loaded native library, or None when unavailable."""
    global _LIB
    with _LOCK:
        if _LIB is None:
            try:
                handle = ctypes.CDLL(_build())
            except (OSError, subprocess.CalledProcessError,
                    FileNotFoundError):
                _LIB = False
                return None
            handle.aggregate_d2.restype = ctypes.c_int64
            handle.aggregate_d2.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p]
            handle.strength_mask.restype = None
            handle.strength_mask.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_double, ctypes.c_void_p]
            handle.symmetrize_mask.restype = None
            handle.symmetrize_mask.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p]
            handle.spgemm_symbolic.restype = None
            handle.spgemm_symbolic.argtypes = [ctypes.c_int64] +                 [ctypes.c_void_p] * 5
            handle.spgemm_numeric.restype = None
            handle.spgemm_numeric.argtypes = [ctypes.c_int64] +                 [ctypes.c_void_p] * 9
            handle.spgemm_numeric_f32.restype = None
            handle.spgemm_numeric_f32.argtypes = [ctypes.c_int64] +                 [ctypes.c_void_p] * 9
            handle.spgemm_numeric_block.restype = None
            handle.spgemm_numeric_block.argtypes = [ctypes.c_int64] +                 [ctypes.c_void_p] * 9 + [ctypes.c_int64] * 3
            handle.spgemm_masked.restype = None
            handle.spgemm_masked.argtypes = [ctypes.c_int64] +                 [ctypes.c_void_p] * 9
            handle.spai0_diag.restype = None
            handle.spai0_diag.argtypes = [ctypes.c_int64] +                 [ctypes.c_void_p] * 4
            for nm in ("ell_pack", "ell_pack_f32"):
                fn = getattr(handle, nm)
                fn.restype = None
                fn.argtypes = [ctypes.c_int64, ctypes.c_void_p,
                               ctypes.c_void_p, ctypes.c_void_p,
                               ctypes.c_int64, ctypes.c_int64,
                               ctypes.c_void_p, ctypes.c_void_p]
            for nm in ("filter_count", "filter_count_f32"):
                fn = getattr(handle, nm)
                fn.restype = None
                fn.argtypes = [
                    ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_double, ctypes.c_void_p]
            handle.iluk_symbolic.restype = ctypes.c_int64
            handle.iluk_symbolic.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p]
            for nm in ("filter_fill", "filter_fill_f32"):
                fn = getattr(handle, nm)
                fn.restype = None
                fn.argtypes = [
                    ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_double, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
            handle.dia_mark.restype = None
            handle.dia_mark.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p]
            for nm in ("dia_pack_f64_f32", "dia_pack_f64_f64",
                       "dia_pack_f32_f32"):
                fn = getattr(handle, nm)
                fn.restype = None
                fn.argtypes = [ctypes.c_int64] + [ctypes.c_void_p] * 5
            for nm in ("dia_fnma_batch_f64", "dia_fnma_batch_f32"):
                fn = getattr(handle, nm)
                fn.restype = None
                fn.argtypes = [ctypes.c_int64, ctypes.c_int64] + \
                    [ctypes.c_void_p] * 7
            handle.rs_cfsplit.restype = None
            handle.rs_cfsplit.argtypes = [ctypes.c_int64] + \
                [ctypes.c_void_p] * 6
            _LIB = handle
        return _LIB or None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def native_aggregates(A, eps_strong: float):
    """(agg, n_agg) via the native greedy distance-2 pass, or None if the
    native library is unavailable or the values are not float64-able."""
    L = lib()
    if L is None or A.is_block or np.iscomplexobj(A.val):
        return None
    try:
        val = np.ascontiguousarray(A.val, dtype=np.float64)
    except (TypeError, ValueError):
        return None
    ptr = np.ascontiguousarray(A.ptr, dtype=np.int64)
    col = np.ascontiguousarray(A.col, dtype=np.int32)
    n = A.nrows
    strong = np.empty(A.nnz, dtype=np.uint8)
    L.strength_mask(n, _ptr(ptr), _ptr(col), _ptr(val),
                    float(eps_strong), _ptr(strong))
    L.symmetrize_mask(n, _ptr(ptr), _ptr(col), _ptr(strong))
    agg = np.empty(n, dtype=np.int64)
    n_agg = L.aggregate_d2(n, _ptr(ptr), _ptr(col), _ptr(strong), _ptr(agg))
    return agg, int(n_agg)


def native_spgemm(A, B):
    """C = A @ B via the native two-phase hash SpGEMM, or None if
    unavailable. Returns (ptr, col, val) — val is (nnz,) for scalar inputs
    or (nnz, br, bc) for block inputs. Covers f64, f32, and block f64/f32
    values (reference parity: amgcl/detail/spgemm.hpp handles every value
    type); complex stays on scipy.

    Only engaged on multi-core hosts: the OpenMP parallelism is the whole
    point — single-threaded, scipy's SMMP kernel is faster than the hash
    accumulator, so we defer to it there."""
    L = lib()
    force = os.environ.get("AMGCL_TPU_FORCE_NATIVE_SPGEMM") == "1"
    if L is None or (L.omp_max_threads() < 2 and not force):
        return None
    if A.is_block != B.is_block:
        return None            # mixed block/scalar: caller unblocks
    if A.is_block and A.block_size[1] != B.block_size[0]:
        return None
    if A.ncols != B.nrows:
        raise ValueError("spgemm dimension mismatch: %s x %s"
                         % (A.shape, B.shape))
    if np.iscomplexobj(A.val) or np.iscomplexobj(B.val):
        return None
    f32 = (not A.is_block and A.val.dtype == np.float32
           and B.val.dtype == np.float32)
    vdt = np.float32 if f32 else np.float64
    try:
        aval = np.ascontiguousarray(A.val, dtype=vdt)
        bval = np.ascontiguousarray(B.val, dtype=vdt)
    except (TypeError, ValueError):
        return None
    aptr = np.ascontiguousarray(A.ptr, dtype=np.int64)
    acol = np.ascontiguousarray(A.col, dtype=np.int32)
    bptr = np.ascontiguousarray(B.ptr, dtype=np.int64)
    bcol = np.ascontiguousarray(B.col, dtype=np.int32)
    n = A.nrows
    rn = np.empty(n, dtype=np.int64)
    L.spgemm_symbolic(n, _ptr(aptr), _ptr(acol), _ptr(bptr), _ptr(bcol),
                      _ptr(rn))
    cptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(rn, out=cptr[1:])
    ccol = np.empty(cptr[-1], dtype=np.int32)
    if A.is_block:
        br, bk = A.block_size
        bc = B.block_size[1]
        cval = np.empty((cptr[-1], br, bc), dtype=np.float64)
        L.spgemm_numeric_block(
            n, _ptr(aptr), _ptr(acol), _ptr(aval), _ptr(bptr), _ptr(bcol),
            _ptr(bval), _ptr(cptr), _ptr(ccol), _ptr(cval), br, bk, bc)
        return cptr, ccol, cval
    cval = np.empty(cptr[-1], dtype=vdt)
    kern = L.spgemm_numeric_f32 if f32 else L.spgemm_numeric
    kern(n, _ptr(aptr), _ptr(acol), _ptr(aval), _ptr(bptr),
         _ptr(bcol), _ptr(bval), _ptr(cptr), _ptr(ccol), _ptr(cval))
    return cptr, ccol, cval


def native_spgemm_masked(n, aptr, acol, aval, bptr, bcol, bval, tptr, tcol):
    """tval[q] = (A B)[i, tcol[q]] restricted to the target pattern — the
    Chow-Patel sweep kernel (no symbolic phase, no full product). Returns
    the target values array or None when the native library is missing."""
    L = lib()
    if L is None:
        return None
    aval = np.ascontiguousarray(aval, dtype=np.float64)
    bval = np.ascontiguousarray(bval, dtype=np.float64)
    aptr = np.ascontiguousarray(aptr, dtype=np.int64)
    acol = np.ascontiguousarray(acol, dtype=np.int32)
    bptr = np.ascontiguousarray(bptr, dtype=np.int64)
    bcol = np.ascontiguousarray(bcol, dtype=np.int32)
    tptr = np.ascontiguousarray(tptr, dtype=np.int64)
    tcol = np.ascontiguousarray(tcol, dtype=np.int32)
    tval = np.empty(len(tcol), dtype=np.float64)
    L.spgemm_masked(int(n), _ptr(aptr), _ptr(acol), _ptr(aval), _ptr(bptr),
                    _ptr(bcol), _ptr(bval), _ptr(tptr), _ptr(tcol),
                    _ptr(tval))
    return tval


def native_filtered(A, eps_strong):
    """(ptr, col, val, dinv) of the strength-filtered lumped matrix in the
    matrix's own value dtype (f64/f32), or None if unavailable."""
    L = lib()
    if L is None or A.is_block or np.iscomplexobj(A.val):
        return None
    vdt = np.dtype(A.val.dtype)
    if vdt == np.float64:
        count_fn, fill_fn = L.filter_count, L.filter_fill
    elif vdt == np.float32:
        count_fn, fill_fn = L.filter_count_f32, L.filter_fill_f32
    else:
        return None
    val = np.ascontiguousarray(A.val)
    ptr = np.ascontiguousarray(A.ptr, dtype=np.int64)
    col = np.ascontiguousarray(A.col, dtype=np.int32)
    n = A.nrows
    rn = np.empty(n, dtype=np.int64)
    count_fn(n, _ptr(ptr), _ptr(col), _ptr(val), float(eps_strong),
             _ptr(rn))
    optr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(rn, out=optr[1:])
    ocol = np.empty(optr[-1], dtype=np.int32)
    oval = np.empty(optr[-1], dtype=vdt)
    dinv = np.empty(n, dtype=vdt)
    fill_fn(n, _ptr(ptr), _ptr(col), _ptr(val), float(eps_strong),
            _ptr(optr), _ptr(ocol), _ptr(oval), _ptr(dinv))
    return optr, ocol, oval, dinv


def native_ell_pack(A, K: int, out_dtype):
    """(cols, vals) dense ELL planes for host CSR ``A``, value cast fused
    into the pack; None when unavailable. vals is (n, K[, br, bc]) in
    ``out_dtype`` (f32/f64)."""
    L = lib()
    if L is None or np.iscomplexobj(A.val):
        return None
    odt = np.dtype(out_dtype)
    if odt == np.float32:
        kern = L.ell_pack_f32
    elif odt == np.float64:
        kern = L.ell_pack
    else:
        return None
    try:
        val = np.ascontiguousarray(A.val, dtype=np.float64)
    except (TypeError, ValueError):
        return None
    ptr = np.ascontiguousarray(A.ptr, dtype=np.int64)
    col = np.ascontiguousarray(A.col, dtype=np.int32)
    n = A.nrows
    br, bc = A.block_size
    bs = br * bc
    cols = np.zeros((n, K), dtype=np.int32)
    shape = (n, K) if bs == 1 else (n, K, br, bc)
    vals = np.zeros(shape, dtype=odt)
    kern(n, _ptr(ptr), _ptr(col), _ptr(val), K, bs, _ptr(cols), _ptr(vals))
    return cols, vals


def native_spai0_diag(A):
    """The SPAI-0 diagonal m_i = a_ii / sum_j a_ij^2 in one native pass,
    or None when unavailable (scalar f64-able values only)."""
    L = lib()
    if L is None or A.is_block or np.iscomplexobj(A.val):
        return None
    try:
        val = np.ascontiguousarray(A.val, dtype=np.float64)
    except (TypeError, ValueError):
        return None
    ptr = np.ascontiguousarray(A.ptr, dtype=np.int64)
    col = np.ascontiguousarray(A.col, dtype=np.int32)
    m = np.empty(A.nrows, dtype=np.float64)
    L.spai0_diag(A.nrows, _ptr(ptr), _ptr(col), _ptr(val), _ptr(m))
    return m


def native_iluk_pattern(A, k: int):
    """Level-of-fill ILU(k) pattern: (ptr, col) of the symbolic factor, or
    None if the native library is unavailable. The input pattern must be
    sorted (CSR canonical form)."""
    L = lib()
    if L is None or A.is_block:
        return None
    ptr = np.ascontiguousarray(A.ptr, dtype=np.int64)
    col = np.ascontiguousarray(A.col, dtype=np.int32)
    n = A.nrows
    budget = max(A.nnz * (k + 2), 64)
    for _ in range(8):
        optr = np.zeros(n + 1, dtype=np.int64)
        ocol = np.empty(budget, dtype=np.int32)
        got = L.iluk_symbolic(n, _ptr(ptr), _ptr(col), int(k), budget,
                              _ptr(optr), _ptr(ocol))
        if got >= 0:
            return optr, ocol[:got]
        budget *= 2
    raise MemoryError("iluk pattern did not fit after retries")


def native_dia_offsets(A):
    """Distinct diagonal offsets of a scalar CSR via the parallel native
    mark pass, or None when unavailable."""
    L = lib()
    if L is None or A.is_block:
        return None
    ptr = np.ascontiguousarray(A.ptr, dtype=np.int64)
    col = np.ascontiguousarray(A.col, dtype=np.int32)
    base = A.nrows - 1
    hits = np.zeros(base + A.ncols, dtype=np.uint8)
    L.dia_mark(A.nrows, _ptr(ptr), _ptr(col), _ptr(hits))
    return np.flatnonzero(hits) - base


def native_dia_pack(A, offsets, out_dtype):
    """(ndiag, nrows) diagonal-major array for the device DIA format, with
    the host-f64 -> device dtype cast fused into the scatter. Returns None
    when the native library or the dtype pair is unsupported."""
    L = lib()
    out_dtype = np.dtype(out_dtype)
    if L is None or A.is_block:
        return None
    pair = (np.dtype(A.val.dtype), out_dtype)
    fn = {(np.dtype(np.float64), np.dtype(np.float32)): L.dia_pack_f64_f32,
          (np.dtype(np.float64), np.dtype(np.float64)): L.dia_pack_f64_f64,
          (np.dtype(np.float32), np.dtype(np.float32)): L.dia_pack_f32_f32,
          }.get(pair)
    if fn is None:
        return None
    ptr = np.ascontiguousarray(A.ptr, dtype=np.int64)
    col = np.ascontiguousarray(A.col, dtype=np.int32)
    val = np.ascontiguousarray(A.val)
    base = A.nrows - 1
    slot = np.zeros(base + A.ncols, dtype=np.int32)
    slot[np.asarray(offsets) + base] = np.arange(len(offsets),
                                                 dtype=np.int32)
    out = np.zeros((len(offsets), A.nrows), dtype=out_dtype)
    fn(A.nrows, _ptr(ptr), _ptr(col), _ptr(val), _ptr(slot), _ptr(out))
    return out


def native_dia_fnma_batch(abase, a_idx, bbase, b_idx, shifts, obase,
                          out_idx):
    """All pair products of one diagonal-Galerkin stage in a single call:
    ``obase[out_idx[p]] -= abase[a_idx[p]] * shift(bbase[b_idx[p]],
    shifts[p])``. Pairs sharing an output row must be contiguous (the
    OpenMP split is per output row). Returns False when unavailable."""
    L = lib()
    if L is None:
        return False
    dt = np.dtype(obase.dtype)
    if abase.dtype != dt or bbase.dtype != dt:
        return False
    if dt == np.float64:
        fn = L.dia_fnma_batch_f64
    elif dt == np.float32:
        fn = L.dia_fnma_batch_f32
    else:
        return False
    for a in (abase, bbase, obase):
        if not a.flags.c_contiguous:
            return False
    n = obase.shape[1]
    a_idx = np.ascontiguousarray(a_idx, dtype=np.int64)
    b_idx = np.ascontiguousarray(b_idx, dtype=np.int64)
    shifts = np.ascontiguousarray(shifts, dtype=np.int64)
    out_idx = np.ascontiguousarray(out_idx, dtype=np.int64)
    # the OpenMP split parallelizes over contiguous out_idx groups; a
    # caller interleaving output rows would race two threads on one row —
    # cheap O(npairs) check beats a silent wrong coarse operator
    if len(out_idx) and np.count_nonzero(np.diff(out_idx)) \
            != len(np.unique(out_idx)) - 1:
        raise ValueError(
            "native_dia_fnma_batch requires pairs sharing an output row "
            "to be contiguous in out_idx")
    fn(n, len(a_idx), _ptr(abase), _ptr(a_idx), _ptr(bbase), _ptr(b_idx),
       _ptr(shifts), _ptr(obase), _ptr(out_idx))
    return True


def native_rs_cfsplit(ptr, col, strong, stp, stc, cf):
    """Classic RS C/F split (sequential dynamic measures) in native code;
    returns the updated cf array or None when unavailable. ``cf`` arrives
    with no-strong-connection rows pre-marked 2 and is modified in a
    copy."""
    L = lib()
    if L is None:
        return None
    n = len(ptr) - 1
    ptr = np.ascontiguousarray(ptr, dtype=np.int64)
    col = np.ascontiguousarray(col, dtype=np.int32)
    strong = np.ascontiguousarray(strong, dtype=np.uint8)
    stp = np.ascontiguousarray(stp, dtype=np.int64)
    stc = np.ascontiguousarray(stc, dtype=np.int32)
    out = np.ascontiguousarray(cf, dtype=np.int8).copy()
    L.rs_cfsplit(n, _ptr(ptr), _ptr(col), _ptr(strong), _ptr(stp),
                 _ptr(stc), _ptr(out))
    return out
