"""Unstructured-matrix device SpMV: windowed ELL.

This is the TPU answer to the reference's general-sparsity GPU story
(cuSPARSE CSR SpMV, amgcl/backend/cuda.hpp:60-843; generated block kernels,
amgcl/backend/vexcl_static_matrix.hpp:228-1031). A TPU has no hardware
scatter/gather against HBM — XLA lowers an arbitrary ``jnp.take`` to a
serialized gather (~27 ms per SpMV of the 85,623-row FE operator on a
v5e). The format restructures the access pattern instead of translating
CSR:

1. **Host-side row binning (RCM)**: reverse Cuthill-McKee confines each row
   tile's column support to a narrow window (the executed reorder,
   ``telemetry/structure.reorder_plan``, or ``utils/adapters.
   cuthill_mckee`` — the adapter the reference also applies for cache
   locality, amgcl/adapter/reorder.hpp). The reorder is absorbed into the
   hierarchy: P/R transfers see the permuted operator, so the solve phase
   never pays it.

2. **Windowed ELL in entry vregs**: per 1024-row tile, columns are stored
   relative to the tile's window start. Each group of 128 consecutive rows
   stores its ELL slots as (8 slots, 128 lanes) entry vregs, slot k of a
   row holding its k-th smallest column (``telemetry/structure.
   vreg_slots``); a padding slot has value 0 and a column inside its
   vreg's scan range, so it never widens the scan.

On TPU, scalar operators of <= 32-bit values take :func:`well_spmv`, a
Pallas kernel that gathers inside VMEM with the 2-D lane gather Mosaic
lowers (``take_along_axis`` within one (8, 128) vreg, ``tpu.
dynamic_gather``): for each entry vreg it scans the x rows its columns
fall in, ``[lo, hi)`` from the packer, broadcasting one 128-wide x row to
the vreg, gathering on ``col & 127`` and keeping the lanes whose
``col >> 7`` is that row. A 1-D gather from a VMEM window does not lower
(tests/test_chip_compile.py). Block values, 64-bit operators, stacked
traces and non-TPU backends keep one XLA gather over absolute columns.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.tree_util import register_pytree_node_class

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops.pallas_spmv import (pallas_enabled,
                                       pallas_locally_disabled,
                                       pallas_mode, probe_report)
from amgcl_tpu.telemetry.compile_watch import watched_jit as _watched_jit
from amgcl_tpu.telemetry.structure import vreg_scan, vreg_slots

_TILE = 1024          # rows per tile; multiple of the 1024 DMA alignment
_WIN_ALIGN = 1024     # x-window sizes rounded up to the DMA tiling
_LANES = 128          # rows per entry vreg (lanes)
_SLOTS = 8            # ELL slots per entry vreg (sublanes)
#: aligned blocks of 8 x rows a scan loop step reads (v5e, FE level 0:
#: 1.76 ms an SpMV at 1, 0.99 ms at 4, 0.93 ms at 8)
_UNROLL = 8
#: the kernel's VMEM estimate cap (pipelined entry blocks + x window),
#: under Mosaic's 16 MB scoped default
_KERNEL_VMEM_BYTES = 12 << 20
_WELL_OK: dict = {}
#: y[q, l] = x[q, idx[q, l]]: the lane gather within one (8, 128) vreg
#: that Mosaic lowers (``tpu.dynamic_gather``), as jnp.take_along_axis
#: builds it
_LANE_GATHER = lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


@register_pytree_node_class
class WindowedEllMatrix:
    """ELL storage binned into row tiles with per-tile x-windows, as
    entry vregs.

    ``cols_local[v, s, l]`` is the column of slot ``(v % kv) * 8 + s`` of
    row ``(v // kv) * 128 + l``, relative to ``window_starts`` of the
    row's tile; ``vals`` has the same shape. ``scan[2 v : 2 v + 2]`` is
    vreg v's ``[lo, hi)`` in window-local x rows. The window width
    ``win`` is the static max over tiles (rounded up).

    Block values (BCSR convention, ops/csr.py): vals gains trailing
    (br, bc) dims, cols/windows index BLOCK columns, shape is in block
    units and x is logically (ncols, bc) flattened — the same windowed
    access pattern with a per-node matvec in the reduction (the
    reference's BCSR micro-kernels, amgcl/value_type/static_matrix.hpp:
    43-342, recast as batched einsums).
    """

    def __init__(self, window_starts, cols_local, vals, scan, shape, win,
                 block=(1, 1), tile=_TILE):
        self.window_starts = window_starts    # (n_tiles,) int32
        self.cols_local = cols_local          # (n_vregs, 8, 128) int32
        self.vals = vals                      # (n_vregs, 8, 128[, br, bc])
        self.scan = scan                      # (2 n_vregs,) int32
        self.shape = (int(shape[0]), int(shape[1]))
        self.win = int(win)
        self.block = (int(block[0]), int(block[1]))
        self.tile = int(tile)

    @property
    def dtype(self):
        return self.vals.dtype

    @property
    def kv(self):
        """Entry vregs per 128-row group (ELL slots / 8)."""
        return self.cols_local.shape[0] // (
            self.window_starts.shape[0] * (self.tile // _LANES))

    def tree_flatten(self):
        return ((self.window_starts, self.cols_local, self.vals,
                 self.scan),
                (self.shape, self.win, self.block, self.tile))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def kernel_status(self, x_dtype=None):
        """("pallas", None) when ``mv`` of an x of ``x_dtype`` (default:
        the values' dtype) takes :func:`well_spmv`, else ("xla", reason)."""
        x_dtype = self.dtype if x_dtype is None else x_dtype
        if self.block != (1, 1):
            return "xla", "block values"
        ip = pallas_mode(self.dtype, x_dtype)
        if ip is None:
            if not pallas_enabled() or pallas_locally_disabled():
                return "xla", "Pallas disabled"
            if max(jnp.dtype(self.dtype).itemsize,
                   jnp.dtype(x_dtype).itemsize) > 4:
                return "xla", "64-bit values"
            return "xla", "not on TPU"
        if _vmem_bytes(self.cols_local.shape[0]
                       // self.window_starts.shape[0],
                       self.win) > _KERNEL_VMEM_BYTES:
            return "xla", "vmem"
        if ip is False and not kernel_supported(self, x_dtype):
            return "xla", "probe declined"
        return "pallas", None

    def mv(self, x):
        if x.ndim == 1 and self.kernel_status(x.dtype)[0] == "pallas":
            return well_spmv(self.scan, self.window_starts, self.cols_local,
                             self.vals, x, n_out=self.shape[0],
                             win=self.win, kv=self.kv,
                             interpret=pallas_mode(self.dtype, x.dtype))
        return self._mv_xla(x)

    def _mv_xla(self, x):
        # one XLA gather over absolute columns
        n_tiles = self.window_starts.shape[0]
        groups, kv = self.tile // _LANES, self.kv
        cols = (self.cols_local.reshape(n_tiles, -1)
                + self.window_starts[:, None]).reshape(-1)
        lead = (n_tiles, groups, kv, _SLOTS, _LANES)
        out_dtype = jnp.result_type(self.dtype, x.dtype)
        br, bc = self.block
        if (br, bc) != (1, 1):
            xg = jnp.take(x.reshape(self.shape[1], bc), cols, axis=0) \
                .reshape(*lead, bc)
            y = jnp.einsum("tgkslij,tgkslj->tgli",
                           self.vals.reshape(*lead, br, bc),
                           xg.astype(self.vals.dtype),
                           preferred_element_type=out_dtype)
            return y.reshape(-1)[: self.shape[0] * br].astype(out_dtype)
        xg = jnp.take(x, cols, axis=0).reshape(lead)
        y = jnp.einsum("tgksl,tgksl->tgl", self.vals.reshape(lead),
                       xg.astype(self.vals.dtype),
                       preferred_element_type=out_dtype)
        return y.reshape(-1)[: self.shape[0]].astype(out_dtype)

    def bytes(self):
        return (self.cols_local.size * self.cols_local.dtype.itemsize
                + self.vals.size * self.vals.dtype.itemsize
                + self.window_starts.size * 4 + self.scan.size * 4)


def _win_rows(win: int) -> int:
    """x rows (of 128) in a tile's VMEM window: the window, and spare
    rows for the last loop step's blocks past it."""
    return win // _LANES + _SLOTS * (_UNROLL - 1)


def _vmem_bytes(vregs: int, win: int) -> int:
    """VMEM the kernel takes: double-buffered cols and vals blocks, the
    x window and the output block."""
    return (4 * vregs + 2) * _SLOTS * _LANES * 4 \
        + _win_rows(win) * _LANES * 4


def kernel_supported(W: WindowedEllMatrix, x_dtype) -> bool:
    """Probe-compile :func:`well_spmv` once per geometry on this backend
    (dispatch cannot try/except inside an outer jit). Shapes only:
    nothing is allocated."""
    key = (W.cols_local.shape, W.window_starts.shape, W.shape, W.win,
           jnp.dtype(W.dtype).name, jnp.dtype(x_dtype).name)
    if key not in _WELL_OK:
        S = jax.ShapeDtypeStruct
        try:
            well_spmv.lower(S(W.scan.shape, jnp.int32),
                            S(W.window_starts.shape, jnp.int32),
                            S(W.cols_local.shape, jnp.int32),
                            S(W.vals.shape, W.dtype),
                            S((W.shape[1],), x_dtype), n_out=W.shape[0],
                            win=W.win, kv=W.kv, interpret=False).compile()
            _WELL_OK[key] = True
        except Exception as e:       # noqa: BLE001 — Mosaic refusal
            probe_report("well_spmv[%r]" % (key,), e)
            _WELL_OK[key] = False
    return _WELL_OK[key]


@functools.partial(_watched_jit, name="ops.well_spmv",
                   static_argnames=("n_out", "win", "kv", "interpret"))
def well_spmv(scan, window_starts, cols, vals, x, n_out, win, kv,
              interpret: bool = False):
    """y = A x for scalar windowed ELL (layout: :class:`WindowedEllMatrix`)
    with in-register lane gathers. One grid step per row tile, which
    DMAs the tile's x window from HBM at its 1024-aligned start. For each
    entry vreg, a loop over the aligned 8-row blocks of x that hold its
    ``[lo, hi)`` broadcasts each row to (8, 128), gathers it on
    ``col & 127`` and keeps the lanes whose ``col >> 7`` is that row; the
    product with the values is summed over the 8 slots into the row
    group's 128 outputs. x is gathered in float32, whatever its dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_tiles = window_starts.shape[0]
    vregs = cols.shape[0] // n_tiles
    groups = vregs // kv
    m = x.shape[0]
    win_rows = _win_rows(win)
    # the last tile's window, spare rows included, ends inside x
    x_rows = -(-m // _WIN_ALIGN) * (_WIN_ALIGN // _LANES) + win_rows
    x2 = jnp.pad(x.astype(jnp.float32),
                 (0, x_rows * _LANES - m)).reshape(x_rows, _LANES)
    _0 = np.int32(0)

    def kernel(*refs):
        # the body traces in 32 bits: under x64 a loop index is int64,
        # and Mosaic does not lower the conversions
        with jax.enable_x64(False):
            body(*refs)

    def body(scan_ref, starts_ref, x_hbm, c_ref, v_ref, o_ref, xw, sem):
        t = pl.program_id(0)
        row0 = pl.multiple_of(starts_ref[t] // _LANES, _WIN_ALIGN // _LANES)
        cp = pltpu.make_async_copy(x_hbm.at[pl.ds(row0, win_rows)], xw,
                                   sem)
        cp.start()
        cp.wait()

        def one_group(g, out):
            def one_vreg(k, acc):
                v = g * kv + k
                i = 2 * (t * vregs + v)
                col = c_ref[v]
                lane = (col & (_LANES - 1))[..., None]
                xrow = col >> 7
                # the aligned blocks of 8 x rows that hold [lo, hi)
                b0 = scan_ref[i] // _SLOTS
                n = (-(-scan_ref[i + 1] // _SLOTS) - b0 + _UNROLL - 1) \
                    // _UNROLL

                def step(j, xg):
                    # lax primitives, not their jnp wrappers: this body
                    # holds 64 gathers, and each wrapper is a nested jit
                    # to trace
                    for u in range(_UNROLL):
                        r = (b0 + j * _UNROLL + u) * _SLOTS
                        blk = xw[pl.ds(pl.multiple_of(r, _SLOTS), _SLOTS), :]
                        d = xrow - r
                        for s in range(_SLOTS):
                            row = lax.broadcast_in_dim(
                                blk[s:s + 1, :], (_SLOTS, _LANES), (0, 1))
                            got = lax.gather(
                                row, lane, _LANE_GATHER, (1, 1),
                                mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
                            xg = lax.select(d == s, got, xg)
                    return xg

                xg = lax.fori_loop(0, n, step,
                                   jnp.zeros((_SLOTS, _LANES), jnp.float32))
                return acc + jnp.sum(xg * v_ref[v].astype(jnp.float32),
                                     axis=0, keepdims=True)

            acc = lax.fori_loop(0, kv, one_vreg,
                                jnp.zeros((1, _LANES), jnp.float32))
            # row g of the tile's (groups, 128) outputs, kept in registers
            return jnp.where(lax.broadcasted_iota(jnp.int32, out.shape, 0)
                             == g, acc, out)

        o_ref[...] = lax.fori_loop(0, groups, one_group,
                                   jnp.zeros((groups, _LANES), jnp.float32))

    entry = pl.BlockSpec((vregs, _SLOTS, _LANES),
                         lambda t, scan, starts: (t, _0, _0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), entry, entry],
            out_specs=pl.BlockSpec((groups, _LANES),
                                   lambda t, scan, starts: (t, _0)),
            scratch_shapes=[pltpu.VMEM((win_rows, _LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((n_tiles * groups, _LANES),
                                       jnp.float32),
        interpret=interpret,
    )(scan, window_starts, x2, cols, vals)
    return out.reshape(-1)[:n_out].astype(
        jnp.result_type(vals.dtype, x.dtype))


def tile_windows(A: CSR, tile: int):
    """Per-row-tile aligned column windows, shared by the windowed-ELL
    and dense-window builders (one copy of the DMA-shape rules):
    returns (n_tiles, rows, tiles, starts, win) with ``starts`` floored
    to _WIN_ALIGN — Mosaic cannot prove a runtime window start aligned,
    and an unaligned 1-D DMA start is a legalization failure on real
    hardware (r5 chip session) — and ``win`` the _WIN_ALIGN-rounded max
    span. Empty tiles point past the matrix and read zero padding."""
    n, m = A.shape
    n_tiles = -(-n // tile)
    rows = A.expanded_rows()
    tiles = rows // tile
    starts = np.full(n_tiles, m, dtype=np.int64)
    ends = np.zeros(n_tiles, dtype=np.int64)
    if A.nnz:
        np.minimum.at(starts, tiles, A.col)
        np.maximum.at(ends, tiles, A.col + 1)
    empty = ends <= starts          # tiles with no entries read padding
    starts[empty] = m
    ends[empty] = m + 1
    starts = (starts // _WIN_ALIGN) * _WIN_ALIGN
    span = ends - starts
    win = int(span.max()) if n_tiles else 1
    win = -(-win // _WIN_ALIGN) * _WIN_ALIGN
    return n_tiles, rows, tiles, starts, win


def csr_to_windowed_ell(A: CSR, dtype=jnp.float32, tile: int = _TILE,
                        max_win_bytes: int = 8 << 20, why=None):
    """Pack a host CSR (scalar or block-valued BCSR) into windowed ELL.
    Assumes the caller already applied a bandwidth-reducing permutation
    (RCM) if profitable; windows are computed from the matrix as given.
    Returns None when any row tile's column span exceeds the VMEM budget
    (no banded locality). Block matrices index BLOCK columns; the window
    DMA budget scales by the block column width. ``tile`` is a multiple
    of 1024 rows.

    ``why`` (optional dict) receives the decline reason on a None
    return — the format-decision ledger (telemetry/structure.py)
    records it so the X-ray table can say WHY a candidate lost."""
    br, bc = A.block_size
    n_tiles, rows, tiles, starts, win = tile_windows(A, tile)
    # VMEM budget: window + one cols/vals/out tile must fit comfortably
    if win * bc * np.dtype(np.float32).itemsize > max_win_bytes:
        if why is not None:
            why["why"] = "window %d col x 4 B > %d B VMEM budget" \
                % (win * bc, max_win_bytes)
        return None
    kv, flat = vreg_slots(A)
    n_vregs = n_tiles * (tile // _LANES) * kv
    lo, hi = vreg_scan(A, flat, n_vregs, starts, tile)
    # padding slots read column lo * 128 of their vreg's window: inside
    # the scan range, value 0
    cols = np.repeat((lo * _LANES).astype(np.int32), _SLOTS * _LANES)
    cols[flat] = A.col - starts[tiles]
    vals = _pack_values(A, n_vregs, flat, dtype)
    W = WindowedEllMatrix(
        jnp.asarray(starts.astype(np.int32)),
        jnp.asarray(cols.reshape(n_vregs, _SLOTS, _LANES)),
        vals, jnp.asarray(np.stack([lo, hi], axis=1).reshape(-1)),
        A.shape, win, (br, bc), tile)
    steps = int((hi - lo).sum())
    W.scan_stats = {"entry_vregs": int(n_vregs),
                    "scan_xrows_mean": round(steps / max(n_vregs, 1), 4)}
    return W


def _pack_values(A: CSR, n_vregs: int, flat, dtype):
    """A's values in the entry-vreg layout (``flat`` from
    ``vreg_slots``), zero in the padding slots."""
    vdt = np.dtype(dtype) if np.dtype(dtype).kind != "c" else A.val.dtype
    shape = (n_vregs, _SLOTS, _LANES) + (A.block_size if A.is_block
                                          else ())
    vals = np.zeros((n_vregs * _SLOTS * _LANES,) + shape[3:], dtype=vdt)
    vals[flat] = A.val
    return jnp.asarray(vals.reshape(shape), dtype=dtype)


def refresh_values(W: WindowedEllMatrix, A: CSR):
    """W with A's values, when A has W's pattern layout (the numeric
    rebuild, ``ops/device.refresh_values``); None when it does not."""
    kv, flat = vreg_slots(A)
    n_vregs = W.cols_local.shape[0]
    if kv != W.kv or (A.nnz and flat.max() >= n_vregs * _SLOTS * _LANES):
        return None
    return WindowedEllMatrix(
        W.window_starts, W.cols_local,
        _pack_values(A, n_vregs, flat, W.vals.dtype), W.scan, A.shape,
        W.win, W.block, W.tile)


def fe_like_problem(n: int = 85623, nnz_target: int = 2_370_000,
                    seed: int = 0):
    """Synthetic unstructured FE-style SPD system matching poisson3Db's
    profile (85,623 unknowns, ~2.37M nnz — BASELINE config 2; the real
    MatrixMarket file is not redistributable in this image). Random points
    in a unit cube, k-nearest-neighbor graph, symmetrized graph Laplacian
    plus a small mass term: same irregular sparsity class as a tetrahedral
    FE discretization.

    Edge weights scale like a FE stiffness entry, 1/h² with h the node
    distance — the resulting per-row weight SPREAD (nearest neighbors a
    few times heavier than the k-th) is what makes the matrix
    representative for strength-of-connection coarsening: with the
    near-uniform weights of the first version every |a_ij| sat at ~1/k of
    the diagonal, below any sensible eps_strong, ALL rows were isolated,
    and SA (here and in the reference, amg.hpp empty-level error) cannot
    coarsen at all — a degenerate fixture, not a hard one."""
    rng = np.random.RandomState(seed)
    pts = rng.rand(n, 3)
    k = max(int(round(nnz_target / n)) - 1, 4)
    from scipy.spatial import cKDTree
    tree = cKDTree(pts)
    dist, idx = tree.query(pts, k=k + 1)
    rows = np.repeat(np.arange(n), k)
    cols = idx[:, 1:].reshape(-1)
    d = dist[:, 1:].reshape(-1)
    # floor the distance at a fraction of the median: random points have
    # near-coincident pairs that a quality mesh never does, and the
    # unbounded 1/h² weights they produce (4+ orders of magnitude) are
    # about f32 conditioning, not coarsening structure
    d = np.maximum(d, 0.2 * np.median(d))
    d2 = d * d
    w = (1.0 / d2) * (0.9 + 0.2 * rng.rand(len(rows)))
    w *= np.mean(d2)            # O(1) scale, conditioning unaffected
    import scipy.sparse as sp
    G = sp.coo_matrix((w, (rows, cols)), shape=(n, n))
    G = (G + G.T) * 0.5
    L = sp.diags(np.asarray(G.sum(axis=1)).ravel() + 0.01) - G
    Lc = L.tocsr()
    Lc.sort_indices()
    A = CSR.from_scipy(Lc)
    rhs = np.ones(n)
    return A, rhs
