"""Unstructured-matrix device SpMV: windowed ELL.

This is the TPU answer to the reference's general-sparsity GPU story
(cuSPARSE CSR SpMV, amgcl/backend/cuda.hpp:60-843; generated block kernels,
amgcl/backend/vexcl_static_matrix.hpp:228-1031). A TPU has no hardware
scatter/gather against HBM — XLA lowers an arbitrary ``jnp.take`` to a
serialized gather measured at ~130M elem/s (ops/structured.py), which makes
a 2.4M-nnz FE matrix cost ~18 ms per SpMV. The format restructures the
access pattern instead of translating CSR:

1. **Host-side row binning (RCM)**: reverse Cuthill-McKee confines each row
   tile's column support to a narrow window (``utils/adapters.cuthill_mckee``
   — the adapter the reference also applies for cache locality,
   amgcl/adapter/reorder.hpp). The reorder is absorbed into the hierarchy:
   P/R transfers see the permuted operator, so the solve phase never pays it.

2. **Windowed ELL**: per row-tile, columns are stored *relative to the
   tile's window start*. The device array is (n_tiles, tile, K) — static
   shapes, padded with window-local zeros.

The SpMV is one XLA gather over absolute columns. A Pallas kernel that
DMAs each tile's window into VMEM and gathers there does not lower on a
v5e (Mosaic lowers only a 2-D gather within one vreg tile), so there is
none; the dense-window format (ops/densewin.py) is the gather-free kernel
path for unstructured operators.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax.tree_util import register_pytree_node_class

from amgcl_tpu.ops.csr import CSR

_TILE = 1024          # rows per tile; multiple of the 1024 DMA alignment
_WIN_ALIGN = 1024     # x-window sizes rounded up to the DMA tiling


@register_pytree_node_class
class WindowedEllMatrix:
    """ELL storage binned into row tiles with per-tile x-windows.

    cols_local[t, r, k] = column of entry k of row t*tile+r, relative to
    window_starts[t]; padding entries point at slot 0 with val 0. The
    window width ``win`` is the static max over tiles (rounded up).

    Block values (BCSR convention, ops/csr.py): vals gains trailing
    (br, bc) dims, cols/windows index BLOCK columns, shape is in block
    units and x is logically (ncols, bc) flattened — the same windowed
    access pattern with a per-node matvec in the reduction (the
    reference's BCSR micro-kernels, amgcl/value_type/static_matrix.hpp:
    43-342, recast as batched einsums).
    """

    def __init__(self, window_starts, cols_local, vals, shape, win,
                 block=(1, 1)):
        self.window_starts = window_starts    # (n_tiles,) int32
        self.cols_local = cols_local          # (n_tiles, tile, K) int32
        self.vals = vals                      # (n_tiles, tile, K[, br, bc])
        self.shape = (int(shape[0]), int(shape[1]))
        self.win = int(win)
        self.block = (int(block[0]), int(block[1]))

    @property
    def dtype(self):
        return self.vals.dtype

    @property
    def tile(self):
        return self.cols_local.shape[1]

    def tree_flatten(self):
        return ((self.window_starts, self.cols_local, self.vals),
                (self.shape, self.win, self.block))

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, win, block = aux
        return cls(children[0], children[1], children[2], shape, win, block)

    def mv(self, x):
        # global gather: reconstruct absolute columns; one take over x
        n_tiles, tile, K = self.cols_local.shape
        cols = self.cols_local + self.window_starts[:, None, None]
        out_dtype = jnp.result_type(self.dtype, x.dtype)
        br, bc = self.block
        if (br, bc) != (1, 1):
            xb = x.reshape(self.shape[1], bc)
            xg = jnp.take(xb, cols.reshape(-1), axis=0) \
                .reshape(n_tiles, tile, K, bc)
            y = jnp.einsum("trkij,trkj->tri", self.vals,
                           xg.astype(self.vals.dtype),
                           preferred_element_type=out_dtype)
            return y.reshape(n_tiles * tile * br)[
                : self.shape[0] * br].astype(out_dtype)
        xg = jnp.take(x, cols.reshape(-1), axis=0).reshape(n_tiles, tile, K)
        y = jnp.einsum("trk,trk->tr", self.vals,
                       xg.astype(self.vals.dtype),
                       preferred_element_type=out_dtype)
        return y.reshape(n_tiles * tile)[: self.shape[0]].astype(out_dtype)

    def bytes(self):
        return (self.cols_local.size * self.cols_local.dtype.itemsize
                + self.vals.size * self.vals.dtype.itemsize
                + self.window_starts.size * 4)


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
    DMA budget scales by the block column width.

    ``why`` (optional dict) receives the decline reason on a None
    return — the format-decision ledger (telemetry/structure.py)
    records it so the X-ray table can say WHY a candidate lost."""
    br, bc = A.block_size
    n, m = A.shape                  # block units for BCSR
    nnz_row = A.row_nnz()
    K = max(4, int(nnz_row.max()) if n else 1)
    K = -(-K // 4) * 4
    n_tiles, rows, tiles, starts, win = tile_windows(A, tile)
    # VMEM budget: window + one cols/vals/out tile must fit comfortably
    if win * bc * np.dtype(np.float32).itemsize > max_win_bytes:
        if why is not None:
            why["why"] = "window %d col x 4 B > %d B VMEM budget" \
                % (win * bc, max_win_bytes)
        return None
    starts32 = starts.astype(np.int32)

    flat = rows * K + (np.arange(A.nnz) - A.ptr[rows])
    cols = np.zeros(n_tiles * tile * K, dtype=np.int32)
    vdt = np.dtype(dtype) if np.dtype(dtype).kind != "c" else A.val.dtype
    # local columns relative to the window start of the entry's tile
    cols[flat] = A.col - starts[tiles]
    if A.is_block:
        vals = np.zeros((n_tiles * tile * K, br, bc), dtype=vdt)
        vals[flat] = A.val
        return WindowedEllMatrix(
            jnp.asarray(starts32),
            jnp.asarray(cols.reshape(n_tiles, tile, K)),
            jnp.asarray(vals.reshape(n_tiles, tile, K, br, bc),
                        dtype=dtype),
            A.shape, win, (br, bc))
    vals = np.zeros(n_tiles * tile * K, dtype=vdt)
    vals[flat] = A.val
    return WindowedEllMatrix(
        jnp.asarray(starts32),
        jnp.asarray(cols.reshape(n_tiles, tile, K)),
        jnp.asarray(vals.reshape(n_tiles, tile, K), dtype=dtype),
        A.shape, win)


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
