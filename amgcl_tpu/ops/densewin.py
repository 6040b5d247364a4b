"""Dense-window format: gather-free unstructured SpMV for the TPU.

Windowed ELL (ops/unstructured.py) gathers x inside VMEM with a Pallas
kernel that scans, for each (8, 128) entry vreg, the x rows its columns
fall in, one 2-D lane gather per row (a 1-D gather from the window does
not lower on v5e); its time grows with the rows scanned. Its XLA form,
one ``jnp.take``, runs at gather speed (~27 ms per 2.4M-nnz SpMV on a
v5e, ~1/800 of HBM bandwidth).

This format removes the gather entirely: after an RCM reorder each
64-row tile's nonzeros live in a narrow contiguous column window, so
the tile's window slice is stored as a DENSE (tile, win) block and the
SpMV becomes

    y[tile] = B[tile] @ x[start[tile] : start[tile] + win]

— one aligned window DMA plus an elementwise-multiply/lane-reduce, all
ops the DIA kernels already prove on hardware. The trade is HBM
capacity for bandwidth-bound streaming: storage is n·win·itemsize
(~2-4 GB for the 85k-row FE fixture at f32 — the matrix's nnz are
~10 MB), but the SpMV streams it at full HBM rate instead of waiting
on a serialized gather.

Storage-class precedent in the reference: backends choose their own
layout per matrix (amgcl/backend/interface.hpp copy_matrix); the dense
window is simply the layout a systolic/vector machine wants for banded
unstructured rows.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from amgcl_tpu.telemetry.compile_watch import watched_jit as _watched_jit
from jax import lax
from jax.tree_util import register_pytree_node_class

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops.pallas_spmv import pallas_mode, probe_report

_TILE = 64                 # rows per dense block
# window starts/extent alignment — the SAME constant tile_windows()
# floors with (a local copy could drift and make pl.multiple_of assert
# an alignment the builder no longer guarantees)
from amgcl_tpu.ops.unstructured import _WIN_ALIGN  # noqa: E402
_DWIN_OK: dict = {}


def row_spec(pl, tile):
    """Block spec of a per-tile row stream held as an (n_tiles, 1, tile)
    array. The TPU lowering needs a block's last two dims (8, 128)-aligned
    or equal to the array's; a (1, tile) block of an (n_tiles, tile) array
    is neither, this one is. The squeezed leading dim leaves a (1, tile)
    ref in the kernel."""
    _0 = np.int32(0)
    return pl.BlockSpec((None, 1, tile), lambda t, starts: (t, _0, _0))


def max_total_bytes() -> int:
    """Dense-window storage budget (AMGCL_TPU_DWIN_MAX_BYTES, default
    6 GB — the 85k-row FE fine level at f32 is 3.9 GB on 16 GB HBM).
    Hierarchy builds thread a shared :class:`telemetry.ledger
    .DeviceMemoryBudget` seeded from this value through every conversion
    (models/amg.py), so the cap bounds the SUM across the hierarchy; a
    standalone ``csr_to_dense_window`` call without a budget still
    applies it per matrix."""
    try:
        return int(os.environ.get("AMGCL_TPU_DWIN_MAX_BYTES",
                                  str(6 << 30)))
    except ValueError:
        return 6 << 30


@register_pytree_node_class
class DenseWindowMatrix:
    """blocks: (n_tiles, tile, win) dense window slices; window_starts:
    (n_tiles,) int32, multiples of 1024. shape is the logical (n, m)."""

    def __init__(self, window_starts, blocks, shape, win):
        self.window_starts = window_starts
        self.blocks = blocks
        self.shape = (int(shape[0]), int(shape[1]))
        self.win = int(win)

    @property
    def dtype(self):
        return self.blocks.dtype

    @property
    def block(self):
        return (1, 1)

    def tree_flatten(self):
        return (self.window_starts, self.blocks), (self.shape, self.win)

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, win = aux
        return cls(children[0], children[1], shape, win)

    def bytes(self):
        return (self.blocks.size * self.blocks.dtype.itemsize
                + self.window_starts.size * 4)

    def _pallas_mode(self, *vecs, kernel: str = "spmv"):
        """False on real TPU after a support probe, True under the CI
        interpret hook, None -> XLA fallback (the DiaMatrix seam).
        ``kernel`` ('spmv' / 'fused') is probed separately — the fused
        variant adds vector streams that can fail to legalize where the
        plain SpMV compiles, and inside an outer jit that failure would
        be unrecoverable."""
        ip = pallas_mode(self.dtype, *(v.dtype for v in vecs))
        if ip is False and not kernel_supported(
                self.blocks.shape[2], self.blocks.shape[1], self.dtype,
                kernel):
            return None
        return ip

    def mv(self, x):
        ip = self._pallas_mode(x)
        if ip is not None:
            return dense_window_spmv(self.window_starts, self.blocks, x,
                                     self.win, self.shape[0], interpret=ip)
        return self._mv_xla(x)

    def _mv_xla(self, x):
        # testing / fallback path: per-tile dynamic-slice windows (lowers
        # to a gather of window slices — fine on CPU, slow on TPU; the
        # Pallas kernel is the production path there). The product runs
        # at the DECLARED result_type(blocks, x) — a wider x (f64 rhs
        # against f32 blocks) must not be silently demoted to the block
        # dtype before the multiply.
        n_tiles, tile, win = self.blocks.shape
        out_dtype = jnp.result_type(self.dtype, x.dtype)
        xp = jnp.pad(x, (0, win))

        def one(start, blk):
            xw = lax.dynamic_slice(xp, (start,), (win,))
            return jnp.sum(blk.astype(out_dtype)
                           * xw[None, :].astype(out_dtype), axis=1)

        y = jax.vmap(one)(self.window_starts.astype(jnp.int32),
                          self.blocks)
        return y.reshape(n_tiles * tile)[:self.shape[0]]


def kernel_supported(win: int, tile: int = _TILE, dtype=jnp.float32,
                     kernel: str = "spmv") -> bool:
    """Probe-compile ONE kernel variant once per geometry on this
    backend (dispatch cannot try/except inside an outer jit, and the
    fused variant's extra vector streams can fail where the plain SpMV
    compiles)."""
    key = (int(win), int(tile), jnp.dtype(dtype).name, kernel)
    if key not in _DWIN_OK:
        try:
            starts = jnp.zeros(1, jnp.int32)
            blocks = jnp.zeros((1, tile, win), dtype)
            x = jnp.zeros(win, dtype)
            if kernel == "spmv":
                jax.jit(functools.partial(
                    dense_window_spmv, win=win, n_out=tile,
                    interpret=False)).lower(starts, blocks, x).compile()
            else:
                v = jnp.zeros(tile, dtype)
                jax.jit(functools.partial(
                    dense_window_fused, mode="correction", win=win,
                    n_out=tile, interpret=False)).lower(
                        starts, blocks, v, v, v).compile()
            _DWIN_OK[key] = True
        except Exception as e:
            probe_report("dense_window[%r]" % (key,), e)
            _DWIN_OK[key] = False
    return _DWIN_OK[key]


def _dwin_geometry(x, win, n_tiles, tile, n_vecs):
    """Padded x + grid spec: B blocks auto-pipelined per tile, x window
    DMA'd from HBM by the kernel (start indices scalar-prefetched)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    xp = jnp.pad(x, (0, win))
    _0 = np.int32(0)
    vec_spec = row_spec(pl, tile)          # (n_tiles, 1, tile) streams
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # x in HBM
            pl.BlockSpec((1, tile, win),
                         lambda t, starts: (t, _0, _0)),  # dense block
        ] + [vec_spec] * n_vecs,
        out_specs=vec_spec,
        scratch_shapes=[
            # plain 1-D scratch + bare semaphore — the dia_spmv-proven
            # serial shape; a (1, win) row view as the DMA destination
            # produced a Mosaic memref_slice error on v5e
            pltpu.VMEM((win,), x.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return xp, grid_spec


def _dwin_dma(pl, pltpu, starts_smem, x_hbm, xw, sem):
    # starts are 1024-aligned by construction (the builder floors them),
    # but Mosaic cannot prove alignment of a runtime SMEM value —
    # pl.multiple_of carries the invariant to the compiler (the DIA
    # kernels never hit this because their starts are i*tile constants)
    t = pl.program_id(0)
    start = pl.multiple_of(starts_smem[t], _WIN_ALIGN)
    cp = pltpu.make_async_copy(
        x_hbm.at[pl.ds(start, xw.shape[0])], xw, sem)
    cp.start()
    cp.wait()
    return xw


@functools.partial(_watched_jit, name="ops.dense_window_spmv",
                   static_argnames=("win", "n_out", "interpret"))
def dense_window_spmv(window_starts, blocks, x, win, n_out,
                      interpret: bool = False):
    """y = A x: window DMA + (tile, win) multiply / lane reduce."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_tiles, tile, _ = blocks.shape
    out_dtype = jnp.result_type(blocks.dtype, x.dtype)
    xp, grid_spec = _dwin_geometry(x, win, n_tiles, tile, 0)

    def kernel(starts_smem, x_hbm, b_ref, o_ref, xw, sem):
        row = _dwin_dma(pl, pltpu, starts_smem, x_hbm, xw, sem)
        # promote BOTH operands to the declared result dtype — computing
        # at the block dtype would silently round a wider x down (and a
        # bf16-block * f32-x product to bf16)
        prod = b_ref[0].astype(out_dtype) \
            * row[:][None, :].astype(out_dtype)
        o_ref[0] = jnp.sum(prod, axis=1)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tile), out_dtype),
        interpret=interpret,
    )(window_starts, xp, blocks)
    return out.reshape(n_tiles * tile)[:n_out]


@functools.partial(_watched_jit, name="ops.dense_window_fused",
                   static_argnames=("mode", "win", "n_out", "interpret"))
def dense_window_fused(window_starts, blocks, f, x, w, mode, win, n_out,
                       interpret: bool = False):
    """residual: f − A x; correction: x + w ∘ (f − A x) — one pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_tiles, tile, _ = blocks.shape
    out_dtype = jnp.result_type(blocks.dtype, x.dtype, f.dtype)
    n_pad = n_tiles * tile
    vecs = [jnp.pad(f, (0, n_pad - f.shape[0])).reshape(n_tiles, 1, tile)]
    if mode == "correction":
        out_dtype = jnp.result_type(out_dtype, w.dtype)
        vecs.append(jnp.pad(w, (0, n_pad - w.shape[0]))
                    .reshape(n_tiles, 1, tile))
        vecs.append(jnp.pad(x, (0, n_pad - x.shape[0]))
                    .reshape(n_tiles, 1, tile))
    xp, grid_spec = _dwin_geometry(x, win, n_tiles, tile, len(vecs))

    def kernel(starts_smem, x_hbm, b_ref, f_ref, *rest):
        (*wx_refs, o_ref, xw, sem) = rest
        row = _dwin_dma(pl, pltpu, starts_smem, x_hbm, xw, sem)
        # same promotion rule as dense_window_spmv: the A x product runs
        # at the declared result dtype, never at the (possibly narrower)
        # block dtype
        prod = b_ref[0].astype(out_dtype) \
            * row[:][None, :].astype(out_dtype)
        r = f_ref[0].astype(out_dtype) - jnp.sum(prod, axis=1)
        if mode == "residual":
            o_ref[0] = r
        else:
            o_ref[0] = wx_refs[1][0].astype(out_dtype) \
                + wx_refs[0][0].astype(out_dtype) * r

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tile), out_dtype),
        interpret=interpret,
    )(window_starts, xp, blocks, *vecs)
    return out.reshape(n_pad)[:n_out]


def dense_window_residual(window_starts, blocks, f, x, win, n_out,
                          interpret: bool = False):
    return dense_window_fused(window_starts, blocks, f, x, None,
                              "residual", win, n_out, interpret)


def dense_window_scaled_correction(window_starts, blocks, w, f, x, win,
                                   n_out, interpret: bool = False):
    return dense_window_fused(window_starts, blocks, f, x, w,
                              "correction", win, n_out, interpret)


def csr_to_dense_window(A: CSR, dtype=jnp.float32, tile: int = _TILE,
                        max_bytes: int | None = None,
                        require_kernel: bool = False,
                        budget=None, why=None):
    """Build the dense-window form of a scalar CSR, or None when any row
    tile's column span exceeds the storage budget (no banded locality —
    apply RCM first). The dense blocks are materialized ON DEVICE from
    the compact (cols, vals) arrays via K one-hot accumulation passes —
    a host-side dense build would ship n·win floats through the
    interconnect; this ships ~nnz and streams the output once.

    ``budget`` (telemetry.ledger.DeviceMemoryBudget) is the shared
    hierarchy-wide HBM pool: when given, the build declines once the
    block storage would overdraw what earlier conversions left, and
    charges the pool on success — so ``to_device('auto')`` across a whole
    hierarchy can never materialize more dense-window bytes than ONE
    budget, instead of one budget per matrix.

    ``why`` (optional dict) receives the decline reason on a None
    return; a budget-STARVED decline (the bytes fit the pool's total
    but not what earlier levels left) reports exactly ``"budget"``, a
    structurally-too-wide window reports ``"window"`` — the
    distinction the format-decision ledger (telemetry/structure.py)
    threads into the X-ray table."""
    def _decline(reason):
        if why is not None:
            why["why"] = reason
        return None

    if A.is_block or np.dtype(dtype).kind == "c":
        return _decline("block values" if A.is_block
                        else "complex dtype")
    n, m = A.shape
    if n == 0 or A.nnz == 0:
        return _decline("empty")
    from amgcl_tpu.ops.unstructured import tile_windows
    n_tiles, rows, tiles, starts, win = tile_windows(A, tile)
    itemsize = jnp.dtype(dtype).itemsize
    need = n_tiles * tile * win * itemsize
    if why is not None:
        why["need_bytes"] = int(need)
    if budget is not None:
        cap = budget.remaining() if max_bytes is None \
            else min(budget.remaining(), max_bytes)
    else:
        cap = max_total_bytes() if max_bytes is None else max_bytes
    if need > cap:
        # "budget": earlier conversions drained the shared pool this
        # matrix would otherwise fit — distinguishable from "window"
        # (too wide for the pool even when untouched)
        hard = max_total_bytes() if max_bytes is None else max_bytes
        if budget is not None:
            hard = budget.total if max_bytes is None \
                else min(budget.total, max_bytes)
        return _decline("budget" if need <= hard else "window")
    # VMEM: the pipeline double-buffers the (tile, win) block + window
    if (2 * tile + 4) * win * itemsize > 10 << 20:
        return _decline("vmem")
    if require_kernel and not kernel_supported(win, tile, dtype):
        # probe BEFORE materializing the (possibly multi-GB) blocks
        return _decline("kernel")

    nnz_row = A.row_nnz()
    K = max(1, int(nnz_row.max()))
    flat = rows * K + (np.arange(A.nnz) - A.ptr[rows])
    cols = np.zeros(n_tiles * tile * K, dtype=np.int32)
    vals = np.zeros(n_tiles * tile * K, dtype=np.float64)
    cols[flat] = A.col - starts[tiles]
    vals[flat] = A.val
    cols3 = jnp.asarray(cols.reshape(n_tiles, tile, K))
    vals3 = jnp.asarray(vals.reshape(n_tiles, tile, K), dtype=dtype)

    def build(c3, v3):
        # one jitted program (single dispatch — an eager loop would pay
        # a dispatch per slot); padding slots carry val 0 so they
        # contribute nothing wherever their col points
        iota = lax.broadcasted_iota(jnp.int32, (win,), 0)
        B = jnp.zeros((n_tiles, tile, win), dtype)
        for k in range(K):
            B = B + jnp.where(c3[:, :, k, None] == iota[None, None, :],
                              v3[:, :, k, None], 0).astype(dtype)
        return B

    B = jax.jit(build)(cols3, vals3)
    if budget is not None:
        # commit only for a build that actually materialized; the charge
        # cannot fail — `need` was checked against remaining() above and
        # nothing else draws from the pool between (single-threaded setup)
        budget.try_charge(need, tag="dwin n=%d win=%d" % (n, win))
    return DenseWindowMatrix(jnp.asarray(starts.astype(np.int32)), B,
                             A.shape, win)
