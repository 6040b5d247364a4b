"""Device algebra: TPU-resident sparse formats and the backend primitive set.

This is the TPU equivalent of the reference's backend contract — a matrix
type, a vector type (plain jnp arrays), and a small set of parallel
primitives that the entire solve phase is written against (reference:
amgcl/backend/interface.hpp:189-443, amgcl/backend/cuda.hpp:60-843 for the
accelerator-offload pattern).

Formats (chosen for TPU, not translated from CSR):

* :class:`DiaMatrix` — diagonal storage. SpMV is a static unrolled sum of
  shifted element-wise multiplies: zero gathers, pure VPU work, HBM-bound.
  Ideal for stencil-structured levels (the finest levels of most problems).
* :class:`EllMatrix` — padded-row (ELLPACK) storage, scalar or block values.
  SpMV is one gather of x plus a dense reduction over the padded row —
  the general-purpose format; rows are padded to a lane-friendly width.
* :class:`DenseMatrix` — small dense operator; SpMV is an MXU matmul. Used
  for coarse AMG levels where density makes gathers pointless.

All classes are registered JAX pytrees so they can be closed over or passed
through ``jit``/``shard_map`` boundaries; static metadata (shapes, offsets)
lives in the aux data so trace caching works.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.tree_util import register_pytree_node_class

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.telemetry.tracing import phase as _phase

# Pad ELL row widths up to a multiple of this (lane friendliness / fewer
# distinct compiled shapes across levels).
_ELL_PAD = 4


@register_pytree_node_class
class DiaMatrix:
    """Diagonal-format sparse matrix (possibly rectangular).

    data[k, i] holds A[i, i + offsets[k]]; offsets are static Python ints so
    the SpMV unrolls into a fixed sequence of shifted multiply-adds under jit.
    """

    def __init__(self, offsets, data, shape):
        self.offsets = tuple(int(o) for o in offsets)
        self.data = data                       # (ndiag, nrows)
        self.shape = (int(shape[0]), int(shape[1]))

    @property
    def dtype(self):
        return self.data.dtype

    def tree_flatten(self):
        return (self.data,), (self.offsets, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        offsets, shape = aux
        return cls(offsets, children[0], shape)

    def _pallas_mode(self, *vecs):
        """None = use the XLA path; else the ``interpret`` flag for the
        Pallas kernels (False on real TPU, True under the CI test hook).

        AMGCL_TPU_PALLAS_MIN_NDIAG=k routes levels with fewer than k
        diagonals to XLA: its DIA lowering fuses fine at few diagonals
        (fine Poisson levels, 7) and falls off the fusion path as the SA
        stencil grows (coarse levels, 100+) — the per-level A/B knob for
        the chip session, default 0 (Pallas everywhere it applies)."""
        from amgcl_tpu.ops.pallas_spmv import pallas_mode, min_ndiag
        if len(self.offsets) < min_ndiag():
            return None
        return pallas_mode(self.dtype, *(v.dtype for v in vecs))

    def mv(self, x):
        n, m = self.shape
        if x.ndim == 2:
            # stacked (m, B) operand (serve/batched.py): same shifted
            # multiply-add sequence, each diagonal broadcast across the B
            # columns — ONE read of the matrix data retires B right-hand
            # sides (the batched-bytes amortization the ledger models)
            lo = min(self.offsets + (0,))
            base = -lo if lo < 0 else 0
            hi = max(max(self.offsets + (0,)) + n - m, 0)
            xp = jnp.pad(x, ((base, hi), (0, 0)))
            y = jnp.zeros((n, x.shape[1]),
                          dtype=jnp.result_type(self.dtype, x.dtype))
            for k, d in enumerate(self.offsets):
                seg = lax.dynamic_slice(xp, (base + d, 0),
                                        (n, x.shape[1]))
                y = y + self.data[k][:, None] * seg
            return y
        from amgcl_tpu.ops.pallas_spmv import dia_spmv
        ip = self._pallas_mode(x)
        if ip is not None:
            return dia_spmv(self.offsets, self.data, x, interpret=ip)
        lo = min(self.offsets + (0,))
        # each diagonal d reads xp[base+d : base+d+n); pad the tail so the
        # slice stays in range even for tall (nrows > ncols) matrices —
        # lax.dynamic_slice would otherwise clamp and read garbage
        base = -lo if lo < 0 else 0
        hi = max(max(self.offsets + (0,)) + n - m, 0)
        xp = jnp.pad(x, (base, hi))
        y = jnp.zeros(n, dtype=jnp.result_type(self.dtype, x.dtype))
        for k, d in enumerate(self.offsets):
            seg = lax.dynamic_slice(xp, (base + d,), (n,))
            y = y + self.data[k] * seg
        return y

    def bytes(self):
        return self.data.size * self.data.dtype.itemsize


@register_pytree_node_class
class EllMatrix:
    """ELLPACK matrix: cols (n, K) int32, vals (n, K) or (n, K, br, bc).

    Padding entries have col == 0 and val == 0, so they contribute nothing.
    Block values follow the BCSR convention: x is logically (mcols, bc)."""

    def __init__(self, cols, vals, shape, block=(1, 1)):
        self.cols = cols
        self.vals = vals
        self.shape = (int(shape[0]), int(shape[1]))   # in block units
        self.block = (int(block[0]), int(block[1]))

    @property
    def dtype(self):
        return self.vals.dtype

    def tree_flatten(self):
        return (self.cols, self.vals), (self.shape, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, block = aux
        return cls(children[0], children[1], shape, block)

    def mv(self, x):
        br, bc = self.block
        if (br, bc) == (1, 1):
            if x.ndim == 2:
                # stacked (m, B): one gather of the column table serves
                # every right-hand side
                xg = jnp.take(x, self.cols, axis=0)      # (n, K, B)
                return jnp.einsum("nk,nkb->nb", self.vals, xg,
                                  preferred_element_type=jnp.result_type(
                                      self.dtype, x.dtype))
            xg = jnp.take(x, self.cols, axis=0)          # (n, K)
            return jnp.einsum("nk,nk->n", self.vals, xg,
                              preferred_element_type=jnp.result_type(
                                  self.dtype, x.dtype))
        if x.ndim == 2:
            # block values with stacked operands: per-column fallback —
            # the block gather/einsum is written against the logical
            # (mcols, bc) layout of ONE rhs
            return jax.vmap(self.mv, in_axes=1, out_axes=1)(x)
        xb = x.reshape(self.shape[1], bc)
        xg = jnp.take(xb, self.cols, axis=0)             # (n, K, bc)
        y = jnp.einsum("nkij,nkj->ni", self.vals, xg,
                       preferred_element_type=jnp.result_type(
                           self.dtype, x.dtype))
        return y.reshape(self.shape[0] * br)

    def bytes(self):
        return (self.cols.size * self.cols.dtype.itemsize
                + self.vals.size * self.vals.dtype.itemsize)


@register_pytree_node_class
class DenseMatrix:
    """Small dense operator (coarse levels); mv is an MXU matmul."""

    def __init__(self, a):
        self.a = a

    @property
    def shape(self):
        return self.a.shape

    @property
    def dtype(self):
        return self.a.dtype

    def tree_flatten(self):
        return (self.a,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    def mv(self, x):
        return self.a @ x

    def bytes(self):
        return self.a.size * self.a.dtype.itemsize


# -- conversion -------------------------------------------------------------

def csr_to_ell(A: CSR, dtype=jnp.float32) -> EllMatrix:
    """Pack a host CSR/BCSR into device ELL format."""
    nnz_row = A.row_nnz()
    K = int(nnz_row.max()) if A.nrows else 1
    K = max(_ELL_PAD, -(-K // _ELL_PAD) * _ELL_PAD)
    n = A.nrows
    from amgcl_tpu.native import native_ell_pack
    jdt = jnp.dtype(dtype)
    got = None
    if jdt == jnp.dtype(jnp.float32):
        got = native_ell_pack(A, K, np.float32)
    elif jdt == jnp.dtype(jnp.float64):
        got = native_ell_pack(A, K, np.float64)
    if got is not None:
        # native pack fuses the dtype cast — jnp.asarray is then zero-cast
        return EllMatrix(jnp.asarray(got[0]), jnp.asarray(got[1]),
                         A.shape, A.block_size)
    rows = A.expanded_rows()
    # flat scatter beats 2-D fancy indexing ~4x at millions of nonzeros
    flat_idx = rows * K + (np.arange(A.nnz) - A.ptr[rows])
    cols = np.zeros(n * K, dtype=np.int32)
    cols[flat_idx] = A.col
    cols = cols.reshape(n, K)
    if A.is_block:
        br, bc = A.block_size
        vals = np.zeros((n * K, br, bc), dtype=A.val.dtype)
        vals[flat_idx] = A.val
        vals = vals.reshape(n, K, br, bc)
    else:
        vals = np.zeros(n * K, dtype=A.val.dtype)
        vals[flat_idx] = A.val
        vals = vals.reshape(n, K)
    return EllMatrix(jnp.asarray(cols), jnp.asarray(vals, dtype=dtype),
                     A.shape, A.block_size)


def _dia_offsets(A: CSR) -> np.ndarray:
    """Distinct diagonals of A — cached; cheap enough to query during auto
    format selection without committing to the full scatter plan."""
    off = getattr(A, "_dia_offsets_cache", None)
    if off is None:
        from amgcl_tpu.native import native_dia_offsets
        off = native_dia_offsets(A)
        if off is None:
            d = A.col.astype(np.int64) - A.expanded_rows()
            # bincount over the [-(m-1), n-1] diagonal range beats
            # np.unique's O(nnz log nnz) sort by ~8x on stencil matrices
            base = A.nrows - 1
            hits = np.bincount(d + base, minlength=base + A.ncols)
            off = np.flatnonzero(hits) - base
        A._dia_offsets_cache = off
    return off


def _dia_struct(A: CSR):
    """(offsets, flat scatter positions) for the DIA packing — cached on the
    matrix so repeated conversions (e.g. f32 + f64 copies of the same
    operator) skip the O(nnz log) unique/searchsorted."""
    st = getattr(A, "_dia_struct_cache", None)
    if st is not None:
        return st
    rows = A.expanded_rows()
    d = A.col.astype(np.int64) - rows
    offsets = _dia_offsets(A)
    # diagonal -> slot lookup table: one O(nnz) gather instead of an
    # O(nnz log ndiag) searchsorted
    base = A.nrows - 1
    lut = np.zeros(base + A.ncols, dtype=np.int64)
    lut[offsets + base] = np.arange(len(offsets))
    pos = lut[d + base] * A.nrows + rows
    A._dia_struct_cache = (offsets, pos)
    return offsets, pos


def csr_to_dia(A: CSR, dtype=jnp.float32) -> DiaMatrix:
    """Pack a host scalar CSR into device DIA format."""
    assert not A.is_block
    pre = getattr(A, "_dia_prepacked", None)
    if pre is not None:
        # stencil-setup levels are born in DIA layout (ops/stencil.py):
        # the move is a cast + transfer, no scatter
        offs, data = pre
        return DiaMatrix(list(offs),
                         jnp.asarray(np.asarray(data, np.dtype(dtype))),
                         A.shape)
    offsets = _dia_offsets(A)
    from amgcl_tpu.native import native_dia_pack
    data = native_dia_pack(A, offsets, np.dtype(dtype))
    if data is not None:
        # native pack fuses the dtype cast, so jnp.asarray is a pure
        # transfer (no device-side convert compile per shape)
        return DiaMatrix(offsets.tolist(), jnp.asarray(data), A.shape)
    _, pos = _dia_struct(A)
    # single flat scatter instead of 2-D fancy indexing (3-4x faster at
    # tens of millions of nonzeros); scatter straight into the target dtype
    # when the kinds match so the device never runs a convert
    npdt = np.dtype(dtype)
    sdt = npdt if npdt.kind == np.dtype(A.val.dtype).kind else A.val.dtype
    flat = np.zeros(len(offsets) * A.nrows, dtype=sdt)
    flat[pos] = A.val
    data = flat.reshape(len(offsets), A.nrows)
    return DiaMatrix(offsets.tolist(), jnp.asarray(data, dtype=dtype), A.shape)


def csr_to_dia_remainder(A: CSR, hi: "DiaMatrix") -> "DiaMatrix":
    """f32 DIA matrix of the rounding remainders A64 − f32(A64), laid
    out along ``hi``'s offsets — the low half of the double-float
    operator pair the df32 refinement residual streams (ops/dfloat.py).
    Built against hi's offset order by construction, so it pairs with
    any DIA build route (scatter, native, stencil-device)."""
    assert not A.is_block
    offs = np.asarray(hi.offsets, np.int64)
    order = np.argsort(offs)
    rows = A.expanded_rows()
    d = A.col.astype(np.int64) - rows
    idx_sorted = np.searchsorted(offs[order], d)
    idx_sorted = np.clip(idx_sorted, 0, len(offs) - 1)
    k = order[idx_sorted]
    if not np.array_equal(offs[k], d):
        raise ValueError(
            "system matrix has entries outside the device operator's "
            "diagonal set — cannot build the df32 low operator")
    val64 = np.asarray(A.val, np.float64)
    lo_val = (val64 - val64.astype(np.float32).astype(np.float64)) \
        .astype(np.float32)
    data = np.zeros((len(offs), A.nrows), np.float32)
    data[k, rows] = lo_val
    return DiaMatrix(hi.offsets, jnp.asarray(data), A.shape)


def dia_efficiency(A: CSR):
    """(ndiags, fill_ratio) for the DIA packing of A — used by auto format
    selection; fill_ratio = stored / nnz. Only the offsets are computed —
    the O(nnz) scatter plan is built lazily if DIA is actually chosen."""
    nd = len(_dia_offsets(A))
    fill = nd * A.nrows / max(A.nnz, 1)
    return nd, fill


def _decision_candidates(A: CSR, dtype, on_tpu: bool,
                         dense_cutoff: int, max_diags, max_fill,
                         budget):
    """Predicted candidate table for the format-decision ledger
    (telemetry/structure.py candidate_table, priced with the thresholds
    THIS conversion resolved). Never raises — a failed prediction
    degrades to an unrecorded decision, never a failed conversion."""
    try:
        from amgcl_tpu.telemetry.structure import candidate_table
        return candidate_table(
            A, itemsize=jnp.dtype(dtype).itemsize, on_tpu=on_tpu,
            dense_cutoff=dense_cutoff, max_diags=max_diags,
            max_fill=max_fill,
            budget_remaining=budget.remaining()
            if budget is not None else None,
            budget_total=budget.total if budget is not None else None)
    except Exception:
        return None


def _mark_candidate(cands, fmt: str, why: dict):
    """Overwrite a candidate's verdict with what the conversion
    ACTUALLY reported (the predicted eligibility is a model; the
    attempted conversion is ground truth)."""
    if not cands or not why.get("why"):
        return
    for c in cands:
        if c["format"] == fmt:
            c["eligible"] = False
            c["why"] = why["why"]
            return


def _decided(M, A: CSR, fmt: str, cands, forced: bool = False):
    """Attach the format-decision record to a converted matrix — the
    ledger entry ``models/amg.py`` collects per level. Decision
    attributes ride the Python object (device pytrees keep host
    attributes for their lifetime); recording never raises and never
    changes what ``to_device`` returns."""
    try:
        from amgcl_tpu.telemetry.structure import decision_record
        built = M.bytes() if hasattr(M, "bytes") else None
        dec = decision_record(cands or [], fmt, forced=forced,
                              built_bytes=built)
        dec["shape"] = [int(A.shape[0]), int(A.shape[1])]
        dec["nnz"] = int(A.nnz)
        prov = getattr(A, "_reorder_prov", None)
        if prov is not None:
            # executed-reorder provenance (ISSUE 20): this decision was
            # priced on the PERMUTED pattern — record which plan
            dec["reorder"] = dict(prov)
        if fmt == "well":
            # which SpMV the built matrix runs, and what its kernel scans
            kernel, why = M.kernel_status()
            dec["kernel"] = kernel
            if why:
                dec["kernel_why"] = why
            dec.update(getattr(M, "scan_stats", {}))
        M._format_decision = dec
    except Exception:
        pass
    return M


def _ranked_formats(cands):
    """Ledger-driven attempt order for auto selection (ISSUE 20): the
    structured candidates, cheapest price first (predicted SpMV bytes,
    on TPU with the kernel scan or the gather beside them).
    Prediction-ineligible formats keep the legacy preference order at
    the tail — the per-format conversion guards remain the ground truth
    (an attempt can still decline), and ELL stays the unconditional
    terminal fallback outside this ranking. Falls back to the legacy
    order when the prediction itself failed."""
    from amgcl_tpu.telemetry.structure import price
    default = ("dia", "dwin", "well")
    if not cands:
        return default
    priced = {c["format"]: c for c in cands}

    def key(f):
        c = priced.get(f)
        if c is None or not c.get("eligible") \
                or not (c.get("predicted") or {}).get("bytes"):
            return (1, default.index(f))
        return (0, price(c))

    return tuple(sorted(default, key=key))


def to_device(A: CSR, fmt: str = "auto", dtype=jnp.float32,
              max_diags: int | None = None, max_fill: float | None = None,
              dense_cutoff: int = 2048, budget=None):
    """Move a host matrix to the device in a TPU-friendly format.

    ``fmt``: 'auto' | 'ell' | 'dia' | 'dense'. Auto picks DIA when the
    matrix is banded enough (zero-gather SpMV), dense below a size cutoff,
    ELL otherwise. This is the host→device boundary of the setup phase
    (reference: amgcl/amg.hpp:356-364 `copy_matrix`).

    ``budget`` (telemetry.ledger.DeviceMemoryBudget): shared HBM pool the
    dense-window conversion draws from — a hierarchy build passes ONE
    budget for all its levels (models/amg.py), so auto-selection can
    never stack per-matrix allowances into an OOM. Without a budget the
    conversion falls back to the per-matrix env cap.

    Every conversion records a **format-decision ledger** entry on the
    returned matrix (``M._format_decision``, telemetry/structure.py):
    the full candidate table (format × predicted bytes-and-flops per
    SpMV from the ledger cost models), the winner, the margin, and the
    reason — ``"cost"``, ``"budget"`` (a cheaper candidate lost solely
    on the shared HBM budget), or ``"forced"`` (caller-named format) —
    instead of deciding silently. ``AMG.structure_report()`` /
    ``cli --xray`` surface the records."""
    from amgcl_tpu.ops.stencil import HostDia
    if isinstance(A, HostDia):
        # stencil-setup smoother operators live in DIA layout already
        flat = A.flat_offsets()
        order = np.argsort(flat)
        return DiaMatrix(
            [flat[k] for k in order],
            jnp.asarray(np.asarray(A.data[order], np.dtype(dtype))),
            A.shape)
    auto = fmt == "auto"
    on_tpu = jax.default_backend() == "tpu"
    if auto and not A.is_block:
        # measured on v5e: gathers run ~130M elem/s while DIA streams
        # at HBM bandwidth — DIA wins over ELL even at large fill, so
        # accept many more diagonals on TPU (bounded by a 2 GB data
        # guard); an explicit caller-supplied cap is honored as-is
        if max_diags is None:
            max_diags = 512 if on_tpu else 40
        if max_fill is None:
            max_fill = 16.0 if on_tpu else 1.5
    cands = _decision_candidates(A, dtype, on_tpu, dense_cutoff,
                                 max_diags, max_fill, budget) \
        if auto else None
    if fmt == "dense" or (auto and not A.is_block
                          and max(A.shape) <= dense_cutoff
                          and A.nnz > 0.02 * A.shape[0] * A.shape[1]):
        return _decided(DenseMatrix(jnp.asarray(A.to_dense(),
                                                dtype=dtype)),
                        A, "dense", cands, forced=fmt == "dense")
    if fmt == "dia":
        return _decided(csr_to_dia(A, dtype), A, "dia", None,
                        forced=True)
    if fmt == "well":
        from amgcl_tpu.ops.unstructured import csr_to_windowed_ell
        W = csr_to_windowed_ell(A, dtype)
        if W is None:
            raise ValueError(
                "windowed-ELL format needs banded column locality; apply "
                "a Cuthill-McKee reorder first (utils/adapters.Reordered)")
        return _decided(W, A, "well", None, forced=True)
    if fmt == "dwin":
        from amgcl_tpu.ops.densewin import csr_to_dense_window
        D = csr_to_dense_window(A, dtype, budget=budget)
        if D is None:
            raise ValueError(
                "dense-window format needs banded column locality within "
                "the storage budget (AMGCL_TPU_DWIN_MAX_BYTES); apply a "
                "Cuthill-McKee reorder first or raise the budget")
        return _decided(D, A, "dwin", None, forced=True)
    if auto:
        # ledger-driven selection (ISSUE 20): attempt the structured
        # candidates cheapest-predicted-first instead of a fixed
        # preference chain. Each attempt keeps its own eligibility
        # guards — the prediction proposes, the conversion disposes —
        # and a decline is marked on the candidate table so the X-ray
        # distinguishes "lost on cost" from "declined in practice".
        is_cplx = jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating)
        if is_cplx:
            _mark_candidate(cands, "dwin", {"why": "complex dtype"})
            _mark_candidate(cands, "well", {"why": "complex dtype"})
        for f in _ranked_formats(cands):
            if f == "dia" and not A.is_block:
                nd, fill = dia_efficiency(A)
                if (nd <= max_diags and fill <= max_fill
                        and nd * A.nrows * jnp.dtype(dtype).itemsize
                        < 2 << 30):
                    return _decided(csr_to_dia(A, dtype), A, "dia",
                                    cands)
                _mark_candidate(cands, "dia", {
                    "why": "%d diagonals, fill %.2f over the auto "
                    "thresholds" % (nd, fill)})
            elif f == "dwin" and not is_cplx and not A.is_block \
                    and A.shape[0] == A.shape[1] and on_tpu:
                # gather-free dense-window blocks (ops/densewin.py):
                # HBM capacity (n·win·itemsize, budget-gated) traded for
                # streaming; the price ranks it against windowed ELL,
                # whose lane-gather kernel scans x rows per entry vreg
                # (and whose block or 64-bit form runs XLA's gather). SQUARE
                # operators only: auto-converting every rectangular
                # transfer too would multiply the per-matrix budget by
                # the hierarchy depth without an accounting seam — the
                # shared ``budget`` (one per hierarchy build) is that
                # seam (explicit fmt='dwin' remains available)
                from amgcl_tpu.ops.densewin import csr_to_dense_window
                why = {}
                D = csr_to_dense_window(A, dtype, require_kernel=True,
                                        budget=budget, why=why)
                if D is not None:
                    return _decided(D, A, "dwin", cands)
                # the attempted conversion's decline reason beats the
                # prediction — "budget" here is what makes a
                # budget-starved pick distinguishable in the X-ray
                _mark_candidate(cands, "dwin", why)
            elif f == "well" and not is_cplx:
                # unstructured but banded (e.g. after Cuthill-McKee or
                # the executed reorder): windowed ELL bins rows into
                # tiles with narrow column windows, for scalar AND block
                # values (the window bound scales by the block column
                # width inside csr_to_windowed_ell); auto-selection keeps
                # a tighter bound than the explicit 'well' format
                from amgcl_tpu.ops.unstructured import \
                    csr_to_windowed_ell
                why = {}
                W = csr_to_windowed_ell(A, dtype, max_win_bytes=4 << 20,
                                        why=why)
                if W is not None:
                    return _decided(W, A, "well", cands)
                _mark_candidate(cands, "well", why)
    M = csr_to_ell(A, dtype)
    return _decided(M, A, "ell", cands, forced=not auto)


def refresh_values(M, A: CSR, dtype):
    """Value-only refresh of a device matrix from a same-pattern host CSR
    (the numeric-rebuild path, models/amg.py): repack A's values into the
    SAME device format/structure as ``M`` — DIA rides the cached scatter
    plan (or the stencil prepack), ELL/dense are O(nnz) repacks. Returns
    None when the format has no value-only route (windowed/dense-window/
    block formats fall back to a full ``to_device``), or when the derived
    structure unexpectedly differs from ``M``'s (a same-sparsity-contract
    violation the caller resolves with a full conversion)."""
    if isinstance(M, DiaMatrix) and not A.is_block:
        new = csr_to_dia(A, dtype)
        if list(new.offsets) == list(M.offsets):
            return new
        return None
    if isinstance(M, DenseMatrix) and not A.is_block:
        return DenseMatrix(jnp.asarray(A.to_dense(), dtype=dtype))
    if isinstance(M, EllMatrix):
        new = csr_to_ell(A, dtype)
        if new.cols.shape == M.cols.shape:
            return new
        return None
    from amgcl_tpu.ops import unstructured
    if isinstance(M, unstructured.WindowedEllMatrix):
        # same-pattern value scatter into the cached tile/window
        # structure — skips tile_windows (the ufunc.at window scan is
        # the expensive part of the conversion)
        return unstructured.refresh_values(M, A)
    return None


# -- backend primitives (reference: amgcl/backend/interface.hpp:253-443) ----
#
# The hot primitives carry a named scope (telemetry/tracing.py) tagged with
# the operator's device format, so a jax.profiler trace attributes device
# time to "spmv/DiaMatrix", "residual/EllMatrix", ... — zero runtime cost.

#: formats whose ``mv`` accepts stacked (m, B) operands natively; any
#: other format goes through a vmap at the :func:`spmv` seam so the whole
#: backend is stacked-capable without every kernel learning a batch axis
_STACKED_MV = (DiaMatrix, EllMatrix, DenseMatrix)


def spmv(A, x):
    """y = A x. Accepts a stacked ``(m, B)`` operand: formats with a
    native batched ``mv`` (DIA/ELL/Dense) amortize the matrix read over
    the B columns; others fall back to a vmap over columns."""
    with _phase("spmv/" + type(A).__name__):
        if getattr(x, "ndim", 1) == 2 \
                and not isinstance(A, _STACKED_MV):
            # the vmapped 1-D mv must trace its XLA lowering — the hand
            # kernels carry exact 1-D shapes (same rule as vmap_solve /
            # Hierarchy.apply's stacked branch)
            from amgcl_tpu.ops.pallas_spmv import pallas_disabled
            with pallas_disabled():
                return jax.vmap(A.mv, in_axes=1, out_axes=1)(x)
        return A.mv(x)


def residual(f, A, x):
    """r = f - A x (interface.hpp `residual`).

    DIA and dense-window operators take a fused single-pass Pallas kernel
    on TPU — the composed spmv + subtract costs an extra HBM round-trip of
    A x because XLA cannot fuse across the pallas_call boundary. ELL and
    Dense stay composed: their mv is pure XLA, and XLA fuses the
    subtraction into the gather/matmul consumer. Windowed ELL composes
    its mv (the lane-gather kernel on TPU) with the subtraction; it has
    no fused residual kernel."""
    with _phase("residual/" + type(A).__name__):
        return _residual(f, A, x)


def _residual(f, A, x):
    if getattr(x, "ndim", 1) == 2:
        # stacked operands: the fused single-rhs kernels do not apply —
        # compose through the (batched) spmv seam
        return f - spmv(A, x)
    if isinstance(A, DiaMatrix):
        ip = A._pallas_mode(x, f)
        if ip is not None:
            from amgcl_tpu.ops.pallas_spmv import dia_residual
            return dia_residual(A.offsets, A.data, f, x, interpret=ip)
    from amgcl_tpu.ops.densewin import DenseWindowMatrix
    if isinstance(A, DenseWindowMatrix):
        ip = A._pallas_mode(x, f, kernel="fused")
        if ip is not None:
            from amgcl_tpu.ops.densewin import dense_window_residual
            return dense_window_residual(A.window_starts, A.blocks, f, x,
                                         A.win, A.shape[0], interpret=ip)
    return f - A.mv(x)


def scaled_correction(A, w, f, x):
    """x + w ∘ (f − A x) in one fused pass when the operator format has a
    kernel for it (DIA, dense window), else None — the smoother seam asks
    here so format dispatch lives next to residual/spmv_dots instead of
    inside every smoother."""
    with _phase("scaled_correction/" + type(A).__name__):
        return _scaled_correction(A, w, f, x)


def _scaled_correction(A, w, f, x):
    if isinstance(A, DiaMatrix) and w.ndim == 1:
        ip = A._pallas_mode(x, f, w)
        if ip is not None:
            from amgcl_tpu.ops.pallas_spmv import dia_scaled_correction
            return dia_scaled_correction(A.offsets, A.data, w, f, x,
                                         interpret=ip)
    from amgcl_tpu.ops.densewin import DenseWindowMatrix
    if isinstance(A, DenseWindowMatrix) and w.ndim == 1:
        ip = A._pallas_mode(x, f, w, kernel="fused")
        if ip is not None:
            from amgcl_tpu.ops.densewin import (
                dense_window_scaled_correction)
            return dense_window_scaled_correction(
                A.window_starts, A.blocks, w, f, x, A.win, A.shape[0],
                interpret=ip)
    return None


def axpby(a, x, b, y):
    """y = a x + b y."""
    return a * x + b * y


def axpbypcz(a, x, b, y, c, z):
    """z = a x + b y + c z."""
    return a * x + b * y + c * z


def vmul(a, x, y, b, z):
    """z = a x∘y + b z (element-wise product, interface.hpp `vmul`)."""
    return a * x * y + b * z


def inner_product(x, y):
    """Conjugated dot product; the seam the distributed layer swaps for a
    psum-reduced version (reference: solver/detail/default_inner_product.hpp,
    mpi/inner_product.hpp:45-67)."""
    return jnp.vdot(x, y)


def spmv_dots(A, x, w=None, ip=inner_product):
    """(y, <y,y>, <y,x>, <y,w>) with y = A x — the Krylov hot pairs,
    fused into one Pallas pass on the DIA path when ``ip`` is the plain
    single-device dot OR a psum-marked distributed one (``ip.psum_axis``
    set, e.g. ``parallel.dist_matrix.dist_inner_product``): the kernel
    computes the SHARD-LOCAL partials and one stacked ``lax.psum``
    globalizes every dot at once — so distributed solves keep the
    spmv+dot fusion on the local shard AND merge their collectives.
    Any other swapped seam (or a complex dtype — the itemsize gate in
    _pallas_mode excludes those) composes through ``ip``."""
    with _phase("spmv_dots/" + type(A).__name__):
        return _spmv_dots(A, x, w, ip)


def _dots_psum_axis(ip):
    """psum axis of a marked distributed inner product, else None (the
    plain dot fuses without any reduction)."""
    if ip is inner_product:
        return None
    return getattr(ip, "psum_axis", None)


def psum_stacked(dots, axis):
    """Globalize a tuple of shard-local scalar partials with ONE stacked
    psum — the merged-reduction primitive shared by spmv_dots and the
    fused vector tier (ops/fused_vec.py). No-op when ``axis`` is None."""
    dots = tuple(dots)
    if axis is None or not dots:
        return dots
    red = lax.psum(jnp.stack(list(dots)), axis)
    return tuple(red[i] for i in range(len(dots)))


def _globalize_dots(axis, yy, yx, yw):
    """psum_stacked over the spmv dot triple (w slot optional)."""
    if axis is None:
        return yy, yx, yw
    red = psum_stacked((yy, yx) + (() if yw is None else (yw,)), axis)
    return red[0], red[1], (None if yw is None else red[2])


def _spmv_dots(A, x, w=None, ip=inner_product):
    axis = _dots_psum_axis(ip)
    fused_ip = ip is inner_product or axis is not None
    if isinstance(A, DiaMatrix) and fused_ip \
            and A.shape[0] == A.shape[1]:
        m = A._pallas_mode(x) if w is None else A._pallas_mode(x, w)
        if m is not None:
            from amgcl_tpu.ops.pallas_spmv import dia_spmv_dots
            y, yy, yx, yw = dia_spmv_dots(A.offsets, A.data, x, w,
                                          interpret=m)
            return (y,) + _globalize_dots(axis, yy, yx, yw)
    y = A.mv(x)
    if axis is not None:
        # no kernel, but the merged reduction still applies: local
        # vdots + ONE stacked psum instead of 2-3 separate collectives
        return (y,) + _globalize_dots(
            axis, jnp.vdot(y, y), jnp.vdot(y, x),
            None if w is None else jnp.vdot(y, w))
    return y, ip(y, y), ip(y, x), (None if w is None else ip(y, w))


def spmv_dot(A, p, ip=inner_product):
    """(q, <q, p>) with q = A p — the CG hot pair (see spmv_dots)."""
    q, _, qp, _ = spmv_dots(A, p, None, ip)
    return q, qp


def norm(x):
    return jnp.sqrt(jnp.abs(jnp.vdot(x, x)))


def clear(x):
    return jnp.zeros_like(x)


def copy(x):
    return x  # functional arrays: copy is identity


def gather(x, idx):
    return jnp.take(x, idx, axis=0)


def scatter(y, idx, v):
    return y.at[idx].set(v)


def lin_comb(coefs, vecs, b, z):
    """z = sum_i coefs[i] * vecs[i] + b z (interface.hpp lin_comb)."""
    out = b * z
    for c, v in zip(coefs, vecs):
        out = out + c * v
    return out
