"""Device-resident stencil setup: the whole SA hierarchy build on the TPU.

Round-2 state: the stencil setup (ops/stencil.py) ran the smoothed-aggregation
construction on HOST diagonals — vectorized, but bound to one CPU core's
memory bandwidth (the diagonal-pair Galerkin alone streams ~6 GB per fine
level). This module moves the per-level algebra onto the device, where the
same passes are HBM-bound streaming (milliseconds), and the coarse operator
is *born on device* — the solve phase's `_to_device_levels` transfer
disappears for stencil hierarchies.

Per level, ONE jitted program (static plan derived from the offset lists)
computes:

1. strength filter + lumping (elementwise per diagonal, reference:
   amgcl/coarsening/smoothed_aggregation.hpp:157-199),
2. Gershgorin bound ρ and ω = relax·(4/3)/ρ as traced scalars — no host
   round trip (reference: amgcl/backend/builtin.hpp:775-820),
3. M = ω D⁻¹ A_f and its transpose (offset negation + static shifts),
4. X = A − A·M and S = X − Mᵀ·X as `lax.scan`s over the static pair list
   (each step: one dynamic-slice from a padded diagonal stack + fused
   multiply-add — the device analogue of native_dia_fnma_batch, reference
   Galerkin: amgcl/coarsening/detail/galerkin.hpp:53),
5. the tentative collapse Ac = Tᵀ S T as a scan over S diagonals with
   static parity slicing (mirrors ops/stencil.StencilGalerkinPlan),
6. the smoother diagonal (SPAI-0 / damped Jacobi — elementwise,
   reference: amgcl/relaxation/spai0.hpp:49-117),
7. per-coarse-diagonal nonzero counts — the ONLY per-level device→host
   fetch (which candidate diagonals survive decides the next level's
   static plan).

The aggregation shape (which axes coarsen) is decided SPECULATIVELY — every
axis with extent > 1 coarsens by 2, the isotropic common case — and
verified against the data-driven strength counts afterwards; a mismatch
(strong anisotropy → semicoarsening) discards the device build and falls
back to the host path, so numerics always match ops/stencil exactly.

The stage functions are pure on (diagonal arrays, static plan), which is
the shape `shard_map` needs: the distributed setup shards the row axis and
adds halo exchange for the static shifts.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from amgcl_tpu.telemetry.compile_watch import watched_jit as _watched_jit
from jax import lax

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops.stencil import HostDia, host_dia_from_csr, _flat
from amgcl_tpu.telemetry.tracing import setup_scope

_MAX_DIAGS = 34          # per-level gate; pair scans stay ~10^3 steps


def enabled() -> bool:
    """Device setup is the default on TPU; AMGCL_TPU_DEVICE_SETUP=1 forces
    it on other backends (tests), =0 disables everywhere."""
    v = os.environ.get("AMGCL_TPU_DEVICE_SETUP")
    if v == "0":
        return False
    if v == "1":
        return True
    return jax.default_backend() == "tpu"


def tpu_setup_path() -> bool:
    """Which _level_setup formulation to trace: the TPU-tuned static
    unrolled branches (fnma rows / parity collapse), or the scan
    formulation that keeps per-shard op counts bounded on CPU.

    AMGCL_TPU_FORCE_TPU_SETUP_PATH=1 forces the TPU branches on other
    backends so CPU CI can exercise and cross-check them (they were
    previously reachable only on real hardware). The flag is read at
    TRACE time: flipping it between builds of the same shapes needs a
    ``_level_setup.clear_cache()`` (the jit cache does not key on env)."""
    return (jax.default_backend() == "tpu"
            or os.environ.get("AMGCL_TPU_FORCE_TPU_SETUP_PATH") == "1")


# -- static-plan helpers ------------------------------------------------------

def _osum(a, b):
    return (a[0] + b[0], a[1] + b[1], a[2] + b[2])


def _oneg(a):
    return (-a[0], -a[1], -a[2])


def _jshift(v, s):
    """out[i] = v[i + s], zero-filled — static shift (jnp)."""
    if s == 0:
        return v
    n = v.shape[0]
    z = jnp.zeros((abs(s),), v.dtype)
    if s > 0:
        return jnp.concatenate([v[s:], z])
    return jnp.concatenate([z, v[:n + s]])


def _product_plan(src_offs, dst_offs, dims):
    """Static plan for OUT = EMBED − SRC·DST: (out_offs, embed_slots,
    pairs) with pairs rows (k_src, k_dst, flat_shift(src), k_out)."""
    out_offs = sorted(
        set(dst_offs) | {_osum(oa, ob) for oa in src_offs
                         for ob in dst_offs},
        key=lambda o: _flat(o, dims))
    out_idx = {o: k for k, o in enumerate(out_offs)}
    pairs = [(ka, kb, _flat(oa, dims), out_idx[_osum(oa, ob)])
             for ka, oa in enumerate(src_offs)
             for kb, ob in enumerate(dst_offs)]
    embed = [out_idx[o] for o in dst_offs]
    return out_offs, embed, pairs


def _collapse_plan(s_offs, dims, blocks, coarse):
    """Coarse offsets + (ns, n_par) slot table for the Tᵀ·T parity
    collapse (mirrors ops/stencil.StencilGalerkinPlan)."""
    b2, b1, b0 = blocks
    parities = [(pz, py, px) for pz in range(b2) for py in range(b1)
                for px in range(b0)]
    c_set = {}
    rows = []
    for oc in s_offs:
        oz, oy, ox = oc
        row = []
        for (pz, py, px) in parities:
            co = ((pz + oz) // b2, (py + oy) // b1, (px + ox) // b0)
            if co not in c_set:
                c_set[co] = len(c_set)
            row.append(c_set[co])
        rows.append(row)
    c_offs = sorted(c_set, key=lambda o: _flat(o, coarse))
    remap = {c_set[o]: k for k, o in enumerate(c_offs)}
    table = np.asarray([[remap[s] for s in row] for row in rows], np.int32)
    return c_offs, tuple(parities), table


def _fnma_scan(out, src, dst_pad, pairs, pad, n):
    """out[ko] -= src[ka] * dst_pad[kb, pad+s : pad+s+n] for every pair.

    Grouped by OUTPUT row so the row index is STATIC. The original
    formulation scanned over pairs with a traced-row dynamic_update_slice
    into the whole (rows, n) carry — XLA copies the full carry every
    step (r5 on-chip setup profile: 2.3 s per 128³ level for ~100 GB of
    carry copies against ~9 GB of useful traffic). Per output row the
    pair list is short at fine levels (unrolled static slices — XLA
    fuses the fma chain); long lists (coarse SA stencils, hundreds of
    source pairs) use a per-row lax.scan whose carry is ONE row, so the
    worst-case copy is (n,) not (rows, n)."""
    if not pairs:
        return out
    if not tpu_setup_path():
        # CPU (tests on the virtual mesh): the original pair scan — the
        # unrolled form below multiplies the traced op count per shard
        # and blows the 8-virtual-device sharded compile time ~6x
        # (AMGCL_TPU_FORCE_TPU_SETUP_PATH=1 overrides, see tpu_setup_path)
        parr = jnp.asarray(np.asarray(pairs, np.int32))

        def sbody(acc, p):
            ka, kb, s, ko = p[0], p[1], p[2], p[3]
            zero = jnp.zeros((), ka.dtype)
            b = lax.dynamic_slice(dst_pad, (kb, pad + s), (1, n))[0]
            a = lax.dynamic_slice(src, (ka, zero), (1, n))[0]
            row = lax.dynamic_slice(acc, (ko, zero), (1, n))[0] - a * b
            return lax.dynamic_update_slice(acc, row[None], (ko, zero)), \
                None

        out, _ = lax.scan(sbody, out, parr)
        return out
    by_out = {}
    for ka, kb, s, ko in pairs:
        by_out.setdefault(int(ko), []).append((int(ka), int(kb), int(s)))
    rows = [out[k] for k in range(out.shape[0])]
    for ko, plist in by_out.items():
        acc = rows[ko]
        if len(plist) <= 24:
            for ka, kb, s in plist:
                b = lax.slice(dst_pad, (kb, pad + s), (kb + 1, pad + s + n))
                acc = acc - src[ka] * b[0]
        else:
            parr = jnp.asarray(np.asarray(plist, np.int32))

            def body(a_row, p):
                ka, kb, s = p[0], p[1], p[2]
                b = lax.dynamic_slice(dst_pad, (kb, pad + s), (1, n))[0]
                av = lax.dynamic_slice(
                    src, (ka, jnp.zeros((), ka.dtype)), (1, n))[0]
                return a_row - av * b, None

            acc, _ = lax.scan(body, acc, parr)
        rows[ko] = acc
    return jnp.stack(rows)


# -- the per-level device program --------------------------------------------

@functools.partial(
    _watched_jit, name="ops.level_setup",
    static_argnames=("offs", "dims", "blocks", "coarse", "relax_kind"))
def _level_setup(adata, eps_strong, relax_scale, smoother_omega, offs,
                 dims, blocks, coarse, relax_kind):
    """One hierarchy level on device. Static args fix the structure; eps,
    the SA relax factor, and the smoother damping are traced so the
    eps-decay across levels does not force recompiles. Returns
    (m, mt, ac_all, smoother_scale, ac_counts, axis_strong)."""
    n = adata.shape[1]
    dt = adata.dtype
    offs = list(offs)
    eps2 = (eps_strong * eps_strong).astype(dt)

    # 1. strength filter + lumping (ops/stencil.filtered_dia semantics)
    main_k = offs.index((0, 0, 0)) if (0, 0, 0) in offs else None
    dia = jnp.abs(adata[main_k]) if main_k is not None \
        else jnp.zeros((n,), dt)
    af_rows = [None] * len(offs)
    lump = jnp.zeros((n,), dt)
    for k, o in enumerate(offs):
        if k == main_k:
            continue
        a = adata[k]
        dj = _jshift(dia, _flat(o, dims))
        strong = (a * a) > (eps2 * dia * dj)
        af_rows[k] = jnp.where(strong, a, dt.type(0))
        lump = lump + jnp.where(strong, dt.type(0), a)
    main = (adata[main_k] if main_k is not None
            else jnp.zeros((n,), dt)) + lump
    if main_k is not None:
        af_rows[main_k] = main
        af_offs = list(offs)
    else:
        af_rows.append(main)
        af_offs = list(offs) + [(0, 0, 0)]
    af = jnp.stack(af_rows)
    dinv = jnp.where(main != 0, 1.0 / jnp.where(main != 0, main, 1),
                     1.0).astype(dt)

    # per-axis strong-connection counts (speculation check; semantics of
    # ops/stencil.strength_axes)
    axis_strong = []
    for ax in range(3):
        tot = jnp.zeros((), jnp.float32)
        for k, o in enumerate(af_offs):
            if [i for i, c in enumerate(o) if c != 0] == [ax]:
                tot = tot + jnp.count_nonzero(af[k]).astype(jnp.float32)
        axis_strong.append(tot)
    axis_strong = jnp.stack(axis_strong)

    # 2. Gershgorin rho -> omega, traced
    rho = jnp.max(jnp.abs(dinv) * jnp.sum(jnp.abs(af), axis=0))
    omega = (relax_scale.astype(dt) * dt.type(4.0 / 3.0)
             / jnp.maximum(rho, dt.type(1e-30)))

    # 3. M = omega D^-1 Af and its transpose
    m = af * (dinv * omega)[None, :]
    mt = jnp.stack([_jshift(m[k], _flat(_oneg(o), dims))
                    for k, o in enumerate(af_offs)])
    mt_offs = [_oneg(o) for o in af_offs]

    # 4. X = A - A·M ; S = X - Mt·X
    x_offs, _, _ = _product_plan(offs, af_offs, dims)
    x_idx = {o: k for k, o in enumerate(x_offs)}
    a_slots = np.asarray([x_idx[o] for o in offs], np.int32)
    X = jnp.zeros((len(x_offs), n), dt).at[a_slots].set(adata)
    x_pairs = [(ka, kb, _flat(oa, dims), x_idx[_osum(oa, ob)])
               for ka, oa in enumerate(offs)
               for kb, ob in enumerate(af_offs)]
    pad_m = max(max(abs(p[2]) for p in x_pairs), 1)
    X = _fnma_scan(X, adata, jnp.pad(m, ((0, 0), (pad_m, pad_m))),
                   x_pairs, pad_m, n)

    s_offs, s_embed, s_pairs = _product_plan(mt_offs, x_offs, dims)
    S = jnp.zeros((len(s_offs), n), dt) \
        .at[np.asarray(s_embed, np.int32)].set(X)
    pad_x = max(max(abs(p[2]) for p in s_pairs), 1)
    S = _fnma_scan(S, mt, jnp.pad(X, ((0, 0), (pad_x, pad_x))),
                   s_pairs, pad_x, n)

    # 5. collapse Ac = T^T S T
    c_offs, parities, table = _collapse_plan(s_offs, dims, blocks, coarse)
    b2, b1, b0 = blocks
    c2, c1, c0 = coarse
    dims_p = (c2 * b2, c1 * b1, c0 * b0)
    f2, f1, f0 = dims
    n_c = c2 * c1 * c0
    acc0 = jnp.zeros((len(c_offs), n_c), dt)

    if tpu_setup_path():
        # static unrolled collapse: the table is host-known, so every
        # destination row index is STATIC — a scan carrying the whole
        # (c_offs, n_c) accumulator with traced scatter rows forced a
        # full carry copy per step (same disease as the old _fnma_scan;
        # r5 setup profile: ~2.2 s per 128³ level)
        acc = acc0
        for i in range(len(s_offs)):
            v3 = S[i].reshape(f2, f1, f0)
            if dims_p != tuple(dims):
                v3 = jnp.pad(v3, ((0, dims_p[0] - f2),
                                  (0, dims_p[1] - f1),
                                  (0, dims_p[2] - f0)))
            for j, (pz, py, px) in enumerate(parities):
                sl = v3[pz::b2, py::b1, px::b0].reshape(-1)
                acc = acc.at[int(table[i, j])].add(sl)
        ac_all = acc
    else:
        # CPU (virtual-mesh tests): scan keeps the traced op count per
        # shard bounded — see _fnma_scan's backend branch
        def cbody(acc, inp):
            row, slots = inp
            v3 = row.reshape(f2, f1, f0)
            if dims_p != tuple(dims):
                v3 = jnp.pad(v3, ((0, dims_p[0] - f2),
                                  (0, dims_p[1] - f1),
                                  (0, dims_p[2] - f0)))
            for j, (pz, py, px) in enumerate(parities):
                sl = v3[pz::b2, py::b1, px::b0].reshape(-1)
                acc = acc.at[slots[j]].add(sl)
            return acc, None

        ac_all, _ = lax.scan(cbody, acc0, (S, jnp.asarray(table)))
    ac_counts = jnp.sum(ac_all != 0, axis=1).astype(jnp.int32)

    # 6. smoother diagonal from the ORIGINAL operator
    d0 = adata[main_k] if main_k is not None else jnp.ones((n,), dt)
    if relax_kind == "spai0":
        denom = jnp.sum(adata * adata, axis=0)
        scale = d0 / jnp.where(denom != 0, denom, 1)
    else:                                         # damped jacobi
        scale = smoother_omega.astype(dt) * jnp.where(
            d0 != 0, 1.0 / jnp.where(d0 != 0, d0, 1), 0.0).astype(dt)
    return m, mt, ac_all, scale, ac_counts, axis_strong


# -- orchestration ------------------------------------------------------------

def _to_dia_matrix(data_dev, offs3, dims, dtype):
    """Device DIA operator from diagonal rows: flat-sort the offsets and
    merge 3-D couplings that share a flat diagonal on small grids (the
    same merge HostDia.to_csr performs, ops/stencil.py:128-138)."""
    from amgcl_tpu.ops.device import DiaMatrix
    n = int(np.prod(dims))
    flats = np.asarray([_flat(o, dims) for o in offs3])
    uniq = {}
    for k, f in enumerate(flats):
        uniq.setdefault(int(f), []).append(k)
    out_flats = sorted(uniq)
    rows = []
    for f in out_flats:
        idxs = uniq[f]
        row = data_dev[idxs[0]]
        for i in idxs[1:]:
            row = row + data_dev[i]
        rows.append(row)
    data = jnp.stack(rows).astype(jnp.dtype(dtype))
    return DiaMatrix(out_flats, data, (n, n))


class _LevelMeta:
    """Lightweight host-side stand-in for a device-built level (repr /
    bytes bookkeeping — the CSR is never materialized)."""

    def __init__(self, nrows, nnz):
        self.nrows = int(nrows)
        self.nnz = int(nnz)
        self.block_size = (1, 1)
        self.shape = (self.nrows, self.nrows)


def device_build(A: CSR, prm, prof=None):
    """Build the SA hierarchy on device — as far as the diagonal-pair
    Galerkin stays cheap (coarse SA stencils grow to ~125 diagonals by
    level 2, where the CSR SpGEMM route wins). Returns None when the
    configuration falls outside the fast path, else a dict:

    - ``levels``: device ``Level`` list built so far,
    - ``meta``: per-level ``_LevelMeta`` (repr/bytes bookkeeping),
    - ``leftover``: None if the build ran to the coarsest level, else the
      downloaded next operator as CSR (with prepacked DIA + grid dims) for
      the host loop to continue from,
    - ``coarse``: the direct solver (only when leftover is None),
    - ``eps_next``: eps_strong after the per-level decay, for the
      continuation's build context.

    Numerics are identical to the host path either way.

    The stages run under the host loop's set-up stage names
    (``setup_scope`` on ``prof``, the build's profiler): the fine
    operator's DIA packing and upload and each level's DIA operators are
    ``level<i>/transfer``; the ``_level_setup`` program (strength,
    aggregation, smoother state and Galerkin in one device program) and
    its counts fetch are ``level<i>/galerkin``; the fused-leg builds are
    ``level<i>/fused_kernels``; the coarsest operator, its fetch and the
    direct factorization are ``coarse_solver``."""
    from amgcl_tpu.coarsening.smoothed_aggregation import \
        SmoothedAggregation
    from amgcl_tpu.relaxation.spai0 import Spai0
    from amgcl_tpu.relaxation.jacobi import DampedJacobi
    from amgcl_tpu.relaxation.base import ScaledResidualSmoother
    from amgcl_tpu.ops.structured import (
        detect_grid_csr, GridTentative, ImplicitSmoothedP,
        ImplicitSmoothedR)
    from amgcl_tpu.models.amg import Level, Hierarchy
    from amgcl_tpu.solver.direct import DenseDirectSolver

    c = prm.coarsening
    if type(c) is not SmoothedAggregation:
        return None
    if not (c.stencil_setup and c.structured and c.implicit_transfers):
        return None
    if (c.nullspace is not None or c.aggregator is not None
            or c.block_size != 1 or c.power_iters):
        return None
    if A.is_block or np.iscomplexobj(A.val):
        return None
    if prm.matrix_format not in ("auto", "dia"):
        return None
    if jnp.dtype(prm.dtype) not in (jnp.dtype(jnp.float32),
                                    jnp.dtype(jnp.bfloat16)):
        return None
    if isinstance(prm.relax, Spai0):
        relax_kind, sm_omega = "spai0", 0.0
    elif isinstance(prm.relax, DampedJacobi):
        relax_kind, sm_omega = "jacobi", float(prm.relax.damping)
    else:
        return None
    grid = detect_grid_csr(A)
    if grid is None:
        return None
    with setup_scope(prof, "level0/transfer"):
        Ad = host_dia_from_csr(A, grid, np.float32)
        if Ad is None or len(Ad.offsets3) > _MAX_DIAGS:
            return None
        adata = jnp.asarray(Ad.data)

    dtype = prm.dtype
    offs = list(Ad.offsets3)
    dims = tuple(Ad.dims)
    eps = float(c.eps_strong)
    n = int(np.prod(dims))
    meta = [_LevelMeta(n, A.nnz)]
    dev_levels = []

    def leftover_csr():
        """Download the current level and hand it to the host loop with
        its DIA packing and grid dims attached (transfer-only re-use)."""
        with setup_scope(prof, "level%d/transfer" % len(dev_levels)):
            Hl = HostDia(offs, np.asarray(jax.device_get(adata)), dims)
            return Hl.to_csr()

    def result(leftover, coarse_solver):
        return {"levels": dev_levels, "meta": meta, "leftover": leftover,
                "coarse": coarse_solver, "eps_next": eps}

    while (n > prm.coarse_enough
           and len(dev_levels) + 1 < prm.max_levels):
        if len(offs) > _MAX_DIAGS:
            # SA stencil growth crossed into SpGEMM territory: keep the
            # device-built prefix, continue on the host
            if not dev_levels:
                return None
            return result(leftover_csr(), None)
        blocks = tuple(2 if d > 1 else 1 for d in dims)
        if all(b == 1 for b in blocks):
            return None if not dev_levels \
                else result(leftover_csr(), None)
        lvl = "level%d" % len(dev_levels)
        with setup_scope(prof, lvl + "/galerkin"):
            coarse = tuple(-(-d // b) for d, b in zip(dims, blocks))
            m, mt, ac_all, scale, counts, axis_strong = _level_setup(
                adata, jnp.float32(eps), jnp.float32(c.relax),
                jnp.float32(sm_omega), offs=tuple(offs), dims=dims,
                blocks=blocks, coarse=coarse, relax_kind=relax_kind)
            counts_h, axis_h = jax.device_get((counts, axis_strong))
            # speculation check (ops/stencil.strength_axes semantics):
            # every extent>1 axis must actually be strongly coupled. A
            # mismatch is a SEMICOARSENING problem: rerun the level with
            # the measured axes (one extra compile per (dims, blocks)
            # shape — cached across rebuilds); no strong axis at all
            # means aggregation would stall, so that still falls back to
            # the host MIS route.
            want = tuple(
                min(2, dims[i]) if dims[i] > 1 and axis_h[i] >= 0.5 * n
                else 1 for i in range(3))
            if want != blocks:
                if all(b == 1 for b in want):
                    return None if not dev_levels \
                        else result(leftover_csr(), None)
                blocks = want
                coarse = tuple(-(-d // b) for d, b in zip(dims, blocks))
                m, mt, ac_all, scale, counts, axis_strong = _level_setup(
                    adata, jnp.float32(eps), jnp.float32(c.relax),
                    jnp.float32(sm_omega), offs=tuple(offs), dims=dims,
                    blocks=blocks, coarse=coarse, relax_kind=relax_kind)
                counts_h = jax.device_get(counts)

            main_in = (0, 0, 0) in offs
            af_offs = list(offs) + ([] if main_in else [(0, 0, 0)])
            mt_offs = [_oneg(o) for o in af_offs]
            s_offs, _, _ = _product_plan(
                mt_offs, _product_plan(offs, af_offs, dims)[0], dims)
            c_offs, _, _ = _collapse_plan(s_offs, dims, blocks, coarse)
            keep = np.flatnonzero(counts_h)
            if len(keep) == 0:
                return None
            new_offs = [c_offs[k] for k in keep]
            ac = ac_all[jnp.asarray(keep)]

        with setup_scope(prof, lvl + "/transfer"):
            T = GridTentative(dims, blocks, coarse)
            M_dev = _to_dia_matrix(m, af_offs, dims, dtype)
            Mt_dev = _to_dia_matrix(mt, mt_offs, dims, dtype)
            A_lvl = _to_dia_matrix(adata, offs, dims, dtype)
            R_lvl = ImplicitSmoothedR(T, Mt_dev)
            P_lvl = ImplicitSmoothedP(T, M_dev)
            relax_lvl = ScaledResidualSmoother(
                scale.astype(jnp.dtype(dtype)))
        from amgcl_tpu.ops.pallas_vcycle import (build_fused_down,
                                                 build_fused_up)
        with setup_scope(prof, lvl + "/fused_kernels"):
            fd = build_fused_down(A_lvl, R_lvl, relax_lvl)
            fu = build_fused_up(A_lvl, P_lvl, relax_lvl)
        dev_levels.append(Level(A_lvl, relax_lvl, P_lvl, R_lvl, fd, fu))

        adata, offs, dims = ac, new_offs, coarse
        n = int(np.prod(dims))
        meta.append(_LevelMeta(n, int(counts_h[keep].sum())))
        eps *= 0.5

    # coarsest level: small — host direct factorization from fetched data
    if prm.direct_coarse and n > max(4 * prm.coarse_enough, 20000):
        # same stalled-coarsening guard as the host path
        # (models/amg.py _to_device_levels): refuse to densify a huge
        # coarsest level (e.g. a tiny max_levels on a big grid)
        raise RuntimeError(
            "coarsening stalled at %d unknowns (> coarse_enough=%d); "
            "cannot build a dense coarse solver this large — adjust "
            "coarsening parameters or set direct_coarse=False"
            % (n, prm.coarse_enough))
    with setup_scope(prof, "coarse_solver"):
        A_last = _to_dia_matrix(adata, offs, dims, dtype)
        if prm.direct_coarse:
            Hl = HostDia(offs, np.asarray(jax.device_get(adata),
                                          np.float64), dims)
            coarse_solver = DenseDirectSolver.build(Hl.to_csr(), dtype)
            dev_levels.append(Level(A_last, None))
        else:
            coarse_solver = None
            dl = jax.device_get(adata)
            main_k = offs.index((0, 0, 0)) if (0, 0, 0) in offs else None
            d0 = dl[main_k] if main_k is not None else np.ones(n)
            if relax_kind == "spai0":
                denom = (dl * dl).sum(axis=0)
                sc = d0 / np.where(denom != 0, denom, 1)
            else:
                sc = sm_omega * np.where(d0 != 0, 1.0 / np.where(
                    d0 != 0, d0, 1), 0.0)
            dev_levels.append(Level(
                A_last,
                ScaledResidualSmoother(jnp.asarray(sc, dtype=dtype))))
    return result(None, coarse_solver)
