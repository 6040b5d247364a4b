"""Mesh-sharded stencil setup and solve: distributed hierarchy CONSTRUCTION.

The round-2 review's core distributed gap: the hierarchy was built serially
on one host and then sharded (reference builds it distributed —
amgcl/mpi/amg.hpp:163-330 with distributed SpGEMM,
amgcl/mpi/distributed_matrix.hpp:856-1066). For stencil problems the
device setup (ops/stencil_device.py) is already expressed as per-diagonal
streaming passes with STATIC shifts — exactly the shape `shard_map` wants:

- rows are sharded in contiguous z-slabs over the mesh's ``rows`` axis;
- every static shift becomes a ring halo exchange (``lax.ppermute`` of the
  slab edges — zero-filled at the global boundary, matching the serial
  zero-fill shift semantics);
- the Gershgorin bound and strength counts become ``pmax``/``psum``;
- the pair-product scans and the tentative parity collapse are unchanged
  (the collapse is position-local because slab boundaries align with the
  2× aggregation blocks);
- per-level, each shard holds only its slab of every diagonal — per-shard
  peak memory is the serial build's divided by the mesh size.

The solve phase reuses the same slabs: smoother, residual, and transfer
applications are halo-SpMVs (parallel/dist_matrix.py pattern), the coarse
tail below the sharded levels is a replicated serial hierarchy (the
repartition-merge analogue: amgcl/mpi/partition/merge.hpp:47-137), and the
whole AMG-preconditioned CG runs as ONE shard_map'd XLA program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.lax import axis_size as _axis_size
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import register_pytree_node_class

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops.stencil import HostDia, host_dia_from_csr, _flat
from amgcl_tpu.ops.stencil_device import (
    _MAX_DIAGS, _osum, _oneg, _product_plan, _collapse_plan, _fnma_scan)
from amgcl_tpu.parallel.mesh import ROWS_AXIS, put_with_sharding
from amgcl_tpu.parallel.dist_matrix import (dist_inner_product,
                                            dia_halo_mv as _dia_halo_mv)


def _halo_extend(arr, w):
    """(ndiag, nl) -> (ndiag, nl + 2w): ring halo over the rows axis;
    boundary shards see zeros (global zero-fill shift semantics)."""
    if w == 0:
        return arr
    nd = _axis_size(ROWS_AXIS)
    if nd == 1:
        return jnp.pad(arr, ((0, 0), (w, w)))
    fwd = [(i, i + 1) for i in range(nd - 1)]
    bwd = [(i + 1, i) for i in range(nd - 1)]
    prev_tail = lax.ppermute(arr[:, -w:], ROWS_AXIS, fwd)
    next_head = lax.ppermute(arr[:, :w], ROWS_AXIS, bwd)
    return jnp.concatenate([prev_tail, arr, next_head], axis=1)




def _build_fused_slab(mesh, adata, mdata, mtdata, scale, a_flats, m_flats,
                      mt_flats, ldims, lcoarse, blocks, npre=1):
    """FusedSlab for an eligible sharded stencil level, else None.

    Same eligibility logic as the single-chip builders (the shared
    geometry helpers in ops/pallas_vcycle.py) evaluated on the LOCAL
    slab, plus the ring constraint: every frame must be fillable by ONE
    neighbor hop (frame halo ≤ slab size). Matrix/scale frames are
    built once here via a shard_map'd halo extend; vectors are framed
    per cycle. The down frames are only built when ``npre == 1`` (the
    only cycle entry the zero-guess slab kernel serves)."""
    import functools
    from amgcl_tpu.ops.pallas_spmv import pallas_mode
    from amgcl_tpu.ops import pallas_vcycle as pv

    lz, d1, d0 = (int(x) for x in ldims)
    cz, c1, c0 = (int(x) for x in lcoarse)
    if tuple(blocks) != (2, 2, 2) or not a_flats or not mt_flats \
            or not m_flats:
        return None
    k = 128 // d0 if d0 and 128 % d0 == 0 else 0
    s = d1 * d0
    if (not k) or d0 % 2 or d1 % 2 or (k > 1 and d1 % k) or s % 512 \
            or lz % 2 or lz < 2:
        return None
    dt = jnp.dtype(jnp.float32)
    interpret = pallas_mode(dt)
    if interpret is None:
        return None
    nl = lz * s
    nA, nMt, nM = len(a_flats), len(mt_flats), len(m_flats)
    H, _, vmem_dn = pv.down_geometry(a_flats, mt_flats, ldims)
    down_ok = (npre == 1 and H <= nl
               and vmem_dn * dt.itemsize <= pv._VMEM_CAP_BYTES)
    hp, _, vmem_up = pv.up_geometry(a_flats, m_flats, ldims)
    up_ok = (hp <= 2 and hp <= cz and hp * 2 * s <= nl
             and vmem_up * dt.itemsize <= pv._VMEM_CAP_BYTES)
    if not (down_ok or up_ok):
        return None

    L = nl + 2 * H
    Lm = nl + 2 * hp * 2 * s
    _, fv, cv = pv._pack_shape(d1, d0, c1, c0)
    if not interpret and down_ok:
        key = ("slab_dn", tuple(a_flats), tuple(mt_flats),
               tuple(ldims), tuple(lcoarse), H)
        if key not in _SLAB_PROBE:
            try:
                av = jax.ShapeDtypeStruct((nA * L,), dt)
                mv = jax.ShapeDtypeStruct((nMt * L,), dt)
                ra = jax.ShapeDtypeStruct((cv[0], fv[0]), dt)
                rb = jax.ShapeDtypeStruct((fv[1], cv[1]), dt)
                fvec = jax.ShapeDtypeStruct((L,), dt)
                jax.jit(functools.partial(
                    pv.fused_down_sweep, offs_a=tuple(a_flats),
                    offs_m=tuple(mt_flats), dims=tuple(ldims),
                    coarse=tuple(lcoarse), H=H, zero_guess=True,
                    framed=True)).lower(
                        av, mv, ra, rb, fvec, fvec).compile()
                _SLAB_PROBE[key] = True
            except Exception:
                _SLAB_PROBE[key] = False
        down_ok = _SLAB_PROBE[key]
    if not interpret and up_ok:
        key = ("slab_up", tuple(a_flats), tuple(m_flats),
               tuple(ldims), tuple(lcoarse), hp)
        if key not in _SLAB_PROBE:
            try:
                av = jax.ShapeDtypeStruct((nA, nl), dt)
                mv = jax.ShapeDtypeStruct((nM * Lm,), dt)
                ea = jax.ShapeDtypeStruct((fv[0], cv[0]), dt)
                eb = jax.ShapeDtypeStruct((cv[1], fv[1]), dt)
                rv = jax.ShapeDtypeStruct(
                    (cz + 2 * hp, cv[0], cv[1]), dt)
                fvec = jax.ShapeDtypeStruct((nl,), dt)
                uv = jax.ShapeDtypeStruct((nl + 2 * hp * 2 * s,), dt)
                jax.jit(functools.partial(
                    pv.fused_up_sweep, offs_a=tuple(a_flats),
                    offs_m=tuple(m_flats), dims=tuple(ldims),
                    coarse=tuple(lcoarse), halo_planes=hp,
                    framed=True)).lower(
                        av, mv, ea, eb, rv, fvec, fvec, uv).compile()
                _SLAB_PROBE[key] = True
            except Exception:
                _SLAB_PROBE[key] = False
        up_ok = _SLAB_PROBE[key]
    if not (down_ok or up_ok):
        return None

    if k == 1:
        red_a = pv._pair_sum(c1, d1, dt)
        red_b = pv._pair_sum(c0, d0, dt).T
        exp_a, exp_b = red_a.T, red_b.T
    else:
        red_a = jnp.eye(fv[0], dtype=dt)
        red_b = pv._packed_reduce(d0, k, c0, dt)
        exp_a, exp_b = red_a, red_b.T

    def body(ad, mtd, md, sc):
        outs = ()
        if down_ok:
            outs = (_halo_extend(ad, H)[None], _halo_extend(mtd, H)[None],
                    _halo_extend(sc[None], H)[0][None])
        if up_ok:
            outs = outs + (_halo_extend(md, hp * 2 * s)[None],)
        return outs

    out_specs = ()
    if down_ok:
        out_specs = (P(ROWS_AXIS, None, None), P(ROWS_AXIS, None, None),
                     P(ROWS_AXIS, None))
    if up_ok:
        out_specs = out_specs + (P(ROWS_AXIS, None, None),)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None, ROWS_AXIS), P(None, ROWS_AXIS),
                             P(None, ROWS_AXIS), P(ROWS_AXIS)),
                   out_specs=out_specs, check_vma=False)
    got = list(jax.jit(fn)(adata, mtdata, mdata, scale))
    a_fr, mt_fr, w_fr = (got[:3] if down_ok else (None, None, None))
    m_fr = got[-1] if up_ok else None

    if not interpret:
        # real-hardware value check vs the composed slab chain — the
        # slab shapes (thin lz, H == nl windows) are never exercised by
        # the single-chip checks, and a silent Mosaic miscompute here
        # would corrupt the distributed preconditioner with no fallback
        afl, mfl, mtfl = tuple(a_flats), tuple(m_flats), tuple(mt_flats)
        # grid-plan-only level instance: reuses t_mv/t_rmv instead of
        # re-inlining the tentative reshape chains
        plan = DistStencilLevel(None, None, None, None, afl, mfl, mtfl,
                                ldims, lcoarse, blocks)
        frames = []
        frame_specs = []
        if down_ok:
            frames += [a_fr, mt_fr, w_fr]
            frame_specs += [P(ROWS_AXIS, None, None)] * 2 \
                + [P(ROWS_AXIS, None)]
        if up_ok:
            frames.append(m_fr)
            frame_specs.append(P(ROWS_AXIS, None, None))

        def chk(ad, mtd, md, sc, f_l, *fr):
            u_ref = sc * f_l
            outs = ()
            if down_ok:
                afr, mtfr, wfr = fr[:3]
                r = f_l - _dia_halo_mv(ad, afl, u_ref)
                t = r - _dia_halo_mv(mtd, mtfl, r)
                fc_ref = plan.t_rmv(t)
                f_fr = _halo_extend(f_l[None], H)[0]
                rc3, u_z = pv.fused_down_sweep(
                    afr[0].reshape(-1), mtfr[0].reshape(-1),
                    red_a, red_b, f_fr, wfr[0],
                    offs_a=afl, offs_m=mtfl, dims=ldims, coarse=lcoarse,
                    H=H, zero_guess=True, framed=True)
                outs = (fc_ref, rc3.reshape(-1), u_ref, u_z)
            if up_ok:
                mfr = fr[-1]
                uc = plan.t_rmv(f_l)
                tt = plan.t_mv(uc)
                u1 = u_ref + tt - _dia_halo_mv(md, mfl, tt)
                u2_ref = u1 + sc * (f_l - _dia_halo_mv(ad, afl, u1))
                uc_fr = _halo_extend(uc[None], hp * c1 * c0)[0]
                rc3p = uc_fr.reshape(cz + 2 * hp, cv[0], cv[1])
                u_fr = _halo_extend(u_ref[None], hp * 2 * s)[0]
                u2 = pv.fused_up_sweep(
                    ad, mfr[0].reshape(-1), exp_a, exp_b, rc3p, f_l,
                    sc, u_fr, offs_a=afl, offs_m=mfl, dims=ldims,
                    coarse=lcoarse, halo_planes=hp, framed=True)
                outs = outs + (u2_ref, u2)
            return outs

        n_out = (4 if down_ok else 0) + (2 if up_ok else 0)
        cfn = shard_map(
            chk, mesh=mesh,
            in_specs=(P(None, ROWS_AXIS), P(None, ROWS_AXIS),
                      P(None, ROWS_AXIS), P(ROWS_AXIS), P(ROWS_AXIS))
            + tuple(frame_specs),
            out_specs=(P(ROWS_AXIS),) * n_out, check_vma=False)
        rng = np.random.RandomState(23)
        fprobe = put_with_sharding(
            rng.rand(adata.shape[1]).astype(np.float32),
            NamedSharding(mesh, P(ROWS_AXIS)))
        vals = jax.jit(cfn)(adata, mtdata, mdata, scale, fprobe, *frames)
        i = 0
        if down_ok:
            ok = pv._values_agree(vals[1], vals[0], dt) \
                and pv._values_agree(vals[3], vals[2], dt)
            if not ok:
                down_ok = False
                a_fr = mt_fr = w_fr = None
            i = 4
        if up_ok and not pv._values_agree(vals[i + 1], vals[i], dt):
            up_ok = False
            m_fr = None
        if not (down_ok or up_ok):
            return None

    return FusedSlab(
        a_fr, mt_fr, w_fr, m_fr,
        red_a, red_b, exp_a if up_ok else None,
        exp_b if up_ok else None, H, hp, ldims, lcoarse,
        a_flats, mt_flats, m_flats, interpret)


_SLAB_PROBE = {}


# -- sharded per-level setup program -----------------------------------------

def _sharded_level_setup(adata_l, eps_strong, relax_scale, smoother_omega,
                         offs, gdims, lz, blocks, coarse, relax_kind):
    """One hierarchy level on the mesh (runs INSIDE shard_map). Mirrors
    ops/stencil_device._level_setup with halo shifts and psum/pmax
    reductions. adata_l: (ndiag, nl) local slab; gdims global; lz local
    z-planes. Returns (m_l, mt_l, ac_l, scale_l, counts, axis_strong)."""
    d2, d1, d0 = gdims
    nl = adata_l.shape[1]
    dt = adata_l.dtype
    offs = list(offs)
    eps2 = (eps_strong * eps_strong).astype(dt)

    flats = [_flat(o, gdims) for o in offs]
    hmax = max(max(abs(f) for f in flats), 1)

    main_k = offs.index((0, 0, 0)) if (0, 0, 0) in offs else None
    dia = jnp.abs(adata_l[main_k]) if main_k is not None \
        else jnp.zeros((nl,), dt)
    dia_ext = _halo_extend(dia[None], hmax)[0]
    af_rows = [None] * len(offs)
    lump = jnp.zeros((nl,), dt)
    for k, o in enumerate(offs):
        if k == main_k:
            continue
        a = adata_l[k]
        dj = lax.dynamic_slice(dia_ext, (hmax + flats[k],), (nl,))
        strong = (a * a) > (eps2 * dia * dj)
        af_rows[k] = jnp.where(strong, a, dt.type(0))
        lump = lump + jnp.where(strong, dt.type(0), a)
    main = (adata_l[main_k] if main_k is not None
            else jnp.zeros((nl,), dt)) + lump
    if main_k is not None:
        af_rows[main_k] = main
        af_offs = list(offs)
    else:
        af_rows.append(main)
        af_offs = list(offs) + [(0, 0, 0)]
    af = jnp.stack(af_rows)
    dinv = jnp.where(main != 0, 1.0 / jnp.where(main != 0, main, 1),
                     1.0).astype(dt)

    axis_strong = []
    for ax in range(3):
        tot = jnp.zeros((), jnp.float32)
        for k, o in enumerate(af_offs):
            if [i for i, c in enumerate(o) if c != 0] == [ax]:
                tot = tot + jnp.count_nonzero(af[k]).astype(jnp.float32)
        axis_strong.append(lax.psum(tot, ROWS_AXIS))
    axis_strong = jnp.stack(axis_strong)

    rho = lax.pmax(
        jnp.max(jnp.abs(dinv) * jnp.sum(jnp.abs(af), axis=0)), ROWS_AXIS)
    omega = (relax_scale.astype(dt) * dt.type(4.0 / 3.0)
             / jnp.maximum(rho, dt.type(1e-30)))

    m = af * (dinv * omega)[None, :]
    af_flats = [_flat(o, gdims) for o in af_offs]
    hm = max(max(abs(f) for f in af_flats), 1)
    m_ext = _halo_extend(m, hm)
    mt = jnp.stack([
        lax.dynamic_slice(m_ext, (k, hm + _flat(_oneg(o), gdims)),
                          (1, nl))[0]
        for k, o in enumerate(af_offs)])
    mt_offs = [_oneg(o) for o in af_offs]

    # X = A - A·M ; S = X - Mt·X (scan pair products over halo'd sources)
    x_offs, _, _ = _product_plan(offs, af_offs, gdims)
    x_idx = {o: k for k, o in enumerate(x_offs)}
    a_slots = np.asarray([x_idx[o] for o in offs], np.int32)
    X = jnp.zeros((len(x_offs), nl), dt).at[a_slots].set(adata_l)
    x_pairs = [(ka, kb, _flat(oa, gdims), x_idx[_osum(oa, ob)])
               for ka, oa in enumerate(offs)
               for kb, ob in enumerate(af_offs)]
    pad_m = max(max(abs(p[2]) for p in x_pairs), 1)
    X = _fnma_scan(X, adata_l, _halo_extend(m, pad_m), x_pairs, pad_m, nl)

    s_offs, s_embed, s_pairs = _product_plan(mt_offs, x_offs, gdims)
    S = jnp.zeros((len(s_offs), nl), dt) \
        .at[np.asarray(s_embed, np.int32)].set(X)
    pad_x = max(max(abs(p[2]) for p in s_pairs), 1)
    S = _fnma_scan(S, mt, _halo_extend(X, pad_x), s_pairs, pad_x, nl)

    # collapse on the LOCAL slab (aligned with the 2x z-blocks)
    c_offs, parities, table = _collapse_plan(s_offs, gdims, blocks, coarse)
    b2, b1, b0 = blocks
    c2, c1, c0 = coarse
    lcz = lz // b2 if b2 > 1 else lz
    dims_p = (lcz * b2, c1 * b1, c0 * b0)
    n_cl = lcz * c1 * c0
    acc0 = jnp.zeros((len(c_offs), n_cl), dt)

    def cbody(acc, inp):
        row, slots = inp
        v3 = row.reshape(lz, d1, d0)
        if dims_p != (lz, d1, d0):
            v3 = jnp.pad(v3, ((0, dims_p[0] - lz), (0, dims_p[1] - d1),
                              (0, dims_p[2] - d0)))
        for j, (pz, py, px) in enumerate(parities):
            sl = v3[pz::b2, py::b1, px::b0].reshape(-1)
            acc = acc.at[slots[j]].add(sl)
        return acc, None

    ac_l, _ = lax.scan(cbody, acc0, (S, jnp.asarray(table)))
    counts = lax.psum(
        jnp.sum(ac_l != 0, axis=1).astype(jnp.int32), ROWS_AXIS)

    d0v = adata_l[main_k] if main_k is not None else jnp.ones((nl,), dt)
    if relax_kind == "spai0":
        denom = jnp.sum(adata_l * adata_l, axis=0)
        scale = d0v / jnp.where(denom != 0, denom, 1)
    else:
        scale = smoother_omega.astype(dt) * jnp.where(
            d0v != 0, 1.0 / jnp.where(d0v != 0, d0v, 1), 0.0).astype(dt)
    return m, mt, ac_l, scale, counts, axis_strong


# -- sharded hierarchy + solve -----------------------------------------------

@register_pytree_node_class
class FusedSlab:
    """Per-shard framed operands for the fused V-cycle kernels
    (ops/pallas_vcycle.py) on a distributed stencil level.

    The single-chip kernels' zero frames become halo frames filled with
    REAL neighbor-slab values at build time (matrix data, smoother
    scale — static per solve) or per cycle (f, u, uc — one
    ``_halo_extend`` ppermute each, replacing the per-op exchanges of
    the composed slab chain). The flat offsets are identical on the
    slab because shards split whole z-planes."""

    def __init__(self, a_fr, mt_fr, w_fr, m_fr, red_a, red_b, exp_a,
                 exp_b, H, hp, ldims, lcoarse, offs_a, offs_mt, offs_m,
                 interpret):
        self.a_fr = a_fr        # (nd, nA, L) sharded: framed A diagonals
        self.mt_fr = mt_fr      # (nd, nMt, L): framed Mᵀ diagonals
        self.w_fr = w_fr        # (nd, L): framed smoother scale
        self.m_fr = m_fr        # (nd, nM, Lm) or None: framed M (up)
        self.red_a = red_a
        self.red_b = red_b
        self.exp_a = exp_a      # None when the up direction is gated
        self.exp_b = exp_b
        self.H = int(H)
        self.hp = int(hp)
        self.ldims = tuple(int(d) for d in ldims)
        self.lcoarse = tuple(int(c) for c in lcoarse)
        self.offs_a = tuple(int(o) for o in offs_a)
        self.offs_mt = tuple(int(o) for o in offs_mt)
        self.offs_m = tuple(int(o) for o in offs_m)
        self.interpret = bool(interpret)

    @property
    def up_ok(self):
        return self.m_fr is not None

    def tree_flatten(self):
        return ((self.a_fr, self.mt_fr, self.w_fr, self.m_fr,
                 self.red_a, self.red_b, self.exp_a, self.exp_b),
                (self.H, self.hp, self.ldims, self.lcoarse, self.offs_a,
                 self.offs_mt, self.offs_m, self.interpret))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def spec(self):
        sh3 = P(ROWS_AXIS, None, None)
        opt = lambda v, sp: None if v is None else sp
        return FusedSlab(
            opt(self.a_fr, sh3), opt(self.mt_fr, sh3),
            opt(self.w_fr, P(ROWS_AXIS, None)), opt(self.m_fr, sh3),
            P(), P(), opt(self.exp_a, P()), opt(self.exp_b, P()),
            self.H, self.hp, self.ldims, self.lcoarse, self.offs_a,
            self.offs_mt, self.offs_m, self.interpret)


@register_pytree_node_class
class DistStencilLevel:
    """One sharded level: local slabs of the operator/smoother/transfer
    diagonals plus the static grid plan."""

    def __init__(self, adata, scale, mdata, mtdata, a_flats, m_flats,
                 mt_flats, ldims, lcoarse, blocks, fused=None):
        self.adata = adata          # (ndiag, nl) sharded
        self.scale = scale          # (nl,) sharded
        self.mdata = mdata
        self.mtdata = mtdata
        self.a_flats = tuple(a_flats)     # GLOBAL flat offsets
        self.m_flats = tuple(m_flats)
        self.mt_flats = tuple(mt_flats)
        self.ldims = tuple(ldims)         # local slab dims (lz, d1, d0)
        self.lcoarse = tuple(lcoarse)     # local coarse dims
        self.blocks = tuple(blocks)
        self.fused = fused                # FusedSlab or None

    def tree_flatten(self):
        return ((self.adata, self.scale, self.mdata, self.mtdata,
                 self.fused),
                (self.a_flats, self.m_flats, self.mt_flats, self.ldims,
                 self.lcoarse, self.blocks))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], children[3],
                   *aux, fused=children[4])

    # tentative transfer over the local slab (GridTentative logic)
    def t_mv(self, uc):
        (lz, d1, d0), (cz, c1, c0), (b2, b1, b0) = \
            self.ldims, self.lcoarse, self.blocks
        u = uc.reshape(cz, 1, c1, 1, c0, 1)
        u = jnp.broadcast_to(u, (cz, b2, c1, b1, c0, b0))
        u = u.reshape(cz * b2, c1 * b1, c0 * b0)
        return u[:lz, :d1, :d0].reshape(-1)

    def t_rmv(self, v):
        (lz, d1, d0), (cz, c1, c0), (b2, b1, b0) = \
            self.ldims, self.lcoarse, self.blocks
        v3 = v.reshape(lz, d1, d0)
        if (cz * b2, c1 * b1, c0 * b0) != (lz, d1, d0):
            v3 = jnp.pad(v3, ((0, cz * b2 - lz), (0, c1 * b1 - d1),
                              (0, c0 * b0 - d0)))
        v6 = v3.reshape(cz, b2, c1, b1, c0, b0)
        return v6.sum(axis=(1, 3, 5)).reshape(-1)


@register_pytree_node_class
class DistStencilHierarchy:
    """Sharded stencil levels + replicated serial tail."""

    def __init__(self, levels, rep_hier, n_rep, npre=1, npost=1):
        self.levels = list(levels)
        self.rep_hier = rep_hier      # serial Hierarchy, replicated
        self.n_rep = int(n_rep)       # true rows of the replicated top
        self.npre = int(npre)
        self.npost = int(npost)

    def tree_flatten(self):
        return ((self.levels, self.rep_hier),
                (self.n_rep, self.npre, self.npost))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def specs(self):
        specs_levels = []
        for lv in self.levels:
            specs_levels.append(DistStencilLevel(
                P(None, ROWS_AXIS), P(ROWS_AXIS), P(None, ROWS_AXIS),
                P(None, ROWS_AXIS), lv.a_flats, lv.m_flats, lv.mt_flats,
                lv.ldims, lv.lcoarse, lv.blocks,
                None if lv.fused is None else lv.fused.spec()))
        rep = jax.tree.map(lambda _: P(), self.rep_hier)
        return DistStencilHierarchy(specs_levels, rep, self.n_rep,
                                    self.npre, self.npost)

    def shard_cycle(self, i, f):
        if i == len(self.levels):
            # replicated tail: gather, serial hierarchy apply, slice local
            nd = _axis_size(ROWS_AXIS)
            idx = lax.axis_index(ROWS_AXIS)
            nl = f.shape[0]
            full = lax.all_gather(f, ROWS_AXIS, tiled=True)[:self.n_rep]
            u = self.rep_hier.apply(full)
            u = jnp.pad(u, (0, nl * nd - self.n_rep))
            return lax.dynamic_slice(u, (idx * nl,), (nl,))
        lv = self.levels[i]
        amv = partial(_dia_halo_mv, lv.adata, lv.a_flats)
        fz = lv.fused
        if fz is not None and fz.a_fr is not None and self.npre == 1:
            # whole down-sweep as one per-shard kernel on halo frames
            from amgcl_tpu.ops.pallas_vcycle import fused_down_sweep
            f_fr = _halo_extend(f[None], fz.H)[0]
            rc3, u = fused_down_sweep(
                fz.a_fr[0].reshape(-1), fz.mt_fr[0].reshape(-1),
                fz.red_a, fz.red_b, f_fr, fz.w_fr[0],
                offs_a=fz.offs_a, offs_m=fz.offs_mt, dims=fz.ldims,
                coarse=fz.lcoarse, H=fz.H, zero_guess=True, framed=True,
                interpret=fz.interpret)
            fc = rc3.reshape(-1)
        else:
            u = lv.scale * f
            for _ in range(self.npre - 1):
                u = u + lv.scale * (f - amv(u))
            r = f - amv(u)
            # restrict: fc = T^T (r - M^T r)
            t = r - _dia_halo_mv(lv.mtdata, lv.mt_flats, r)
            fc = lv.t_rmv(t)
        uc = self.shard_cycle(i + 1, fc)
        if fz is not None and fz.up_ok and self.npost >= 1:
            # prolong + correct + first post-sweep as one kernel
            from amgcl_tpu.ops.pallas_vcycle import (fused_up_sweep,
                                                     _pack_shape)
            cz, pc1xpc0 = fz.lcoarse[0], fz.lcoarse[1] * fz.lcoarse[2]
            _, _, cv = _pack_shape(fz.ldims[1], fz.ldims[2],
                                   fz.lcoarse[1], fz.lcoarse[2])
            uc_fr = _halo_extend(uc[None], fz.hp * pc1xpc0)[0]
            rc3p = uc_fr.reshape(cz + 2 * fz.hp, cv[0], cv[1])
            s2 = 2 * fz.ldims[1] * fz.ldims[2]
            u_fr = _halo_extend(u[None], fz.hp * s2)[0]
            u = fused_up_sweep(
                lv.adata, fz.m_fr[0].reshape(-1), fz.exp_a, fz.exp_b,
                rc3p, f, lv.scale, u_fr,
                offs_a=fz.offs_a, offs_m=fz.offs_m, dims=fz.ldims,
                coarse=fz.lcoarse, halo_planes=fz.hp, framed=True,
                interpret=fz.interpret)
            extra = self.npost - 1
        else:
            t = lv.t_mv(uc)
            u = u + t - _dia_halo_mv(lv.mdata, lv.m_flats, t)
            extra = self.npost
        for _ in range(extra):
            u = u + lv.scale * (f - amv(u))
        return u

    def shard_apply(self, r):
        return self.shard_cycle(0, r)


class DistStencilSolver:
    """AMG-preconditioned CG on a mesh with DISTRIBUTED hierarchy
    construction for stencil problems. ``DistStencilSolver(A, mesh, prm,
    solver)`` then ``x, info = s(rhs)``."""

    def __init__(self, A, mesh, prm=None, solver: Any = None,
                 rep_coarse_enough: int = 3000):
        from amgcl_tpu.models.amg import AMGParams
        if not isinstance(A, CSR):
            A = CSR.from_scipy(A)
        self.mesh = mesh
        self.prm = prm or AMGParams()
        self.solver = solver
        got = dist_stencil_build(A, mesh, self.prm, rep_coarse_enough)
        if got is None:
            raise ValueError(
                "matrix/config outside the sharded stencil fast path "
                "(needs a structured grid with z-extent divisible by "
                "2x mesh, scalar real f32, SA + spai0/jacobi)")
        self.hier, self.meta = got
        self.n = A.nrows
        self._compiled = None

    def __call__(self, rhs, x0=None):
        import jax.numpy as jnp
        from amgcl_tpu.models.make_solver import SolverInfo
        nd = self.mesh.shape[ROWS_AXIS]
        maxiter = getattr(self.solver, "maxiter", 100) if self.solver \
            else 100
        tol = getattr(self.solver, "tol", 1e-6) if self.solver else 1e-6
        vec = NamedSharding(self.mesh, P(ROWS_AXIS))
        rhs = np.asarray(rhs, np.float32)
        # levels[0].adata.shape is GLOBAL (the sharding is in the array's
        # layout, not its logical shape)
        rhs_p = np.pad(rhs, (0, self.hier.levels[0].adata.shape[1]
                             - len(rhs)))
        f = put_with_sharding(rhs_p, vec)
        x0p = jnp.zeros_like(f) if x0 is None else put_with_sharding(
            np.pad(np.asarray(x0, np.float32),
                   (0, len(rhs_p) - len(rhs))), vec)
        if self._compiled is None:
            hier_specs = self.hier.specs()

            def body(hier, f, x):
                dot = dist_inner_product
                lv0 = hier.levels[0]
                amv = partial(_dia_halo_mv, lv0.adata, lv0.a_flats)
                r = f - amv(x)
                nb = jnp.sqrt(jnp.abs(dot(f, f)))
                scale = jnp.where(nb > 0, nb, 1.0)
                eps = tol * scale

                def cond(st):
                    return (st[4] < maxiter) & (st[5] > eps)

                def it(st):
                    x, r, p, rho_p, k, res = st
                    s = hier.shard_apply(r)
                    rho = dot(r, s)
                    beta = jnp.where(rho_p == 0, 0.0, rho / rho_p)
                    p = s + beta * p
                    q = amv(p)
                    alpha = rho / dot(q, p)
                    x = x + alpha * p
                    r = r - alpha * q
                    return (x, r, p, rho, k + 1,
                            jnp.sqrt(jnp.abs(dot(r, r))))

                st = (x, r, jnp.zeros_like(r), jnp.zeros((), f.dtype), 0,
                      jnp.sqrt(jnp.abs(dot(r, r))))
                x, r, p, rho, k, res = lax.while_loop(cond, it, st)
                return x, k, res / scale

            fn = shard_map(
                body, mesh=self.mesh,
                in_specs=(hier_specs, P(ROWS_AXIS), P(ROWS_AXIS)),
                out_specs=(P(ROWS_AXIS), P(), P()),
                check_vma=False)
            # observed jit (telemetry/compile_watch.py): the stencil
            # solver's whole-mesh CG program is a repeat-solve entry
            # point like dist_cg
            from amgcl_tpu.telemetry.compile_watch import watched_jit
            self._compiled = watched_jit(fn,
                                         name="parallel.dist_stencil_cg")
        x, it, res = self._compiled(self.hier, f, x0p)
        x = np.asarray(x)[: self.n]
        from amgcl_tpu.telemetry import emit as _tel_emit
        info = SolverInfo(int(it), float(res), solver="dist_stencil_cg",
                          extra={"devices": int(nd)})
        _tel_emit(info.to_dict(), event="dist_solve", n=self.n)
        return x, info

    def __repr__(self):
        rows = ["DistStencilSolver over %d devices (sharded setup)"
                % self.mesh.shape[ROWS_AXIS]]
        for i, m in enumerate(self.meta):
            rows.append("%5d %12d" % (i, m))
        return "\n".join(rows)


def dist_stencil_build(A: CSR, mesh, prm, rep_coarse_enough: int = 3000):
    """Sharded hierarchy construction. Returns (DistStencilHierarchy,
    per-level row counts) or None when outside the fast path."""
    from amgcl_tpu.coarsening.smoothed_aggregation import \
        SmoothedAggregation
    from amgcl_tpu.relaxation.spai0 import Spai0
    from amgcl_tpu.relaxation.jacobi import DampedJacobi
    from amgcl_tpu.ops.structured import detect_grid_csr
    from amgcl_tpu.models.amg import AMG, AMGParams

    c = prm.coarsening
    if type(c) is not SmoothedAggregation:
        return None
    if (c.nullspace is not None or c.aggregator is not None
            or c.block_size != 1 or c.power_iters):
        return None
    if A.is_block or np.iscomplexobj(A.val):
        return None
    if jnp.dtype(prm.dtype) != jnp.dtype(jnp.float32):
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
    nd = mesh.shape[ROWS_AXIS]
    d2, d1, d0 = grid
    if d2 % (2 * nd) != 0:
        return None
    Ad = host_dia_from_csr(A, grid, np.float32)
    if Ad is None or len(Ad.offsets3) > _MAX_DIAGS:
        return None

    dims = tuple(grid)
    offs = list(Ad.offsets3)
    sh_mat = NamedSharding(mesh, P(None, ROWS_AXIS))
    adata = put_with_sharding(np.ascontiguousarray(Ad.data), sh_mat)
    eps = float(c.eps_strong)
    n = int(np.prod(dims))
    meta = [n]
    levels = []

    while True:
        d2 = dims[0]
        lz = d2 // nd
        n = int(np.prod(dims))
        # z must split evenly over the mesh; z-COARSENING additionally
        # needs an even local slab (zb below) — semicoarsening in x/y
        # alone works with any lz
        if (n <= rep_coarse_enough or len(offs) > _MAX_DIAGS
                or d2 % nd != 0):
            break
        # Halo-width guard: _halo_extend ships w elements across ONE ring
        # hop, so w must not exceed the local slab (w > nl would make
        # arr[:, -w:] silently clamp, and a coupling reaching past the
        # immediate neighbour needs rows one ring hop cannot supply).  All
        # halo widths used inside _sharded_level_setup derive from
        # |flat(o)| over offs / af_offs / mt_offs, whose magnitudes
        # coincide with offs + the main diagonal.
        nl_guard = lz * dims[1] * dims[2]
        hmax_l = max(max(abs(_flat(o, dims)) for o in offs), 1)
        if hmax_l > nl_guard:
            break
        zb = 2 if dims[0] > 1 and lz % 2 == 0 else 1
        blocks = (zb, 2 if dims[1] > 1 else 1, 2 if dims[2] > 1 else 1)
        if all(b == 1 for b in blocks):
            break
        coarse = tuple(-(-d // b) for d, b in zip(dims, blocks))

        def run_setup(blocks, coarse):
            fn = shard_map(
                partial(_sharded_level_setup,
                        offs=tuple(offs), gdims=dims, lz=lz, blocks=blocks,
                        coarse=coarse, relax_kind=relax_kind),
                mesh=mesh,
                in_specs=(P(None, ROWS_AXIS), P(), P(), P()),
                out_specs=(P(None, ROWS_AXIS), P(None, ROWS_AXIS),
                           P(None, ROWS_AXIS), P(ROWS_AXIS), P(), P()),
                check_vma=False)
            return jax.jit(fn)(adata, jnp.float32(eps),
                               jnp.float32(c.relax), jnp.float32(sm_omega))

        m, mt, ac, scale, counts, axis_strong = run_setup(blocks, coarse)
        counts_h, axis_h = jax.device_get((counts, axis_strong))
        want = tuple(
            min(2, dims[i]) if dims[i] > 1 and axis_h[i] >= 0.5 * n else 1
            for i in range(3))
        if want != blocks:
            # semicoarsening: rerun with the measured strong axes (as the
            # device path does, ops/stencil_device.py). z-coarsening a
            # strong z-axis with an odd local slab is not expressible on
            # this mesh — fall back to the replicated tail.
            if all(b == 1 for b in want) or (want[0] == 2 and zb == 1):
                if not levels:
                    return None
                break
            blocks = want
            coarse = tuple(-(-d // b) for d, b in zip(dims, blocks))
            m, mt, ac, scale, counts, _ = run_setup(blocks, coarse)
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
        ac = ac[jnp.asarray(keep)]

        a_fl = [_flat(o, dims) for o in offs]
        m_fl = [_flat(o, dims) for o in af_offs]
        mt_fl = [_flat(o, dims) for o in mt_offs]
        ld = (lz, dims[1], dims[2])
        lc = (lz // 2 if blocks[0] > 1 else lz, coarse[1], coarse[2])
        levels.append(DistStencilLevel(
            adata, scale, m, mt, a_fl, m_fl, mt_fl, ld, lc, blocks,
            fused=_build_fused_slab(mesh, adata, m, mt, scale, a_fl,
                                    m_fl, mt_fl, ld, lc, blocks,
                                    npre=prm.npre)))
        adata, offs, dims = ac, new_offs, coarse
        meta.append(int(np.prod(dims)))
        eps *= 0.5

    if not levels:
        return None
    # replicated serial tail from the gathered coarse level (the
    # repartition-merge analogue: few rows -> one "rank")
    Hl = HostDia(offs, np.asarray(jax.device_get(adata)), dims)
    Acsr = Hl.to_csr()
    from dataclasses import replace as _dc_replace
    prm2 = _dc_replace(
        prm, coarsening=SmoothedAggregation(eps_strong=eps,
                                            relax=c.relax),
        dtype=jnp.float32)
    rep_amg = AMG(Acsr, prm2)
    hier = DistStencilHierarchy(levels, rep_amg.hierarchy, Acsr.nrows,
                                prm.npre, prm.npost)
    return hier, meta
