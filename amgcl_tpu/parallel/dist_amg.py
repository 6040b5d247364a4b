"""Distributed AMG: serial host construction, mesh-sharded solve.

Architecture decision (vs the reference's mpi::amg,
amgcl/mpi/amg.hpp:49-511): under single-controller JAX the host sees the
whole matrix, so the hierarchy is built once by the serial setup path (the
reference's pattern — hierarchies are always *built* on the CPU and *moved*
to the backend, README.md:22-26) and every level is then partitioned over
the mesh: level operators and transfer operators become
:class:`DistEllMatrix` with static halo plans, smoother state is sharded by
rows, and the coarsest dense solve is replicated (every shard applies the
same small inverse to the all-gathered coarse residual — the TPU equivalent
of the gather-to-masters coarse solve,
amgcl/mpi/direct_solver/solver_base.hpp:41-130).

The Krylov loop reuses the *serial* solver classes inside ``shard_map``,
exactly the reference's trick of pairing serial Krylov bodies with a
distributed matrix and a globalized inner product
(amgcl/mpi/solver/cg.hpp:41-46): the local operator adapter exposes ``.mv``
(halo exchange + local SpMV) and the inner product is psum-reduced.
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
from amgcl_tpu.models.amg import AMG, AMGParams
from amgcl_tpu.models.make_solver import SolverInfo
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.parallel.mesh import ROWS_AXIS, put_sharded
from amgcl_tpu.parallel.dist_ell import (DistEllMatrix,
    build_dist_ell, pack_rows_ell)
from amgcl_tpu.parallel.dist_matrix import dist_inner_product


def _pad_vec(v, nloc, nd, dtype):
    host_dt = np.complex128 if jnp.issubdtype(
        jnp.dtype(dtype), jnp.complexfloating) else np.float64
    out = np.zeros(nloc * nd, dtype=host_dt)
    out[:len(v)] = np.asarray(v, dtype=host_dt)
    return out.astype(np.dtype(dtype))   # stays numpy: see mesh.put_sharded


@register_pytree_node_class
class DistSmoother:
    """Sharded smoother state, one of five kinds (reference role: the
    mpi::relaxation::* wrapper set, amgcl/mpi/relaxation/*.hpp — except
    these shard the GLOBAL smoother state with halo plans instead of
    factoring rank-local blocks, so distributed math == serial math):

      'diag'  — per-row scale (spai0 / damped_jacobi)
      'bdiag' — per-node block scale (block spai0 / block jacobi);
                scale is (nd, ncell_loc, b, b) over the scalar row layout
      'cheb'  — Chebyshev polynomial (SpMV-only, scalars static)
      'ilu'   — global Chow-Patel factors as halo-plan ELL matrices +
                sharded inverted U-diagonal; Jacobi tri-solves are plain
                halo SpMVs (amgcl/relaxation/detail/ilu_solve.hpp:44-129)
      'gs'    — multicolor Gauss-Seidel: global coloring, masks sharded
                by row, one halo SpMV per color
      'spai1' — approximate inverse as a halo-plan ELL matrix
    """

    def __init__(self, kind, scale=None, theta=0.0, delta=1.0, degree=0,
                 Ls=None, Us=None, uinv=None, jacobi_iters=2, masks=None,
                 Msp=None):
        self.kind = kind
        self.scale = scale          # (nd, nloc) or None; dinv for 'gs'
        self.theta = float(theta)
        self.delta = float(delta)
        self.degree = int(degree)
        self.Ls = Ls                # DistEllMatrix (strict lower, 'ilu')
        self.Us = Us                # DistEllMatrix (strict upper, 'ilu')
        self.uinv = uinv            # (nd, nloc) inverted U diagonal
        self.jacobi_iters = int(jacobi_iters)
        self.masks = masks          # (nd, ncolors, nloc) color masks ('gs')
        self.Msp = Msp              # DistEllMatrix approx inverse ('spai1')

    def tree_flatten(self):
        return ((self.scale, self.Ls, self.Us, self.uinv, self.masks,
                 self.Msp),
                (self.kind, self.theta, self.delta, self.degree,
                 self.jacobi_iters))

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind, theta, delta, degree, jacobi_iters = aux
        scale, Ls, Us, uinv, masks, Msp = children
        return cls(kind, scale, theta, delta, degree, Ls, Us, uinv,
                   jacobi_iters, masks, Msp)

    def spec(self):
        mat = lambda m: None if m is None else m.specs()
        vec = lambda v: None if v is None else P(
            ROWS_AXIS, *([None] * (v.ndim - 1)))
        return DistSmoother(self.kind, vec(self.scale), self.theta,
                            self.delta, self.degree, mat(self.Ls),
                            mat(self.Us), vec(self.uinv),
                            self.jacobi_iters, vec(self.masks),
                            mat(self.Msp))

    # -- inside shard_map (Aop wraps the level's halo SpMV) ----------------

    def _cheb(self, Aop, f):
        from amgcl_tpu.relaxation.chebyshev import ChebyshevState
        dinv = None if self.scale is None else self.scale[0]
        st = ChebyshevState(dinv, self.degree, self.theta, self.delta,
                            dinv is not None)
        return st.apply(Aop, f)

    def _ilu(self, f):
        from amgcl_tpu.relaxation.ilu0 import ilu_jacobi_solve
        return ilu_jacobi_solve(self.Ls.shard_mv, self.Us.shard_mv,
                                self.uinv[0], self.jacobi_iters, f)

    def _gs_sweep(self, Aop, f, u, reverse):
        masks = self.masks[0]
        dinv = self.scale[0]
        order = range(masks.shape[0] - 1, -1, -1) if reverse \
            else range(masks.shape[0])
        for c in order:
            u = u + masks[c] * (dinv * (f - Aop.mv(u)))
        return u

    def _bmul(self, f):
        b = self.scale.shape[-1]
        fb = f.reshape(-1, b)
        return jnp.einsum("nij,nj->ni", self.scale[0], fb).reshape(f.shape)

    def apply0(self, Aop, f):
        """One application from a zero initial guess."""
        if self.kind == "cheb":
            return self._cheb(Aop, f)
        if self.kind == "ilu":
            return self._ilu(f)
        if self.kind == "gs":
            return self._gs_sweep(Aop, f, jnp.zeros_like(f), False)
        if self.kind == "spai1":
            return self.Msp.shard_mv(f)
        if self.kind == "bdiag":
            return self._bmul(f)
        return self.scale[0] * f

    def sweep(self, Aop, f, u, reverse=False):
        if self.kind == "cheb":
            return u + self._cheb(Aop, f - Aop.mv(u))
        if self.kind == "ilu":
            return u + self._ilu(f - Aop.mv(u))
        if self.kind == "gs":
            return self._gs_sweep(Aop, f, u, reverse)
        if self.kind == "spai1":
            return u + self.Msp.shard_mv(f - Aop.mv(u))
        if self.kind == "bdiag":
            return u + self._bmul(f - Aop.mv(u))
        return u + self.scale[0] * (f - Aop.mv(u))


@register_pytree_node_class
class DistLevel:
    def __init__(self, A, P_op, R_op, smoother):
        self.A = A
        self.P_op = P_op        # None on the coarsest level
        self.R_op = R_op
        self.smoother = smoother

    def tree_flatten(self):
        return (self.A, self.P_op, self.R_op, self.smoother), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@register_pytree_node_class
class TransitionOps:
    """Transfers between the sharded and replicated parts of the hierarchy
    (the repartition/merge analogue: instead of shrinking to fewer ranks —
    pointless on a TPU mesh where idle chips save nothing — small levels
    are REPLICATED and every shard computes them redundantly, trading tiny
    duplicate FLOPs for zero all_to_all latency per coarse level; reference
    role: amgcl/mpi/partition/merge.hpp).

    p_cols/p_vals: (nd, nloc, K) sharded — P rows by fine shard, columns
    into the replicated coarse vector. r_cols/r_vals: (nd, nc, K) sharded —
    per-shard column-restricted R; the replicated result is the psum of the
    per-shard partial products."""

    def __init__(self, p_cols, p_vals, r_cols, r_vals):
        self.p_cols = p_cols
        self.p_vals = p_vals
        self.r_cols = r_cols
        self.r_vals = r_vals

    def tree_flatten(self):
        return (self.p_cols, self.p_vals, self.r_cols, self.r_vals), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def specs(self):
        sp = P(ROWS_AXIS, None, None)
        return TransitionOps(sp, sp, sp, sp)

    def restrict(self, r_local):
        """sharded fine residual -> replicated coarse rhs."""
        part = jnp.einsum(
            "nk,nk->n", self.r_vals[0],
            jnp.take(r_local, self.r_cols[0], axis=0))
        return lax.psum(part, ROWS_AXIS)

    def prolong(self, uc_full):
        """replicated coarse correction -> sharded fine update."""
        return jnp.einsum(
            "nk,nk->n", self.p_vals[0],
            jnp.take(uc_full, self.p_cols[0], axis=0))


@register_pytree_node_class
class DistHierarchy:
    """Sharded multilevel state; ``shard_apply`` runs inside shard_map."""

    def __init__(self, levels, rep, trans, top_A=None, npre=1, npost=1,
                 ncycle=1, pre_cycles=1, rep_rowshard=False):
        self.levels = list(levels)   # sharded levels (may be empty)
        self.rep = rep               # replicated serial sub-hierarchy
        self.trans = trans           # TransitionOps (None = whole-vector
                                     # gather/slice, the no-shard case)
        self.top_A = top_A           # system matrix when levels is empty
        self.npre = int(npre)
        self.npost = int(npost)
        self.ncycle = int(ncycle)
        self.pre_cycles = int(pre_cycles)
        self.rep_rowshard = bool(rep_rowshard)

    def tree_flatten(self):
        return ((self.levels, self.rep, self.trans, self.top_A),
                (self.npre, self.npost, self.ncycle, self.pre_cycles,
                 self.rep_rowshard))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def specs(self):
        import jax
        lvls = [DistLevel(l.A.specs(),
                          None if l.P_op is None else l.P_op.specs(),
                          None if l.R_op is None else l.R_op.specs(),
                          l.smoother.spec()) for l in self.levels]
        rep_spec = jax.tree.map(lambda _: P(), self.rep)  # fully replicated
        return DistHierarchy(
            lvls, rep_spec,
            None if self.trans is None else self.trans.specs(),
            None if self.top_A is None else self.top_A.specs(),
            self.npre, self.npost, self.ncycle, self.pre_cycles,
            self.rep_rowshard)

    # -- inside shard_map ---------------------------------------------------

    @staticmethod
    def _rowshard_mat_ok(M):
        from amgcl_tpu.ops.device import EllMatrix, DenseMatrix
        return ((isinstance(M, EllMatrix) and M.block == (1, 1))
                or isinstance(M, DenseMatrix))

    def _rowshard_ok(self):
        """The finest replicated level qualifies for row-sharded visits:
        scalar ELL or dense operator, diagonal-scaling smoother, no fused
        sweep closures (their layout assumptions are per-level). P/R may
        be anything (incl. implicit proxies) — they run replicated; the
        sharded work is the smoother/residual passes, which dominate."""
        from amgcl_tpu.relaxation.base import ScaledResidualSmoother
        rep = self.rep
        if len(rep.levels) < 2 or rep.npre < 1:
            return False
        lv = rep.levels[0]
        return (self._rowshard_mat_ok(lv.A)
                and isinstance(lv.relax, ScaledResidualSmoother)
                and lv.relax.scale.ndim == 1
                and lv.down is None and lv.up is None)

    def _rep_rowshard_visit(self, f_full):
        """cycle(0, ·) of the replicated tail with the FINEST tail level
        row-sharded over the mesh: each shard smooths/residuals its own
        row slice of the replicated operator against the replicated
        vector (no halo — x is already whole), one all_gather per op.
        Trades the tail's N-fold redundant FLOPs for a few small
        collectives; ``rep_rowshard=True`` opts in, the 8-device dryrun
        A/Bs it (ROADMAP 'coarse levels underutilize large meshes')."""
        from amgcl_tpu.ops import device as sdev
        rep = self.rep
        lv = rep.levels[0]
        A = lv.A
        n = A.shape[0]
        nd = _axis_size(ROWS_AXIS)
        nloc = -(-n // nd)
        n_pad = nloc * nd
        s = lax.axis_index(ROWS_AXIS)

        from amgcl_tpu.ops.device import EllMatrix

        def row_slice_op(M):
            """Local-rows matvec closure for an ELL or dense operator."""
            if isinstance(M, EllMatrix):
                K = M.cols.shape[1]
                cp = jnp.pad(M.cols, ((0, n_pad - n), (0, 0)))
                vp = jnp.pad(M.vals, ((0, n_pad - n), (0, 0)))
                c = lax.dynamic_slice(cp, (s * nloc, np.int32(0)), (nloc, K))
                v = lax.dynamic_slice(vp, (s * nloc, np.int32(0)), (nloc, K))
                return lambda x_full: jnp.einsum(
                    "nk,nk->n", v, jnp.take(x_full, c, axis=0),
                    preferred_element_type=f_full.dtype)
            ap = jnp.pad(M.a, ((0, n_pad - n), (0, 0)))
            a = lax.dynamic_slice(ap, (s * nloc, np.int32(0)),
                                  (nloc, M.a.shape[1]))
            return lambda x_full: (a @ x_full).astype(f_full.dtype)

        def vec_slice(v_full):
            vp = jnp.pad(v_full, (0, n_pad - v_full.shape[0]))
            return lax.dynamic_slice(vp, (s * nloc,), (nloc,))

        def allg(y_loc):
            return lax.all_gather(y_loc, ROWS_AXIS, tiled=True)[:n]

        mv_loc = row_slice_op(A)
        w_loc = vec_slice(lv.relax.scale)
        f_loc = vec_slice(f_full)

        # pre-smoothing: first sweep from zero, then scaled-residual sweeps
        u_loc = w_loc * f_loc
        for _ in range(rep.npre - 1):
            u_loc = u_loc + w_loc * (f_loc - mv_loc(allg(u_loc)))
        u_full = allg(u_loc)
        # sharded residual -> replicated restrict + coarse tail-of-tail
        r_full = allg(f_loc - mv_loc(u_full))
        fc = sdev.spmv(lv.R, r_full)
        uc = rep.cycle(1, fc)
        for _ in range(rep.ncycle - 1):
            rc = sdev.residual(fc, rep.levels[1].A, uc)
            uc = uc + rep.cycle(1, rc)
        # replicated prolong (P may be an implicit proxy), local correct,
        # then sharded post-smoothing
        u_loc = u_loc + vec_slice(sdev.spmv(lv.P, uc))
        for _ in range(rep.npost):
            u_loc = u_loc + w_loc * (f_loc - mv_loc(allg(u_loc)))
        return allg(u_loc)

    def _rep_visit(self, fc_full):
        if self.rep_rowshard and self._rowshard_ok():
            return self._rep_rowshard_visit(fc_full)
        return self.rep.cycle(0, fc_full)

    def _rep_solve(self, fc_full):
        """Replicated sub-hierarchy visit(s): every shard runs the same
        serial cycle on the full coarse vector — redundant FLOPs on tiny
        levels instead of per-level collectives (or row-sharded finest
        tail level under ``rep_rowshard``)."""
        from amgcl_tpu.ops import device as sdev
        uc = self._rep_visit(fc_full)
        for _ in range(self.ncycle - 1):
            rc = fc_full - sdev.spmv(self.rep.levels[0].A, uc)
            uc = uc + self._rep_visit(rc)
        return uc

    def shard_cycle(self, i, f):
        lv = self.levels[i]
        Aop = _LocalOp(lv.A)
        sm = lv.smoother
        if self.npre > 0:
            u = sm.apply0(Aop, f)
            for _ in range(self.npre - 1):
                u = sm.sweep(Aop, f, u)
        else:
            u = jnp.zeros_like(f)
        r = f - lv.A.shard_mv(u)
        if i == len(self.levels) - 1:
            # boundary to the replicated tail
            fc_full = self.trans.restrict(r)
            uc_full = self._rep_solve(fc_full)
            u = u + self.trans.prolong(uc_full)
        else:
            fc = lv.R_op.shard_mv(r)
            uc = self.shard_cycle(i + 1, fc)
            for _ in range(self.ncycle - 1):   # W-cycle extra coarse visits
                rc = fc - self.levels[i + 1].A.shard_mv(uc)
                uc = uc + self.shard_cycle(i + 1, rc)
            u = u + lv.P_op.shard_mv(uc)
        for _ in range(self.npost):
            u = sm.sweep(Aop, f, u, reverse=True)   # matches apply_post
        return u

    def _whole_vector_apply(self, r):
        """No sharded levels: gather the whole (small) residual, run the
        replicated hierarchy, slice the local part back."""
        M = self.rep.system_matrix
        # scalar length: ELL block matrices report shape in block units
        n_rep = M.shape[0] * getattr(M, "block", (1, 1))[0]
        nloc = r.shape[0]
        r_full = lax.all_gather(r, ROWS_AXIS, tiled=True)[:n_rep]
        u_full = self.rep.apply(r_full)
        pad = jnp.zeros(nloc * _axis_size(ROWS_AXIS), u_full.dtype)
        pad = lax.dynamic_update_slice(pad, u_full, (0,))
        s = lax.axis_index(ROWS_AXIS)
        return lax.dynamic_slice(pad, (s * nloc,), (nloc,))

    def shard_apply(self, r):
        if not self.levels:
            return self._whole_vector_apply(r)
        x = self.shard_cycle(0, r)
        for _ in range(self.pre_cycles - 1):
            rr = r - self.levels[0].A.shard_mv(x)
            x = x + self.shard_cycle(0, rr)
        return x

    def system_A(self):
        """The Krylov-loop operator. ``top_A`` takes precedence when set:
        under a narrowed precond_dtype it holds the solver-precision copy
        of the system matrix (mixing.hpp seam — the residual recursion
        must track the full-precision operator, not the bf16 hierarchy's
        finest level)."""
        return self.top_A if self.top_A is not None else self.levels[0].A


def _transition_ops(Pt: CSR, Rt: CSR, nd, nloc, mesh, dtype):
    """Build TransitionOps from the host transfer operators at the
    sharded/replicated boundary. Pt: (n_fine, nc); Rt: (nc, n_fine)."""
    n_f, nc = Pt.shape
    # P: rows sharded by the fine partition, columns global (replicated uc)
    prows = Pt.expanded_rows()
    K1 = max(int(Pt.row_nnz().max()), 1) if Pt.nnz else 1
    pc = np.zeros((nd, nloc, K1), dtype=np.int32)
    vdt = np.result_type(Pt.val.dtype, np.float64)
    pv = np.zeros((nd, nloc, K1), dtype=vdt)
    for s_ in range(nd):
        r0, r1 = min(s_ * nloc, n_f), min((s_ + 1) * nloc, n_f)
        lo, hi = int(Pt.ptr[r0]), int(Pt.ptr[r1])
        c, v = pack_rows_ell(prows[lo:hi] - r0, Pt.col[lo:hi],
                              Pt.val[lo:hi], nloc, K1)
        pc[s_], pv[s_] = c, v
    # R: per-shard column restriction; rows = full coarse vector
    rrows = Rt.expanded_rows()
    owner = np.minimum(Rt.col // nloc, nd - 1)
    K2 = 1
    for s_ in range(nd):
        sel = owner == s_
        if sel.any():
            K2 = max(K2, int(np.bincount(rrows[sel], minlength=nc).max()))
    rc = np.zeros((nd, nc, K2), dtype=np.int32)
    rv = np.zeros((nd, nc, K2), dtype=vdt)
    for s_ in range(nd):
        sel = owner == s_
        c, v = pack_rows_ell(rrows[sel], Rt.col[sel] - s_ * nloc,
                              Rt.val[sel], nc, K2)
        rc[s_], rv[s_] = c, v
    put = lambda a, dt: put_sharded(a, mesh, dt)
    return TransitionOps(put(pc, jnp.int32), put(pv, dtype),
                         put(rc, jnp.int32), put(rv, dtype))


def _build_dist_smoother(relax, Ak, Ak_s, dA, mesh, nd, dtype):
    """Shard one level's smoother state over the mesh. Every registry
    smoother family is supported with its GLOBAL state (halo-plan ELL
    factors / masks), so distributed smoothing is bit-for-bit the serial
    math — unlike the reference, whose mpi wrappers degrade ILU/GS to the
    rank-local block (amgcl/mpi/relaxation/*.hpp). Unsupported smoother
    types raise instead of silently degrading."""
    from amgcl_tpu.relaxation.chebyshev import ChebyshevState
    from amgcl_tpu.relaxation.ilu0 import ILU0, ILUT, ILUK, ILUP
    from amgcl_tpu.relaxation.gauss_seidel import GaussSeidel, \
        greedy_coloring
    from amgcl_tpu.relaxation.spai1 import Spai1

    n_pad = dA.nloc * nd

    def shard_vec(v, fill=0.0):
        host_dt = np.result_type(np.asarray(v).dtype, np.float64)
        pad = np.full(n_pad, fill, dtype=host_dt)
        pad[:len(v)] = np.asarray(v, dtype=host_dt)
        return put_sharded(pad.reshape(nd, dA.nloc), mesh, dtype)

    if isinstance(relax, (ILU0, ILUT, ILUK, ILUP)):
        Lh, Uh, udia = relax.build_host(Ak)
        # factor partitions must match the level's (possibly shrunk) one
        return DistSmoother(
            "ilu", Ls=build_dist_ell(Lh, mesh, dtype, nloc=dA.nloc,
                                     ncloc=dA.nloc),
            Us=build_dist_ell(Uh, mesh, dtype, nloc=dA.nloc,
                              ncloc=dA.nloc),
            uinv=shard_vec(1.0 / udia, fill=1.0),
            jacobi_iters=relax.jacobi_iters)
    if isinstance(relax, GaussSeidel):
        color = greedy_coloring(Ak_s.to_scipy())
        nc = int(color.max()) + 1
        masks = np.zeros((nc, n_pad))
        masks[color, np.arange(Ak_s.nrows)] = 1.0
        masks = masks.reshape(nc, nd, dA.nloc).transpose(1, 0, 2)
        return DistSmoother(
            "gs", scale=shard_vec(Ak_s.diagonal(invert=True)),
            masks=put_sharded(masks, mesh, dtype))
    if isinstance(relax, Spai1):
        Mh = relax.build_host(Ak)
        return DistSmoother("spai1", Msp=build_dist_ell(
            Mh, mesh, dtype, nloc=dA.nloc, ncloc=dA.nloc))

    st = relax.build(Ak, dtype)
    if isinstance(st, ChebyshevState):
        dinv_sh = shard_vec(st.dinv) if st.scale else None
        return DistSmoother("cheb", dinv_sh, st.theta, st.delta, st.degree)
    if hasattr(st, "scale") and np.ndim(st.scale) == 1:
        return DistSmoother("diag", shard_vec(st.scale))
    if hasattr(st, "scale") and np.ndim(st.scale) == 3:
        b = int(np.shape(st.scale)[-1])
        if dA.nloc % b:
            raise ValueError(
                "block smoother blocks (b=%d) straddle the shard boundary "
                "(nloc=%d); choose a mesh with nloc divisible by b"
                % (b, dA.nloc))
        vdt = np.result_type(np.asarray(st.scale).dtype, np.float64)
        M = np.zeros((n_pad // b, b, b), dtype=vdt)
        M[:np.shape(st.scale)[0]] = np.asarray(st.scale, dtype=vdt)
        return DistSmoother("bdiag", put_sharded(
            M.reshape(nd, dA.nloc // b, b, b), mesh, dtype))
    raise ValueError(
        "smoother %s has no distributed form; use one of damped_jacobi/"
        "spai0/spai1/chebyshev/gauss_seidel/ilu0/iluk/ilup/ilut"
        % type(relax).__name__)


class _LocalOp:
    """Shard-local operator adapter: gives the serial Krylov bodies their
    ``.mv`` while the halo exchange happens underneath."""

    def __init__(self, dist_mat):
        self.m = dist_mat

    def mv(self, x):
        return self.m.shard_mv(x)


class DistAMGSolver:
    """mpi::make_solver equivalent: distributed AMG-preconditioned Krylov
    over the mesh, one compiled SPMD program per (structure, params)."""

    def __init__(self, A, mesh, prm: Optional[AMGParams] = None,
                 solver: Any = None, replicate_below: int = 4096,
                 device_mis: bool = False, min_per_shard: int = 0,
                 repartition: float = 0.0, precond_dtype: Any = None,
                 rep_rowshard: bool = False):
        """``device_mis=True`` runs the aggregation MIS rounds sharded on
        the mesh (parallel/dist_mis.py) instead of the host greedy pass —
        the reference's distributed-PMIS role
        (amgcl/mpi/coarsening/pmis.hpp), reformulated as halo-plan row-max
        propagation.

        ``min_per_shard`` concentrates mid-size sharded levels on fewer
        shards (the repartition-merge analogue, see the level loop).

        ``repartition`` > 0 permutes any coarse sharded level whose halo
        fraction (parallel/repartition.py) exceeds the value — the
        reference's mpi::partition::parmetis/ptscotch role
        (parmetis.hpp:105-199: A <- I^T A I, P <- P I) realized as an RCM
        locality permutation of the level's index space.

        ``precond_dtype`` stores the sharded level/transfer/smoother
        arrays in a narrower dtype (e.g. bfloat16 — halves HBM bytes per
        V-cycle) while the Krylov vectors stay in ``prm.dtype`` — the
        distributed rendition of the mixing.hpp precision seam.

        ``rep_rowshard=True`` row-shards the FINEST replicated-tail
        level's smoother/residual/prolong work across the mesh (one
        all_gather per op) instead of every shard redundantly computing
        the whole tail — trades tail FLOPs for small collectives; worth
        it when the tail is fat relative to ICI latency (A/B'd in the
        multichip dryrun)."""
        if not isinstance(A, CSR):
            A = CSR.from_scipy(A)
        self.mesh = mesh
        self.prm = prm or AMGParams()
        if device_mis:
            import copy as _copy
            from amgcl_tpu.parallel.dist_mis import make_mesh_aggregator
            prm2 = _copy.copy(self.prm)
            coars = _copy.deepcopy(self.prm.coarsening)
            if not hasattr(coars, "aggregator"):
                raise ValueError(
                    "device_mis needs an aggregation-based coarsening "
                    "(smoothed_aggregation / aggregation), got %s"
                    % type(coars).__name__)
            if A.is_block or getattr(coars, "block_size", 1) > 1:
                # pointwise (block) aggregation takes a different path that
                # bypasses the aggregator hook — fail loudly rather than
                # silently running the host pass
                raise ValueError(
                    "device_mis does not support block (pointwise) "
                    "aggregation yet; unblock the system or drop "
                    "device_mis")
            coars.aggregator = make_mesh_aggregator(mesh)
            prm2.coarsening = coars
            self.prm = prm2
        if getattr(self.prm.coarsening, "stencil_setup", False):
            # the stencil setup path returns implicit transfer proxies;
            # this wrapper shards explicit CSR P/R, so keep the CSR route
            import copy as _copy
            prm2 = _copy.copy(self.prm)
            prm2.coarsening = _copy.deepcopy(self.prm.coarsening)
            prm2.coarsening.stencil_setup = False
            self.prm = prm2
        self.solver = solver or CG()
        dtype = self.prm.dtype                    # Krylov vector dtype
        mat_dtype = precond_dtype or dtype        # sharded operator dtype
        nd = mesh.shape[ROWS_AXIS]

        # serial host-side construction; the device filter skips serial
        # device states for levels this wrapper re-shards itself (they'd be
        # discarded — e.g. a second Chow-Patel factorization per level).
        # It mirrors the replicate-split rule below: a level is replicated
        # iff it is the last, or coarse enough and not the finest.
        host = AMG(A, self.prm,
                   device_filter=lambda j, sz, last: last or (
                       j > 0 and sz < replicate_below))
        self.host_amg = host
        # split: levels at or above `replicate_below` rows stay sharded;
        # the tail is replicated (the merge/repartition analogue) — at
        # minimum the coarsest level
        sizes = [h[0].nrows * h[0].block_size[0] for h in host.host_levels]
        if len(sizes) == 1:
            t = 0                      # whole hierarchy replicated
        else:
            t = next((j for j, sz in enumerate(sizes)
                      if sz < replicate_below and j > 0),
                     len(sizes) - 1)
        self._split = t
        # mid-size level shrink (reference: mpi::partition::merge,
        # merge.hpp:47-137 with min_per_proc): a level whose even spread
        # would drop below `min_per_shard` rows/shard is concentrated on
        # the first ceil(n / min_per_shard) shards instead — fewer halo
        # pairs, bigger per-shard blocks, same SPMD program
        def lvl_nloc(n_scalar):
            base = -(-n_scalar // nd)
            return max(base, min(int(min_per_shard), n_scalar)) \
                if min_per_shard else base

        nlocs = [lvl_nloc(h[0].nrows * h[0].block_size[0])
                 for h in host.host_levels[:t]]
        # the EXECUTED per-level partition (min_per_shard concentration
        # included) — the per-shard ledger derives its strip bounds from
        # exactly this, so a skewed partition reports its real imbalance
        self._nlocs = list(nlocs)
        self.repartition_report = []
        if repartition and t > 1:
            from amgcl_tpu.parallel.repartition import \
                repartition_host_levels
            # after nlocs: the halo metric must describe the EXECUTED
            # layout, incl. the min_per_shard concentration
            self.repartition_report = repartition_host_levels(
                host.host_levels, t, float(repartition), nd, nlocs)
        levels = []
        for k, (Ak, Pk, Rk) in enumerate(host.host_levels[:t]):
            Ak_s = Ak.unblock() if Ak.is_block else Ak
            dA = build_dist_ell(Ak_s, mesh, mat_dtype, nloc=nlocs[k],
                                ncloc=nlocs[k])
            dP = dR = None
            # the last sharded level's transfers become the transition ops,
            # so don't build (then discard) distributed versions of them
            if Pk is not None and k != t - 1:
                dP = build_dist_ell(
                    Pk.unblock() if Pk.is_block else Pk, mesh, mat_dtype,
                    nloc=nlocs[k], ncloc=nlocs[k + 1])
                dR = build_dist_ell(
                    Rk.unblock() if Rk.is_block else Rk, mesh, mat_dtype,
                    nloc=nlocs[k + 1], ncloc=nlocs[k])
            sm = _build_dist_smoother(self.prm.relax, Ak, Ak_s, dA, mesh,
                                      nd, mat_dtype)
            levels.append(DistLevel(dA, dP, dR, sm))

        # replicated tail = the serial device hierarchy's own levels
        from amgcl_tpu.models.amg import Hierarchy as SerialHierarchy
        rep = SerialHierarchy(host.hierarchy.levels[t:],
                              host.hierarchy.coarse,
                              self.prm.npre, self.prm.npost,
                              self.prm.ncycle, 1)
        top_A = None
        trans = None
        if t == 0:
            # no sharded levels: top_A IS the Krylov operator and nothing
            # else — always solver precision (the preconditioner runs
            # through the replicated hierarchy)
            A0 = host.host_levels[0][0]
            top_A = build_dist_ell(A0.unblock() if A0.is_block else A0,
                                   mesh, dtype)
        else:
            Pt = host.host_levels[t - 1][1]
            Rt = host.host_levels[t - 1][2]
            trans = _transition_ops(
                Pt.unblock() if Pt.is_block else Pt,
                Rt.unblock() if Rt.is_block else Rt,
                nd, levels[-1].A.nloc, mesh, mat_dtype)
        if levels and jnp.dtype(mat_dtype) != jnp.dtype(dtype):
            # mixing.hpp seam: the Krylov loop needs a solver-precision
            # system matrix; the narrowed copy serves only the cycle
            A0 = host.host_levels[0][0]
            top_A = build_dist_ell(A0.unblock() if A0.is_block else A0,
                                   mesh, dtype, nloc=nlocs[0],
                                   ncloc=nlocs[0])
        self.hier = DistHierarchy(levels, rep, trans, top_A,
                                  self.prm.npre, self.prm.npost,
                                  self.prm.ncycle, self.prm.pre_cycles,
                                  rep_rowshard=rep_rowshard)
        self.n = A.nrows * A.block_size[0]
        first_A = levels[0].A if levels else top_A
        self.n_pad = first_A.nloc * nd
        self._compiled = None

    def _build_compiled(self):
        solver = self.solver
        hier_specs = self.hier.specs()
        n_true = self.n
        nloc = self.n_pad // self.mesh.shape[ROWS_AXIS]

        def body(hier, rhs, x0):
            Aop = _LocalOp(hier.system_A())
            kw = {}
            # IDR(s) derives its shadow space from GLOBAL row indices so the
            # distributed run uses exactly the serial shadow space (see
            # solver/idrs.py); hand it the shard's global index window.
            from amgcl_tpu.solver.idrs import IDRs
            if isinstance(solver, IDRs):
                kw = dict(
                    row_index=lax.axis_index(ROWS_AXIS) * nloc
                    + jnp.arange(nloc),
                    n_valid=n_true)
            # [:3]: solvers with record_history return an extra element
            x, it, res = solver.solve(
                Aop, hier.shard_apply, rhs, x0,
                inner_product=dist_inner_product, **kw)[:3]
            return x, it, res

        fn = shard_map(
            body, mesh=self.mesh,
            in_specs=(hier_specs, P(ROWS_AXIS), P(ROWS_AXIS)),
            out_specs=(P(ROWS_AXIS), P(), P()),
            check_vma=False)
        # observed jit (telemetry/compile_watch.py): THE distributed
        # AMG solve program — a retrace per call here is the worst
        # silent-latency case on a pod
        from amgcl_tpu.telemetry.compile_watch import watched_jit
        return watched_jit(fn, name="parallel.dist_amg_solve")

    def __call__(self, rhs, x0=None):
        dtype = self.prm.dtype
        nd = self.mesh.shape[ROWS_AXIS]
        rhs_p = put_sharded(
            _pad_vec(np.asarray(rhs), self.n_pad // nd, nd, dtype),
            self.mesh)
        x0_p = jnp.zeros_like(rhs_p) if x0 is None else put_sharded(
            _pad_vec(np.asarray(x0), self.n_pad // nd, nd, dtype),
            self.mesh)
        import time as _time
        t0 = _time.perf_counter()
        first_call = self._compiled is None
        if first_call:
            self._compiled = self._build_compiled()
        x, it, res = self._compiled(self.hier, rhs_p, x0_p)
        from amgcl_tpu.parallel.mesh import host_full
        from amgcl_tpu.telemetry import emit as _tel_emit
        # it/res land here already mesh-reduced (psum dots, replicated
        # out-specs) — the report is identical on every shard
        info = SolverInfo(
            int(it), float(res),
            wall_time_s=_time.perf_counter() - t0,
            solver=type(self.solver).__name__,
            resources=self.resource_ledger(),
            extra={"devices": int(nd),
                   **({"first_call": True} if first_call else {})})
        _tel_emit(info.to_dict(), event="dist_solve", n=self.n)
        return host_full(x)[:self.n], info

    def resource_ledger(self):
        """Distributed resource ledger: per-sharded-level halo comm per
        SpMV, aggregated cycle/iteration wire volume across the mesh, and
        the memory side (sharded device bytes + the replicated tail's
        hierarchy ledger). Cached per build; never raises."""
        cached = getattr(self, "_resources_cache", None)
        if cached is not None:
            return cached
        from amgcl_tpu.telemetry import ledger as L
        try:
            nd = int(self.mesh.shape[ROWS_AXIS])
            itemsize = jnp.dtype(self.prm.dtype).itemsize
            sweeps = self.prm.npre + self.prm.npost + 1  # sweeps + resid
            lv_rows = []
            cyc = {"msgs": 0, "bytes": 0}
            for k, lv in enumerate(self.hier.levels):
                c = L.comm_model(lv.A, nd) or {"msgs": 0, "bytes": 0}
                row = {"level": k, "per_spmv": c,
                       "spmvs_per_cycle": sweeps}
                cyc["msgs"] += c["msgs"] * sweeps
                cyc["bytes"] += c["bytes"] * sweeps
                for T in (lv.P_op, lv.R_op):
                    tc = L.comm_model(T, nd) if T is not None else None
                    if tc:
                        cyc["msgs"] += tc["msgs"]
                        cyc["bytes"] += tc["bytes"]
                lv_rows.append(row)
            if self.hier.trans is not None:
                # transition restrict psums the FULL replicated coarse
                # vector across the mesh once per cycle
                nc = int(self.hier.trans.r_cols.shape[1])
                red = L.allreduce_model(nd, nc, itemsize)
                cyc["msgs"] += red["msgs"]
                cyc["bytes"] += red["bytes"]
                lv_rows.append({"level": "transition",
                                "allreduce": {"count": nc, **red}})
            pre_cycles = max(int(self.prm.pre_cycles), 1)
            top = self.hier.top_A if self.hier.top_A is not None \
                else (self.hier.levels[0].A if self.hier.levels else None)
            sname = type(self.solver).__name__
            spmvs, papps, dots, _ = L.KRYLOV_OPS.get(sname, (1, 1, 4, 4))
            top_comm = (L.comm_model(top, nd) if top is not None
                        else None) or {"msgs": 0, "bytes": 0}
            red1 = L.allreduce_model(nd, 1, itemsize)
            per_iter = {
                "msgs": (spmvs * top_comm["msgs"]
                         + papps * pre_cycles * cyc["msgs"]
                         + dots * red1["msgs"]),
                "bytes": (spmvs * top_comm["bytes"]
                          + papps * pre_cycles * cyc["bytes"]
                          + dots * red1["bytes"])}
            # per-shard imbalance (telemetry/comm.py): exact useful-work
            # rows/nnz per shard from the host CSR at the EXECUTED
            # partition — a min_per_shard concentration or a naturally
            # skewed level shows its real load factor here, padding-
            # uniform device buffers notwithstanding. Nested guard: a
            # wrapper without host_levels (StripAMGSolver reuses this
            # method) keeps its comm/memory ledger and just skips the
            # shard tables.
            from amgcl_tpu.telemetry import comm as _comm
            dist = {"devices": nd,
                    "provenance": _comm.hw_provenance(self.mesh)}
            try:
                dist_levels = []
                worst = 1.0
                nlocs = self._nlocs
                for k, lv in enumerate(self.hier.levels):
                    Ak = self.host_amg.host_levels[k][0]
                    Ak_s = Ak.unblock() if Ak.is_block else Ak
                    bounds = _comm.even_bounds(Ak_s.nrows, nd,
                                               nloc=nlocs[k])
                    row = _comm.level_shard_costs(Ak_s, bounds)
                    row["level"] = k
                    row["halo_slab"] = int(lv.A.send_idx.shape[-1]) \
                        if lv.A.send_idx is not None else 0
                    dist_levels.append(row)
                    worst = max(worst, row["imbalance"]["factor"])
                dist["levels"] = dist_levels
                dist["imbalance_factor"] = round(worst, 4)
            except Exception as e:
                dist["levels_error"] = repr(e)[:120]
            cached = {
                "comm": {"devices": nd, "levels": lv_rows,
                         "per_cycle": cyc, "per_iteration": per_iter},
                "dist": dist,
                "memory": {
                    # global logical bytes of the sharded arrays (each
                    # shard holds 1/nd of these)
                    "sharded_bytes": L._leaf_bytes(
                        (self.hier.levels, self.hier.trans,
                         self.hier.top_A)),
                    # the replicated tail lives whole on EVERY shard
                    "replicated_bytes": L._leaf_bytes(self.hier.rep),
                }}
        except Exception as e:
            cached = {"error": repr(e)[:200]}
        self._resources_cache = cached
        return cached

    def __repr__(self):
        return ("DistAMGSolver over %d devices\n%r"
                % (self.mesh.shape[ROWS_AXIS], self.host_amg))
