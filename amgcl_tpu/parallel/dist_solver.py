"""Distributed Krylov solve: the serial CG body over shard-resident vectors,
with psum-globalized reductions — exactly the reference's recipe of reusing
the serial solver with a distributed InnerProduct
(amgcl/mpi/solver/cg.hpp:41-46).

The whole iteration (halo exchanges, local SpMVs, psum dots) is one
``shard_map``-ped ``lax.while_loop`` — a single XLA program per solve across
the mesh, compiled once per (mesh, matrix structure, solver params) and
cached for repeat solves.

Two iteration bodies:

* :func:`dist_cg` — the classical Jacobi-CG recurrence, three scalar
  psums per iteration (rho, p·Ap, ‖r‖²).
* :func:`dist_cg_pipelined` — the Ghysels–Vanroose pipelined recurrence:
  the three reductions merge into ONE psum of a stacked 3-vector per
  iteration, and the body is ordered so the collective shares no
  operands with the next SpMV + preconditioner application — XLA's
  async-collective scheduler can run the allreduce while the halo SpMV
  streams, the same overlap-by-data-independence trick as
  ``dist_matrix.dia_halo_mv``. On a network where the allreduce latency
  rivals the local SpMV (large meshes, small shards) this is the
  standard latency-hiding CG. Enabled per call (``pipelined=True``) or
  process-wide via ``AMGCL_TPU_PIPELINED_CG=1``.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from amgcl_tpu.parallel.mesh import ROWS_AXIS
from amgcl_tpu.parallel.dist_matrix import DistDiaMatrix, dist_inner_product


def pipelined_cg_enabled() -> bool:
    """AMGCL_TPU_PIPELINED_CG=1 makes :func:`dist_cg` route through the
    merged-reduction pipelined body by default (per-call ``pipelined=``
    still wins). Default off: the classical recurrence is the
    bit-familiar baseline and the pipelined one reorders the roundoff."""
    return os.environ.get("AMGCL_TPU_PIPELINED_CG", "0") == "1"


@lru_cache(maxsize=64)
def _compiled_dist_cg(mesh, offsets, shape, maxiter, tol):
    """jit-compiled distributed CG keyed on structure, not data."""
    from amgcl_tpu.telemetry import health as H
    A = DistDiaMatrix(offsets, None, shape)  # structure only; data is an arg

    def body_shard(data, f, x, di):
        dot = dist_inner_product
        spmv = partial(A.shard_mv, data)
        r = f - spmv(x)
        norm_rhs = jnp.sqrt(jnp.abs(dot(f, f)))
        scale = jnp.where(norm_rhs > 0, norm_rhs, 1.0)
        eps = tol * scale

        def cond(st):
            x, r, p, rho_p, it, res, hs = st
            return (it < maxiter) & (res > eps) & H.keep_going(hs)

        def body(st):
            x, r, p, rho_p, it, res, hs = st
            s = di * r
            rho = dot(r, s)
            beta = jnp.where(rho_p == 0, 0.0, rho / rho_p)
            p_n = s + beta * p
            q = spmv(p_n)
            qp = dot(q, p_n)
            alpha = rho / jnp.where(qp == 0, 1.0, qp)
            x_n = x + alpha * p_n
            r_n = r - alpha * q
            res_n = jnp.sqrt(jnp.abs(dot(r_n, r_n)))
            # same guard set as the serial CG; every input is already
            # psum-reduced, so the trips (and the early exit they drive)
            # are bitwise identical on every shard
            ok, hs = H.step(
                hs, it, res_n / scale,
                ((H.BREAKDOWN_RHO, H.bad_denom(rho)),
                 (H.BREAKDOWN_ALPHA, H.bad_denom(qp)),
                 (H.INDEFINITE, jnp.real(qp) < 0, False)))
            x, r, p, rho, res = H.commit(
                ok, (x_n, r_n, p_n, rho, res_n), (x, r, p, rho_p, res))
            return (x, r, p, rho, it + ok.astype(jnp.int32), res, hs)

        res0 = jnp.sqrt(jnp.abs(dot(r, r)))
        st = (x, r, jnp.zeros_like(r), jnp.zeros((), f.dtype),
              jnp.zeros((), jnp.int32), res0, H.init_state(res0 / scale))
        x, r, p, rho, it, res, hs = lax.while_loop(cond, body, st)
        return x, it, res / scale, hs.flags, hs.first_it

    fn = shard_map(
        body_shard, mesh=mesh,
        in_specs=(P(None, ROWS_AXIS), P(ROWS_AXIS), P(ROWS_AXIS),
                  P(ROWS_AXIS)),
        out_specs=(P(ROWS_AXIS), P(), P(), P(), P()),
        check_vma=False)
    # observed jit (telemetry/compile_watch.py): a dist_cg that retraces
    # per solve — a drifting halo plan or maxiter/tol passed non-static —
    # shows up as a retrace finding instead of silent compile seconds
    from amgcl_tpu.telemetry.compile_watch import watched_jit
    return watched_jit(fn, name="parallel.dist_cg")


@lru_cache(maxsize=64)
def _compiled_dist_cg_pipelined(mesh, offsets, shape, maxiter, tol):
    """jit-compiled pipelined (Ghysels–Vanroose) distributed CG: ONE
    psum of a stacked (γ, δ, ‖r‖²) partial 3-vector per iteration, with
    the next SpMV + Jacobi application data-independent of the
    collective so the scheduler can overlap them."""
    from amgcl_tpu.telemetry import health as H
    A = DistDiaMatrix(offsets, None, shape)  # structure only

    def body_shard(data, f, x, di):
        spmv = partial(A.shard_mv, data)
        r = f - spmv(x)
        u = di * r
        w = spmv(u)
        # setup reductions merged too: (γ0, δ0, ‖r0‖², ‖f‖²) in one psum
        g0 = lax.psum(jnp.stack([jnp.vdot(r, u), jnp.vdot(w, u),
                                 jnp.vdot(r, r), jnp.vdot(f, f)]),
                      ROWS_AXIS)
        gamma0, delta0, rr0, ff = g0[0], g0[1], g0[2], g0[3]
        norm_rhs = jnp.sqrt(jnp.abs(ff))
        scale = jnp.where(norm_rhs > 0, norm_rhs, 1.0)
        eps = tol * scale
        res0 = jnp.sqrt(jnp.abs(rr0))
        m0 = di * w
        nv0 = spmv(m0)
        zero = jnp.zeros_like(r)
        one = jnp.ones((), f.dtype)

        def cond(st):
            it, res, hs = st[12], st[13], st[14]
            return (it < maxiter) & (res > eps) & H.keep_going(hs)

        def body(st):
            (x, r, u, w, z, q, s, p, m, nv, gam_p, alpha_p, it, res,
             hs, gam, delta) = st
            beta = jnp.where(it == 0, 0.0,
                             gam / jnp.where(gam_p == 0, 1.0, gam_p))
            denom = delta - beta * gam / alpha_p
            alpha = gam / jnp.where(denom == 0, 1.0, denom)
            z_n = nv + beta * z
            q_n = m + beta * q
            s_n = w + beta * s
            p_n = u + beta * p
            x_n = x + alpha * p_n
            r_n = r - alpha * s_n
            u_n = u - alpha * q_n
            w_n = w - alpha * z_n
            # the ONE collective of the iteration: (γ', δ', ‖r‖²) from a
            # single stacked psum of the shard-local partials ...
            g = lax.psum(jnp.stack([jnp.vdot(r_n, u_n),
                                    jnp.vdot(w_n, u_n),
                                    jnp.vdot(r_n, r_n)]), ROWS_AXIS)
            # ... while the next iteration's Jacobi apply + halo SpMV
            # stream: they consume only w_n, sharing no operands with
            # the psum RESULT, so the async-collective scheduler can
            # overlap them (same structure as dia_halo_mv's interior)
            m_n = di * w_n
            nv_n = spmv(m_n)
            gam_n, delta_n, rr = g[0], g[1], g[2]
            res_n = jnp.sqrt(jnp.abs(rr))
            # same guard family as dist_cg: γ is the rho-analogue, the
            # recurrence denominator the alpha-analogue, and δ = <Au, u>
            # the p·Ap indefiniteness probe (informational, like the
            # classical body's); every input is psum-replicated so trips
            # are bitwise identical per shard
            ok, hs = H.step(
                hs, it, res_n / scale,
                ((H.BREAKDOWN_RHO, H.bad_denom(gam)),
                 (H.BREAKDOWN_ALPHA, H.bad_denom(denom)),
                 (H.INDEFINITE, jnp.real(delta) < 0, False)))
            (x, r, u, w, z, q, s, p, m, nv, gam_p, alpha_p, res, gam,
             delta) = H.commit(
                ok,
                (x_n, r_n, u_n, w_n, z_n, q_n, s_n, p_n, m_n, nv_n,
                 gam, alpha, res_n, gam_n, delta_n),
                (x, r, u, w, z, q, s, p, m, nv, gam_p, alpha_p, res,
                 gam, delta))
            return (x, r, u, w, z, q, s, p, m, nv, gam_p, alpha_p,
                    it + ok.astype(jnp.int32), res, hs, gam, delta)

        st = (x, r, u, w, zero, zero, zero, zero, m0, nv0, one, one,
              jnp.zeros((), jnp.int32), res0,
              H.init_state(res0 / scale), gamma0, delta0)
        out = lax.while_loop(cond, body, st)
        x, it, res, hs = out[0], out[12], out[13], out[14]
        return x, it, res / scale, hs.flags, hs.first_it

    fn = shard_map(
        body_shard, mesh=mesh,
        in_specs=(P(None, ROWS_AXIS), P(ROWS_AXIS), P(ROWS_AXIS),
                  P(ROWS_AXIS)),
        out_specs=(P(ROWS_AXIS), P(), P(), P(), P()),
        check_vma=False)
    from amgcl_tpu.telemetry.compile_watch import watched_jit
    return watched_jit(fn, name="parallel.dist_cg_pipelined")


class _DistResult(tuple):
    """(x, iters, rel_resid) that additionally carries ``.report`` — the
    telemetry SolveReport built from the mesh-reduced scalars (the iters/
    residual out-specs are already psum-globalized and replicated)."""
    report = None


def dist_cg(A: DistDiaMatrix, mesh, rhs, x0=None, dinv=None,
            maxiter: int = 200, tol: float = 1e-6, pipelined=None):
    """Jacobi-preconditioned distributed CG. ``dinv`` is the (sharded)
    inverted diagonal; identity preconditioning when None.

    ``pipelined`` selects the merged-reduction Ghysels–Vanroose body
    (ONE psum of a stacked 3-vector per iteration instead of three
    scalar collectives); ``None`` reads ``AMGCL_TPU_PIPELINED_CG``.

    Returns (x, iters, rel_resid) with x sharded over rows; the tuple's
    ``.report`` attribute holds the structured SolveReport and the record
    is emitted through the process-global telemetry sink."""
    import time as _time
    from amgcl_tpu.parallel.mesh import put_with_sharding
    from amgcl_tpu.telemetry import SolveReport, emit as _tel_emit
    if pipelined is None:
        pipelined = pipelined_cg_enabled()
    t0 = _time.perf_counter()
    vec = NamedSharding(mesh, P(ROWS_AXIS))
    rhs = put_with_sharding(rhs, vec)
    x0 = jnp.zeros_like(rhs) if x0 is None else put_with_sharding(x0, vec)
    dinv = jnp.ones_like(rhs) if dinv is None else put_with_sharding(dinv,
                                                                     vec)
    build = _compiled_dist_cg_pipelined if pipelined else _compiled_dist_cg
    fn = build(mesh, A.offsets, A.shape, int(maxiter), float(tol))
    x, it, res, hflags, hfirst = fn(A.data, rhs, x0, dinv)
    from amgcl_tpu.telemetry.health import decode as _decode_health
    health = _decode_health(hflags, hfirst)
    nd = int(mesh.shape[ROWS_AXIS])
    # halo/psum wire model (telemetry/ledger.py), priced from the SAME
    # declaration the static auditor (analysis/jaxpr_audit.py) checks
    # the traced body against: classical = three scalar psums/iter,
    # pipelined = ONE psum of a stacked 3-vector
    from amgcl_tpu.telemetry.ledger import (comm_model,
                                            krylov_comm_model,
                                            DIST_CG_COLLECTIVES)
    contract = DIST_CG_COLLECTIVES[
        "dist_cg_pipelined" if pipelined else "dist_cg"]
    spmv_comm = comm_model(A, nd)
    itemsize = jnp.dtype(rhs.dtype).itemsize
    per_iter = krylov_comm_model(
        spmv_comm, nd, itemsize, spmvs=contract["spmvs"],
        dots=contract["psums"],
        elems_per_dot=contract["elems_per_psum"])
    resources = {"comm": {
        "devices": nd,
        "per_spmv": spmv_comm,
        "per_iteration": per_iter}}
    # per-shard ledger + hardware provenance (telemetry/comm.py): the
    # distributed half of SolveReport.resources — per-shard rows/nnz/
    # halo and the load-imbalance factor, plus the ICI-vs-CPU-fallback
    # tag the gates key their platform-mismatch skip on
    extra = {"devices": nd}
    try:
        from amgcl_tpu.telemetry import comm as _comm
        dist_res = _comm.dist_resources(A, nd)
        if dist_res is not None:
            resources["dist"] = dist_res
        extra["provenance"] = _comm.hw_provenance(mesh)
    except Exception:
        pass                     # observability must never fail a solve
    report = SolveReport(
        int(it), float(res), wall_time_s=_time.perf_counter() - t0,
        solver="dist_cg_pipelined" if pipelined else "dist_cg",
        resources=resources, health=health,
        extra=extra)
    _tel_emit(report.to_dict(), event="dist_solve", n=int(A.shape[0]))
    out = _DistResult((x, int(it), float(res)))
    out.report = report
    return out


def dist_cg_pipelined(A: DistDiaMatrix, mesh, rhs, x0=None, dinv=None,
                      maxiter: int = 200, tol: float = 1e-6):
    """The merged-reduction pipelined CG, explicitly (see dist_cg)."""
    return dist_cg(A, mesh, rhs, x0=x0, dinv=dinv, maxiter=maxiter,
                   tol=tol, pipelined=True)
