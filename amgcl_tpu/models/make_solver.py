"""``make_solver`` — bundle a preconditioner with a Krylov solver behind one
call, compiled as a single XLA program (reference:
amgcl/make_solver.hpp:41-231).

Mixed precision comes for free at this seam: the preconditioner hierarchy may
live in a lower precision than the Krylov iteration (reference:
amgcl/backend/detail/mixing.hpp:45-73, examples/mixed_precision.cpp:32-44) —
the apply casts the residual down and the correction back up.
"""

from __future__ import annotations

import time
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops import device as dev
from amgcl_tpu.models.amg import AMG, AMGParams
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.telemetry import SolveReport, phase, emit as telemetry_emit
from amgcl_tpu.telemetry import compile_watch as _cwatch
from amgcl_tpu.telemetry.tracing import solve_span, span

#: compile-watch label of the fused solve program (one jit cache per
#: make_solver instance; the watch aggregates them under this name)
_SOLVE_FN = "make_solver._solve_fn"

#: historical name — every solve now returns the full structured report
#: (telemetry/report.py); the old (iters, resid, history) construction and
#: ``iters, error = info`` unpacking are preserved by SolveReport itself.
SolverInfo = SolveReport


class make_solver:
    """P+S bundle: ``solve = make_solver(A, precond=AMGParams(), solver=CG())``
    then ``x, info = solve(rhs)``.

    The system matrix used by the Krylov loop is moved to the device in
    ``solver_dtype`` (which may differ from the preconditioner dtype)."""

    def __init__(self, A, precond: Any = None, solver: Any = None,
                 solver_dtype=None, matrix_format: str = "auto",
                 refine: int = 0, refine_dtype: str = "auto",
                 batch: Any = None, recovery: Any = None):
        with span("setup/make_solver"):
            # ``recovery``: the fault-tolerance ladder (faults/recovery.py).
            # None = follow AMGCL_TPU_RECOVERY (off unless "1"); True =
            # policy from env (checkpoint cadence via AMGCL_TPU_CKPT_EVERY);
            # False = off; a RecoveryPolicy instance is used as-is.
            self.recovery = recovery
            # ``batch``: declared multi-RHS bucket size (serve/): ``__call__``
            # accepts a stacked (n, B) rhs regardless; the declared value is
            # the default bucket a SolverService built on this bundle uses
            self.batch = int(batch) if batch else None
            if not isinstance(A, CSR):
                A = CSR.from_scipy(A)
            self.A_host = A
            precond = precond if precond is not None else AMGParams()
            built_from_A = False
            if isinstance(precond, AMGParams):
                self.precond = AMG(A, precond)
                self.precond_dtype = precond.dtype
                built_from_A = True
            elif hasattr(precond, "hierarchy"):
                # prebuilt preconditioner (AMG, AsPreconditioner, Dummy, ...)
                self.precond = precond
                self.precond_dtype = getattr(precond, "dtype", None) \
                    or precond.prm.dtype
            else:
                raise TypeError(
                    "precond must be AMGParams or an object with .hierarchy, "
                    "got %r" % type(precond))
            # executed-reorder threading: when the hierarchy was
            # built in a permuted frame (AMG._build applied the structure
            # advisor's plan), every solver-side device operator must live
            # in the SAME frame — rhs/x0 are permuted in and x un-permuted
            # out per solve (_solve_once), so callers never see the layout.
            self._reorder = plan = getattr(self.precond, "_reorder", None)
            self._perm_dev = None
            Ah = A
            if plan is not None:
                hl0 = self.precond.host_levels[0][0]
                if built_from_A:
                    Ah = hl0       # the permuted fine operator, as built
                else:
                    from amgcl_tpu.telemetry import structure as _st
                    if _st.fingerprint(A) != plan["fingerprint"]:
                        raise ValueError(
                            "prebuilt preconditioner was reordered for a "
                            "different sparsity pattern than the system "
                            "matrix; rebuild the preconditioner from this "
                            "matrix or set AMGCL_TPU_REORDER=off")
                    Ah = CSR(hl0.ptr, hl0.col,
                             np.asarray(A.val)[plan["val_perm"]], A.ncols)
            self.solver = solver or CG()
            self.solver_dtype = solver_dtype or self.precond_dtype
            self.refine = int(refine)
            self.matrix_format = matrix_format
            self._built_from_A = built_from_A
            hier_A = getattr(getattr(self.precond, "hierarchy", None),
                             "system_matrix", None)
            if (built_from_A and hier_A is not None
                    and self.solver_dtype == self.precond_dtype
                    and matrix_format == "auto"):
                # the hierarchy's finest-level operator IS this matrix in
                # the same format/dtype — skip a duplicate device
                # conversion. (Only when the preconditioner was built from
                # A right here — a prebuilt preconditioner may wrap a
                # different operator.)
                self.A_dev = hier_A
            else:
                # share the hierarchy's dense-window HBM budget when there
                # is one — the Krylov-side copy draws from the same pool as
                # the level operators instead of claiming a fresh allowance
                with span("setup/system_operator"):
                    self.A_dev = dev.to_device(
                        Ah, matrix_format, self.solver_dtype,
                        budget=getattr(self.precond, "_dwin_budget", None))
            # refinement needs the outer residual b - A x evaluated more
            # accurately than the working precision (the f32 evaluation
            # floors around eps32·||A||·||x||/||b||, far above 1e-6 for
            # large stiff systems). Two routes:
            #   'float64' — the wide operator (reference spirit; on TPU the
            #               f64 pass runs in software emulation);
            #   'df32'    — compensated two-f32 arithmetic (ops/dfloat.py):
            #               the same accuracy class at f32 hardware speed,
            #               DIA operators only; the f32 rhs is treated as
            #               exact (b_lo = 0).
            # 'auto' picks df32 on TPU for real-f32 DIA systems, float64
            # elsewhere.
            self.A_dev64 = None
            self.refine_mode = None
            # True when the df32 self-check failed and refinement fell back
            # to float64
            self.refine_fallback = False
            if self.refine > 0:
                import jax as _jax
                if refine_dtype == "auto":
                    use_df = (_jax.default_backend() == "tpu"
                              and isinstance(self.A_dev, dev.DiaMatrix)
                              and jnp.dtype(self.solver_dtype)
                              == jnp.dtype(jnp.float32))
                    refine_dtype = "df32" if use_df else "float64"
                if refine_dtype == "df32":
                    # the lo operator is the f32 rounding remainder and the
                    # Dekker splitter is f32-specific — the hi half must be
                    # exactly float32
                    if not isinstance(self.A_dev, dev.DiaMatrix) \
                            or jnp.dtype(self.solver_dtype) \
                            != jnp.dtype(jnp.float32):
                        raise ValueError(
                            "refine_dtype='df32' needs a float32 DIA system "
                            "matrix; use refine_dtype='float64'")
                    self.refine_mode = "df32"
                    with span("setup/system_operator"):
                        self.A_dev64 = self._build_lo_operator(Ah)
                    with span("setup/df32_selfcheck"):
                        df32_sound = self._df32_selfcheck(Ah)
                    if not df32_sound:
                        # error-free transforms assume every f32 op rounds
                        # once — a backend compiling them with excess
                        # precision or reassociation silently degrades the
                        # compensated residual to the plain-f32 floor; ONE
                        # on-device check against a host f64 reference
                        # catches that class before it becomes a
                        # convergence mystery
                        import warnings
                        warnings.warn(
                            "df32 compensated residual failed its on-device "
                            "accuracy self-check; falling back to "
                            "refine_dtype='float64'")
                        if not _jax.config.jax_enable_x64:
                            warnings.warn(
                                "refine>0 with refine_dtype='float64' "
                                "requires jax_enable_x64; without it the "
                                "float64 residual silently truncates to "
                                "float32 and refinement gains nothing")
                        self.refine_mode = "float64"
                        self.refine_fallback = True
                        with span("setup/system_operator"):
                            self.A_dev64 = dev.to_device(
                                Ah, matrix_format, self._wide_dtype())
                else:
                    if not _jax.config.jax_enable_x64:
                        import warnings
                        warnings.warn(
                            "refine>0 with refine_dtype='float64' requires "
                            "jax_enable_x64; without it the float64 "
                            "residual silently truncates to float32 and "
                            "refinement gains nothing — enable x64, drop "
                            "refine, or use refine_dtype='df32'")
                    self.refine_mode = "float64"
                    with span("setup/system_operator"):
                        self.A_dev64 = dev.to_device(Ah, matrix_format,
                                                     self._wide_dtype())
            self._compiled = None
            try:
                # measured-memory attribution (telemetry/memwatch.py): the
                # Krylov-side system operator(s) get their own owner row,
                # separate from the hierarchy the AMG registers itself
                from amgcl_tpu.telemetry import memwatch as _mw
                if _mw.enabled():
                    _mw.register_owner("operator", self)
            except Exception:
                pass

    def _build_lo_operator(self, A):
        """DIA matrix of the f32 rounding remainders: A ≈ A_hi + A_lo
        with A_hi = self.A_dev (the f32 operator) — the low half of the
        double-float pair, same offsets/layout (ops/dfloat.py)."""
        return dev.csr_to_dia_remainder(A, self.A_dev)

    def _df32_selfcheck(self, A) -> bool:
        """One-shot device-vs-host check of the compensated residual:
        ||r_df − r64|| must sit well below the plain-f32 evaluation
        floor on a random probe vector."""
        from amgcl_tpu.ops.dfloat import dia_residual_df
        rng = np.random.RandomState(23)
        n = A.nrows
        x32 = rng.rand(n).astype(np.float32)
        # b = f32-rounded A x makes the true residual eps-small, i.e.
        # TOTAL cancellation: the plain-f32 evaluation is ~100% wrong
        # there (that is the floor refinement exists to beat) while a
        # working compensated evaluation recovers it to ~eps² — the
        # discriminating scenario (a random b would make r O(1) and
        # both evaluations agree to eps·||r||)
        ax64 = A.spmv(x32.astype(np.float64))
        b32 = ax64.astype(np.float32)
        r64 = b32.astype(np.float64) - ax64
        zeros = jnp.zeros(n, jnp.float32)
        # JITTED, like the production residual inside _solve_fn — an
        # eager evaluation would not exercise the fused compilation
        # regime whose reassociation the check exists to catch
        r_df = np.asarray(jax.jit(dia_residual_df, static_argnums=0)(
            self.A_dev.offsets, self.A_dev.data, self.A_dev64.data,
            jnp.asarray(b32), zeros, jnp.asarray(x32), zeros),
            np.float64)
        r_f32 = np.asarray(dev.residual(
            jnp.asarray(b32), self.A_dev, jnp.asarray(x32)), np.float64)
        err_df = float(np.linalg.norm(r_df - r64))
        err_f32 = float(np.linalg.norm(r_f32 - r64))
        return err_df < 1e-2 * err_f32 + 1e-12 * n

    def rebuild(self, A):
        """Fast path for time-dependent problems: rebuild the hierarchy
        (reusing transfer operators) AND refresh the solver-side operators,
        so subsequent calls solve the new system (reference: amg::rebuild +
        make_solver owning both halves)."""
        if not isinstance(A, CSR):
            A = CSR.from_scipy(A)
        if not hasattr(self.precond, "rebuild"):
            raise TypeError("preconditioner %r does not support rebuild"
                            % type(self.precond).__name__)
        self.precond.rebuild(A)
        self.A_host = A
        # re-read the plan (AMG.rebuild preserves it; a device-built
        # _build resets it) and refresh the solver-side operators in the
        # hierarchy's frame — host_levels[0][0] is already permuted
        self._reorder = plan = getattr(self.precond, "_reorder", None)
        self._perm_dev = None
        Ah = self.precond.host_levels[0][0] if plan is not None else A
        hier_A = getattr(getattr(self.precond, "hierarchy", None),
                         "system_matrix", None)
        if (getattr(self, "_built_from_A", False) and hier_A is not None
                and self.solver_dtype == self.precond_dtype
                and self.matrix_format == "auto"):
            # same aliasing as __init__: the rebuilt hierarchy's finest
            # operator IS this matrix in the same format/dtype — reuse
            # it instead of materializing a duplicate device copy (the
            # farm's eviction/readmission cycles would otherwise leak a
            # finest-operator copy per readmission into HBM)
            self.A_dev = hier_A
        else:
            # same budget sharing as __init__: precond.rebuild() made a
            # fresh hierarchy-wide pool — the Krylov-side copy must draw
            # from it, not claim a second full dense-window allowance
            self.A_dev = dev.to_device(
                Ah, self.matrix_format, self.solver_dtype,
                budget=getattr(self.precond, "_dwin_budget", None))
        if self.refine > 0:
            if self.refine_mode == "df32":
                if not isinstance(self.A_dev, dev.DiaMatrix):
                    raise ValueError(
                        "rebuilt matrix is no longer DIA-eligible; "
                        "df32 refinement needs a DIA system matrix — "
                        "rebuild with matrix_format='dia' or construct "
                        "a new solver with refine_dtype='float64'")
                self.A_dev64 = self._build_lo_operator(Ah)
            else:
                self.A_dev64 = dev.to_device(Ah, self.matrix_format,
                                             self._wide_dtype())
        self._compiled = None
        self._hier_stats_cache = None
        self._resources_cache = None

    # -- eviction / readmission (serve/farm.py HBM admission) ---------------

    def release_device(self):
        """Eviction hook: drop the bundle's device state — the compiled
        solve program, the Krylov-side operator copies, and (through
        ``AMG.release_device``) the whole hierarchy — while keeping the
        host matrix, the params, and the cached setup plans. Readmission
        (:meth:`readmit`) is a ``rebuild()``-class numeric refresh, not
        a fresh setup."""
        self._compiled = None
        self.A_dev = None
        self.A_dev64 = None
        self._perm_dev = None
        self._hier_stats_cache = None
        self._resources_cache = None
        rel = getattr(self.precond, "release_device", None)
        if callable(rel):
            rel()

    def readmit(self):
        """Re-materialize the device state after
        :meth:`release_device`: rebuild against the current host matrix
        (numeric Galerkin on cached plans + device conversion). No-op
        when already resident."""
        if self.A_dev is None:
            self.rebuild(self.A_host)

    def _perm_pair(self):
        """Device-resident (perm, iperm) int32 pair for the executed
        reorder, built lazily and cached (release_device drops it).
        Applied OUTSIDE the jitted solve program: the program signature
        stays identical to the identity-layout one, so the jaxpr audit
        contracts and compile-watch entries are untouched."""
        pair = self._perm_dev
        if pair is None:
            plan = self._reorder
            pair = (jnp.asarray(plan["perm"], jnp.int32),
                    jnp.asarray(plan["iperm"], jnp.int32))
            self._perm_dev = pair
        return pair

    def _wide_dtype(self):
        return jnp.complex128 if jnp.issubdtype(
            jnp.dtype(self.solver_dtype), jnp.complexfloating) \
            else jnp.float64

    def _solve_fn(self, A_dev, A_dev64, hier, rhs, x0):
        pdtype = self.precond_dtype

        def apply_precond(r):
            with phase("precond"):
                z = hier.apply(r.astype(pdtype))
            return z.astype(rhs.dtype)

        with phase("krylov/" + type(self.solver).__name__):
            got = self.solver.solve(A_dev, apply_precond, rhs, x0)
        x, iters, resid = got[:3]
        # trailing elements by the solver's declared flags: history when
        # record_history, the HealthState when guard (telemetry/history.py
        # _hist_result — index arithmetic, not shape-guessing)
        rec_hist = bool(getattr(self.solver, "record_history", False))
        hist = got[3] if rec_hist else None
        hstate = got[3 + rec_hist] \
            if getattr(self.solver, "guard", False) else None
        hist_n = iters          # history covers the initial solve only
        if self.refine > 0:
            # correction-form iterative refinement (classic mixed-
            # precision recipe, mixing.hpp's spirit taken further): the
            # outer residual r = b − A x is evaluated beyond the working
            # precision, the correction solve runs in the working
            # precision. Two residual evaluators share ONE loop:
            #   float64 — wide operator (on TPU: software-emulated f64;
            #             the r5 chip session measured it at ~1/3 of the
            #             whole solve);
            #   df32    — compensated two-f32 arithmetic (ops/dfloat.py)
            #             at f32 hardware speed; the f32 rhs is treated
            #             as exact (b_lo = 0) — for f64-critical rhs use
            #             refine_dtype='float64'.
            if self.refine_mode == "df32":
                from amgcl_tpu.ops.dfloat import (dia_residual_df,
                                                  df_add_vec)
                A_lo = A_dev64      # the slot carries the lo operator
                zeros = jnp.zeros_like(rhs)

                def true_res(st):
                    xh, xl = st
                    return dia_residual_df(
                        A_dev.offsets, A_dev.data, A_lo.data, rhs,
                        zeros, xh, xl)

                def accumulate(st, dx):
                    return df_add_vec(st[0], st[1], dx)

                def finalize(st, rt, scale):
                    import jax as _jax
                    xh, xl = st
                    if _jax.config.jax_enable_x64:
                        # one wide combine at the very end — the loop
                        # itself never touches emulated f64
                        wide = self._wide_dtype()
                        return xh.astype(wide) + xl.astype(wide), rt
                    # without x64 the pair collapses back to ONE f32:
                    # report the residual of the x actually returned,
                    # not of the pair (which can be far better)
                    xc = xh + xl
                    r = dia_residual_df(A_dev.offsets, A_dev.data,
                                        A_lo.data, rhs, zeros, xc,
                                        zeros)
                    return xc, jnp.sqrt(jnp.abs(
                        dev.inner_product(r, r))) / scale

                state0 = (x, zeros)
                norm_src = rhs
            else:
                wide = self._wide_dtype()
                rhs64 = rhs.astype(wide)

                def true_res(st):
                    return dev.residual(rhs64, A_dev64, st)

                def accumulate(st, dx):
                    return st + dx.astype(wide)

                def finalize(st, rt, scale):
                    return st, rt

                state0 = x.astype(wide)
                norm_src = rhs64
            x, iters, resid, hstate = self._refine_loop(
                A_dev, apply_precond, rhs, state0, iters, norm_src,
                true_res, accumulate, finalize, hstate)
        return x, iters, resid, hist, hist_n, hstate

    def _refine_loop(self, A_dev, apply_precond, rhs, state0, iters,
                     norm_src, true_res, accumulate, finalize,
                     hstate=None):
        """Shared refinement scaffolding: while the scaled residual norm
        of ``true_res(state)`` exceeds tol (up to ``refine`` restarts),
        solve the correction in working precision and ``accumulate`` it
        into the solution state; ``finalize`` maps the final state to
        (x, resid). ``hstate`` (the initial solve's HealthState, or None
        with guards off) accumulates the correction solves' guard flags
        — a breakdown inside a correction must reach SolveReport.health,
        not vanish into the ``[:2]`` slice. First-trip iterations keep
        the earliest record (correction-local indices for flags only a
        correction tripped)."""
        from jax import lax as _lax
        nb = jnp.sqrt(jnp.abs(dev.inner_product(norm_src, norm_src)))
        scale = jnp.where(nb > 0, nb, 1.0)
        tol = getattr(self.solver, "tol", 1e-6)
        guard = hstate is not None and getattr(self.solver, "guard", False)

        def res_norm(r):
            return jnp.sqrt(jnp.abs(dev.inner_product(r, r))) / scale

        def cond(st):
            state, r, it, k, rt, hflags, hfirst = st
            return (rt > tol) & (k < self.refine)

        # stop correction solves exactly at the global absolute target
        # when the solver supports a dynamic abstol (CG does)
        import inspect
        has_abstol = "abstol" in inspect.signature(
            self.solver.solve).parameters

        def body(st):
            state, r, it, k, rt, hflags, hfirst = st
            kw = {}
            if has_abstol:
                kw["abstol"] = jnp.abs(tol * scale).astype(rhs.real.dtype)
            got = self.solver.solve(
                A_dev, apply_precond, r.astype(rhs.dtype),
                jnp.zeros_like(rhs), **kw)
            dx, it2 = got[:2]
            if guard:
                ch = got[-1]          # health is always the last element
                hflags = hflags | ch.flags
                hfirst = jnp.where(hfirst >= 0, hfirst, ch.first_it)
            state = accumulate(state, dx)
            r = true_res(state)
            return (state, r, it + it2, k + 1, res_norm(r), hflags,
                    hfirst)

        if guard:
            hflags0, hfirst0 = hstate.flags, hstate.first_it
        else:                         # structural dummies
            hflags0 = jnp.zeros((), jnp.int32)
            hfirst0 = jnp.zeros((1,), jnp.int32)
        r0 = true_res(state0)
        state, _, iters, _, rt, hflags, hfirst = _lax.while_loop(
            cond, body, (state0, r0, iters, 0, res_norm(r0), hflags0,
                         hfirst0))
        if guard:
            hstate = hstate._replace(flags=hflags, first_it=hfirst)
        x, resid = finalize(state, rt, scale.astype(rhs.dtype))
        return x, iters, resid, hstate

    def _recovery_policy(self):
        """Resolve the ``recovery=`` constructor arg (see __init__) to
        a RecoveryPolicy or None. Imported lazily — the faults layer
        never loads on the plain solve path."""
        rec = self.recovery
        if rec is None:
            import os
            if os.environ.get("AMGCL_TPU_RECOVERY", "0") != "1":
                return None
            rec = True
        if rec is False:
            return None
        from amgcl_tpu.faults.recovery import RecoveryPolicy
        if isinstance(rec, RecoveryPolicy):
            return rec
        return RecoveryPolicy.from_env()

    def __call__(self, rhs, x0=None):
        """One solve. With recovery off (the default) this is exactly
        the historical single-dispatch path (:meth:`_solve_once`); with
        recovery on, fatal guard trips and device losses walk the
        bounded escalation ladder (faults/recovery.py) and the attempt
        trail lands on ``SolveReport.recovery``."""
        policy = self._recovery_policy()
        if policy is None:
            return self._solve_once(rhs, x0)
        from amgcl_tpu.faults.recovery import solve_with_recovery
        return solve_with_recovery(self, rhs, x0, policy)

    def _solve_once(self, rhs, x0=None):
        """One dispatch of the solve program, under the ``solve`` span
        (telemetry/tracing.py) and its steps: ``solve/prepare``,
        ``solve/dispatch``, ``solve/fetch`` (the host waits on the
        device) and ``solve/report``."""
        shp = np.shape(rhs)
        first_call = self._compiled is None
        with solve_span(first_call=first_call,
                        batched=len(shp) == 2) as sp:
            sp.step("solve/prepare")
            rhs, x0, rhs_d, x0_d = self._prepare(rhs, x0, shp)
            t0 = time.perf_counter()
            if first_call:
                self._wrapped_solve_fn()
            entry, nspec = self._dispatch_entry()
            cw0 = _cwatch.snapshot(_SOLVE_FN) if _cwatch.enabled() \
                else None
            sp.step("solve/dispatch")
            got = self._dispatch(entry, nspec, rhs, x0, rhs_d, x0_d)
            x = got[0]
            if getattr(self, "_reorder", None) is not None:
                _, iperm = self._perm_pair()
                x = jnp.take(x, iperm, axis=0)   # to the caller's frame
            sp.step("solve/fetch")
            # ONE device->host round trip for everything the SolverInfo
            # needs — separate int()/float()/np.asarray() conversions
            # would each pay a full device sync (the None slots for
            # hist/health pass through device_get as empty pytree nodes)
            fetched = jax.device_get(got[1:6])
            sp.step("solve/report")
            report = self._report(fetched, shp, rhs, x0, x, t0,
                                  first_call, cw0, sp)
        return x, report

    def _prepare(self, rhs, x0, shp):
        """Shape checks, device arrays of ``rhs`` and ``x0`` (zeros when
        None), and their copies in the hierarchy's frame."""
        n = self.A_host.nrows * self.A_host.block_size[0]
        batched = len(shp) == 2
        if not (shp == (n,) or (batched and shp[0] == n and shp[1] >= 1)):
            raise ValueError(
                "rhs has shape %s but the system has %d unknowns "
                "(stacked multi-RHS must be (n, B))" % (shp, n))
        if batched and self.refine > 0:
            raise ValueError(
                "stacked multi-RHS solves do not support iterative "
                "refinement yet; build the bundle with refine=0")
        rhs = jnp.asarray(rhs, dtype=self.solver_dtype)
        if x0 is not None:
            if np.shape(x0) != shp:
                raise ValueError(
                    "x0 has shape %s but rhs has shape %s"
                    % (np.shape(x0), shp))
            x0 = jnp.asarray(x0, dtype=self.solver_dtype)
        else:
            x0 = jnp.zeros_like(rhs)
        # executed-reorder seam: dispatch in the hierarchy's permuted
        # frame; the ORIGINAL-frame rhs/x0 names stay live for the df32
        # runtime check and the flight recorder (both evaluate against
        # self.A_host, which is original-order). jnp.take with axis=0
        # covers the stacked (n, B) case unchanged.
        rhs_d, x0_d = rhs, x0
        if getattr(self, "_reorder", None) is not None:
            perm, _ = self._perm_pair()
            rhs_d = jnp.take(rhs, perm, axis=0)
            x0_d = jnp.take(x0, perm, axis=0)
        return rhs, x0, rhs_d, x0_d

    def _dispatch_entry(self):
        """The program this call dispatches, and the pending numeric
        fault spec (None unless a fault plan fired)."""
        # fault seams (faults/inject.py), both one env read when no
        # plan is armed: ``device.loss`` raises the typed error at the
        # dispatch boundary (the recovery ladder resumes from the last
        # checkpoint); a fired ``numeric.*`` rule routes THIS call
        # through a fresh jit wrap so the fault bakes into a throwaway
        # trace — begin/end scope the pending spec to this dispatch,
        # so the clean cached program (and any OTHER trace in the
        # process) never carries the fault, and the rule's
        # after/count/p trigger logic sees one check per dispatch
        entry = self._compiled
        nspec = None
        import os as _os
        if _os.environ.get("AMGCL_TPU_FAULT_PLAN"):
            from amgcl_tpu.faults import DeviceLostError
            from amgcl_tpu.faults import inject as _inject
            if _inject.should_fire("device.loss",
                                   target="solve") is not None:
                raise DeviceLostError(
                    "injected device loss at the solve dispatch seam")
            if getattr(self.solver, "guard", False):
                # guard=False solvers never reach the numeric seam —
                # firing the rule there would book a fault (event,
                # counter, flight trip) that was never actually
                # planted; leave it armed instead
                nspec = _inject.begin_numeric_dispatch()
            if nspec is not None:
                entry = _cwatch.watched_jit(self._solve_fn,
                                            name=_SOLVE_FN)
        return entry, nspec

    def _dispatch(self, entry, nspec, rhs, x0, rhs_d, x0_d):
        try:
            return entry(self.A_dev, self.A_dev64,
                         self.precond.hierarchy, rhs_d, x0_d)
        except Exception as e:
            # OOM seam (ISSUE 18): a backend RESOURCE_EXHAUSTED used to
            # escape as a raw XlaRuntimeError — classify, trip the
            # memwatch forensics (flight bundle with the memory
            # timeline + top-owner table), and re-raise typed so the
            # serve/farm layers treat it admission-class
            from amgcl_tpu import faults as _faults
            if not _faults.is_resource_exhausted(e):
                raise
            from amgcl_tpu.telemetry import memwatch as _mw
            _mw.record_allocation_failure("solve.dispatch", e,
                                          bundle=self, rhs=rhs, x0=x0)
            raise _faults.AllocationError(
                "device allocation failed dispatching the solve: "
                "hierarchy holds %d measured bytes, system operator %d"
                " — evict a resident operator or lower the problem "
                "size (%s)"
                % (_mw.measured_tree_bytes(self.precond.hierarchy),
                   _mw.measured_tree_bytes(self.A_dev),
                   str(e)[:200])) from e
        finally:
            if nspec is not None:
                from amgcl_tpu.faults import inject as _inject
                _inject.end_numeric_dispatch()

    def _report(self, fetched, shp, rhs, x0, x, t0, first_call, cw0, sp):
        """The SolveReport of one call from its fetched scalars; each
        telemetry leg it calls runs between ``sp.begin(
        "solve/report/<leg>")`` and ``sp.end()`` on the ``solve`` span
        ``sp``, a child of its ``solve/report`` step."""
        n = self.A_host.nrows * self.A_host.block_size[0]
        batched = len(shp) == 2
        iters, resid, hist_buf, hist_n, hstate = fetched
        hist = None
        per_rhs = None
        if batched:
            # per-column convergence record; the headline iters/resid
            # are the batch maxima (the numbers a latency SLO cares
            # about), per-column detail rides ``extra["per_rhs"]``
            per_rhs = {"iters": [int(v) for v in np.atleast_1d(iters)],
                       "resid": [float(v) for v in np.atleast_1d(resid)]}
            if hist_buf is not None:
                # (B, maxiter) with per-column recorded counts (== the
                # per-column iters; refine is gated off when batched):
                # slice each column by its own count, headline history =
                # the slowest column's (matches the headline iters)
                hb = np.asarray(hist_buf)
                hn = per_rhs["iters"]
                per_rhs["history"] = [hb[b, :hn[b]].tolist()
                                      for b in range(hb.shape[0])]
                hist = hb[int(np.argmax(hn)), :max(hn)]
            iters = max(per_rhs["iters"])
            resid = max(per_rhs["resid"])
        elif hist_buf is not None:
            # slice by the recorded count — NaN filtering would also drop
            # genuine NaN residuals from a breakdown
            hist = np.asarray(hist_buf)[:int(hist_n)]
        health = None
        if hstate is not None:
            sp.begin("solve/report/health")
            from amgcl_tpu.telemetry import health as _health
            if batched:
                from amgcl_tpu.serve.batched import decode_batched_health
                health = decode_batched_health(
                    np.atleast_1d(np.asarray(hstate.flags)),
                    np.atleast_2d(np.asarray(hstate.first_it)))
            else:
                health = _health.decode(hstate.flags, hstate.first_it)
            sp.end()
        wall = time.perf_counter() - t0
        extra = {"first_call": True} if first_call else {}
        if batched:
            extra["batch"] = int(shp[1])
            extra["per_rhs"] = per_rhs
        if first_call and self.refine_mode == "df32":
            # satellite of _df32_selfcheck: the standalone-jit check ran
            # the residual kernel ALONE — the full _solve_fn program fuses
            # it into the refinement loop, where reassociation can undo
            # the compensation. Validate the first compiled call's
            # reported residual against a host f64 residual once.
            sp.begin("solve/report/df32_check")
            self._check_df32_runtime(rhs, x, float(resid))
            sp.end()
        if getattr(self, "_df32_drift", None) is not None:
            # set by _check_df32_runtime on harmful drift — sticky so the
            # doctor sees it on every later report from this bundle
            extra["df32_drift"] = self._df32_drift
        # which lowering this dispatch took: stacked traces run with the
        # Pallas gates off ("xla-batched"), single-rhs dispatches take
        # the hand kernels where the gates allow ("pallas") and XLA
        # otherwise — recorded so CPU-fallback vs kernel runs are
        # distinguishable in rollups (the PR-5 platform-mismatch lesson).
        # The tag is captured when a trace happens and stickied on the
        # bundle: warm dispatches reuse jit's cached executable, so the
        # gate state that governed the TRACE is the truth, not the live
        # gate state at report time (which env flips can change between
        # calls)
        compile_rec = None
        delta = None
        if cw0 is not None:
            # per-call compile delta: 0 new traces on a warm repeat, 1 on
            # a fresh shape — the recompile counter the roofline tests
            # pin down
            sp.begin("solve/report/compile_watch")
            cw1 = _cwatch.snapshot(_SOLVE_FN)
            delta = _cwatch.delta(cw0, cw1)
            sp.end()
        tags = getattr(self, "_lowering_tags", None)
        if tags is None:
            tags = self._lowering_tags = {}
        # keyed by the abstract shape: the first call per shape IS the
        # trace, so the tag is captured at trace time with or without
        # the compile watch. Deliberately NOT refreshed on the watch's
        # new_traces delta — the _SOLVE_FN counter is process-global,
        # so a concurrent trace by a DIFFERENT bundle would relabel
        # this bundle's warm calls from post-flip gate state
        key = shp
        if key not in tags:
            from amgcl_tpu.serve.batched import lowering_kind
            tags[key] = lowering_kind(batched, self.solver_dtype)
        lowering = tags[key]
        if delta is not None:
            compile_rec = {"function": _SOLVE_FN,
                           **delta,
                           "signatures": cw1["signatures"],
                           "lowering": lowering,
                           "totals": {"traces": cw1["traces"],
                                      "compile_s": cw1["compile_s"]}}
        else:
            # the tag must survive AMGCL_TPU_COMPILE_WATCH=0 — it is a
            # lowering fact, not a compile statistic
            extra["lowering"] = lowering
        resources = self._resources()
        if batched and resources and "error" not in resources:
            # per-iteration model with the batch axis: operator reads
            # amortize over B, vector streams and FLOPs scale with it
            # (ledger.krylov_iteration_model) — a copy, so the cached
            # single-rhs model keeps pricing unbatched calls
            try:
                from amgcl_tpu.telemetry import ledger as _ledger
                resources = dict(resources)
                resources["per_iteration"] = \
                    _ledger.krylov_iteration_model(
                        type(self.solver).__name__, self.A_dev,
                        (resources.get("cycle") or {}).get("total"),
                        getattr(getattr(self.precond, "prm", None),
                                "pre_cycles", 1),
                        batch=int(shp[1]))
            except Exception:
                pass
        from amgcl_tpu.telemetry import memwatch as _mw
        if resources is not None and _mw.enabled():
            # measured memory join (telemetry/memwatch.py): what the
            # device ACTUALLY holds for this bundle, with provenance —
            # in place on the cached dict (prior reports alias it)
            sp.begin("solve/report/memwatch")
            try:
                bm = _mw.solve_resources(self)
                if bm is not None:
                    resources["bytes_measured"] = bm
            except Exception:
                pass         # measurement must never fail a solve
            sp.end()
        report = SolveReport(
            int(iters), float(resid), hist, wall_time_s=wall,
            solves_per_sec=round(shp[1] / wall, 3)
            if batched and wall > 0 else None,
            solver=type(self.solver).__name__,
            hierarchy=self._hierarchy_stats(),
            resources=resources,
            health=health,
            compile=compile_rec,
            # the first call's wall time includes jit trace + compile —
            # flag it so sink consumers can separate it from steady state
            extra=extra)
        # flight recorder (telemetry/flight.py): ring this solve's
        # capsule (O(1) — refs to the immutable arrays, weakref to the
        # bundle) and, on a FATAL guard trip, dump a self-contained
        # replay bundle so the field incident becomes a deterministic
        # repro. Best-effort: the recorder must never fail a solve.
        from amgcl_tpu.telemetry import flight as _flight
        if _flight.enabled():
            sp.begin("solve/report/flight")
            try:
                _flight.record_solve(self, rhs, x0, report)
                if _flight.fatal_health(health):
                    _flight.dump("health_trip", bundle=self, rhs=rhs,
                                 x0=x0, report=report,
                                 tags={"flags": health.get("flags")})
            except Exception:
                pass
            sp.end()
        # process-global JSONL sink (telemetry/sink.py); the NullSink check
        # keeps the unconfigured hot path free of the to_dict() conversion
        # (this function already fights per-call host overhead — see the
        # single-fetch comment in _solve_once)
        from amgcl_tpu.telemetry.sink import NullSink, get_default_sink
        if not isinstance(get_default_sink(), NullSink):
            sp.begin("solve/report/sink")
            telemetry_emit(report.to_dict(), event="solve", n=n)
            if health is not None and not health["ok"]:
                # a dedicated, easily-grepped event for every
                # unhealthy solve — the decoded guard record plus
                # the numbers a dashboard alert needs
                telemetry_emit(event="health", n=n,
                               solver=type(self.solver).__name__,
                               iters=int(iters), resid=float(resid),
                               **health)
            sp.end()
        return report

    def _wrapped_solve_fn(self):
        """THE jit wrap of the solve program — observed jit
        (telemetry/compile_watch.py): traces, backend compiles and
        compile seconds land in SolveReport.compile; a retrace on a new
        shape after warmup is flagged for the doctor. One method so the
        static donation audit (analysis/jaxpr_audit.audit_make_solver)
        lowers the SAME wrap the solve runs — when ROADMAP item 1 adds
        donated buffers here, the audit sees them."""
        if self._compiled is None:
            self._compiled = _cwatch.watched_jit(
                self._solve_fn, name=_SOLVE_FN)
        return self._compiled

    def _hierarchy_stats(self):
        # invariant per built hierarchy — cached; rebuild() invalidates
        cached = getattr(self, "_hier_stats_cache", None)
        if cached is None:
            stats = getattr(self.precond, "hierarchy_stats", None)
            cached = stats() if callable(stats) else None
            self._hier_stats_cache = cached
        return cached

    def _resources(self):
        """SolveReport.resources: hierarchy memory totals, the per-stage
        cycle FLOP/byte model, the per-Krylov-iteration model, dense-
        window budget use and the setup-phase profile (telemetry/
        ledger.py). Cached per build; never raises — a ledger bug must
        not turn a converged solve into a failure."""
        cached = getattr(self, "_resources_cache", None)
        if cached is None:
            try:
                from amgcl_tpu.telemetry import ledger as _ledger
                rl = getattr(self.precond, "resource_ledger", None)
                led = rl() if callable(rl) else None
                cycle = led["cycle"]["total"] if led else None
                pre_cycles = getattr(getattr(self.precond, "prm", None),
                                     "pre_cycles", 1)
                cached = {"per_iteration": _ledger.krylov_iteration_model(
                    type(self.solver).__name__, self.A_dev, cycle,
                    pre_cycles)}
                if led is not None:
                    cached["memory"] = {
                        "bytes": led["totals"]["bytes"],
                        "by_format": led["totals"]["by_format"],
                        "coarse_solver_bytes": led["coarse_solver_bytes"]}
                    cached["cycle"] = led["cycle"]
                    for key in ("dense_window", "setup"):
                        if led.get(key) is not None:
                            cached[key] = led[key]
            except Exception as e:
                cached = {"error": repr(e)[:200]}
            self._resources_cache = cached
        return cached

    def _check_df32_runtime(self, rhs_dev, x, reported):
        """One-shot validation of the compiled df32 refinement: the
        REPORTED relative residual of the first _solve_fn call must be
        consistent with the host-f64 residual of the returned solution.
        The standalone-jit selfcheck misses fusion/reassociation drift
        that only appears when the compensated kernel is compiled INSIDE
        the refinement loop; this catches it where it matters. Returns
        the host-f64 relative residual (None when unscored)."""
        b64 = np.asarray(rhs_dev, np.float64)
        x64 = np.asarray(x, np.float64)
        nb = float(np.linalg.norm(b64))
        if nb == 0 or not np.all(np.isfinite(x64)):
            return None
        actual = float(np.linalg.norm(b64 - self.A_host.spmv(x64)) / nb)
        tol = float(getattr(self.solver, "tol", 1e-6))
        if actual > max(10.0 * reported, 2.0 * tol) \
                and actual > 1e-12 * len(b64):
            import warnings
            self._df32_drift = {"reported": reported, "actual": actual}
            warnings.warn(
                "df32 refinement drift: the compiled solve reports a "
                "relative residual of %.3e but the host float64 residual "
                "of the returned solution is %.3e — the fused compilation "
                "likely reassociated the compensated arithmetic; use "
                "refine_dtype='float64' (trusted residuals) or report "
                "this configuration" % (reported, actual))
        telemetry_emit(event="df32_check", reported=reported,
                       actual=actual, n=len(b64))
        return actual

    def __repr__(self):
        return ("make_solver\n===========\nSolver: %s\n\nPreconditioner:\n%r"
                % (type(self.solver).__name__, self.precond))
