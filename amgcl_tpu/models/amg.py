"""The AMG hierarchy: host-side construction, device-side V/W-cycle.

Mirrors the capability of the reference's ``amg<Backend, Coarsening, Relax>``
(amgcl/amg.hpp:63-557): the hierarchy is built level by level on the host in
CSR (do_init loop, amg.hpp:467-512), each level's operator/transfer matrices
and smoother state are moved to the device, and ``apply`` runs the multigrid
cycle (amg.hpp:514-553) as a fully traced XLA program — the level count is
static, so the cycle recursion unrolls into one fused graph.
"""

from __future__ import annotations

import time

from dataclasses import dataclass, field, replace
from typing import Any, Optional

import numpy as np
import jax.numpy as jnp
from jax.tree_util import register_pytree_node_class

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops import device as dev
from amgcl_tpu.coarsening.smoothed_aggregation import SmoothedAggregation
from amgcl_tpu.coarsening.stall import CoarseningStall
from amgcl_tpu.relaxation.spai0 import Spai0
from amgcl_tpu.solver.direct import DenseDirectSolver
from amgcl_tpu.telemetry.tracing import phase, setup_scope, span


@dataclass
class AMGParams:
    """Hierarchy parameters (reference: amg::params, amgcl/amg.hpp:93-182)."""
    coarsening: Any = field(default_factory=SmoothedAggregation)
    relax: Any = field(default_factory=Spai0)
    coarse_enough: int = 3000
    direct_coarse: bool = True
    max_levels: int = 100
    npre: int = 1
    npost: int = 1
    ncycle: int = 1          # 1 = V-cycle, 2 = W-cycle
    pre_cycles: int = 1      # cycles per preconditioner application
    dtype: Any = jnp.float32
    matrix_format: str = "auto"   # device format for level operators


@register_pytree_node_class
class Level:
    """Device-resident state of one hierarchy level."""

    def __init__(self, A, relax, P=None, R=None, down=None, up=None):
        self.A = A          # device matrix (level operator)
        self.relax = relax  # smoother state (None on the coarsest level)
        self.P = P          # prolongation to this level from the next coarser
        self.R = R          # restriction to the next coarser level
        self.down = down    # optional fused residual+restrict kernel handle
        self.up = up        # optional fused prolong+correct+smooth handle

    def tree_flatten(self):
        return (self.A, self.relax, self.P, self.R, self.down,
                self.up), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@register_pytree_node_class
class Hierarchy:
    """Pytree of levels + coarse solver; ``cycle``/``apply`` are traceable."""

    def __init__(self, levels, coarse, npre=1, npost=1, ncycle=1,
                 pre_cycles=1):
        self.levels = list(levels)
        self.coarse = coarse
        self.npre = int(npre)
        self.npost = int(npost)
        self.ncycle = int(ncycle)
        self.pre_cycles = int(pre_cycles)

    def tree_flatten(self):
        return ((self.levels, self.coarse),
                (self.npre, self.npost, self.ncycle, self.pre_cycles))

    @classmethod
    def tree_unflatten(cls, aux, children):
        levels, coarse = children
        return cls(levels, coarse, *aux)

    # -- the multigrid cycle (reference: amgcl/amg.hpp:514-553) -------------

    def cycle(self, i, f):
        """One multigrid cycle at level i for rhs f, zero initial guess.

        Every stage is wrapped in a ``jax.named_scope`` (telemetry/
        tracing.py) so a ``jax.profiler`` trace groups device time into the
        reference profiler tree's five phases — pre_smooth / restrict /
        coarse_solve / prolong / post_smooth — per level; the fused
        whole-leg kernels get their own down_fused / up_fused scopes."""
        lv = self.levels[i]
        if i == len(self.levels) - 1:
            with phase("level%d/coarse_solve" % i):
                if self.coarse is not None:
                    return self.coarse.solve(f)
                u = lv.relax.apply(lv.A, f)
                return u
        # prebuilt fused-sweep kernels carry exact 1-D shapes and call
        # pallas_call without re-checking the gates — a stacked/vmapped
        # trace (pallas_locally_disabled) must take the composed path
        from amgcl_tpu.ops.pallas_spmv import pallas_locally_disabled
        fused_ok = not pallas_locally_disabled()
        fc = None
        if self.npre == 1 and fused_ok and lv.down is not None \
                and lv.down.w is not None:
            # whole down-sweep in one pass: pre-smooth from zero,
            # residual, filtered tentative restriction
            with phase("level%d/down_fused" % i):
                u, fc = lv.down.zero(f)
        else:
            with phase("level%d/pre_smooth" % i):
                if self.npre > 0:
                    u = lv.relax.apply(lv.A, f)  # first pre-sweep from zero
                    for _ in range(self.npre - 1):
                        u = lv.relax.apply_pre(lv.A, f, u)
                else:
                    u = dev.clear(f)
            if fused_ok and lv.down is not None:
                # one-pass residual + filtered tentative restriction
                with phase("level%d/restrict" % i):
                    fc = lv.down(f, u)
        if fc is None:
            with phase("level%d/restrict" % i):
                r = dev.residual(f, lv.A, u)
                fc = dev.spmv(lv.R, r)
        uc = self.cycle(i + 1, fc)
        for _ in range(self.ncycle - 1):      # W-cycle: extra coarse visits
            rc = dev.residual(fc, self.levels[i + 1].A, uc)
            uc = uc + self.cycle(i + 1, rc)
        if fused_ok and lv.up is not None and self.npost >= 1:
            # one-pass prolong + correct + first post-smoothing sweep
            with phase("level%d/up_fused" % i):
                u = lv.up(f, u, uc)
            extra = self.npost - 1
        else:
            with phase("level%d/prolong" % i):
                u = u + dev.spmv(lv.P, uc)
            extra = self.npost
        if extra > 0:
            with phase("level%d/post_smooth" % i):
                for _ in range(extra):
                    u = lv.relax.apply_post(lv.A, f, u)
        return u

    def apply(self, r):
        """Preconditioner application (amg.hpp:288-297): pre_cycles cycles.

        Accepts a stacked ``(n, B)`` residual block (serve/batched.py):
        the cycle is vmapped over the trailing batch axis, so ONE XLA
        program runs the whole V-cycle for B right-hand sides — every
        level operator is read once per sweep regardless of B once XLA
        batches the level matvecs."""
        if getattr(r, "ndim", 1) == 2:
            import jax
            from amgcl_tpu.ops.pallas_spmv import pallas_disabled
            # the 1-D hand kernels (incl. the prebuilt fused sweeps) do
            # not carry a batch axis — the stacked trace takes the XLA
            # lowerings, which batch natively under vmap; thread-local,
            # so concurrent single-rhs traces keep their kernels
            with pallas_disabled():
                return jax.vmap(self.apply, in_axes=1, out_axes=1)(r)
        x = self.cycle(0, r)
        for _ in range(self.pre_cycles - 1):
            rr = dev.residual(r, self.levels[0].A, x)
            x = x + self.cycle(0, rr)
        return x

    @property
    def system_matrix(self):
        return self.levels[0].A


def _human_bytes(n: float) -> str:
    for unit in ("B", "K", "M", "G"):
        if n < 1024 or unit == "G":
            return "%.2f %s" % (n, unit)
        n /= 1024.0


class AMG:
    """Host-side builder + owner of the device hierarchy.

    Usage::

        P = AMG(A, AMGParams(...))
        z = P.hierarchy.apply(r)      # traceable
    """

    def __init__(self, A: CSR, prm: Optional[AMGParams] = None,
                 device_filter=None):
        """``device_filter(idx, scalar_size, is_last) -> bool`` optionally
        skips device realization (matrix move + smoother build) for levels
        a wrapper will re-shard itself — DistAMGSolver passes one so
        ILU/GS/SPAI states are not built twice per sharded level. Skipped
        levels get a ``Level(None, None, None, None)`` placeholder."""
        self.prm = prm or AMGParams()
        if not isinstance(A, CSR):
            A = CSR.from_scipy(A)
        self._device_filter = device_filter
        self.host_levels = []   # list of (A, P, R) host CSR per level
        self._build(A)

    # -- setup (reference: amgcl/amg.hpp:467-512 do_init) -------------------

    def _build(self, A: CSR):
        """The whole build, under the ``setup/hierarchy`` span; its
        ``path`` attribute says which set-up ran: ``device`` (every
        level built on the device, ops/stencil_device.py), ``hybrid``
        (a device-built prefix, then the host loop) or ``host``;
        ``well_pallas`` and ``well_xla`` count the windowed-ELL operators
        (A, P and R of every level) that take the lane-gather kernel and
        those that run XLA's gather."""
        with span("setup/hierarchy") as sp:
            self._build_levels(A)
            sp.set(path="host" if not self._device_built
                   else "hybrid" if self._dev_prefix else "device",
                   **self._well_kernels())

    def _well_kernels(self):
        from amgcl_tpu.ops.unstructured import WindowedEllMatrix
        kernels = [M.kernel_status()[0]
                   for lv in self.hierarchy.levels
                   for M in (lv.A, lv.P, lv.R)
                   if isinstance(M, WindowedEllMatrix)]
        return {"well_pallas": kernels.count("pallas"),
                "well_xla": kernels.count("xla")}

    def _build_levels(self, A: CSR):
        prm = self.prm
        self._device_built = False
        self._dev_prefix = []
        self._prefix_released = False
        self._ledger_cache = None
        self._probe_cache = None
        self._roofline_cache = None
        self._structure_cache = None
        self._format_decisions = None
        self._reorder = None
        # setup-phase profiler (PR 1 instrumented the SOLVE phase only):
        # device-synced tic/toc scopes + setup/* spans around
        # coarsening / galerkin / device transfer / smoother setup, on
        # both set-up paths, exported through hierarchy_stats()["setup"]
        # and the resource ledger
        from amgcl_tpu.utils.profiler import Profiler
        prof = self.setup_profile = Profiler.device()
        self._setup_t0 = time.perf_counter()
        n_prefix = 0
        eps_override = None
        if self._device_filter is None:
            # whole-hierarchy device setup for stencil problems: every
            # level's filter/smoother/Galerkin runs on the accelerator and
            # the level operators are born device-resident
            # (ops/stencil_device.py); None -> host path, same numerics
            from amgcl_tpu.ops import stencil_device as sdev
            if sdev.enabled():
                got = sdev.device_build(A, prm, prof)
                if got is not None:
                    self._device_built = True
                    meta_rows = [(m_, None, None) for m_ in got["meta"]]
                    # keep the REAL fine-level CSR in row 0 — consumers
                    # (pyamgcl_compat, adapters) read host_levels[0][0]
                    # as the system matrix
                    meta_rows[0] = (A, None, None)
                    if got["leftover"] is None:
                        self.hierarchy = Hierarchy(
                            got["levels"], got["coarse"], prm.npre,
                            prm.npost, prm.ncycle, prm.pre_cycles)
                        self.host_levels = meta_rows
                        self._setup_wall_s = \
                            time.perf_counter() - self._setup_t0
                        self._memwatch_built()
                        return
                    # hybrid: SA stencil growth moved past the
                    # diagonal-pair regime — continue with the classic
                    # (SpGEMM) loop from the downloaded coarse level
                    self._dev_prefix = got["levels"]
                    self._meta_prefix = meta_rows[:-1]
                    n_prefix = len(self._dev_prefix)
                    A = got["leftover"]
                    eps_override = got["eps_next"]
        if self._device_filter is None and not self._device_built \
                and not n_prefix and A.block_size == (1, 1):
            # executed reorder (ISSUE 20): when the structure advisor
            # predicts the layout wins back >= GAIN_FLOOR of SpMV bytes
            # (or AMGCL_TPU_REORDER forces a variant), permute the fine
            # operator HERE, before coarsening — the whole hierarchy,
            # transfer operators included, is then built in the permuted
            # frame and the device transfer absorbs the reorder for
            # free. make_solver permutes rhs/x0 in and un-permutes x
            # out, so the permutation is invisible at every outer seam.
            import jax
            from amgcl_tpu.telemetry import structure as _st
            with setup_scope(prof, "reorder"):
                try:
                    _isz = jnp.dtype(prm.dtype).itemsize
                except TypeError:
                    _isz = 4
                plan = _st.reorder_plan(
                    A, on_tpu=jax.default_backend() == "tpu",
                    itemsize=_isz)
                if plan is not None:
                    from amgcl_tpu.utils.adapters import permute
                    A = permute(A, plan["perm"])
                    A._reorder_prov = {
                        "variant": plan["variant"],
                        "fingerprint": plan["fingerprint"],
                        "predicted_gain": plan["predicted_gain"]}
                    self._reorder = plan
        coarsening = prm.coarsening
        # per-build state (eps_strong decay, coarse nullspace, grid dims)
        # lives in this context dict, NOT on the policy object — building
        # twice from one params object produces identical hierarchies
        ctx = {}
        if eps_override is not None:
            ctx["eps_strong"] = eps_override
        if getattr(coarsening, "setup_dtype", False) is None:
            # a <=32-bit device hierarchy lets the stencil setup algebra
            # run in float32 — same convergence, half the memory traffic
            try:
                if jnp.dtype(prm.dtype).itemsize <= 4 and not \
                        jnp.issubdtype(prm.dtype, jnp.complexfloating):
                    ctx["setup_dtype"] = np.float32
            except TypeError:
                pass
        host = []
        Acur = A
        while (Acur.nrows * Acur.block_size[0] > prm.coarse_enough
               and n_prefix + len(host) + 1 < prm.max_levels):
            lvl = "level%d" % (n_prefix + len(host))
            try:
                with setup_scope(prof, lvl + "/coarsening"):
                    P, R = coarsening.transfer_operators(Acur, ctx)
            except CoarseningStall:
                break     # expected terminal condition: close the
                          # hierarchy here; other ValueErrors propagate
                          # (a bare except here once mislabeled a fixture
                          # bug as a stall — see coarsening/stall.py)
            if P.ncols == 0 or P.ncols >= Acur.ncols:
                break  # coarsening stalled
            with setup_scope(prof, lvl + "/galerkin"):
                Ac = coarsening.coarse_operator(Acur, P, R, ctx)
            host.append((Acur, P, R))
            Acur = Ac
        host.append((Acur, None, None))
        self.host_levels = (self._meta_prefix + host) if n_prefix else host
        self._coarse_op = coarsening.coarse_operator
        self._to_device_levels()
        # wall time of THIS build: the profiler's own total keeps ticking
        # after construction, so attribution needs the frozen number
        self._setup_wall_s = time.perf_counter() - self._setup_t0

    def rebuild(self, A):
        """Numeric-only rebuild for time-dependent problems: the matrix
        VALUES changed, the sparsity (and thus the aggregation, transfer
        operators, Galerkin plans, and device-format structure) is reused
        (reference: amg::rebuild, amgcl/amg.hpp:229-269 with
        allow_rebuild).

        Accepts a CSR with the SAME sparsity pattern — asserted, a
        structural change needs a fresh ``AMG`` — or just the new value
        array (``rebuild(new_vals)``), which skips the pattern comparison
        entirely. Each level re-runs only the numeric Galerkin/smoothing
        segment kernels against the plans cached on the transfer
        operators (ops/segment_spgemm.py, ops/stencil.py), the smoother
        states, and the device value refresh — no strength graphs, no
        aggregation, no symbolic SpGEMM, and the device transfer
        operators (frozen by the rebuild contract) are reused as-is."""
        old0 = self.host_levels[0][0]
        # executed-reorder interplay: when a plan is active, host_levels
        # holds the PERMUTED operator while callers hand back values in
        # the ORIGINAL ordering (time-dependent loops never learn about
        # the permutation). val_perm maps original-order values into the
        # permuted frame; a caller handing back the permuted pattern
        # itself (e.g. readmit) passes through untouched.
        plan = getattr(self, "_reorder", None)
        if isinstance(A, np.ndarray):
            if A.shape != old0.val.shape:
                raise ValueError(
                    "rebuild(new_vals): value array shape %r does not "
                    "match the operator's %r"
                    % (A.shape, old0.val.shape))
            vals = np.asarray(A)
            if plan is not None:
                vals = vals[plan["val_perm"]]
            A = CSR(old0.ptr, old0.col, vals, old0.ncols)
            same_pattern = True
        else:
            if not isinstance(A, CSR):
                A = CSR.from_scipy(A)
            if A.shape != old0.shape:
                raise ValueError(
                    "rebuild requires the same matrix dimensions")
            if plan is not None and A.nnz == old0.nnz and not (
                    A.ptr is old0.ptr and A.col is old0.col) and (
                    (A.ptr is plan["ptr"] and A.col is plan["col"])
                    or (np.array_equal(A.ptr, plan["ptr"])
                        and np.array_equal(A.col, plan["col"]))):
                # original-order CSR: re-permute the values into the
                # frame the hierarchy lives in (pure O(nnz) take)
                A = CSR(old0.ptr, old0.col,
                        np.asarray(A.val)[plan["val_perm"]], old0.ncols)
            same_pattern = A.nnz == old0.nnz and (
                (A.ptr is old0.ptr and A.col is old0.col)
                or (np.array_equal(A.ptr, old0.ptr)
                    and np.array_equal(A.col, old0.col)))
        if getattr(self, "_device_built", False) \
                or getattr(self, "_dev_prefix", []) \
                or getattr(self, "_prefix_released", False):
            # device-built (and hybrid device-prefix) hierarchies redo
            # the whole (cheap, on-device) build; the transfer structure
            # is re-derived identically. _device_built covers both today
            # — the prefix check is belt-and-braces so meta rows with
            # P=None can never reach the numeric loop below
            self._build(A)
            return
        if not same_pattern:
            raise ValueError(
                "rebuild requires the same sparsity pattern (values-only "
                "update); construct a new AMG for structural changes")
        # structure-only caches carry over (the pattern is identical):
        # the DIA scatter plan and row expansion are what make the
        # device value refresh O(nnz) with no symbolic work
        for attr in ("_rows_cache", "_dia_struct_cache",
                     "_dia_offsets_cache", "_grid_dims"):
            if not hasattr(A, attr) and hasattr(old0, attr):
                setattr(A, attr, getattr(old0, attr))
        from amgcl_tpu.utils.profiler import Profiler
        prof = self.setup_profile = Profiler.device()
        self._setup_t0 = time.perf_counter()
        self._ledger_cache = None
        self._probe_cache = None
        self._roofline_cache = None
        self._structure_cache = None
        # one-time on a first rebuild: when the numeric backend is the
        # device, make sure every CSR level carries a Galerkin plan so
        # this and every later rebuild is a pure numeric segment pass
        # (on the CPU backend the native hash-SpGEMM outruns a host
        # segment pass over the materialized multiply list, so general
        # levels keep the host route there; selection levels always plan)
        from amgcl_tpu.ops import segment_spgemm as seg
        host = []
        Acur = A
        for i, (Ai, P, R) in enumerate(self.host_levels[:-1]):
            if isinstance(P, CSR) and not seg.host_setup_forced():
                seg.ensure_plan(Ai, P, R,
                                force=seg.device_numeric(Ai.val.dtype))
            host.append((Acur, P, R))
            with setup_scope(prof, "level%d/galerkin" % i):
                Acur = self._coarse_op(Acur, P, R)
        host.append((Acur, None, None))
        # a released hierarchy (release_device) has no old device levels
        # to reuse — the transfers re-pack fresh, but the numeric path
        # above (cached plans, no aggregation/symbolic work) is the same
        old_hier = getattr(self, "hierarchy", None)
        old_levels = old_hier.levels if old_hier is not None else None
        self.host_levels = host
        self._to_device_levels(reuse_transfers=old_levels)
        self._setup_wall_s = time.perf_counter() - self._setup_t0

    def _to_device_levels(self, reuse_transfers=None):
        """``reuse_transfers``: the previous build's device levels during
        a numeric rebuild — the transfer operators (P/R device matrices,
        frozen under the rebuild contract) are carried over instead of
        re-packed, and level operators with a cached conversion structure
        refresh values only."""
        prm = self.prm
        host = self.host_levels
        dtype = prm.dtype
        dev_levels = []
        prefix = getattr(self, "_dev_prefix", [])
        prof = getattr(self, "setup_profile", None)
        # ONE dense-window HBM budget for the whole hierarchy: every
        # to_device('auto') below draws from it, so the storage-hungry
        # format cannot stack its per-matrix allowance level after level
        # (the round-5 ADVICE finding). rebuild() re-enters here with a
        # fresh pool — the old hierarchy's buffers are dropped with it.
        from amgcl_tpu.telemetry.ledger import dense_window_budget
        self._dwin_budget = dense_window_budget()
        # format-decision ledger (telemetry/structure.py): one record
        # per level operator, collected off the converted matrices so
        # the hierarchy carries its own decision history; a numeric
        # rebuild's value-refreshed levels (no fresh conversion) keep
        # the previous build's records — the structure is identical
        prev_dec = getattr(self, "_format_decisions", None)
        decisions = []

        def _note_decision(i, M):
            dec = getattr(M, "_format_decision", None)
            if dec is None and prev_dec is not None \
                    and i < len(prev_dec):
                dec = prev_dec[i]
            decisions.append(dec)

        for i, (Ai, P, R) in enumerate(host[:-1]):
            if i < len(prefix):
                # device-built level (ops/stencil_device.py) — already
                # device-resident, host row is bookkeeping metadata only
                dev_levels.append(prefix[i])
                decisions.append(None)
                continue
            if self._device_filter is not None and not self._device_filter(
                    i, Ai.nrows * Ai.block_size[0], False):
                dev_levels.append(Level(None, None, None, None))
                decisions.append(None)
                continue
            lvl = "level%d" % i
            spec = getattr(P, "_implicit_spec", None)
            old = reuse_transfers[i] if reuse_transfers is not None \
                and i < len(reuse_transfers) else None
            with setup_scope(prof, lvl + "/transfer"):
                if old is not None and old.A is not None:
                    # numeric rebuild: transfers are frozen — reuse the
                    # device matrices; the level operator refreshes
                    # values into the old structure where the format
                    # supports it (full reconvert otherwise)
                    P_dev, R_dev = old.P, old.R
                    A_dev = dev.refresh_values(old.A, Ai, dtype)
                    if A_dev is None:
                        A_dev = dev.to_device(Ai, prm.matrix_format,
                                              dtype,
                                              budget=self._dwin_budget)
                elif spec is not None:
                    # matrix-free smoothed transfers: no gather-heavy
                    # device P/R
                    from amgcl_tpu.ops.structured import \
                        build_implicit_transfers
                    P_dev, R_dev = build_implicit_transfers(
                        spec, dtype, prm.matrix_format)
                else:
                    # auto: banded transfers (RCM-ordered fine rows
                    # against contiguously-numbered aggregates) take
                    # windowed ELL / DIA and ride the same Pallas SpMV as
                    # the level operators; irregular ones fall back to
                    # take-ELL
                    P_dev = dev.to_device(P, "auto", dtype,
                                          budget=self._dwin_budget)
                    R_dev = dev.to_device(R, "auto", dtype,
                                          budget=self._dwin_budget)
                if old is None or old.A is None:
                    A_dev = dev.to_device(Ai, prm.matrix_format, dtype,
                                          budget=self._dwin_budget)
            from amgcl_tpu.ops.pallas_vcycle import (build_fused_down,
                                                     build_fused_up)
            with setup_scope(prof, lvl + "/relax_setup"):
                relax_state = prm.relax.build(Ai, dtype)
            with setup_scope(prof, lvl + "/fused_kernels"):
                fd = build_fused_down(A_dev, R_dev, relax_state)
                fu = build_fused_up(A_dev, P_dev, relax_state)
            _note_decision(i, A_dev)
            dev_levels.append(Level(A_dev, relax_state, P_dev, R_dev,
                                    fd, fu))
        Alast = host[-1][0]
        n_last = Alast.nrows * Alast.block_size[0]
        if prm.direct_coarse and n_last > max(4 * prm.coarse_enough, 20000):
            # coarsening stalled far above the direct-solve regime: refusing
            # to densify an enormous matrix beats an OOM (the reference hits
            # error::empty_level in the analogous situation, amg.hpp:375-380)
            raise RuntimeError(
                "coarsening stalled at %d unknowns (> coarse_enough=%d); "
                "cannot build a dense coarse solver this large — adjust "
                "coarsening parameters or set direct_coarse=False"
                % (n_last, prm.coarse_enough))
        old_last = reuse_transfers[len(host) - 1] \
            if reuse_transfers is not None \
            and len(reuse_transfers) == len(host) else None
        with setup_scope(prof, "coarse_solver"):
            A_last_dev = None
            if old_last is not None and old_last.A is not None:
                A_last_dev = dev.refresh_values(old_last.A, Alast, dtype)
            if A_last_dev is None:
                A_last_dev = dev.to_device(Alast, prm.matrix_format,
                                           dtype,
                                           budget=self._dwin_budget)
            if prm.direct_coarse:
                coarse = DenseDirectSolver.build(Alast, dtype)
                last = Level(A_last_dev, None)
            else:
                coarse = None
                last = Level(A_last_dev, prm.relax.build(Alast, dtype))
        _note_decision(len(host) - 1, A_last_dev)
        dev_levels.append(last)
        self._format_decisions = decisions
        self.hierarchy = Hierarchy(
            dev_levels, coarse, prm.npre, prm.npost, prm.ncycle,
            prm.pre_cycles)
        self._memwatch_built()

    def _memwatch_built(self):
        # measured-memory attribution (telemetry/memwatch.py): own this
        # hierarchy's live device buffers in the weakref registry and
        # drop a setup-phase point on the memory timeline; no-op when
        # the observatory is off, never fails the build
        try:
            from amgcl_tpu.telemetry import memwatch as _mw
            if _mw.enabled():
                _mw.register_owner("hierarchy", self)
                _mw.snapshot("amg.setup",
                             levels=len(self.hierarchy.levels))
        except Exception:
            pass

    @property
    def dtype(self):
        return self.prm.dtype

    # -- eviction / readmission (serve/farm.py HBM admission) ---------------

    def release_device(self):
        """Eviction hook: drop every device-resident buffer — the
        hierarchy pytree (level operators, transfers, smoother states,
        fused kernel handles, coarse factor) and the derived caches —
        while KEEPING the host CSR levels and the Galerkin/transfer
        plans cached on them. Readmission is therefore ``rebuild(...)``
        — the numeric segment passes plus fresh device conversion, no
        strength graphs, no aggregation, no symbolic SpGEMM — never a
        fresh setup. ``bytes()`` reports 0 while released."""
        self.hierarchy = None
        if getattr(self, "_dev_prefix", []):
            # a HYBRID build (device prefix + classic continuation) must
            # keep routing rebuild through _build after release — its
            # host_levels start with meta rows (P=None) the numeric
            # rebuild loop cannot process. Remember the prefix existed
            # before dropping its device buffers.
            self._prefix_released = True
        self._dev_prefix = []
        self._dwin_budget = None
        self._ledger_cache = None
        self._probe_cache = None
        self._roofline_cache = None
        self._structure_cache = None
        try:
            from amgcl_tpu.telemetry import memwatch as _mw
            _mw.snapshot("amg.release")
        except Exception:
            pass

    @property
    def device_resident(self) -> bool:
        return getattr(self, "hierarchy", None) is not None

    def readmit(self):
        """Re-materialize the device hierarchy after
        :meth:`release_device` — the same-values numeric rebuild path
        (no-op when already resident)."""
        if not self.device_resident:
            A0 = self.host_levels[0][0]
            if getattr(self, "_device_built", False) \
                    or getattr(self, "_reorder", None) is not None:
                # reorder-active: A0 is the PERMUTED operator — hand the
                # CSR back (identity-pattern pass-through) so rebuild's
                # original-order value mapping never double-permutes
                self.rebuild(A0)
            else:
                self.rebuild(A0.val)   # values-only: skip the pattern
                #                        comparison against itself
            try:
                from amgcl_tpu.telemetry import memwatch as _mw
                _mw.snapshot("amg.readmit")
            except Exception:
                pass

    # -- observability (reference: amgcl/amg.hpp:560-598) -------------------

    def resource_ledger(self):
        """Full resource ledger (telemetry/ledger.py): per-level device
        bytes by format, analytic FLOP/byte per cycle stage, dense-window
        budget use, and the setup-phase profile. Cached per build —
        rebuild() invalidates."""
        cached = getattr(self, "_ledger_cache", None)
        if cached is None:
            from amgcl_tpu.telemetry.ledger import hierarchy_ledger
            cached = hierarchy_ledger(
                self.hierarchy, self.host_levels,
                budget=getattr(self, "_dwin_budget", None),
                setup_profile=getattr(self, "setup_profile", None))
            self._ledger_cache = cached
        return cached

    def memory_report(self):
        """Measured-vs-model memory join (telemetry/memwatch.py §DESIGN
        20): live device bytes per level and slot — what the runtime
        actually holds — joined against the analytic resource ledger,
        with a ``provenance: model|measured`` tag and the headline
        ``drift_ratio``. Works evicted (all zeros); feed the result to
        ``telemetry.diagnose(memory=...)`` for drift findings."""
        from amgcl_tpu.telemetry import memwatch
        return memwatch.hierarchy_report(self)

    def setup_report(self):
        """Stage-by-stage attribution of the last build/rebuild
        (telemetry/ledger.setup_attribution): measured per-stage seconds
        joined to the setup traffic model, plus the named-stage coverage
        fraction — the setup-phase counterpart of ``roofline()``."""
        from amgcl_tpu.telemetry.ledger import setup_attribution
        return setup_attribution(getattr(self, "setup_profile", None),
                                 self.host_levels,
                                 total_s=getattr(self, "_setup_wall_s",
                                                 None))

    def roofline(self, reps: Optional[int] = None,
                 peaks: Optional[dict] = None):
        """Measured roofline attribution (telemetry/roofline.py): drive
        every V-cycle stage standalone under a device-synced profiler
        (``AMGCL_TPU_ROOFLINE_REPS`` repetitions each), join the
        per-stage times to the ledger's FLOP/byte model, and return
        achieved GB/s / GFLOP/s per stage vs the device peaks
        (auto-detected; ``AMGCL_TPU_PEAK_{GBPS,FLOPS}`` override) with
        compute-/memory-bound classification and ranked bottlenecks.
        Cached per build (the measurement jit-compiles one small program
        per stage); ``rebuild()`` invalidates. The measurement profiler
        rides along under ``"_prof"`` (stripped from JSONL exports) so
        ``cli.py --trace`` can render the stage timeline with the
        achieved-GB/s counter track. Passing explicit ``reps``/``peaks``
        re-measures instead of returning the cached default run."""
        cached = getattr(self, "_roofline_cache", None)
        if cached is None or reps is not None or peaks is not None:
            from amgcl_tpu.telemetry import roofline as _roofline
            prof = _roofline.measure_stages(self.hierarchy, reps=reps)
            cached = _roofline.roofline(self.hierarchy, prof=prof,
                                        peaks=peaks)
            cached["_prof"] = prof
            self._roofline_cache = cached
        return cached

    def probe_convergence(self, n_iters: int = 12, seed: int = 1234,
                          with_smoother: bool = True):
        """Measured per-level convergence diagnostics (telemetry/
        health.py): for each level, the error-reduction factor of the
        multigrid cycle rooted there (test-vector cycling on a zero rhs,
        normalized each step — the asymptotic AMG convergence factor)
        and the smoother's spectral-radius estimate by power iteration.
        A level whose factor approaches 1 is where the coarsening fails
        — identifiable before the first solve. Cached per build (the
        probe jit-compiles one small program per level);
        ``hierarchy_stats()`` folds the cached rows into its per-level
        report and ``cli.py --doctor`` prints them."""
        cached = getattr(self, "_probe_cache", None)
        if cached is None:
            from amgcl_tpu.telemetry.health import probe_hierarchy
            cached = probe_hierarchy(self.hierarchy, n_iters=n_iters,
                                     seed=seed,
                                     with_smoother=with_smoother)
            self._probe_cache = cached
        return cached

    def structure_report(self, advise=None, variants=None):
        """The operator X-ray (telemetry/structure.py): per-level
        structural analytics (bandwidth/envelope, diagonal occupancy,
        ELL padding waste, dense-window density curve, structure
        fingerprint), the format-decision ledger ``to_device('auto')``
        recorded during this build (candidate table + winner + margin
        + reason), and the reorder-gain advisor's predicted
        densification per level. Host-side analytics only — nothing is
        built or compiled (``STRUCTURE_CONTRACTS`` asserts a
        compile-watch delta of zero). Cached per build; ``rebuild()``
        invalidates (the values changed, the structure report did not
        — but a rebuild may reconvert a level). ``advise``: True /
        False / "auto" (default: "auto" — advisor on levels up to the
        ``AMGCL_TPU_XRAY_MAX_ADVISE_NNZ`` ceiling); passing explicit
        ``advise``/``variants`` re-runs instead of returning the
        cached default."""
        cached = getattr(self, "_structure_cache", None)
        if cached is not None and advise is None and variants is None:
            return cached
        import jax
        from amgcl_tpu.telemetry import structure as _structure
        try:
            itemsize = int(jnp.dtype(self.prm.dtype).itemsize)
        except TypeError:
            itemsize = 4
        xray = _structure.hierarchy_xray(
            self.host_levels,
            decisions=getattr(self, "_format_decisions", None),
            advise_mode="auto" if advise is None else advise,
            variants=variants, itemsize=itemsize,
            on_tpu=jax.default_backend() == "tpu")
        if advise is None and variants is None:
            self._structure_cache = xray
        return xray

    def hierarchy_stats(self):
        """Structured hierarchy report: per-level rows/nnz/dtype/device
        format plus grid and operator complexity — the machine-readable
        source both ``__repr__`` and the JSONL telemetry path render from
        (reference prints this as text only, amg.hpp:560-598). Each level
        additionally carries its device-byte breakdown and analytic SpMV
        cost from the resource ledger — and, once ``probe_convergence()``
        has run, the measured convergence factor + smoother spectral
        radius — and the top level the whole-cycle FLOP/byte totals."""
        host = self.host_levels
        nnz0 = host[0][0].nnz
        rows0 = host[0][0].nrows
        dev_levels = self.hierarchy.levels
        led = self.resource_ledger()
        levels = []
        for i, (Ai, _, _) in enumerate(host):
            lv = dev_levels[i] if i < len(dev_levels) else None
            A_dev = getattr(lv, "A", None)
            row = {
                "level": i,
                "rows": int(Ai.nrows),
                # device-built meta rows carry nrows/nnz but no block info
                "unknowns": int(Ai.nrows
                                * getattr(Ai, "block_size", (1, 1))[0]),
                "nnz": int(Ai.nnz),
                "format": type(A_dev).__name__ if A_dev is not None
                else None,
                "fused": ("d" if getattr(lv, "down", None) is not None
                          else "")
                + ("u" if getattr(lv, "up", None) is not None else ""),
            }
            if i < len(led["levels"]):
                row["bytes"] = led["levels"][i]["bytes"]
                row["spmv"] = led["levels"][i]["spmv"]
            probe = getattr(self, "_probe_cache", None)
            if probe is not None and i < len(probe):
                row["conv_factor"] = probe[i].get("conv_factor")
                if probe[i].get("smoother_rho") is not None:
                    row["smoother_rho"] = probe[i]["smoother_rho"]
            # operator X-ray fold (same pattern as the probe rows):
            # once structure_report() has run, each level carries its
            # compact structural metrics + the recorded format decision
            xray = getattr(self, "_structure_cache", None)
            if xray is not None and i < len(xray["levels"]):
                xrow = xray["levels"][i]
                met = xrow.get("metrics")
                if met is not None:
                    srow = {
                        "bandwidth_max": met["bandwidth"]["max"],
                        "ndiags": met["diagonals"]["ndiags"],
                        "dia_fill": met["diagonals"]["fill"],
                        "ell_pad_frac": met["ell"]["lane_pad_frac"],
                        "window_fill": met["window"]["fill"],
                    }
                    dec = xrow.get("decision")
                    if dec is not None:
                        srow["decision"] = {
                            "fmt": dec.get("fmt"),
                            "reason": dec.get("reason"),
                            "margin": dec.get("margin")}
                    best = (xrow.get("advisor") or {}).get("best")
                    if best and best.get("gain") is not None:
                        srow["predicted_reorder_gain"] = best["gain"]
                    row["structure"] = srow
            levels.append(row)
        out = {
            "n_levels": len(host),
            "operator_complexity":
                sum(l[0].nnz for l in host) / max(nnz0, 1),
            "grid_complexity":
                sum(l[0].nrows for l in host) / max(rows0, 1),
            "dtype": str(jnp.dtype(self.prm.dtype)),
            "bytes": int(self.bytes()),
            "levels": levels,
            "cycle": dict(led["cycle"]["total"]),
        }
        if led.get("dense_window") is not None:
            out["dense_window"] = led["dense_window"]
        xray = getattr(self, "_structure_cache", None)
        if xray is not None and xray.get("summary"):
            out["structure"] = xray["summary"]
        return out

    def __repr__(self):
        st = self.hierarchy_stats()
        lines = [
            "Number of levels:    %d" % st["n_levels"],
            "Operator complexity: %.2f" % st["operator_complexity"],
            "Grid complexity:     %.2f" % st["grid_complexity"],
            "Memory footprint:    %s" % _human_bytes(st["bytes"]),
            "",
            "level     unknowns       nonzeros",
            "---------------------------------",
        ]
        for lv in st["levels"]:
            lines.append("%5d %12d %14d"
                         % (lv["level"], lv["rows"], lv["nnz"]))
        fused = ["%d%s" % (lv["level"], lv["fused"])
                 for lv in st["levels"] if lv["fused"]]
        if fused:
            lines.append("fused V-cycle kernels (level+direction): "
                         + " ".join(fused))
        return "\n".join(lines)

    def bytes(self):
        """Device bytes of the whole hierarchy pytree — operators,
        transfers, smoother states, coarse factor (the reference's bytes()
        additionally counts its preallocated f/u/t work vectors,
        amg.hpp:332-343; here those are XLA-managed temporaries).
        0 while evicted (``release_device``) — the number the farm pool
        charges and the eviction tests assert drops."""
        if getattr(self, "hierarchy", None) is None:
            return 0
        import jax
        total = 0
        for leaf in jax.tree.leaves(self.hierarchy):
            if hasattr(leaf, "size") and hasattr(leaf, "dtype"):
                total += leaf.size * leaf.dtype.itemsize
        return total
