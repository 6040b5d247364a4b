"""Resource ledger — where the hierarchy's bytes, FLOPs and messages go.

The reference reports *time* (profiler.hpp) and *structure* (the level
table of amg.hpp:560-598); what it never accounts is the resource side
that actually limits a sparse solver on an accelerator: device memory by
storage format, HBM traffic per cycle stage, and (distributed) halo
bytes on the wire. This module is the single place those models live:

* :class:`DeviceMemoryBudget` — a shared byte budget one hierarchy build
  threads through every ``to_device('auto')`` call, so storage-hungry
  formats (the dense-window blocks, ops/densewin.py) decrement ONE
  hierarchy-wide pool instead of each matrix consulting the per-matrix
  ``AMGCL_TPU_DWIN_MAX_BYTES`` cap independently.
* :func:`mv_cost` — analytic (flops, HBM bytes) of one SpMV per device
  format; :func:`cycle_cost_model` composes them into the per-stage
  FLOP/byte map of one multigrid cycle, :func:`krylov_iteration_model`
  into the per-iteration cost of the outer Krylov loop. Divide the two
  numbers and you have the roofline x-coordinate of each stage.
* :func:`hierarchy_ledger` — the per-level device-memory map (operator /
  transfer / smoother / fused-kernel bytes, by format) whose totals are
  DEFINED as the leaf-byte sum of the hierarchy pytree, so they can never
  drift from the live buffers (tests assert ledger total == AMG.bytes()).
* :func:`comm_model` / :func:`allreduce_model` — halo-exchange message
  counts and wire bytes per SpMV for the distributed matrix types, and
  the ring-allreduce model for psum'd dots.
* :func:`xla_cost_analysis` — optional cross-check of the analytic
  numbers against XLA's own compiled cost analysis, where the backend
  exposes one.

Everything returned is plain JSON-clean data (ints/floats/strings) so it
rides the telemetry sink unmodified.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

import numpy as np


# ---------------------------------------------------------------------------
# shared device-memory budget
# ---------------------------------------------------------------------------

def _charge_fault(budget_name: str) -> bool:
    """Allocation fault seam (faults/inject.py): an armed ``alloc.*``
    rule in ``AMGCL_TPU_FAULT_PLAN`` forces the next charge(s) to be
    refused — simulated HBM OOM at farm admission (``alloc.farm`` on
    the ``farm_hbm`` pool) or dense-window conversion (``alloc.dwin``
    on every other budget). One env read when no plan is set."""
    if not os.environ.get("AMGCL_TPU_FAULT_PLAN"):
        return False
    try:
        from amgcl_tpu.faults import inject as _inject
        site = "alloc.farm" if budget_name == "farm_hbm" \
            else "alloc.dwin"
        return _inject.should_fire(site, target=budget_name) is not None
    except Exception:
        return False


class DeviceMemoryBudget:
    """Byte budget shared across one hierarchy build.

    Consumers ask ``remaining()`` before materializing a storage-hungry
    buffer and ``try_charge(nbytes, tag)`` when they commit one; the
    charge log keeps per-matrix attribution for the ledger. Exceeding the
    budget is impossible by construction — ``try_charge`` refuses instead
    of overdrawing."""

    def __init__(self, total_bytes: int, name: str = "dense_window"):
        self.total = int(total_bytes)
        self.name = name
        self.used = 0
        self.charges = []           # [(tag, bytes), ...]

    def remaining(self) -> int:
        return self.total - self.used

    def try_charge(self, nbytes: int, tag: str = "") -> bool:
        nbytes = int(nbytes)
        if _charge_fault(self.name):
            return False
        if nbytes < 0 or self.used + nbytes > self.total:
            return False
        self.used += nbytes
        self.charges.append((tag, nbytes))
        return True

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "total_bytes": self.total,
                "used_bytes": self.used,
                "remaining_bytes": self.remaining(),
                "charges": [{"tag": t, "bytes": b}
                            for t, b in self.charges]}

    def __repr__(self):
        return "DeviceMemoryBudget(%s: %d/%d bytes)" % (
            self.name, self.used, self.total)


def dense_window_budget() -> DeviceMemoryBudget:
    """Fresh hierarchy-wide dense-window budget from
    ``AMGCL_TPU_DWIN_MAX_BYTES`` (same knob as before, new semantics: the
    cap now bounds the SUM over every dense-window conversion that
    shares the budget, not each matrix separately)."""
    from amgcl_tpu.ops.densewin import max_total_bytes
    return DeviceMemoryBudget(max_total_bytes(), name="dense_window")


class LruMemoryPool(DeviceMemoryBudget):
    """:class:`DeviceMemoryBudget` generalized to a farm-wide RESIDENT
    SET: named charges that can be released again (eviction returns the
    bytes) and re-charged (readmission), with least-recently-used
    ordering maintained by :meth:`touch` so the farm's admission loop
    can always name the coldest resident hierarchy to evict
    (serve/farm.py; ``AMG.bytes()`` is the accounting unit per charge).

    ``total_bytes <= 0`` means unlimited — the pool still tracks
    residency and LRU order, it just never refuses a charge. The charge
    log inherited from the base class stays append-only: a release
    appends a negative-byte row rather than rewriting history, so the
    ledger remains an audit trail."""

    def __init__(self, total_bytes: int = 0, name: str = "farm_hbm"):
        total = int(total_bytes or 0)
        self.unlimited = total <= 0
        super().__init__(total if total > 0 else (1 << 62), name)
        # the base class's append-only charge log was sized for ONE
        # hierarchy build; a farm pool lives for the process and under
        # eviction pressure appends ~2 rows per batch — bound it (the
        # recent tail is still an audit trail, the totals are exact)
        from collections import deque
        self.charges = deque(self.charges, maxlen=256)
        #: key -> bytes; insertion order IS the LRU order (coldest first)
        self._resident: Dict[str, int] = {}

    def charge(self, key: str, nbytes: int) -> bool:
        """Admit ``key`` at ``nbytes``. Re-charging a resident key
        swaps its charge ATOMICALLY — on failure the old charge is
        restored, never dropped: the key's buffers are still live, and
        a window where a resident operator looks evicted would let the
        farm's dispatch run a redundant readmission (and understate
        ``used``) while the caller waits to retry. False when it does
        not fit; the caller evicts ``coldest()`` and retries. A failed
        or successful re-charge both move the key to the warm end of
        the LRU order (it was just touched)."""
        nbytes = int(nbytes)
        old = self._resident.pop(key, None)
        if old is not None:
            self.used -= old
        if not self.try_charge(nbytes, tag=key):
            if old is not None:
                self.used += old
                self._resident[key] = old
            return False
        if old is not None:
            self.charges.append((key + ":released", -old))
        self._resident[key] = nbytes
        return True

    def release(self, key: str) -> int:
        """Evict ``key``: return its bytes to the pool (0 when it was
        not resident)."""
        nbytes = self._resident.pop(key, 0)
        if nbytes:
            self.used -= nbytes
            self.charges.append((key + ":released", -nbytes))
        return nbytes

    def touch(self, key: str) -> None:
        """Mark ``key`` most-recently-used (dict re-insertion moves it
        to the warm end of the LRU order)."""
        if key in self._resident:
            self._resident[key] = self._resident.pop(key)

    def coldest(self, exclude=()) -> Optional[str]:
        """The least-recently-used resident key outside ``exclude`` —
        the eviction victim; None when nothing is evictable."""
        for key in self._resident:
            if key not in exclude:
                return key
        return None

    def resident(self) -> Dict[str, int]:
        """Copy of the resident map in LRU order (coldest first)."""
        return dict(self._resident)

    def resize(self, total_bytes: int) -> None:
        """Change the budget in place (the CLI/bench demos size the cap
        from the tenants actually built). The caller evicts down to the
        new cap; the pool only re-arms the refusal threshold."""
        total = int(total_bytes or 0)
        self.unlimited = total <= 0
        self.total = total if total > 0 else (1 << 62)

    def to_dict(self) -> Dict[str, Any]:
        out = super().to_dict()
        if self.unlimited:
            out["total_bytes"] = 0
            out["remaining_bytes"] = None
        out["resident"] = dict(self._resident)
        return out


# ---------------------------------------------------------------------------
# per-format analytic SpMV cost
# ---------------------------------------------------------------------------

def _leaf_bytes(tree) -> int:
    """Device bytes of every array leaf in a pytree (0 for None)."""
    if tree is None:
        return 0
    import jax
    total = 0
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += leaf.size * leaf.dtype.itemsize
    return total


def _vec_dims(M):
    """Scalar-expanded (rows, cols) of an operator (block-aware; a
    GridTentative's 3-D ``block`` names grid coarsening factors, not a
    value block — only 2-tuples scale the vector dims)."""
    blk = getattr(M, "block", None)
    br, bc = blk if isinstance(blk, tuple) and len(blk) == 2 else (1, 1)
    return M.shape[0] * br, M.shape[1] * bc


def _itemsize(M) -> int:
    try:
        return int(np.dtype(M.dtype).itemsize)
    except Exception:
        return 4


def mv_cost(M) -> Dict[str, int]:
    """Analytic cost of one ``y = M x``: ``{"flops", "bytes"}``.

    The byte count is the HBM-traffic model (stored operator streamed
    once + x read + y written), which is what bounds these kernels on
    TPU; gather-paying formats move more in practice — this is the
    roofline floor, not a measurement."""
    if M is None:
        return {"flops": 0, "bytes": 0}
    name = type(M).__name__
    rows, cols = _vec_dims(M)
    itemsize = _itemsize(M)
    stored = _leaf_bytes(M)
    vec = (rows + cols) * itemsize
    flops = None
    if name in ("DiaMatrix", "DistDiaMatrix"):
        flops = 2 * len(M.offsets) * rows
    elif name == "EllMatrix":
        flops = 2 * int(M.vals.size)
    elif name == "DenseMatrix":
        flops = 2 * rows * cols
    elif name == "DenseWindowMatrix":
        flops = 2 * int(M.blocks.size)
    elif name == "WindowedEllMatrix":
        flops = 2 * int(M.vals.size)
    elif name in ("GridTentative", "AggTentative"):
        # piecewise-constant transfer: one add per fine point
        flops = rows
    elif name in ("TentativeP", "TentativeR"):
        inner = mv_cost(M.T)
        return {"flops": inner["flops"], "bytes": inner["bytes"]}
    elif name == "ImplicitSmoothedP":
        inner = mv_cost(M.M)
        return {"flops": mv_cost(M.T)["flops"] + inner["flops"] + rows,
                "bytes": stored + vec}
    elif name == "ImplicitSmoothedR":
        inner = mv_cost(M.Mt)
        return {"flops": mv_cost(M.T)["flops"] + inner["flops"] + rows,
                "bytes": stored + vec}
    if flops is None:
        # generic fallback: two flops per stored value
        flops = 2 * max(stored // max(itemsize, 1), 1)
    return {"flops": int(flops), "bytes": int(stored + vec)}


# ---------------------------------------------------------------------------
# cycle / iteration cost models
# ---------------------------------------------------------------------------

def _add(a, b):
    return {"flops": a["flops"] + b["flops"], "bytes": a["bytes"] + b["bytes"]}


def _scale(a, k):
    return {"flops": a["flops"] * k, "bytes": a["bytes"] * k}


def _zero_sweep_cost(relax, n: int, vec: int) -> Optional[Dict[str, int]]:
    """Cost of ONE smoother application from a ZERO initial guess, where
    the smoother family makes that cheap: the scaled-residual smoothers
    (Jacobi/SPAI-0, relaxation/base.py) reduce to ``u = scale ∘ f`` — no
    operator stream at all (the residual of a zero guess IS f). None for
    smoother families whose from-zero application still streams the
    operator (Chebyshev, ILU, GS) — callers fall back to the full-sweep
    model. Keeping this stage-accurate is what lets the roofline's
    per-stage model bytes agree with ``xla_cost_analysis`` instead of
    over-charging the first pre-sweep a full operator pass."""
    scale = getattr(relax, "scale", None)
    if scale is None:
        return None
    b = int(scale.shape[-1]) if getattr(scale, "ndim", 1) == 3 else 1
    flops = 2 * n * b if b > 1 else n
    return {"flops": int(flops), "bytes": _leaf_bytes(relax) + 2 * vec}


def cycle_cost_model(hier) -> Dict[str, Any]:
    """Per-stage FLOPs/HBM-bytes of ONE multigrid cycle of ``hier``
    (models/amg.Hierarchy or compatible). Stage model per level is the
    STREAMING FLOOR — what a perfect single-pass kernel moves, which is
    what the fused sweep/residual kernels run on TPU and what XLA's
    elementwise fusion approaches elsewhere: a smoother sweep streams
    the operator and its own state once plus {x in, f in, x out}
    (the Ax intermediate is never materialized) — except the FIRST
    pre-sweep, which runs from a zero guess and for the scaled-residual
    family is just ``scale ∘ f`` (see :func:`_zero_sweep_cost`); the
    residual the operator plus {x, f in, r out}; transfers stream
    themselves plus their vectors. W-cycles visit level i ``ncycle**i``
    times.

    Levels carrying the whole-leg fused kernels (ops/pallas_vcycle.py,
    ``lv.down``/``lv.up``) are priced as the SINGLE passes the cycle
    actually runs — no double counting of the intermediate vectors the
    composed stages would re-stream: a ``down_fused`` row replaces
    pre_smooth + restrict when the zero-guess leg engages (npre == 1,
    scalar scaled-residual smoother), the ``restrict`` row becomes the
    one-pass residual+restrict kernel whenever ``lv.down`` exists, and
    an ``up_fused`` row absorbs prolong + the first post-sweep (the
    ``post_smooth`` row keeps the full-npost model for the roofline
    join, which rescales it — the level total charges only the
    remaining npost−1 sweeps)."""
    levels = getattr(hier, "levels", [])
    npre = getattr(hier, "npre", 1)
    npost = getattr(hier, "npost", 1)
    ncycle = max(getattr(hier, "ncycle", 1), 1)
    coarse = getattr(hier, "coarse", None)
    stages = []
    total = {"flops": 0, "bytes": 0}
    for i, lv in enumerate(levels):
        A = getattr(lv, "A", None)
        visits = ncycle ** i
        if A is None:
            stages.append({"level": i, "visits": visits, "skipped": True})
            continue
        n, _ = _vec_dims(A)
        itemsize = _itemsize(A)
        vec = n * itemsize
        a_cost = mv_cost(A)
        row: Dict[str, Any] = {"level": i, "visits": visits}
        if i == len(levels) - 1:
            if coarse is not None:
                cb = _leaf_bytes(coarse)
                row["coarse_solve"] = {"flops": 2 * n * n,
                                       "bytes": cb + 2 * vec}
            else:
                # smoother-as-coarse-solve: one standalone application
                row["coarse_solve"] = _add(
                    {"flops": n, "bytes": 2 * vec},
                    {"flops": 0, "bytes": _leaf_bytes(lv.relax)})
            level_total = row["coarse_solve"]
        else:
            rx_b = _leaf_bytes(getattr(lv, "relax", None))
            # streaming floors (what a perfect single-pass kernel moves
            # — and what the fused dia/windowed-ELL sweep kernels and
            # XLA's elementwise fusion actually run): a sweep reads
            # {x, f, smoother state}, streams A and writes x' — the Ax
            # intermediate is never materialized, so it is not charged
            # (a_cost already carries the x read + one vector write);
            # same for the residual's r and the prolong's correction add
            sweep = _add(a_cost, {"flops": 3 * n, "bytes": vec + rx_b})
            resid = _add(a_cost, {"flops": n, "bytes": vec})
            zero = _zero_sweep_cost(getattr(lv, "relax", None), n, vec)
            if npre > 0 and zero is not None:
                row["pre_smooth"] = _add(zero, _scale(sweep, npre - 1))
            else:
                row["pre_smooth"] = _scale(sweep, npre)
            row["restrict"] = _add(resid, mv_cost(lv.R))
            row["prolong"] = _add(mv_cost(lv.P),
                                  {"flops": n, "bytes": vec})
            row["post_smooth"] = _scale(sweep, npost)
            down = getattr(lv, "down", None)
            up = getattr(lv, "up", None)
            vec_c = _vec_dims(lv.R)[0] * itemsize   # coarse-vector bytes
            fused_zero = npre == 1 and down is not None \
                and getattr(down, "w", None) is not None
            if down is not None:
                # the one-pass kernel streams ITS operand copy once plus
                # {f, u} in and fc out — this is what the cycle runs for
                # its residual+restrict whenever the leg exists
                down_pass = {"flops": row["restrict"]["flops"],
                             "bytes": _leaf_bytes(down) + 2 * vec + vec_c}
                row["restrict"] = down_pass
                if fused_zero:
                    # zero-guess whole leg: same pass also emits the
                    # pre-smoothed iterate (writes u instead of reading
                    # it) — byte count identical, flops add the sweep's
                    row["down_fused"] = {
                        "flops": row["pre_smooth"]["flops"]
                        + down_pass["flops"],
                        "bytes": down_pass["bytes"]}
            fused_up = up is not None and npost >= 1
            if fused_up:
                row["up_fused"] = {
                    "flops": row["prolong"]["flops"]
                    + (row["post_smooth"]["flops"] / npost
                       if npost else 0),
                    "bytes": _leaf_bytes(up) + 3 * vec + vec_c}
            level_total = {"flops": 0, "bytes": 0}
            if fused_zero:
                level_total = _add(level_total, row["down_fused"])
            else:
                level_total = _add(level_total, row["pre_smooth"])
                level_total = _add(level_total, row["restrict"])
            if fused_up:
                level_total = _add(level_total, row["up_fused"])
                if npost > 1:
                    level_total = _add(level_total, _scale(
                        row["post_smooth"], (npost - 1) / npost))
            else:
                level_total = _add(level_total, row["prolong"])
                level_total = _add(level_total, row["post_smooth"])
        total = _add(total, _scale(level_total, visits))
        stages.append(row)
    out = {"stages": stages, "total": dict(total)}
    if total["bytes"]:
        out["total"]["flop_per_byte"] = round(
            total["flops"] / total["bytes"], 4)
    return out


#: per-iteration operation counts (spmv, precond applies, dots, axpys) —
#: the documented model behind krylov_iteration_model; approximate for the
#: restarted methods (counts are per inner step).
KRYLOV_OPS = {
    "CG":         (1, 1, 3, 3),
    "BiCGStab":   (2, 2, 7, 6),
    "BiCGStabL":  (2, 2, 8, 8),
    "GMRES":      (1, 1, 4, 4),
    "FGMRES":     (1, 1, 4, 4),
    "LGMRES":     (1, 1, 6, 6),
    "IDRs":       (2, 2, 8, 8),
    "Richardson": (1, 1, 1, 2),
    "PreOnly":    (0, 1, 0, 0),
}

#: n-vector HBM streams per iteration (reads + writes at working dtype)
#: of the FUSED iteration bodies (ops/fused_vec.py): every dot that
#: rides an update or an spmv pass costs zero extra streams, so the
#: vector traffic is just the distinct operand reads + result writes.
#: The unfused composition pays 2·dots + 3·axpys streams instead (each
#: dot re-reads its two operands, each axpby reads two and writes one).
#: CG: rho(2: r,s) + p-update(3) + fused xr tail(4r+2w) = 11.
#: BiCGStab: p-update(4) + s-update(3) + fused tail(6r+2w) = 15 (rho,
#: <rhat,v>, <t,t>, <t,s>, ‖r‖² all ride spmv/update passes).
#: Others estimated the same way from their rewritten bodies.
KRYLOV_VEC_STREAMS_FUSED = {
    "CG":         11,
    "BiCGStab":   15,
    "BiCGStabL":  24,
    "GMRES":      16,
    "FGMRES":     16,
    "LGMRES":     20,
    "IDRs":       30,
    "Richardson": 4,
    "PreOnly":    0,
}


#: fused-engagement CONTRACT per solver (audited statically by
#: analysis/jaxpr_audit.py): (fused `_fused_pass` call sites per
#: iteration body with the tier on, whether the per-iteration
#: vector-stream recount from the jaxpr must EXACTLY equal
#: KRYLOV_VEC_STREAMS_FUSED). Declared next to the byte model it
#: protects: if an iteration body loses its fused kernels (a silently
#: dead Pallas path, an accidental decomposition), the audit fails
#: before any benchmark runs. Solvers whose stream-table entry is per
#: INNER step or an estimate (the restarted/recycling methods carry
#: whole basis matrices through the outer body, which the audit weighs
#: as k streams each) pin only the fused-pass count; the GMRES family's
#: merged reductions are matvec ``stack_dots``, not ``_fused_pass``
#: kernels, hence 0 there.
KRYLOV_FUSED_PASSES = {
    "CG":         (1, True),
    "BiCGStab":   (1, True),
    "BiCGStabL":  (2, False),
    "GMRES":      (0, False),
    "FGMRES":     (0, False),
    "LGMRES":     (0, False),
    "IDRs":       (5, False),
    "Richardson": (0, False),
    "PreOnly":    (0, False),
}


#: collective CONTRACT of the distributed Krylov bodies (audited
#: statically): psums per iteration, elements the stacked psum carries,
#: halo SpMVs per iteration. parallel/dist_solver.py prices its
#: SolveReport comm model FROM this table (dots=psums,
#: elems_per_dot=elems_per_psum), so the model and the traced program
#: are checked against one declaration — a third psum sneaking back
#: into dist_cg_pipelined fails the audit, not a chip session.
DIST_CG_COLLECTIVES = {
    "dist_cg":           {"psums": 3, "elems_per_psum": 1, "spmvs": 1},
    "dist_cg_pipelined": {"psums": 1, "elems_per_psum": 3, "spmvs": 1},
}


#: collective CONTRACT of the comm-measurement stage pairs
#: (telemetry/comm.py, audited statically by
#: analysis/jaxpr_audit.audit_comm_stages): each measured stage must
#: contain EXACTLY the listed collectives (and zero of every other
#: kind), and every ``*_ablated`` stand-in must have a collective
#: census of EXACTLY 0 — the ablation subtraction
#: ``comm_s = t(measured) − t(ablated)`` is only an attribution of
#: collective wall time if the ablated program really dropped the
#: collectives and nothing else. A psum sneaking into a stand-in (or a
#: halo exchange falling out of a measured stage) fails the analysis
#: gate, not a measurement session.
COMM_STAGE_CONTRACTS = {
    "halo_dia":           {"ppermute": 2},
    "halo_ell":           {"all_to_all": 1},
    "psum":               {"psum": 1},
    "iter_classical_dia": {"psum": 3, "ppermute": 2},
    "iter_pipelined_dia": {"psum": 1, "ppermute": 2},
    "iter_classical_ell": {"psum": 3, "all_to_all": 1},
    "iter_pipelined_ell": {"psum": 1, "all_to_all": 1},
}


#: donation CONTRACT per jitted entry point: how many argument buffers
#: the lowered program is expected to alias into outputs. All zero
#: today — the audit's informational finding is the standing reminder
#: that ROADMAP item 1's resident solve loop wants donated x/r buffers;
#: when that lands, this table changes in the same commit (or the audit
#: fails CI).
DONATION_CONTRACTS = {
    "make_solver._solve_fn": 0,
    # the resident serve loop (serve/service.py) donates the iterate
    # buffer x0 into the solution output — exactly ONE aliased argument
    # buffer in the lowered program. The auditor (jaxpr_audit.
    # audit_serve) lowers the service's actual jit wrap and fails the
    # analysis gate if the aliasing is lost.
    "serve.solve_step": 1,
}


#: host-purity CONTRACT of the operator X-ray (telemetry/structure.py,
#: audited by analysis/jaxpr_audit.audit_structure): the X-ray path —
#: structure metrics, the format-decision candidate table, the
#: reorder-gain advisor — is host-side analytics ONLY. Statically, the
#: module may import neither jax nor any jax-importing ops module
#: (``jax_imports`` counts violations found by AST scan; ops.csr is
#: numpy-only and allowed). Dynamically, a full ``structure_report``
#: (+ advisor) over a built hierarchy must leave the process
#: compile/trace counters untouched — no new traces, no new backend
#: compiles beyond the spmv/solve entry points that already exist
#: (compile_watch delta 0). A violation is an error finding in the
#: analysis gate, not a slow chip-session surprise.
STRUCTURE_CONTRACTS = {
    "telemetry.structure": {"jax_imports": 0, "new_traces": 0,
                            "new_backend_compiles": 0},
}


#: setup CONTRACT of the traced device-setup entry points (audited
#: statically by analysis/jaxpr_audit.audit_setup): the per-level build
#: programs — MIS rounds, segment-Galerkin, smoothing SpGEMM, stencil
#: pair-Galerkin — must contain NO host callbacks (a host round trip per
#: level serializes the setup exactly like the VERDICT-r5 dispatch
#: overhead serialized the solve), no collectives (serial setup; the
#: sharded MIS has its own contract), and no float-width casts on
#: matrix-sized values (the numeric rebuild must stay bit-stable in the
#: build dtype — any mixing happens at the declared host seam, not
#: inside the kernels).
SETUP_CONTRACTS = {
    "coarsening.device_aggregates":
        {"host_callbacks": 0, "collectives": 0, "narrowing_casts": 0},
    "ops.segment_galerkin":
        {"host_callbacks": 0, "collectives": 0, "narrowing_casts": 0},
    "ops.segment_spgemm":
        {"host_callbacks": 0, "collectives": 0, "narrowing_casts": 0},
    "ops.transfer_smooth":
        {"host_callbacks": 0, "collectives": 0, "narrowing_casts": 0},
    "ops.stencil_galerkin":
        {"host_callbacks": 0, "collectives": 0, "narrowing_casts": 0},
}


# ---------------------------------------------------------------------------
# setup-phase cost model + stage attribution
# ---------------------------------------------------------------------------

def setup_cost_model(host_levels) -> Dict[str, Dict[str, int]]:
    """Analytic traffic model per setup stage, keyed by the
    ``models/amg.py`` setup-scope names (``level<i>/galerkin``, ...).
    Galerkin stages price the CACHED segment/stencil plan where one
    exists (gather + multiply + scatter-add ≈ 3 streams per multiply-
    list entry); plan-less stages fall back to an nnz-proportional
    SpGEMM estimate. Numbers are a traffic model for the attribution
    join (GB/s column), not a measurement."""
    rows: Dict[str, Dict[str, int]] = {}
    if not host_levels:
        return rows
    for i, (Ai, P, _R) in enumerate(host_levels[:-1]):
        try:
            itemsize = Ai.val.dtype.itemsize
            nnz = int(Ai.nnz)
        except Exception:
            continue
        plan = getattr(P, "_seg_plan", None)
        spec = getattr(P, "_implicit_spec", None)
        gplan = spec.get("_gplan") if isinstance(spec, dict) else None
        if plan is not None:
            flops = int(plan.flops)
        elif gplan is not None:
            flops = int(gplan.flops)
        else:
            flops = 4 * nnz            # host hash-SpGEMM estimate
        rows["level%d/galerkin" % i] = {
            "flops": 2 * flops, "bytes": 3 * flops * itemsize}
        # strength graph + aggregation: a few full passes over A
        rows["level%d/coarsening" % i] = {
            "flops": 2 * nnz, "bytes": 4 * nnz * itemsize}
        rows["level%d/transfer" % i] = {
            "flops": 0, "bytes": 2 * nnz * itemsize}
        rows["level%d/relax_setup" % i] = {
            "flops": 2 * nnz, "bytes": 2 * nnz * itemsize}
    try:
        Alast = host_levels[-1][0]
        nl = int(Alast.nrows)
        rows["coarse_solver"] = {"flops": 2 * nl ** 3 // 3,
                                 "bytes": 8 * nl * nl}
    except Exception:
        pass
    return rows


def setup_attribution(setup_profile, host_levels=None,
                      total_s: Optional[float] = None) -> Dict[str, Any]:
    """Stage-by-stage attribution of the measured setup/rebuild profile
    (``AMG.setup_profile``), joined to :func:`setup_cost_model` — the
    setup-phase counterpart of the solve roofline. Returns::

        {"rows": [{stage, seconds, frac, flops?, bytes?, gbps?}...],
         "total_s", "named_s", "coverage"}

    ``coverage`` is the fraction of the build's wall total inside NAMED
    top-level stages (nested substages don't double count) — the bench
    record's "attributed setup time" number. ``total_s`` should be the
    wall time of the build itself (models/amg.py records it): the
    profiler's own total keeps ticking after the build, so exporting it
    later would dilute coverage."""
    if setup_profile is None:
        return {"rows": [], "total_s": 0.0, "named_s": 0.0,
                "coverage": 0.0}
    prof = setup_profile.to_dict() if hasattr(setup_profile, "to_dict") \
        else dict(setup_profile)
    model = setup_cost_model(host_levels) if host_levels else {}
    rows: List[Dict[str, Any]] = []
    named = 0.0

    def walk(scopes, prefix, depth):
        nonlocal named
        for name, rec in scopes.items():
            # round BEFORE accumulating so named_s equals the sum of the
            # reported top-level row seconds exactly
            t = round(float(rec.get("total_s", 0.0)), 5)
            path = prefix + name
            if depth == 0:
                named += t
            row: Dict[str, Any] = {"stage": path, "seconds": round(t, 5),
                                   "nested": depth > 0}
            m = model.get(path)
            if m is not None:
                row.update(m)
                if t > 0 and m.get("bytes"):
                    row["gbps"] = round(m["bytes"] / t / 1e9, 3)
            rows.append(row)
            walk(rec.get("children", {}), path + "/", depth + 1)

    walk(prof.get("scopes", {}), "", 0)
    total = float(total_s) if total_s else \
        (float(prof.get("total_s") or named) or named)
    for row in rows:
        row["frac"] = round(row["seconds"] / total, 4) if total else 0.0
    rows.sort(key=lambda r: -r["seconds"])
    return {"rows": rows, "total_s": round(total, 5),
            "named_s": round(named, 9),
            "coverage": round(named / total, 4) if total else 0.0}


def fused_vec_modeled() -> bool:
    """Whether the iteration model should charge the fused vector-tier
    byte counts — mirrors ops.fused_vec.fused_vec_enabled without
    importing jax (this module stays stdlib+numpy-only)."""
    return os.environ.get("AMGCL_TPU_FUSED_VEC", "1") != "0"


def krylov_iteration_model(solver_name: str, A_dev,
                           cycle_total: Optional[Dict[str, int]] = None,
                           pre_cycles: int = 1,
                           fused: Optional[bool] = None,
                           batch: int = 1,
                           effective_batch: Optional[int] = None
                           ) -> Dict[str, Any]:
    """FLOPs/HBM-bytes of one outer Krylov iteration: the solver's SpMVs
    and vector work plus ``pre_cycles`` multigrid cycles per
    preconditioner application (``cycle_total`` from cycle_cost_model).

    ``fused`` selects the vector-traffic model: the fused tier
    (ops/fused_vec.py, default when ``AMGCL_TPU_FUSED_VEC`` is on)
    streams each iteration vector once per compound primitive
    (:data:`KRYLOV_VEC_STREAMS_FUSED`), so the dots are byte-free; the
    composed model charges every dot and axpby its own passes. FLOPs are
    identical either way — fusion moves bytes, not arithmetic.

    ``batch`` adds the stacked multi-RHS axis (serve/batched.py): FLOPs
    and per-vector streams scale with B, but the Krylov operator's
    STORED bytes are read once per SpMV regardless of B — the
    amortization that makes one stacked dispatch beat B single solves
    even before dispatch overhead. The multigrid-cycle bytes are scaled
    by B conservatively (the cycle total has no stored/vector split
    here), so the modeled amortization is a floor, not the full win.

    ``effective_batch`` prices padding: the serve path zero-pads
    partial batches up to a power-of-two bucket (serve/service.py), so
    only ``effective_batch`` of the ``batch`` columns are real work.
    The model then also reports ``batch_fill`` plus the effective and
    padding-waste splits of flops/bytes — wasted FLOPs scale with the
    padded columns, wasted bytes with their per-column vector traffic
    only (the stored operator is read once regardless), so the roofline
    can separate effective from padded throughput."""
    spmv, papp, dots, axpys = KRYLOV_OPS.get(solver_name, (1, 1, 4, 4))
    if fused is None:
        fused = fused_vec_modeled()
    batch = max(int(batch), 1)
    n, _ = _vec_dims(A_dev) if A_dev is not None else (0, 0)
    itemsize = _itemsize(A_dev) if A_dev is not None else 4
    vec = n * itemsize
    mv = mv_cost(A_dev)
    stored_once = 0
    if batch > 1 and A_dev is not None:
        stored = _leaf_bytes(A_dev)
        mv = {"flops": mv["flops"] * batch,
              "bytes": stored + batch * max(mv["bytes"] - stored, 0)}
        stored_once = stored * spmv
    cost = _scale(mv, spmv)
    streams = KRYLOV_VEC_STREAMS_FUSED.get(solver_name) if fused else None
    if streams is None:
        fused = False
        streams = 2 * dots + 3 * axpys
    cost = _add(cost, {"flops": (2 * dots + 2 * axpys) * n * batch,
                       "bytes": streams * vec * batch})
    if cycle_total:
        cost = _add(cost, _scale(
            {"flops": cycle_total["flops"], "bytes": cycle_total["bytes"]},
            papp * max(int(pre_cycles), 1) * batch))
    out = {"solver": solver_name, "spmvs": spmv, "precond_applies": papp,
           "dots": dots, "axpys": axpys, "vec_streams": streams,
           "fused_vec": bool(fused), **cost}
    if batch > 1:
        out["batch"] = batch
    if effective_batch is not None:
        eff = min(max(int(effective_batch), 0), batch)
        fill = eff / batch
        # wasted bytes: the per-column-scaled traffic only — the stored
        # operator read (stored_once) is paid once whatever the fill
        per_col_bytes = max(cost["bytes"] - stored_once, 0)
        waste_f = int(round(cost["flops"] * (1 - fill)))
        waste_b = int(round(per_col_bytes * (1 - fill)))
        out["effective_batch"] = eff
        out["batch_fill"] = round(fill, 4)
        out["padding_waste_flops"] = waste_f
        out["padding_waste_bytes"] = waste_b
        out["effective_flops"] = cost["flops"] - waste_f
        out["effective_bytes"] = cost["bytes"] - waste_b
    if cost["bytes"]:
        out["flop_per_byte"] = round(cost["flops"] / cost["bytes"], 4)
    return out


# ---------------------------------------------------------------------------
# hierarchy memory ledger
# ---------------------------------------------------------------------------

def hierarchy_ledger(hier, host_levels=None,
                     budget: Optional[DeviceMemoryBudget] = None,
                     setup_profile=None) -> Dict[str, Any]:
    """Per-level device-memory map of a hierarchy.

    Totals are the leaf-byte sums of exactly the pytree slots a Level
    carries (A, relax, P, R, down, up) plus the coarse solver — the same
    leaves ``AMG.bytes()`` walks, so ``totals.bytes`` equals the live
    buffer total by construction."""
    levels = []
    by_format: Dict[str, int] = {}
    tot = {"operator": 0, "transfer": 0, "relax": 0, "fused": 0}
    for i, lv in enumerate(getattr(hier, "levels", [])):
        A = getattr(lv, "A", None)
        op_b = _leaf_bytes(A)
        p_b = _leaf_bytes(getattr(lv, "P", None))
        r_b = _leaf_bytes(getattr(lv, "R", None))
        rx_b = _leaf_bytes(getattr(lv, "relax", None))
        fu_b = _leaf_bytes(getattr(lv, "down", None)) \
            + _leaf_bytes(getattr(lv, "up", None))
        fmt = type(A).__name__ if A is not None else None
        row = {
            "level": i,
            "format": fmt,
            "bytes": {"operator": op_b, "P": p_b, "R": r_b,
                      "relax": rx_b, "fused": fu_b,
                      "total": op_b + p_b + r_b + rx_b + fu_b},
            "spmv": mv_cost(A),
        }
        if host_levels is not None and i < len(host_levels):
            Ai = host_levels[i][0]
            row["rows"] = int(Ai.nrows)
            row["nnz"] = int(Ai.nnz)
        levels.append(row)
        if fmt:
            by_format[fmt] = by_format.get(fmt, 0) + op_b
        for Tm in (getattr(lv, "P", None), getattr(lv, "R", None)):
            if Tm is not None:
                tname = "transfer/" + type(Tm).__name__
                by_format[tname] = by_format.get(tname, 0) + _leaf_bytes(Tm)
        tot["operator"] += op_b
        tot["transfer"] += p_b + r_b
        tot["relax"] += rx_b
        tot["fused"] += fu_b
    coarse_b = _leaf_bytes(getattr(hier, "coarse", None))
    out: Dict[str, Any] = {
        "levels": levels,
        "coarse_solver_bytes": coarse_b,
        "totals": {**tot,
                   "bytes": sum(tot.values()) + coarse_b,
                   "by_format": by_format},
        "cycle": cycle_cost_model(hier),
    }
    if budget is not None:
        out["dense_window"] = budget.to_dict()
    if setup_profile is not None:
        to_dict = getattr(setup_profile, "to_dict", None)
        out["setup"] = to_dict() if callable(to_dict) else setup_profile
    return out


def summarize_ledger(led: Dict[str, Any]) -> Dict[str, Any]:
    """Compact one-record summary of a hierarchy ledger — what bench.py
    embeds (and the regression gate compares as 'peak ledger bytes')."""
    out = {
        "hierarchy_bytes": led["totals"]["bytes"],
        "by_format": led["totals"]["by_format"],
        "cycle_flops": led["cycle"]["total"]["flops"],
        "cycle_bytes": led["cycle"]["total"]["bytes"],
    }
    fpb = led["cycle"]["total"].get("flop_per_byte")
    if fpb is not None:
        out["cycle_flop_per_byte"] = fpb
    dw = led.get("dense_window")
    if dw is not None:
        out["dense_window_used"] = dw["used_bytes"]
        out["dense_window_total"] = dw["total_bytes"]
    return out


def _human_bytes(n: float) -> str:
    for unit in ("B", "K", "M", "G"):
        if abs(n) < 1024 or unit == "G":
            return "%.2f %s" % (n, unit)
        n /= 1024.0


def format_ledger(led: Dict[str, Any]) -> str:
    """Human-readable rendering of a hierarchy ledger (the CLI's
    ``--ledger`` table)."""
    lines = ["Resource ledger:",
             "level  format            operator  transfer     relax"
             "     fused   F/B(spmv)",
             "-" * 78]
    for row in led["levels"]:
        b = row["bytes"]
        sp = row["spmv"]
        fpb = (sp["flops"] / sp["bytes"]) if sp["bytes"] else 0.0
        lines.append("%5d  %-16s %9s %9s %9s %9s %9.3f" % (
            row["level"], row["format"] or "-",
            _human_bytes(b["operator"]), _human_bytes(b["P"] + b["R"]),
            _human_bytes(b["relax"]), _human_bytes(b["fused"]), fpb))
    t = led["totals"]
    lines.append("-" * 78)
    lines.append("total device bytes: %s  (operator %s, transfer %s, "
                 "relax %s, fused %s, coarse %s)" % (
                     _human_bytes(t["bytes"]), _human_bytes(t["operator"]),
                     _human_bytes(t["transfer"]), _human_bytes(t["relax"]),
                     _human_bytes(t["fused"]),
                     _human_bytes(led["coarse_solver_bytes"])))
    cyc = led["cycle"]["total"]
    lines.append("one cycle: %.3g MFLOP / %s streamed  ->  %.3f flop/byte"
                 % (cyc["flops"] / 1e6, _human_bytes(cyc["bytes"]),
                    cyc.get("flop_per_byte", 0.0)))
    dw = led.get("dense_window")
    if dw is not None:
        lines.append("dense-window budget: %s / %s used" % (
            _human_bytes(dw["used_bytes"]), _human_bytes(dw["total_bytes"])))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# distributed communication models
# ---------------------------------------------------------------------------

def comm_model(M, nd: int) -> Optional[Dict[str, Any]]:
    """Halo-exchange messages and wire bytes of ONE distributed SpMV.

    Delegates to the matrix's own ``halo_comm(nd)`` (dist_matrix /
    dist_ell define it next to the exchange they model); None when the
    operator has no distributed exchange."""
    fn = getattr(M, "halo_comm", None)
    if callable(fn):
        return fn(int(nd))
    return None


def allreduce_model(nd: int, count: int, itemsize: int) -> Dict[str, int]:
    """Ring-allreduce wire model of ``lax.psum`` over ``count`` elements:
    2(nd-1) steps, each moving count/nd elements per device pair —
    ~2·count·itemsize total on the wire for large nd."""
    nd = max(int(nd), 1)
    if nd == 1:
        return {"msgs": 0, "bytes": 0}
    msgs = 2 * (nd - 1)
    return {"msgs": msgs, "bytes": int(2 * (nd - 1) / nd * count * itemsize)}


def krylov_comm_model(spmv_comm: Optional[Dict[str, Any]], nd: int,
                      itemsize: int, spmvs: int = 1,
                      dots: int = 3,
                      elems_per_dot: int = 1) -> Dict[str, Any]:
    """Per-iteration comm of a distributed Krylov loop: the SpMV halo
    exchanges plus one allreduce per inner-product GROUP.

    ``dots`` counts the collectives (the latency-bearing quantity);
    ``elems_per_dot`` the scalars each one carries — a merged-reduction
    body like the pipelined CG psums ONE stacked 3-vector per iteration
    (``dots=1, elems_per_dot=3``) where the classical body pays three
    separate scalar collectives."""
    base = {"msgs": 0, "bytes": 0}
    if spmv_comm:
        base = {"msgs": spmv_comm["msgs"] * spmvs,
                "bytes": spmv_comm["bytes"] * spmvs}
    red = allreduce_model(nd, max(int(elems_per_dot), 1), itemsize)
    out = {"msgs": base["msgs"] + dots * red["msgs"],
           "bytes": base["bytes"] + dots * red["bytes"],
           "spmvs": spmvs, "dots": dots}
    if elems_per_dot != 1:
        out["elems_per_dot"] = int(elems_per_dot)
    return out


# ---------------------------------------------------------------------------
# XLA cross-check
# ---------------------------------------------------------------------------

def xla_cost_analysis(fn, *args) -> Optional[Dict[str, float]]:
    """Compile ``fn(*args)`` and read XLA's own cost analysis — the
    cross-check for the analytic models above. Returns
    ``{"flops", "bytes_accessed"}`` or None when the backend does not
    expose cost analysis (never raises)."""
    try:
        import jax
        c = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0] if c else None
        if not c:
            return None
        out = {}
        if c.get("flops") is not None:
            out["flops"] = float(c["flops"])
        ba = c.get("bytes accessed", c.get("bytes_accessed"))
        if ba is not None:
            out["bytes_accessed"] = float(ba)
        return out or None
    except Exception:
        return None
