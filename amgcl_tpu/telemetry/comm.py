"""Measured distributed-communication attribution + per-shard imbalance.

The distributed solvers have carried *analytic* comm models since PR 2
(``ledger.comm_model`` / ``krylov_comm_model``) and static collective
contracts since PR 6 (``ledger.DIST_CG_COLLECTIVES``) — but nothing ever
*measured* where the wall time of a distributed iteration goes. HPCG's
lesson (PAPERS.md) is that the comm fraction is the quantity that
decides multi-chip viability, so this module is the mesh counterpart of
``telemetry/roofline.py``: it joins measured stage seconds to the comm
models the auditor already checks.

The measurement trick is **comm ablation**: every distributed stage is
timed twice from the same program skeleton — once with the real
collectives (ppermute ring / all_to_all slab / psum) and once with
*local stand-ins of identical shape and downstream compute*
(``dist_matrix._local_exchange`` et al.), so the difference of the two
device-synced medians is the collective's wall share, overlap included.
The stand-ins are numerically wrong at shard edges on purpose and are
never dispatched by a solve; the jaxpr auditor
(``analysis/jaxpr_audit.audit_comm_stages`` vs
``ledger.COMM_STAGE_CONTRACTS``) pins their collective census to
exactly 0 — an ablated variant that quietly kept a collective would
poison the subtraction.

Pieces:

* :func:`comm_stages` — the measured/ablated stage-pair plan for a
  distributed operator (``DistDiaMatrix`` ring halo / ``DistEllMatrix``
  all_to_all slab, the stacked psum, and one representative Krylov
  iteration per ``DIST_CG_COLLECTIVES`` body).
* :func:`measure_comm` / :func:`comm_attribution` — drive the pairs
  standalone under a device-synced profiler (the
  ``roofline.measure_stages`` discipline: compile + warmup outside the
  scopes, ``AMGCL_TPU_COMM_REPS`` reps) and join against the ledger
  models: achieved wire GB/s per collective, comm fraction per
  iteration, model-vs-measured divergence findings for
  ``telemetry.diagnose(comm=...)``.
* :func:`dist_resources` / :func:`shard_costs` / :func:`imbalance` —
  the per-shard side of the resource ledger: rows/nnz/halo-width/bytes
  per shard and the load-imbalance factor (max/mean shard cost).
* :func:`measure_shard_spread` — measured per-shard stage-time spread:
  each shard's local SpMV timed standalone under ``shard<i>/...``
  scopes (exported as a per-device Perfetto track group by
  ``cli.py --dist-report --trace``).
* :func:`hw_provenance` — the hardware stamp every bench/scaling record
  carries: device kind, mesh/topology shape, and the ICI vs
  CPU-fallback platform tag the gates key their platform-mismatch skip
  on.

Everything returned is JSON-clean; jax is imported lazily inside the
measurement functions (module import stays cheap).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

import numpy as np

from amgcl_tpu.telemetry import ledger as _ledger

#: collective census expected of each measured stage, keyed by the
#: stage's ``contract`` name — lives in ledger next to its siblings
COMM_STAGE_CONTRACTS = _ledger.COMM_STAGE_CONTRACTS


def comm_reps() -> int:
    """Timed repetitions per comm stage (``AMGCL_TPU_COMM_REPS``,
    default 5 — collective timings jitter more than kernel timings, the
    median needs a few samples)."""
    try:
        return max(int(os.environ.get("AMGCL_TPU_COMM_REPS", "5")), 1)
    except ValueError:
        return 5


# ---------------------------------------------------------------------------
# hardware provenance
# ---------------------------------------------------------------------------

def hw_provenance(mesh=None) -> Dict[str, Any]:
    """The hardware stamp of a measurement: device platform/kind, device
    counts, mesh shape, and ``platform_tag`` — ``"ici"`` on real TPU
    meshes (collectives ride the inter-chip interconnect) vs
    ``"cpu-fallback"`` on the host-virtual mesh (collectives are XLA
    shared-memory copies; absolute wire rates do NOT transfer to
    hardware). The gates use this for their platform-mismatch skip."""
    out: Dict[str, Any] = {"device_platform": None, "device_kind": None,
                           "device_count": None, "mesh_devices": None,
                           "mesh_shape": None, "platform_tag": None}
    try:
        import jax
        dev0 = jax.devices()[0]
        out["device_platform"] = dev0.platform
        out["device_kind"] = getattr(dev0, "device_kind", None)
        out["device_count"] = len(jax.devices())
    except Exception:
        return out
    if mesh is not None:
        try:
            out["mesh_devices"] = int(np.prod(list(mesh.shape.values())))
            out["mesh_shape"] = dict(mesh.shape)
        except Exception:
            pass
    out["platform_tag"] = "ici" if out["device_platform"] == "tpu" \
        else "cpu-fallback"
    return out


# ---------------------------------------------------------------------------
# per-shard imbalance (host-side, no measurement)
# ---------------------------------------------------------------------------

def imbalance(costs) -> Dict[str, Any]:
    """Load-imbalance summary of per-shard costs: ``factor`` is
    max/mean — 1.0 is perfectly balanced, 2.0 means the critical shard
    carries twice the average and the mesh runs at half its aggregate
    rate during that stage."""
    vals = [float(c) for c in costs if c is not None]
    if not vals or max(vals) <= 0:
        return {"max": 0.0, "mean": 0.0, "factor": 1.0}
    mean = sum(vals) / len(vals)
    return {"max": max(vals), "mean": round(mean, 6),
            "factor": round(max(vals) / mean, 4) if mean > 0 else 1.0}


def shard_costs(ptr, bounds) -> List[Dict[str, int]]:
    """Per-shard ``{shard, rows, nnz}`` of a CSR row partition: ``ptr``
    is the row pointer, ``bounds`` the partition boundaries
    ``[r0, r1, ..., rn]`` (len = shards + 1). This is the exact useful
    work per shard — a deliberately skewed strip partition shows up
    here, padding-uniform device buffers notwithstanding."""
    ptr = np.asarray(ptr)
    n = len(ptr) - 1
    out = []
    for s in range(len(bounds) - 1):
        r0 = min(max(int(bounds[s]), 0), n)
        r1 = min(max(int(bounds[s + 1]), r0), n)
        out.append({"shard": s, "rows": r1 - r0,
                    "nnz": int(ptr[r1] - ptr[r0])})
    return out


def even_bounds(n: int, nd: int, nloc: Optional[int] = None) -> List[int]:
    """Row-partition boundaries of the even (or ``nloc``-concentrated)
    strip split the distributed builders use: shard s owns rows
    ``[s*nloc, min((s+1)*nloc, n))`` — trailing shards may own nothing
    under a ``min_per_shard`` concentration."""
    nloc = -(-n // nd) if nloc is None else int(nloc)
    return [min(s * nloc, n) for s in range(nd + 1)]


def _dia_shard_rows(offsets, n: int, nd: int,
                    itemsize: int) -> List[Dict[str, Any]]:
    """Per-shard cost rows of an evenly strip-partitioned DIA operator,
    derived from the static structure alone: stored (padded) values,
    in-range values (the useful nnz — diagonals clip at the matrix
    edges, so edge shards carry slightly less), and the halo elements
    each shard exchanges per SpMV (interior shards both directions,
    edge shards one)."""
    offsets = tuple(int(o) for o in offsets)
    nloc = n // nd if nd and n % nd == 0 else -(-n // max(nd, 1))
    w = max(max(offsets), -min(offsets), 0) if offsets else 0
    out = []
    for s in range(nd):
        r0, r1 = s * nloc, min((s + 1) * nloc, n)
        nnz = 0
        for off in offsets:
            lo = max(r0, -off if off < 0 else 0)
            hi = min(r1, n - off if off > 0 else n)
            nnz += max(0, hi - lo)
        sides = 2 if 0 < s < nd - 1 else (1 if nd > 1 else 0)
        out.append({
            "shard": s, "rows": r1 - r0, "nnz": int(nnz),
            "stored_bytes": len(offsets) * (r1 - r0) * itemsize,
            "halo_elems": w * sides})
    return out


def dist_resources(A, nd: int) -> Optional[Dict[str, Any]]:
    """The per-shard ledger of one distributed operator — what rides
    ``SolveReport.resources["dist"]``: per-shard rows/nnz/bytes/halo
    rows, the load-imbalance factor over useful nnz, and the halo
    pattern. For ``DistEllMatrix`` the device buffers are
    padding-uniform by construction (every shard is padded to the same
    K slots), so the cost rows carry the padded slot count and the
    imbalance is reported over the padded cost — the *useful*-work
    imbalance of an uneven partition is visible through
    :func:`shard_costs` on the host CSR (dist_amg's ledger does that
    per level). None for operators with no distributed structure."""
    nd = int(nd)
    name = type(A).__name__
    if name == "DistDiaMatrix":
        itemsize = np.dtype(A.data.dtype).itemsize \
            if A.data is not None else 4
        rows = _dia_shard_rows(A.offsets, A.shape[0], nd, itemsize)
        return {
            "format": name, "devices": nd,
            "halo_width": int(A.halo), "pattern": "ring",
            "per_shard": rows,
            "imbalance": imbalance([r["nnz"] for r in rows]),
        }
    if name == "DistEllMatrix":
        itemsize = np.dtype(A.loc_vals.dtype).itemsize \
            if A.loc_vals is not None else 4
        k1 = int(A.loc_cols.shape[-1])
        k2 = int(A.rem_cols.shape[-1])
        c = int(A.send_idx.shape[-1]) if A.send_idx is not None else 0
        rows = [{"shard": s, "rows": A.nloc,
                 "padded_slots": A.nloc * (k1 + k2),
                 "stored_bytes": A.nloc * (k1 + k2) * itemsize,
                 "halo_elems": c * (nd - 1)}
                for s in range(nd)]
        return {
            "format": name, "devices": nd,
            "halo_slab": c, "pattern": "all_to_all",
            "per_shard": rows,
            "imbalance": imbalance([r["padded_slots"] for r in rows]),
            "padding_uniform": True,
        }
    return None


def level_shard_costs(host_csr, bounds) -> Dict[str, Any]:
    """One hierarchy level's useful-work shard table: exact per-shard
    rows/nnz from the host CSR at the EXECUTED partition (``bounds``
    from :func:`even_bounds`, min_per_shard concentration included) +
    the imbalance factor over nnz."""
    rows = shard_costs(host_csr.ptr, bounds)
    return {"per_shard": rows,
            "imbalance": imbalance([r["nnz"] for r in rows])}


# ---------------------------------------------------------------------------
# measured stages: comm-ablated pairs
# ---------------------------------------------------------------------------

def _rand_sharded(mesh, n, dtype, seed):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from amgcl_tpu.parallel.mesh import ROWS_AXIS, put_with_sharding
    v = np.random.RandomState(seed).standard_normal(n)
    return put_with_sharding(
        np.asarray(v, np.dtype(jnp.dtype(dtype))),
        NamedSharding(mesh, P(ROWS_AXIS)))


def _iter_leg(spmv, r, x, di, pipelined: bool, ablate: bool):
    """ONE representative Jacobi-CG iteration leg, shared by the DIA and
    ELL stage builders so both measure the same program their
    ``COMM_STAGE_CONTRACTS`` entries describe — collective for
    collective the ``DIST_CG_COLLECTIVES`` body: classical = 3 scalar
    psums, pipelined = ONE stacked 3-element psum; the halo SpMV rides
    ``spmv``. ``ablate`` drops every psum (the halo ablation happens
    inside the caller's ``spmv``). Returns (x_n, r_n, rr(1,))."""
    import jax.numpy as jnp
    from jax import lax
    from amgcl_tpu.parallel.mesh import ROWS_AXIS
    s = di * r
    q = spmv(s)
    if pipelined:
        g = jnp.stack([jnp.vdot(r, s), jnp.vdot(q, s),
                       jnp.vdot(r, r)])
        if not ablate:
            g = lax.psum(g, ROWS_AXIS)
        rho, qp, rr = g[0], g[1], g[2]
    else:
        def dot(a, b):
            v = jnp.vdot(a, b)
            return v if ablate else lax.psum(v, ROWS_AXIS)
        rho = dot(r, s)
        qp = dot(q, s)
        alpha0 = rho / jnp.where(qp == 0, 1.0, qp)
        rr = dot(r - alpha0 * q, r - alpha0 * q)
    alpha = rho / jnp.where(qp == 0, 1.0, qp)
    return x + alpha * s, r - alpha * q, jnp.reshape(rr, (1,))


def _dia_stages(A, mesh, pipelined: bool) -> List[Dict[str, Any]]:
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from amgcl_tpu.parallel.mesh import ROWS_AXIS
    from amgcl_tpu.parallel import dist_matrix as DM
    from amgcl_tpu.telemetry.compile_watch import watched_jit

    offsets = tuple(A.offsets)
    nd = int(mesh.shape[ROWS_AXIS])
    n = int(A.shape[0])
    dtype = A.data.dtype
    itemsize = np.dtype(dtype).itemsize
    vspec = P(ROWS_AXIS)
    dspec = P(None, ROWS_AXIS)
    x = _rand_sharded(mesh, n, dtype, 0)
    f = _rand_sharded(mesh, n, dtype, 1)
    di = _rand_sharded(mesh, n, dtype, 2)

    def spmv_of(ablate):
        ex = DM._local_exchange if ablate else DM._ring_exchange
        ga = DM._gather_local if ablate else DM._gather_ring
        return lambda d, v: DM.dia_halo_mv(d, offsets, v,
                                           exchange=ex, gather=ga)

    def halo_fn(ablate):
        body = spmv_of(ablate)
        return shard_map(body, mesh=mesh, in_specs=(dspec, vspec),
                         out_specs=vspec, check_vma=False)

    def iter_fn(ablate):
        spmv = spmv_of(ablate)

        def body(d, ff, xx, dd):
            return _iter_leg(lambda v: spmv(d, v), ff, xx, dd,
                             pipelined, ablate)

        out3 = (vspec, vspec, vspec if ablate else P())
        return shard_map(body, mesh=mesh,
                         in_specs=(dspec, vspec, vspec, vspec),
                         out_specs=out3, check_vma=False)

    halo = watched_jit(halo_fn(False), name="telemetry.comm_halo")
    halo_ab = watched_jit(halo_fn(True),
                          name="telemetry.comm_halo_ablated")
    it = watched_jit(iter_fn(False), name="telemetry.comm_iter")
    it_ab = watched_jit(iter_fn(True),
                        name="telemetry.comm_iter_ablated")
    halo_model = A.halo_comm(nd) or {"msgs": 0, "bytes": 0}
    elems = 3 if pipelined else 1
    stages = [
        {"key": "halo", "contract": "halo_dia",
         "fn": halo, "fn_ablated": halo_ab, "args": (A.data, x),
         "model": halo_model},
        _psum_stage(mesh, n, dtype, elems),
        {"key": "iteration",
         "contract": "iter_pipelined_dia" if pipelined
         else "iter_classical_dia",
         "fn": it, "fn_ablated": it_ab, "args": (A.data, f, x, di),
         "model": _ledger.krylov_comm_model(
             halo_model, nd, itemsize, spmvs=1,
             dots=1 if pipelined else 3, elems_per_dot=elems)},
    ]
    return stages


def _ell_stages(A, mesh, pipelined: bool) -> List[Dict[str, Any]]:
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from amgcl_tpu.parallel.mesh import ROWS_AXIS
    from amgcl_tpu.telemetry.compile_watch import watched_jit

    nd = int(mesh.shape[ROWS_AXIS])
    n = int(A.shape[0])
    dtype = A.loc_vals.dtype
    itemsize = np.dtype(dtype).itemsize
    vspec = P(ROWS_AXIS)
    specs = A.specs()
    x = _rand_sharded(mesh, n, dtype, 0)
    f = _rand_sharded(mesh, n, dtype, 1)
    di = _rand_sharded(mesh, n, dtype, 2)
    ident = lambda send: send          # the all_to_all stand-in

    def halo_fn(ablate):
        def body(Ae, v):
            return Ae.shard_mv(v, exchange=ident if ablate else None)
        return shard_map(body, mesh=mesh, in_specs=(specs, vspec),
                         out_specs=vspec, check_vma=False)

    def iter_fn(ablate):
        def body(Ae, ff, xx, dd):
            return _iter_leg(
                lambda v: Ae.shard_mv(
                    v, exchange=ident if ablate else None),
                ff, xx, dd, pipelined, ablate)

        out3 = (vspec, vspec, vspec if ablate else P())
        return shard_map(body, mesh=mesh,
                         in_specs=(specs, vspec, vspec, vspec),
                         out_specs=out3, check_vma=False)

    halo = watched_jit(halo_fn(False), name="telemetry.comm_halo")
    halo_ab = watched_jit(halo_fn(True),
                          name="telemetry.comm_halo_ablated")
    it = watched_jit(iter_fn(False), name="telemetry.comm_iter")
    it_ab = watched_jit(iter_fn(True),
                        name="telemetry.comm_iter_ablated")
    halo_model = A.halo_comm(nd) or {"msgs": 0, "bytes": 0}
    elems = 3 if pipelined else 1
    return [
        {"key": "halo", "contract": "halo_ell",
         "fn": halo, "fn_ablated": halo_ab, "args": (A, x),
         "model": halo_model},
        _psum_stage(mesh, n, dtype, elems),
        {"key": "iteration",
         "contract": "iter_pipelined_ell" if pipelined
         else "iter_classical_ell",
         "fn": it, "fn_ablated": it_ab, "args": (A, f, x, di),
         "model": _ledger.krylov_comm_model(
             halo_model, nd, itemsize, spmvs=1,
             dots=1 if pipelined else 3, elems_per_dot=elems)},
    ]


def _psum_stage(mesh, n, dtype, elems: int) -> Dict[str, Any]:
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from amgcl_tpu.parallel.mesh import ROWS_AXIS
    from amgcl_tpu.telemetry.compile_watch import watched_jit

    nd = int(mesh.shape[ROWS_AXIS])
    itemsize = np.dtype(dtype).itemsize
    vspec = P(ROWS_AXIS)
    x = _rand_sharded(mesh, n, dtype, 3)
    y = _rand_sharded(mesh, n, dtype, 4)

    def fn(ablate):
        def body(a, b):
            parts = jnp.stack([jnp.vdot(a, b), jnp.vdot(a, a),
                               jnp.vdot(b, b)][:elems])
            return parts if ablate else lax.psum(parts, ROWS_AXIS)
        return shard_map(body, mesh=mesh, in_specs=(vspec, vspec),
                         out_specs=vspec if ablate else P(),
                         check_vma=False)

    return {"key": "psum", "contract": "psum",
            "fn": watched_jit(fn(False), name="telemetry.comm_psum"),
            "fn_ablated": watched_jit(
                fn(True), name="telemetry.comm_psum_ablated"),
            "args": (x, y), "elems": elems,
            "model": _ledger.allreduce_model(nd, elems, itemsize)}


def comm_stages(A, mesh, pipelined: bool = False) -> List[Dict[str, Any]]:
    """The measured/ablated stage-pair plan for one distributed
    operator: halo SpMV, stacked psum, and one representative Krylov
    iteration (classical 3-psum or pipelined merged-reduction body per
    ``pipelined``). Each entry carries the two jitted variants, concrete
    sharded args, the contract key the auditor checks the traced pair
    against, and the ledger wire model of the real variant."""
    name = type(A).__name__
    if name == "DistDiaMatrix":
        return _dia_stages(A, mesh, pipelined)
    if name == "DistEllMatrix":
        return _ell_stages(A, mesh, pipelined)
    raise TypeError("no comm stages for operator type %r" % name)


# ---------------------------------------------------------------------------
# measurement + the model join
# ---------------------------------------------------------------------------

def measure_comm(A, mesh, reps: Optional[int] = None, prof=None,
                 pipelined: bool = False) -> Dict[str, Any]:
    """Time every stage pair standalone under a device-synced profiler
    (compile + warmup OUTSIDE the scopes, ``reps`` reps each at
    ``comm/<stage>`` / ``comm/<stage>_ablated``) and reduce to per-stage
    rows: the MEDIAN measured vs ablated microseconds, the collective
    wall share
    ``comm_us = max(measured − ablated, 0)`` (the two variants partition
    the stage by construction), comm fraction, the ledger wire model,
    and achieved wire GB/s where the share is resolvable."""
    import time as _time
    import jax
    from amgcl_tpu.utils.profiler import Profiler
    reps = comm_reps() if reps is None else max(int(reps), 1)
    prof = prof if prof is not None else Profiler.device()
    stages = comm_stages(A, mesh, pipelined=pipelined)
    # per-rep durations collected alongside the profiler scopes: the
    # reported numbers are MEDIANS (one GC/scheduler outlier in either
    # arm must not flip the ablation subtraction — the jitter is why
    # comm_reps() takes several samples); the scope tree keeps the
    # per-occurrence events for the Perfetto export
    medians: Dict[str, float] = {}
    for st in stages:
        for ablate in (False, True):
            fn = st["fn_ablated"] if ablate else st["fn"]
            jax.block_until_ready(fn(*st["args"]))     # compile + warm
            scope = st["key"] + ("_ablated" if ablate else "")
            ts = []
            for _ in range(reps):
                t0 = _time.perf_counter()
                with prof.scope("comm"):
                    with prof.scope(scope):
                        jax.block_until_ready(fn(*st["args"]))
                ts.append(_time.perf_counter() - t0)
            medians[scope] = float(np.median(ts))
    rows: List[Dict[str, Any]] = []
    for st in stages:
        t = medians.get(st["key"], 0.0)
        ta = medians.get(st["key"] + "_ablated", 0.0)
        comm_s = max(t - ta, 0.0)
        if not (st["model"] or {}).get("msgs"):
            # no modeled comm (single shard / zero halo): the pair is
            # structurally identical and any difference is jitter, not
            # a collective — report the zero the structure implies
            comm_s = 0.0
        row: Dict[str, Any] = {
            "stage": st["key"], "contract": st["contract"],
            "t_us": round(t * 1e6, 3),
            "ablated_us": round(ta * 1e6, 3),
            "comm_us": round(comm_s * 1e6, 3),
            "comm_fraction": round(comm_s / t, 4) if t > 0 else 0.0,
            "model": st["model"],
        }
        wire_bytes = (st["model"] or {}).get("bytes", 0)
        if comm_s > 0 and wire_bytes:
            row["wire_gbps"] = round(wire_bytes / comm_s / 1e9, 3)
        rows.append(row)
    from amgcl_tpu.parallel.mesh import ROWS_AXIS
    return {"devices": int(mesh.shape[ROWS_AXIS]),
            "reps": reps, "pipelined": bool(pipelined),
            "rows": rows, "_prof": prof}


def comm_attribution(A, mesh, solver: Optional[str] = None,
                     reps: Optional[int] = None,
                     prof=None) -> Dict[str, Any]:
    """The join: measured comm seconds vs the PR-2 comm models, per
    collective and per iteration, for the distributed Krylov body named
    by ``solver`` (``dist_cg`` / ``dist_cg_pipelined``; None reads the
    ``AMGCL_TPU_PIPELINED_CG`` dispatch like the solver itself). Returns
    a JSON-clean record with ``per_iteration`` carrying the headline
    numbers (comm fraction, achieved wire GB/s against the ICI peak
    where one is known) and ``findings`` carrying the divergence
    diagnostics ``telemetry.diagnose(comm=...)`` folds in."""
    from amgcl_tpu.parallel.mesh import ROWS_AXIS
    if solver is None:
        from amgcl_tpu.parallel.dist_solver import pipelined_cg_enabled
        solver = "dist_cg_pipelined" if pipelined_cg_enabled() \
            else "dist_cg"
    pipelined = solver == "dist_cg_pipelined"
    contract = _ledger.DIST_CG_COLLECTIVES[solver]
    meas = measure_comm(A, mesh, reps=reps, prof=prof,
                        pipelined=pipelined)
    nd = meas["devices"]
    by_key = {r["stage"]: r for r in meas["rows"]}
    it = by_key.get("iteration", {})
    halo = by_key.get("halo", {})
    psum = by_key.get("psum", {})
    stage_sum_us = (halo.get("comm_us", 0.0) * contract["spmvs"]
                    + psum.get("comm_us", 0.0) * contract["psums"])
    itemsize = 4
    try:
        itemsize = np.dtype(
            A.data.dtype if hasattr(A, "data") and A.data is not None
            else A.loc_vals.dtype).itemsize
    except Exception:
        pass
    model = _ledger.krylov_comm_model(
        _ledger.comm_model(A, nd), nd, itemsize,
        spmvs=contract["spmvs"], dots=contract["psums"],
        elems_per_dot=contract["elems_per_psum"])
    from amgcl_tpu.telemetry.roofline import ici_peak_gbps
    peak = ici_peak_gbps()
    per_iter: Dict[str, Any] = {
        "t_us": it.get("t_us"),
        "comm_us": it.get("comm_us"),
        "comm_fraction": it.get("comm_fraction"),
        "stage_sum_comm_us": round(stage_sum_us, 3),
        "model": model,
        "collectives": dict(contract),
    }
    comm_s = (it.get("comm_us") or 0.0) / 1e6
    if comm_s > 0 and model["bytes"]:
        per_iter["wire_gbps"] = round(model["bytes"] / comm_s / 1e9, 3)
    if peak is not None:
        per_iter["ici_peak_gbps"] = peak
        if per_iter.get("wire_gbps"):
            per_iter["frac_ici_peak"] = round(
                per_iter["wire_gbps"] / peak, 4)
    rec = {"solver": solver, "devices": nd,
           "provenance": hw_provenance(mesh),
           "stages": meas["rows"], "per_iteration": per_iter,
           "_prof": meas["_prof"]}
    rec["findings"] = comm_findings(rec)
    return rec


def comm_findings(rec: Dict[str, Any],
                  comm_bound_threshold: float = 0.5) -> List[Dict[str, Any]]:
    """Model-vs-measured divergence findings from one attribution record
    (``telemetry.diagnose()`` shape: severity/code/message/suggestion).
    Ranked: comm-bound iterations first, then wire-rate divergence from
    the ICI peak, then the provenance caveat on host-virtual meshes."""
    out: List[Dict[str, Any]] = []
    pi = rec.get("per_iteration") or {}
    frac = pi.get("comm_fraction")
    prov = rec.get("provenance") or {}
    if frac is not None and frac >= comm_bound_threshold:
        out.append({
            "severity": "warning", "code": "comm_bound",
            "message": "distributed iteration is %.0f%% collective wall "
                       "time (%s devices, %s body)"
                       % (100 * frac, rec.get("devices"),
                          rec.get("solver")),
            "suggestion": "merge reductions (dist_cg_pipelined psums "
                          "ONE stacked 3-vector/iter — "
                          "AMGCL_TPU_PIPELINED_CG=1), widen shards "
                          "(fewer devices per problem), or narrow the "
                          "band to shrink the halo"})
    peak = pi.get("ici_peak_gbps")
    wire = pi.get("wire_gbps")
    if peak and wire is not None:
        if wire < 0.05 * peak:
            out.append({
                "severity": "warning", "code": "comm_divergence",
                "message": "measured collective wire rate %.2f GB/s is "
                           "%.1f%% of the ICI peak (%.0f GB/s) — the "
                           "comm model's wire bytes and the measured "
                           "seconds diverge"
                           % (wire, 100 * wire / peak, peak),
                "suggestion": "small messages are latency-bound, not "
                              "bandwidth-bound: check message sizes in "
                              "the comm model, collective overlap "
                              "(the data-independent ordering), and "
                              "per-collective dispatch overhead"})
        elif wire > 1.5 * peak:
            out.append({
                "severity": "info", "code": "comm_overlapped",
                "message": "apparent wire rate %.0f GB/s exceeds the "
                           "ICI peak — the scheduler hides the "
                           "exchange behind local compute (the "
                           "ablation measures only the exposed "
                           "fraction)" % wire,
                "suggestion": None})
    if prov.get("platform_tag") == "cpu-fallback":
        out.append({
            "severity": "info", "code": "comm_platform",
            "message": "comm measured on the host-virtual mesh "
                       "(collectives are XLA shared-memory copies, "
                       "not ICI) — fractions are indicative, absolute "
                       "wire rates are not",
            "suggestion": "re-run on a TPU mesh for hardware numbers; "
                          "the gate skips cross-platform comparisons "
                          "via the provenance tag"})
    rows = rec.get("stages") or []
    if rows and all((r.get("comm_us") or 0) == 0 for r in rows):
        out.append({
            "severity": "info", "code": "comm_noise",
            "message": "every measured collective share is 0 — the "
                       "ablation difference is below timing noise on "
                       "this mesh",
            "suggestion": "raise AMGCL_TPU_COMM_REPS for more samples"})
    return out


# ---------------------------------------------------------------------------
# measured per-shard spread
# ---------------------------------------------------------------------------

def measure_shard_spread(A, mesh, reps: Optional[int] = None,
                         prof=None) -> Optional[Dict[str, Any]]:
    """Measured per-shard stage-time spread: each shard's LOCAL SpMV
    work timed standalone (no collectives) under ``shard<i>/spmv``
    scopes — the measured counterpart of the structural imbalance
    tables, and the per-device Perfetto track group
    (``cli.py --dist-report --trace``). DistDiaMatrix only (the ELL
    buffers are padding-uniform, every shard runs the same slot count
    by construction); None when the operator has no per-shard split."""
    if type(A).__name__ != "DistDiaMatrix":
        return None
    import time as _time
    import jax
    import jax.numpy as jnp
    from jax import lax
    from amgcl_tpu.parallel.mesh import ROWS_AXIS
    from amgcl_tpu.utils.profiler import Profiler
    reps = comm_reps() if reps is None else max(int(reps), 1)
    prof = prof if prof is not None else Profiler.device()
    nd = int(mesh.shape[ROWS_AXIS])
    offsets = tuple(A.offsets)
    w = int(A.halo)
    n = int(A.shape[0])
    nloc = n // nd
    data = np.asarray(A.data)

    def local_mv(d, v):
        xe = jnp.pad(v, (w, w))
        y = jnp.zeros(v.shape[0], jnp.result_type(d.dtype, v.dtype))
        for k, s in enumerate(offsets):
            y = y + d[k] * lax.dynamic_slice(xe, (w + s,), (nloc,))
        return y

    from amgcl_tpu.telemetry.compile_watch import watched_jit
    jf = watched_jit(local_mv, name="telemetry.comm_shard_spmv")
    rng = np.random.RandomState(0)
    per = []
    for s in range(nd):
        d_s = jnp.asarray(data[:, s * nloc:(s + 1) * nloc])
        x_s = jnp.asarray(rng.standard_normal(nloc), d_s.dtype)
        jax.block_until_ready(jf(d_s, x_s))            # compile + warm
        ts = []
        for _ in range(reps):
            t0 = _time.perf_counter()
            with prof.scope("shard%d" % s):
                with prof.scope("spmv"):
                    jax.block_until_ready(jf(d_s, x_s))
            ts.append(_time.perf_counter() - t0)
        per.append(float(np.median(ts)))               # outlier-robust
    return {"per_shard_us": [round(t * 1e6, 3) for t in per],
            "spread": imbalance(per), "_prof": prof}


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def format_dist_report(dist: Optional[Dict[str, Any]],
                       spread: Optional[Dict[str, Any]] = None) -> str:
    """Human-readable per-shard table (the CLI's ``--dist-report``)."""
    if not dist:
        return "(no per-shard ledger: operator exposes no " \
               "distributed structure)"
    lines = ["Per-shard ledger (%s, %d devices, %s halo):"
             % (dist.get("format"), dist.get("devices", 0),
                dist.get("pattern"))]
    lines.append("shard     rows        nnz/slots     halo elems"
                 "   measured us")
    lines.append("-" * 62)
    per_us = (spread or {}).get("per_shard_us") or []
    for r in dist.get("per_shard", []):
        s = r["shard"]
        lines.append("%5d %8d %16s %12s %12s" % (
            s, r.get("rows", 0),
            r.get("nnz", r.get("padded_slots", "-")),
            r.get("halo_elems", "-"),
            ("%.1f" % per_us[s]) if s < len(per_us) else "-"))
    lines.append("-" * 62)
    imb = dist.get("imbalance") or {}
    lines.append("load imbalance (max/mean shard cost): %.3f%s"
                 % (imb.get("factor", 1.0),
                    "  [padding-uniform device buffers]"
                    if dist.get("padding_uniform") else ""))
    if spread:
        lines.append("measured spmv spread (max/mean shard time): %.3f"
                     % spread["spread"]["factor"])
    return "\n".join(lines)


def format_comm(rec: Dict[str, Any]) -> str:
    """Human-readable comm attribution (the CLI's ``--dist-report``)."""
    lines = ["Comm attribution (%s body, %d devices, measured via "
             "comm-ablated stand-ins):"
             % (rec.get("solver"), rec.get("devices", 0))]
    lines.append("stage        measured us   ablated us     comm us"
                 "   comm frac   wire GB/s")
    lines.append("-" * 76)
    for r in rec.get("stages", []):
        lines.append("%-12s %12.1f %12.1f %11.1f %11.3f %11s" % (
            r["stage"], r["t_us"], r["ablated_us"], r["comm_us"],
            r["comm_fraction"],
            ("%.2f" % r["wire_gbps"]) if r.get("wire_gbps") else "-"))
    pi = rec.get("per_iteration") or {}
    lines.append("-" * 76)
    model = pi.get("model") or {}
    lines.append(
        "per iteration: %.1f us, comm fraction %.3f  (model: %d msgs / "
        "%s wire bytes%s)" % (
            pi.get("t_us") or 0.0, pi.get("comm_fraction") or 0.0,
            model.get("msgs", 0), model.get("bytes", 0),
            (", %.1f%% of ICI peak" % (100 * pi["frac_ici_peak"]))
            if pi.get("frac_ici_peak") is not None else ""))
    for f in rec.get("findings", []):
        lines.append("  [%s] %s" % (f["severity"].upper(), f["message"]))
        if f.get("suggestion"):
            lines.append("      -> %s" % f["suggestion"])
    return "\n".join(lines)
