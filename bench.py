"""Headline benchmark: 3D Poisson 128^3 (2,097,152 unknowns, ~14.6M nnz),
smoothed aggregation + CG + spai0 — the reference's shared-memory benchmark
configuration (docs/benchmarks.rst:60-79, BASELINE.json configs[0]).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Baselines (BASELINE.md; docs/smem_data/poisson/amgcl-cuda.txt:1): the
reference's CUDA backend on a Tesla K80 solves the 150^3 problem in 0.55 s
and sets it up in 1.33 s. Volume-scaled to N^3: solve 0.55*(N/150)^3,
setup 1.33*(N/150)^3. vs_baseline = baseline_time / our_time (>1 = faster
than the K80 reference).

The measurement runs in this process, on the TPU that JAX finds. With no
TPU it exits nonzero and prints no timing: there is no CPU fallback.

    python bench.py                 # one measurement pass on the TPU
    python bench.py --check [paths] # run the tier-1 pytest line and emit
                                    # a JSONL record with DOTS_PASSED
                                    # (also runs the regression gate and
                                    # attaches the cross-round trend +
                                    # roofline/compile summaries)
    python bench.py --gate [cand]   # regression gate: compare a candidate
                                    # record (default: the last-good run
                                    # itself) against BENCH_LAST_GOOD.json
                                    # under AMGCL_TPU_GATE_* tolerances;
                                    # exit nonzero on regression
    python bench.py --trend [sink.jsonl]
                                    # cross-round trajectory: the headline
                                    # fields of BENCH_r*.json as a table +
                                    # percentile rollups (p50/p90/p99),
                                    # optionally rolling up a JSONL sink
                                    # file too; --prom PATH additionally
                                    # writes Prometheus exposition text.
                                    # Rounds that regressed beyond the
                                    # gate's time tolerance gain a 'why'
                                    # column — the top attributed stage
                                    # from telemetry/diff.py ('-' when
                                    # the older round predates per-stage
                                    # data)
    python bench.py --why A.json B.json
                                    # cross-run regression attribution:
                                    # compare two records of the same
                                    # kind (bench worker records, solve
                                    # reports, or multichip records)
                                    # stage by stage and decompose the
                                    # wall/iters/bytes delta into ranked
                                    # per-stage contributions
                                    # (telemetry/diff.py); emits ONE
                                    # bench_why JSONL record
    python bench.py --vecbench [n ...]
                                    # microbenchmark: fused vector kernels
                                    # (ops/fused_vec.py) vs the composed
                                    # axpby+dot per vector size (including
                                    # the stacked (n, B) tier), emitted
                                    # as a bench_vecbench JSONL record
    python bench.py --scaling       # distributed scaling harness: weak +
                                    # strong sweeps over the mesh (8
                                    # virtual CPU devices forced where no
                                    # TPU is attached) for dist CG /
                                    # pipelined CG / dist AMG, with
                                    # measured comm attribution, per-shard
                                    # imbalance and the collective-census
                                    # cross-check; emits ONE structured
                                    # multichip_scaling record and writes
                                    # MULTICHIP_LATEST.json — the --gate /
                                    # --check candidate scored against the
                                    # previous round's MULTICHIP_r*.json
                                    # (AMGCL_TPU_GATE_MULTICHIP)
    python bench.py --throughput [B ...]
                                    # serving throughput: solves/sec of the
                                    # stacked multi-RHS path at B in
                                    # {1, 8, 32} (or the given list) vs the
                                    # honest un-chained single-solve rate;
                                    # emitted as a bench_throughput JSONL
                                    # record and gated round-over-round via
                                    # AMGCL_TPU_GATE_THROUGHPUT
    python bench.py --farm [T [R]]  # multi-tenant farm throughput: T
                                    # tenants (default 3) with distinct
                                    # operators round-robined R rounds
                                    # (default 6) through one SolverFarm
                                    # under an eviction-forcing byte
                                    # budget; aggregate solves/sec +
                                    # per-tenant p99 + eviction counts,
                                    # emitted as a bench_farm JSONL record
                                    # and gated round-over-round via
                                    # AMGCL_TPU_GATE_FARM
    python bench.py --storm [--smoke] [--trace PATH]
                                    # OPEN-LOOP load harness
                                    # (serve/storm.py): a seeded Poisson
                                    # offered-load ladder + a mixed
                                    # poisson/burst/ramp profile storm
                                    # through a multi-tenant SolverFarm,
                                    # latency measured from SCHEDULED
                                    # arrival (no coordinated omission);
                                    # emits ONE bench_storm record with
                                    # the latency-vs-load curve, the
                                    # saturation knee, goodput accounting
                                    # and per-phase span attribution,
                                    # writes STORM_LATEST.json, gated
                                    # round-over-round via
                                    # AMGCL_TPU_GATE_STORM. --smoke is
                                    # the seeded ~10 s CI variant;
                                    # --trace PATH writes the Perfetto
                                    # storm timeline

All JSON emission routes through the telemetry sink
(amgcl_tpu/telemetry/sink.py) — loaded by FILE PATH below because the sink
is stdlib-only while the package __init__ pulls in jax, which the
jax-free modes (--check, --gate, --trend, --why) do not import.
"""

import importlib.util
import json
import os
import re
import subprocess
import sys
import threading
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
_LAST_GOOD_PATH = os.path.join(_REPO, "BENCH_LAST_GOOD.json")
_N = int(os.environ.get("AMGCL_TPU_BENCH_N", "128"))
_METRIC = "poisson3d_%d_sa_cg_spai0_solve_time" % _N


def _load_by_path(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, *relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_sink():
    return _load_by_path("_amgcl_tpu_sink",
                         ("amgcl_tpu", "telemetry", "sink.py"))


def _load_metrics():
    # stdlib-only, like the sink: the jax-free modes aggregate with it
    return _load_by_path("_amgcl_tpu_metrics",
                         ("amgcl_tpu", "telemetry", "metrics.py"))


def _load_diff():
    # stdlib-only structured report diffing (telemetry/diff.py) — the
    # --why / --trend / gate-failure attribution engine, loaded by file
    # path for the same no-jax reason as the sink
    return _load_by_path("_amgcl_tpu_diff",
                         ("amgcl_tpu", "telemetry", "diff.py"))


_sink = _load_sink()
#: one JSON line to stdout — the contract the driver parses; no stamping
#: or NaN-cleaning so the line matches the historical print(json.dumps())
_stdout_sink = _sink.JsonlSink(stream=sys.stdout, stamp_records=False,
                               clean_records=False)

def _git_head():
    return _sink.git_commit(_REPO)


# ===========================================================================
# worker: one measurement pass
# ===========================================================================

_T0 = time.time()
_STAGES = []
_PARTIAL = {}


def _stage(name):
    _STAGES.append((name, time.time()))
    print("@@stage %.1f %s" % (time.time() - _T0, name))
    sys.stdout.flush()


def _worker_watchdog():
    """In-process total deadline: emit a diagnostic JSON naming the last
    stage reached, then hard-exit, so a stage that never returns still
    leaves its partial results."""
    total = float(os.environ.get("AMGCL_TPU_BENCH_DEADLINE", "1500"))

    def guard():
        left = total - (time.time() - _T0)
        if left > 0:
            time.sleep(left)
        last = _STAGES[-1][0] if _STAGES else "start"
        out = {"metric": _METRIC, "value": None, "unit": "s",
               "vs_baseline": None,
               "error": "bench wedged during '%s' (%.0fs worker deadline)"
                        % (last, total),
               "stages_reached": {n: round(t - _T0, 1) for n, t in _STAGES}}
        out.update(_PARTIAL)
        _stdout_sink.emit(out)
        os._exit(2)

    threading.Thread(target=guard, daemon=True).start()


def _deadline_left():
    """Seconds until the worker watchdog fires (AMGCL_TPU_BENCH_DEADLINE,
    default 1500 s)."""
    total = float(os.environ.get("AMGCL_TPU_BENCH_DEADLINE", "1500"))
    return total - (time.time() - _T0)


def _dispatch_overhead(reps=5):
    """Median wall time of an already-compiled trivial dispatch + scalar
    fetch — the per-call cost floor of the runtime, subtracted from
    chained measurements."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    f = jax.jit(lambda s: s * 2.0)
    x = jnp.float32(1.0)
    float(f(x))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(f(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _timed_chain(fn_ops, reps, repeats, overhead):
    """Time ``reps`` data-dependent applications of fn inside ONE jitted
    scan, fetching a single scalar — so per-dispatch host sync amortizes
    away. ``fn_ops`` is ``(fn, ops)``: fn(ops, carry_or_None) with the
    operator pytree as an explicit jit argument (closure constants would
    embed every operator array in the program as a constant). Returns
    median per-application seconds."""
    import jax
    import numpy as np
    from jax import lax

    fn, ops = fn_ops

    def many(args):
        def body(c, _):
            return fn(args, c), None
        out, _ = lax.scan(body, fn(args, None), None, length=reps - 1)
        return out.sum()

    f = jax.jit(many)
    float(f(ops))                   # compile + warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(f(ops))
        ts.append(time.perf_counter() - t0)
    return (float(np.median(ts)) - overhead) / reps


def _diff_timeit(fn, x0, reps=(50, 250), carry_plus_x0=False, aux=None):
    """Per-op seconds for a shape-preserving ``fn`` by timing ONE jitted
    scan at two lengths and dividing the difference by the length delta.
    The per-dispatch round trip and its jitter can swamp a short chain of
    µs-scale ops, and subtracting a separately-measured overhead leaves
    the signal inside that noise. The two-length difference cancels
    dispatch, fetch and warm-cache effects exactly. Can return ~0 (even slightly clamped-up
    negative) under extreme jitter; callers guard ratios with _floor."""
    import jax
    import numpy as np
    from jax import lax

    r1, r2 = reps

    def chain(r):
        # ``aux`` (operator arrays) rides through jit as an ARGUMENT —
        # closure constants would embed the data in the program
        def many(a, x):
            def body(c, _):
                out = (fn(a, c) if aux is not None else fn(c)) * 0.5
                return (out + x if carry_plus_x0 else out), None
            out, _ = lax.scan(body, x, None, length=r)
            return out.sum()

        f = jax.jit(many)
        float(f(aux, x0))               # compile + warm
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(f(aux, x0))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    return max(chain(r2) - chain(r1), 0.0) / (r2 - r1)


def _floor(us, lo=0.05):
    """Ratio-denominator guard for _diff_timeit results (µs)."""
    return max(us, lo)


def _traffic_model(solver, npre, npost, pre_cycles):
    """Approximate HBM bytes moved per CG iteration (documented model, not
    a measurement): per level, each smoother application and the residual
    stream the operator once plus a few vector passes; transfers stream
    once per cycle; the fine level adds the CG body's SpMV and ~14 vector
    passes (dots/axpbys). Used for achieved_gbps / hbm_frac."""
    def mat_bytes(m):
        try:
            return int(m.bytes())
        except Exception:
            return 0

    levels = solver.precond.hierarchy.levels
    itemsize = 4
    per_cycle = 0
    for i, lv in enumerate(levels):
        n = lv.A.shape[0] if lv.A is not None else 0
        a = mat_bytes(lv.A)
        vec = n * itemsize
        if i < len(levels) - 1:
            per_cycle += (npre + npost) * (a + 4 * vec)   # smoother sweeps
            per_cycle += a + 2 * vec                       # residual
            per_cycle += mat_bytes(lv.R) + mat_bytes(lv.P) + 4 * vec
        else:
            per_cycle += 2 * a + 4 * vec                   # coarse solve-ish
    n0 = levels[0].A.shape[0]
    per_iter = pre_cycles * per_cycle + mat_bytes(levels[0].A) \
        + 14 * n0 * itemsize
    return per_iter


def _bench_levels(solver):
    """Per-level SpMV timings: XLA lowering vs the Pallas DIA kernel where
    the level is DIA-formatted. Chains 50 SpMVs inside ONE jitted scan and
    fetches a scalar (per-dispatch host sync swamps a single op).
    Returns a list of dicts."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from amgcl_tpu.ops.device import DiaMatrix
    from amgcl_tpu.ops.pallas_spmv import dia_spmv

    timeit = _diff_timeit               # two-length difference (see above)

    out = []
    for li, lv in enumerate(solver.precond.hierarchy.levels):
        M = lv.A
        if M.shape[0] != M.shape[1]:
            continue
        n_cols = M.shape[1] * getattr(M, "block", (1, 1))[1]
        x = jnp.asarray(np.random.RandomState(li).rand(n_cols),
                        dtype=jnp.float32)
        saved = os.environ.get("AMGCL_TPU_PALLAS")
        os.environ["AMGCL_TPU_PALLAS"] = "0"   # mv() gates on this at trace
        try:
            t_x = timeit(M.mv, x)
        finally:
            if saved is None:
                del os.environ["AMGCL_TPU_PALLAS"]
            else:
                os.environ["AMGCL_TPU_PALLAS"] = saved
        row = {"level": li, "format": type(M).__name__,
               "rows": int(M.shape[0]),
               "xla_us": round(t_x * 1e6, 1)}
        if isinstance(M, DiaMatrix):
            offs = tuple(M.offsets)
            interp = jax.default_backend() != "tpu"
            row["ndiag"] = len(offs)
            row["pallas_us"] = round(timeit(
                lambda v: dia_spmv(offs, M.data, v, interpret=interp), x)
                * 1e6, 1)
            if interp:
                row["pallas_interpret_mode"] = True
            elif row["pallas_us"] == 0.0 or row["xla_us"] == 0.0:
                # an exact 0.0 is _diff_timeit's negative-difference
                # clamp, i.e. jitter won — no verdict from that arm
                row["winner"] = "noise"
            else:
                row["winner"] = "pallas" \
                    if row["pallas_us"] < row["xla_us"] else "xla"
            # fused residual (one-pass f - A x) vs composed (spmv kernel +
            # XLA subtract, with the HBM round-trip of A x in between) —
            # decides whether the fused kernels stay default-on
            from amgcl_tpu.ops.pallas_spmv import dia_residual
            f = jnp.asarray(np.random.RandomState(li + 1).rand(M.shape[0]),
                            dtype=jnp.float32)
            row["fused_resid_us"] = round(timeit(
                lambda v: dia_residual(offs, M.data, f, v,
                                       interpret=interp), x) * 1e6, 1)
            row["composed_resid_us"] = round(timeit(
                lambda v: f - dia_spmv(offs, M.data, v, interpret=interp),
                x) * 1e6, 1)
        if getattr(lv, "down", None) is not None:
            # one-pass down-sweep tail vs the composed 3-op chain (the
            # timeit scan needs shape-preserving fns, so wrap both to
            # return a fine-grid vector via the prolongation broadcast)
            f = jnp.asarray(np.random.RandomState(li + 2).rand(M.shape[0]),
                            dtype=jnp.float32)
            from amgcl_tpu.ops import device as _dv
            T = lv.R.T
            row["fused_down_us"] = round(timeit(
                lambda v: T.mv(lv.down(f, v)), x) * 1e6, 1)
            # honest baseline: the ACTUAL fallback path (which already
            # rides the fused dia_residual kernel), not spmv + subtract
            row["composed_down_us"] = round(timeit(
                lambda v: T.mv(lv.R.mv(_dv.residual(f, lv.A, v))), x)
                * 1e6, 1)
        if getattr(lv, "up", None) is not None:
            from amgcl_tpu.ops import device as _d
            f = jnp.asarray(np.random.RandomState(li + 3).rand(M.shape[0]),
                            dtype=jnp.float32)
            uc = jnp.asarray(np.random.RandomState(li + 4).rand(
                lv.R.shape[0]), dtype=jnp.float32)
            row["fused_up_us"] = round(timeit(
                lambda v: lv.up(f, v, uc), x) * 1e6, 1)
            row["composed_up_us"] = round(timeit(
                lambda v: lv.relax.apply_post(
                    lv.A, f, v + _d.spmv(lv.P, uc)), x) * 1e6, 1)
        out.append(row)
    return out


def _bench_unstructured():
    """Unstructured SpMV comparison: FE-style matrix at poisson3Db's
    profile (BASELINE config 2), RCM-reordered; times the plain-ELL
    jnp.take path vs the windowed-ELL XLA path (ops/unstructured.py) and
    the dense-window kernel with chained SpMVs per measurement."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from amgcl_tpu.ops.csr import CSR
    from amgcl_tpu.ops import device as dev
    from amgcl_tpu.ops.unstructured import (
        csr_to_windowed_ell, fe_like_problem)
    from amgcl_tpu.utils.adapters import cuthill_mckee, permute

    cache = os.path.join(_REPO, ".bench_fe_cache.npz")
    n_target = int(os.environ.get("AMGCL_TPU_BENCH_UNSTRUCT_N", "85623"))
    fe_version = 2      # v2: 1/h² edge weights (v1 was SA-degenerate)
    A = None
    if os.path.exists(cache):
        try:
            z = np.load(cache)
            if int(z["n"]) == n_target and "version" in z.files \
                    and int(z["version"]) == fe_version:
                A = CSR(z["ptr"], z["col"], z["val"], int(z["n"]))
        except Exception:
            A = None
    if A is None:
        A, _ = fe_like_problem(n=n_target)
        A = permute(A, cuthill_mckee(A))
        np.savez(cache, ptr=A.ptr, col=A.col, val=A.val, n=A.nrows,
                 version=fe_version)

    x = jnp.asarray(np.random.RandomState(0).rand(A.nrows), jnp.float32)

    def timeit(fn):
        # shorter chains than _bench_levels: the take-ELL arm is ~30 ms
        # per op on this fixture, so the work dominates and long chains
        # would cost minutes; the difference still cancels dispatch
        return _diff_timeit(fn, x, reps=(10, 30),
                            carry_plus_x0=True) * 1e6  # us per spmv

    out = {"n": A.nrows, "nnz": A.nnz}
    E = dev.csr_to_ell(A, jnp.float32)
    out["ell_take_us"] = round(timeit(E.mv), 1)
    W = csr_to_windowed_ell(A, jnp.float32)
    if W is not None:
        out["win"] = W.win
        out["well_xla_us"] = round(timeit(W.mv), 1)

    # gather-free dense-window format (ops/densewin.py): storage-for-
    # bandwidth trade; on TPU this is the production unstructured path
    # (auto-selected), so its SpMV row is the one the solve runs on
    try:
        from amgcl_tpu.ops.densewin import (csr_to_dense_window,
                                            dense_window_spmv)
        D = csr_to_dense_window(A, jnp.float32, require_kernel=True)
        if D is not None:
            out["dwin_win"] = D.win
            out["dwin_gb"] = round(D.bytes() / 1e9, 2)
            out["dwin_spmv_us"] = round(_diff_timeit(
                lambda a, v: dense_window_spmv(
                    a[0], a[1], v, D.win, D.shape[0]),
                x, reps=(10, 30), carry_plus_x0=True,
                aux=(D.window_starts, D.blocks)) * 1e6, 1)
            out["dwin_speedup_vs_take"] = round(
                out["ell_take_us"] / _floor(out["dwin_spmv_us"]), 2)
        else:
            out["dwin_win"] = None
    except Exception as e:
        out["dwin_error"] = repr(e)[:200]

    # EXECUTED reorder (ISSUE 20 tentpole attribution): the permuted-
    # banded fixture through the production seams — reorder_plan()
    # computes the RCM permutation, to_device('auto') re-prices the
    # candidate table on each ordering, and the format-decision records
    # carry the model bytes that explain the wall-time gain. 'rcm' is
    # forced (not 'auto') so the row is deterministic across hosts even
    # when the advisor's gain floor would sit right at the threshold.
    try:
        from amgcl_tpu.telemetry import structure as _st
        from amgcl_tpu.utils.adapters import permute as _permute
        Ax, _A0, _pm = _st.permuted_banded(4096, bw=16, seed=7, local=32)
        rx = {"n": Ax.nrows, "nnz": Ax.nnz}
        plan = _st.reorder_plan(Ax, on_tpu=True, mode="rcm")
        if plan is None:
            rx["note"] = "reorder_plan declined"
        else:
            rx["variant"] = plan["variant"]
            rx["predicted_gain"] = plan["predicted_gain"]
            Bx = _permute(Ax, plan["perm"])
            xr = jnp.asarray(np.random.RandomState(3).rand(Ax.nrows),
                             jnp.float32)
            for tag, mat in (("identity", Ax), ("reordered", Bx)):
                M = dev.to_device(mat, "auto", jnp.float32)
                d = getattr(M, "_format_decision", None) or {}
                rx[tag] = {
                    "format": d.get("fmt"),
                    "model_bytes": (d.get("predicted") or {}).get("bytes"),
                    "stored_bytes": d.get("stored_bytes"),
                    "spmv_us": round(_diff_timeit(
                        lambda v, _M=M: dev.spmv(_M, v), xr,
                        reps=(10, 30), carry_plus_x0=True) * 1e6, 1)}
            ti = rx["identity"]["spmv_us"]
            tr = rx["reordered"]["spmv_us"]
            if ti and tr:
                rx["measured_gain"] = round(ti / _floor(tr), 3)
            bi = rx["identity"]["model_bytes"]
            br = rx["reordered"]["model_bytes"]
            if bi and br:
                rx["model_bytes_gain"] = round(bi / br, 3)
        out["reorder_exec"] = rx
    except Exception as e:
        out["reorder_exec"] = {"error": repr(e)[:200]}

    # end-to-end SOLVE at the poisson3Db profile (BASELINE tutorial rows:
    # builtin 0.592 s / GTX 1050 Ti CUDA 0.171 s, AMG(SA)+BiCGStab) — a
    # synthetic same-class matrix, so the comparison is indicative of the
    # problem CLASS, not the exact SuiteSparse instance
    left = _deadline_left()
    if left < 150:
        out["solve"] = {"skipped": "%.0fs left < ~150s solve cost" % left}
        return out
    try:
        from amgcl_tpu.models.make_solver import make_solver
        from amgcl_tpu.models.amg import AMGParams
        from amgcl_tpu.solver.bicgstab import BiCGStab
        s = make_solver(A, AMGParams(dtype=jnp.float32),
                        BiCGStab(maxiter=300, tol=1e-8), refine=2)
        rhs = jnp.asarray(np.ones(A.nrows), jnp.float32)
        t0 = time.perf_counter()
        xs, info = s(rhs)
        jax.block_until_ready(xs)
        t_setup_solve = time.perf_counter() - t0       # includes compile
        t0 = time.perf_counter()
        xs, info = s(rhs)
        jax.block_until_ready(xs)
        t_solve = time.perf_counter() - t0
        out["solve"] = {
            "solve_s": round(t_solve, 4), "iters": int(info.iters),
            "resid": float(info.resid),
            "first_call_s": round(t_setup_solve, 3),
            "vs_poisson3Db_cpu": round(0.592 / t_solve, 3),
            "vs_poisson3Db_cuda": round(0.171 / t_solve, 3)}
    except Exception as e:
        out["solve"] = {"error": repr(e)}
    return out


def _bench_extra_configs():
    """Compact analogues of BASELINE configs 3 (Serena-class: block value
    type) and 4 (Stokes-class: schur_pressure_correction). The real
    SuiteSparse matrices are not redistributable in this image, so these
    are generated systems of the same class; timings are absolute (no
    vs_baseline), chained like the headline measurement."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import scipy.sparse as sp
    from amgcl_tpu.ops.csr import CSR
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.bicgstab import BiCGStab
    from amgcl_tpu.solver.gmres import FGMRES
    from amgcl_tpu.models.schur import SchurPressureCorrection
    from amgcl_tpu.utils.sample_problem import poisson3d_block

    out = {}

    def timed_solve(solver, rhs):
        x, info = solver(rhs)            # compile + warm
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        x, info = solver(rhs)
        jax.block_until_ready(x)
        return time.perf_counter() - t0, info

    # config-3 analogue: block 3x3 system, SA + spai0 + BiCGStab
    try:
        n = int(os.environ.get("AMGCL_TPU_BENCH_BLOCK_N", "48"))
        A, rhs = poisson3d_block(n, 3)
        s = make_solver(A, AMGParams(dtype=jnp.float32),
                        BiCGStab(maxiter=200, tol=1e-6))
        t, info = timed_solve(s, jnp.asarray(rhs, jnp.float32))
        out["block3_n%d" % n] = {
            "rows": A.nrows * 3, "solve_s": round(t, 4),
            "iters": int(info.iters), "resid": float(info.resid)}
        # block SpMV format decision: windowed block-ELL vs the einsum
        # block-ELL XLA path on the fine-level operator
        from amgcl_tpu.ops import device as devops
        from amgcl_tpu.ops.unstructured import csr_to_windowed_ell
        xv = jnp.asarray(np.random.RandomState(0).rand(A.nrows * 3),
                         jnp.float32)

        def timeit(fn):
            return round(_diff_timeit(fn, xv, carry_plus_x0=True)
                         * 1e6, 1)

        E = devops.csr_to_ell(A, jnp.float32)
        out["block3_ell_einsum_us"] = timeit(E.mv)
        Wb = csr_to_windowed_ell(A, jnp.float32)
        if Wb is not None:
            out["block3_well_xla_us"] = timeit(Wb.mv)
    except Exception as e:
        out["block3"] = {"error": repr(e)}

    # config-4 analogue: stabilized Stokes saddle point + Schur PC + FGMRES
    left = _deadline_left()
    if left < 150:
        out["stokes_schur"] = {"skipped": "%.0fs left < ~150s config cost"
                                          % left}
        return out
    try:
        n = int(os.environ.get("AMGCL_TPU_BENCH_STOKES_N", "48"))
        T = sp.diags([-np.ones(n - 1), 2 * np.ones(n), -np.ones(n - 1)],
                     [-1, 0, 1])
        L = (sp.kron(sp.identity(n), T)
             + sp.kron(T, sp.identity(n))).tocsr()
        nu = L.shape[0]
        Av = sp.block_diag([L, L]).tocsr()
        D = sp.diags([-np.ones(nu - 1), np.ones(nu)], [-1, 0],
                     shape=(nu, nu))
        B = sp.hstack([D, 0.5 * D]).tocsr()
        K = sp.bmat([[Av, B.T], [B, -sp.identity(nu) * 1e-2]]).tocsr()
        pmask = np.zeros(K.shape[0], dtype=bool)
        pmask[2 * nu:] = True
        Ks = CSR.from_scipy(K)
        pre = SchurPressureCorrection(
            Ks, pmask, usolver_prm=AMGParams(dtype=jnp.float32),
            psolver_prm=AMGParams(dtype=jnp.float32),
            approx_schur=True, dtype=jnp.float32)
        s = make_solver(Ks, pre, FGMRES(maxiter=300, tol=1e-6))
        t, info = timed_solve(s, np.ones(Ks.nrows))
        out["stokes_schur_n%d" % n] = {
            "rows": Ks.nrows, "solve_s": round(t, 4),
            "iters": int(info.iters), "resid": float(info.resid)}
    except Exception as e:
        out["stokes_schur"] = {"error": repr(e)}
    return out


def _setup_attr_summary(report, top=12):
    """Compact form of AMG.setup_report() for the bench record: the
    named-stage coverage fraction plus the top (non-nested) stages."""
    rows = [[r["stage"], r["seconds"]] for r in report.get("rows", [])
            if not r.get("nested")][:top]
    return {"coverage": report.get("coverage"),
            "total_s": report.get("total_s"),
            "named_s": report.get("named_s"), "stages": rows}


def use_compile_cache(jax):
    """Persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    points when it is set (JAX reads it itself), else the fixed
    ``<repo>/.jax_cache`` — a fixed path, so later runs hit."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main_worker():
    """One measurement pass in this process. Exits 1, printing no timing,
    when JAX finds no TPU."""
    import numpy as np
    import jax
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print("bench.py: no TPU (JAX platform %r); nothing measured"
              % dev0.platform, file=sys.stderr)
        return 1
    _stage("device init")
    _worker_watchdog()
    use_compile_cache(jax)
    # x64 so the refinement's outer residual really is float64 (the
    # correction solves stay float32)
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from amgcl_tpu.utils.sample_problem import poisson3d
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.cg import CG

    n = _N
    solve_base = 0.55 * (n / 150.0) ** 3    # K80 CUDA, volume-scaled
    setup_base = 1.33 * (n / 150.0) ** 3

    # environment telemetry: host contention invalidated the r03→r04
    # cross-round comparison (same code, 4× slower generation); record the
    # load so future readers can tell a regression from a noisy host
    ncpu = os.cpu_count() or 1
    load0 = os.getloadavg()
    _PARTIAL["telemetry"] = {
        "ncpu": ncpu,
        "loadavg_start": [round(v, 2) for v in load0],
        "contended": load0[0] / ncpu > 0.5,
        "timing": "median-of-k chained (see _timed_chain)"}

    _stage("problem gen")
    t0 = time.perf_counter()
    A, rhs = poisson3d(n)
    t_gen = time.perf_counter() - t0

    _stage("hierarchy setup")
    # ONE definition of the headline configuration — the setup-profile
    # stage re-runs exactly this so its warm-cache premise holds
    headline_config = dict(solver=lambda: CG(maxiter=100, tol=1e-6),
                           refine=3)
    t0 = time.perf_counter()
    prm = AMGParams(dtype=jnp.float32)
    solver = make_solver(A, prm, headline_config["solver"](),
                         refine=headline_config["refine"])
    t_setup = time.perf_counter() - t0
    _PARTIAL.update({
        "setup_s": round(t_setup, 3),
        "setup_vs_baseline": round(setup_base / t_setup, 3),
        "gen_s": round(t_gen, 3),
        "device": str(dev0), "device_platform": dev0.platform,
        "device_kind": getattr(dev0, "device_kind", None)})
    # uniform hardware-provenance stamp (telemetry/comm.py): device
    # kind, topology, and the ICI vs CPU-fallback tag every gate's
    # platform-mismatch skip reads through _record_platform
    try:
        from amgcl_tpu.telemetry.comm import hw_provenance
        _PARTIAL["provenance"] = hw_provenance()
    except Exception:
        pass
    # stage-by-stage setup attribution (telemetry/ledger.
    # setup_attribution): named-stage coverage + the top stages, captured
    # NOW — the rebuild stage below replaces the profiler
    try:
        _PARTIAL["setup_attribution"] = _setup_attr_summary(
            solver.precond.setup_report())
    except Exception as e:
        _PARTIAL["setup_attribution"] = {"error": repr(e)[:200]}
    # which levels carry the fused sweep kernels (empty on CPU fallback
    # where pallas_mode gates them off — documents engagement per run)
    _PARTIAL["fused_levels"] = " ".join(
        "%d%s%s" % (i, "d" if lv.down is not None else "",
                    "u" if lv.up is not None else "")
        for i, lv in enumerate(solver.precond.hierarchy.levels)
        if lv.down is not None or lv.up is not None)
    # why any fused tier is missing: the probe/value-check decline log
    # (worker stderr never reaches the committed artifact)
    from amgcl_tpu.ops.pallas_spmv import PROBE_DECLINES
    if PROBE_DECLINES:
        _PARTIAL["fused_declines"] = [
            [n_, r] for n_, r in PROBE_DECLINES[:20]]

    rhs_dev = jnp.asarray(rhs, dtype=jnp.float32)
    x0 = jnp.zeros_like(rhs_dev)

    _stage("dispatch overhead probe")
    overhead = _dispatch_overhead()
    _PARTIAL["dispatch_overhead_s"] = round(overhead, 4)

    # one plain call for convergence info + per-call wall time (includes
    # dispatch/sync and the single-round-trip info fetch)
    _stage("solve compile+run")
    x, info = solver(rhs_dev)               # compile + warm
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    x, info = solver(rhs_dev)
    jax.block_until_ready(x)
    wall_per_call = time.perf_counter() - t0

    true_res = float(np.linalg.norm(rhs - A.spmv(np.asarray(x, np.float64)))
                     / np.linalg.norm(rhs))
    _PARTIAL.update({
        "value": round(wall_per_call, 4),
        "vs_baseline": round(solve_base / wall_per_call, 3),
        "wall_per_call_s": round(wall_per_call, 4),
        "iters": int(info.iters), "resid": float(info.resid),
        "true_resid": true_res})
    # numerical-health guard decode (telemetry/health.py): the gate's
    # health check compares this against the last-good record — a
    # previously-clean problem that now trips any guard is a regression
    if getattr(info, "health", None) is not None:
        _PARTIAL["health"] = info.health

    # amortized timing: chain solves inside one scan so per-dispatch host
    # latency does not pollute the device-time measurement — this is the
    # headline number
    _stage("solve chained timing")
    reps, repeats = 4, 3
    _PARTIAL["telemetry"]["chain_reps"] = reps
    _PARTIAL["telemetry"]["timing_repeats"] = repeats

    def chained_step(slv):
        # the 0*c term makes each solve data-depend on the previous one,
        # so chained repetitions cannot be reordered or elided. The
        # operators ride as explicit args (_timed_chain passes them back
        # through jit): closing over them would embed every level's data
        # as MLIR constants — with the fused-kernel frames that is
        # ~300 MB of program text
        ops = (slv.A_dev, slv.A_dev64, slv.precond.hierarchy)

        def one(args, c):
            A_dev, A_dev64, hier = args
            r = rhs_dev if c is None else rhs_dev + 0 * c
            got = slv._solve_fn(A_dev, A_dev64, hier, r, x0)
            return got[0].astype(jnp.float32)
        return one, ops

    try:
        t_solve = _timed_chain(chained_step(solver), reps,
                               repeats, overhead)
        t_solve = max(t_solve, 1e-9)
    except Exception:
        t_solve = wall_per_call
    _PARTIAL.update({
        "value": round(t_solve, 4),
        "vs_baseline": round(solve_base / t_solve, 3)})

    # resource ledger (telemetry/ledger.py): hierarchy bytes by format,
    # analytic cycle FLOP/byte, dense-window budget use — the gate's
    # 'peak ledger bytes' source and the roofline x-coordinate
    try:
        from amgcl_tpu.telemetry.ledger import summarize_ledger
        _PARTIAL["ledger"] = summarize_ledger(
            solver.precond.resource_ledger())
    except Exception as e:
        _PARTIAL["ledger"] = {"error": repr(e)[:200]}

    # operator X-ray summary (telemetry/structure.py): per-level format
    # decisions (winner + reason) and waste metrics on EVERY record, so
    # --why / --trend can attribute format-decision changes across
    # rounds (AMGCL_TPU_XRAY=0 opts out). Metrics + decision ledger
    # only — the advisor's RCM pass stays out of the headline worker
    # (bench --xray is the advisor's measured validation arm)
    if os.environ.get("AMGCL_TPU_XRAY", "1") != "0":
        try:
            from amgcl_tpu.telemetry.structure import xray_summary
            _PARTIAL["structure"] = xray_summary(
                solver.precond.structure_report(advise=False))
        except Exception as e:
            _PARTIAL["structure"] = {"error": repr(e)[:200]}

    # bandwidth observability: documented traffic model / measured time.
    # The ledger's per-iteration model is the primary source — it prices
    # the fused tiers (single-pass V-cycle legs, fused vector algebra)
    # at their actual single-stream cost instead of double counting the
    # composed stages; the legacy composed formula stays as the fallback
    per_iter_bytes = ((info.resources or {}).get("per_iteration")
                      or {}).get("bytes")
    if not per_iter_bytes:
        per_iter_bytes = _traffic_model(solver, prm.npre, prm.npost,
                                        prm.pre_cycles)
    iters = max(int(info.iters), 1)
    achieved = per_iter_bytes * iters / t_solve / 1e9
    _PARTIAL["model_bytes_per_iter"] = int(per_iter_bytes)
    _PARTIAL["achieved_gbps"] = round(achieved, 1)
    from amgcl_tpu.telemetry.roofline import device_peaks
    peak = device_peaks()["gbps"]
    _PARTIAL["hbm_peak_gbps"] = peak
    _PARTIAL["hbm_frac"] = round(achieved / peak, 3)

    # roofline summary (telemetry/roofline.py): the ledger's per-
    # iteration model over the CHAINED solve time vs auto-detected peaks
    # — the trend's roofline_frac column
    try:
        from amgcl_tpu.telemetry import roofline as _roofline
        pi = (info.resources or {}).get("per_iteration")
        if pi:
            rf = _roofline.solve_roofline(pi, iters, t_solve)
            if rf is not None:
                _PARTIAL["roofline"] = rf
    except Exception as e:
        _PARTIAL["roofline"] = {"error": repr(e)[:200]}

    # compile accounting (telemetry/compile_watch.py): per-function
    # traces/compiles/compile-seconds + retrace events for this run —
    # a retrace regression shows up in the committed record
    try:
        from amgcl_tpu.telemetry import compile_watch as _cwatch
        if _cwatch.enabled():
            snap = _cwatch.snapshot()
            _PARTIAL["compile"] = {
                "totals": snap["totals"],
                "functions": {name: {"traces": rec["traces"],
                                     "compile_s": rec["compile_s"],
                                     "retraces": rec["retraces"]}
                              for name, rec in snap["functions"].items()
                              if rec["traces"] or rec["compile_s"]},
                "retrace_events": snap["retrace_events"][-10:]}
    except Exception as e:
        _PARTIAL["compile"] = {"error": repr(e)[:200]}

    # same-sparsity numeric rebuild (ROADMAP item 2, time-stepping
    # workloads): identical values, so every later stage still measures
    # the same operator. Warm median-of-2 — the first rebuild pays the
    # one-time plan construction/compiles, which a time-stepping loop
    # amortizes away; that cost is recorded separately.
    _stage("hierarchy rebuild")
    try:
        pre = solver.precond
        if hasattr(pre, "rebuild"):
            vals = A.val.copy()
            t0 = time.perf_counter()
            pre.rebuild(vals)
            _PARTIAL["rebuild_first_s"] = round(
                time.perf_counter() - t0, 3)
            ts = []
            for _ in range(2):
                t0 = time.perf_counter()
                pre.rebuild(vals)
                ts.append(time.perf_counter() - t0)
            rebuild_s = float(np.median(ts))
            _PARTIAL["rebuild_s"] = round(rebuild_s, 4)
            _PARTIAL["rebuild_vs_setup"] = round(
                rebuild_s / max(t_setup, 1e-9), 4)
    except Exception as e:
        _PARTIAL["rebuild_error"] = repr(e)[:200]

    # Optional deep-dive stages, highest decision-leverage first, each
    # gated on the time left before the watchdog (the r5 chip run burned
    # half its budget in 'block + stokes configs' and got killed mid-
    # stage; a skipped stage with a recorded reason beats a wedge). Cost
    # estimates are the observed r5 stage durations + compile margin.
    def _enough(key, est):
        left = _deadline_left()
        if left > est:
            return True
        _PARTIAL[key] = {"skipped": "%.0fs left < ~%.0fs stage cost"
                                    % (left, est)}
        return False

    if _enough("roofline_stages", 150):
        # measured per-(level, stage) cycle times (telemetry/roofline.
        # measure_stages) in the compact form telemetry/diff.py joins —
        # the rows that let a LATER round's gate failure name the stage
        # that regressed instead of just the ratio (--why / --trend why)
        _stage("roofline stages")
        try:
            roof = solver.precond.roofline()
            _PARTIAL["roofline_stages"] = [
                {"level": r["level"], "stage": r["stage"],
                 "visits": r.get("visits", 1), "t_s": r["t_s"],
                 "model_bytes": r.get("model_bytes"),
                 "model_flops": r.get("model_flops")}
                for r in roof.get("stages", [])]
        except Exception as e:
            _PARTIAL["roofline_stages"] = {"error": repr(e)[:200]}

    levels = None
    if _enough("levels", 180):
        _stage("per-level timings")
        try:
            levels = _bench_levels(solver)
        except Exception as e:       # per-level timing must never kill the
            levels = [{"error": repr(e)}]   # headline number
        _PARTIAL["levels"] = levels
    if _enough("setup_profile", 120):
        # warm-cache setup re-run: all programs are already compiled, so
        # its stage attribution decomposes the REBUILD cost (device
        # programs vs fetch round trips vs fused probe/value checks)
        _stage("setup profile")
        try:
            t0 = time.perf_counter()
            s_rep = make_solver(A, prm, headline_config["solver"](),
                                refine=headline_config["refine"])
            _PARTIAL["setup_repeat_s"] = round(time.perf_counter() - t0, 3)
            # per-stage attribution of the warm re-run (device-setup
            # stages included), same shape as setup_attribution above
            _PARTIAL["setup_repeat_attribution"] = _setup_attr_summary(
                s_rep.precond.setup_report())
        except Exception as e:
            _PARTIAL["setup_repeat_attribution"] = {"error": repr(e)}
    if _enough("bf16", 200):
        # the ROADMAP's f32-vs-bf16 hierarchy decision, measured: same
        # problem, bf16 level operators (half the HBM bytes per
        # iteration) + f64-residual refinement; more iterations vs
        # cheaper iterations is exactly the hardware question
        _stage("bf16 hierarchy probe")
        try:
            t0 = time.perf_counter()
            prm16 = AMGParams(dtype=jnp.bfloat16)
            solver16 = make_solver(A, prm16, CG(maxiter=200, tol=1e-6),
                                   refine=3)
            t_setup16 = time.perf_counter() - t0
            x16, info16 = solver16(rhs_dev)
            jax.block_until_ready(x16)
            t16 = max(_timed_chain(chained_step(solver16), reps,
                                   repeats, overhead), 1e-9)
            tr16 = float(np.linalg.norm(
                rhs - A.spmv(np.asarray(x16, np.float64)))
                / np.linalg.norm(rhs))
            _PARTIAL["bf16"] = {
                "solve_s": round(t16, 4), "setup_s": round(t_setup16, 3),
                "iters": int(info16.iters), "true_resid": tr16,
                "speedup_vs_f32": round(t_solve / t16, 3)}
        except Exception as e:
            _PARTIAL["bf16"] = {"error": repr(e)}
    if _enough("throughput", 200):
        # serving throughput (serve/): stacked multi-RHS solves/sec at
        # B in {1, 8, 32} vs the honest un-chained single rate — the
        # gate's AMGCL_TPU_GATE_THROUGHPUT metric (ROADMAP item 1's
        # acceptance: b32 >= 4x the un-chained single-solve rate)
        _stage("throughput")
        try:
            _PARTIAL["throughput"] = _bench_throughput(solver, rhs_dev,
                                                       True)
        except Exception as e:
            _PARTIAL["throughput"] = {"error": repr(e)[:200]}
    if _enough("farm", 240):
        # multi-tenant farm throughput under eviction pressure — the
        # AMGCL_TPU_GATE_FARM metric (agg_sps) rides the record
        _stage("farm")
        try:
            _PARTIAL["farm"] = _bench_farm(True)
        except Exception as e:
            _PARTIAL["farm"] = {"error": repr(e)[:200]}
    if _enough("unstructured", 320):
        _stage("unstructured spmv")
        try:
            _PARTIAL["unstructured"] = _bench_unstructured()
        except Exception as e:
            _PARTIAL["unstructured"] = {"error": repr(e)}
    if _enough("xray", 150):
        # the advisor-validation join (--xray) rides the worker record
        # so the gate's AMGCL_TPU_GATE_XRAY check scores it per round:
        # predicted reorder gain vs measured, same experiment the CLI
        # prints, just stored under 'xray' instead of its own record
        _stage("xray join")
        try:
            xrec = _xray_record(
                n=int(os.environ.get("AMGCL_TPU_XRAY_N", "4096")),
                bw=int(os.environ.get("AMGCL_TPU_XRAY_BW", "16")),
                local=int(os.environ.get("AMGCL_TPU_XRAY_LOCAL", "32")),
                seed=7)
            _PARTIAL["xray"] = {
                "value": xrec["value"], "n": xrec["n"], "bw": xrec["bw"],
                "advisor": xrec["advisor"], "join": xrec["join"],
                "end_to_end": xrec["end_to_end"],
                "formats": xrec["formats"]}
        except Exception as e:
            _PARTIAL["xray"] = {"error": repr(e)[:200]}
    if _enough("extra_configs", 300):
        _stage("block + stokes configs")
        try:
            _PARTIAL["extra_configs"] = _bench_extra_configs()
        except Exception as e:
            _PARTIAL["extra_configs"] = {"error": repr(e)}
    loadN = os.getloadavg()
    _PARTIAL["telemetry"]["loadavg_end"] = [round(v, 2) for v in loadN]
    _PARTIAL["telemetry"]["contended"] = (
        _PARTIAL["telemetry"]["contended"] or loadN[0] / ncpu > 0.5)
    out = {"metric": _METRIC, "unit": "s"}
    out.update(_PARTIAL)
    if levels is not None:
        out["levels"] = levels
    _stdout_sink.emit(out)
    _sink.emit(dict(out), event="bench_worker")
    return 0


def _bench_throughput(solver, rhs_dev, on_tpu, bs=(1, 8, 32)):
    """Solves/sec of the stacked multi-RHS path at each batch size in
    ``bs``, against the honest UN-CHAINED single-solve rate (every
    per-call overhead included — that is the number batching amortizes).
    ``solver`` is the headline bundle; the measurement builds a
    refine-free CG bundle SHARING its hierarchy (stacked solves gate
    out refinement), so no second setup cost is paid.

    Each row also carries SERVICE-measured per-request latency
    percentiles (``latency_ms`` p50/p99 + ``service_sps``): 2B requests
    pushed through a real ``SolverService`` at that bucket, so the
    BENCH_r* trend tracks serving latency — queue, padding and sync
    included — not just raw stacked solves/sec."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.solver.cg import CG
    slv = make_solver(solver.A_host, solver.precond,
                      CG(maxiter=100, tol=1e-6))
    rhs1 = jnp.asarray(rhs_dev, jnp.float32)

    def timed(call, warm=1, reps=3):
        for _ in range(warm):
            x, _ = call()
            jax.block_until_ready(x)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            x, info = call()
            jax.block_until_ready(x)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), info

    t1, info1 = timed(lambda: slv(rhs1))
    out = {"single_unchained_s": round(t1, 4),
           "single_unchained_sps": round(1.0 / t1, 3),
           "iters_b1": int(info1.iters), "rows": []}
    for B in bs:
        cols = np.stack([np.asarray(rhs_dev) * (1.0 + 0.1 * k)
                         for k in range(B)], axis=1)
        Rh = jnp.asarray(cols, jnp.float32)
        reps = 2 if B >= 8 and not on_tpu else 3
        tB, infoB = timed(lambda: slv(Rh), reps=reps)
        sps = B / tB
        row = {"B": int(B), "batch_s": round(tB, 4),
               "solves_per_sec": round(sps, 3),
               "iters_max": int(infoB.iters),
               "speedup_vs_single": round(sps * t1, 3)}
        row.update(_serve_latency(slv, rhs_dev, B))
        out["rows"].append(row)
        out["b%d_sps" % B] = row["solves_per_sec"]
        if row.get("latency_ms"):
            out["b%d_p99_ms" % B] = row["latency_ms"]["p99"]
    if "b32_sps" in out:
        out["speedup_b32_vs_single"] = round(out["b32_sps"] * t1, 3)
    return out


def _serve_latency(slv, rhs_dev, B, factor=2):
    """Per-request latency p50/p99 through a resident SolverService at
    bucket ``B`` — the serving numbers (queue wait + padding + solve +
    sync), not the bare stacked-dispatch rate. ``factor * B`` requests
    give the bucket at least two full batches. Never fails the bench:
    errors come back as ``latency_error``.

    This harness is CLOSED-LOOP (submit blocks when the queue fills, so
    the arrival process slows down with the server — coordinated
    omission), and its rows say so: ``closed_loop``/``latency_basis``
    label the service-measured ``latency_ms`` percentiles, and
    ``open_loop_latency_ms`` carries the honest companion derived from
    INTENDED arrivals — every request here is intended at t0 (a burst
    the loop would fire instantly if never blocked), so its open-loop
    latency is completion minus t0, queueing included. The open-loop
    storm harness (``bench --storm``) measures the same quantity under
    a real arrival process."""
    import numpy as np
    try:
        from amgcl_tpu.serve import SolverService
        reqs = max(factor * B, 4)
        # ONE device_get; per-submit np.asarray(rhs_dev) would pay a
        # full device->host transfer per request and compete with the
        # service worker for the device mid-measurement
        rhs_host = np.asarray(rhs_dev)
        import time as _time
        from amgcl_tpu.telemetry import metrics as _metrics
        with SolverService(slv, batch=B, flush_ms=5.0) as svc:
            # warm the (shape, B) bucket OUTSIDE the measured window:
            # the service's jitted entry has its own compile cache, so
            # without this the percentiles track cold XLA compiles
            # (and early partial-bucket compiles), not serving latency
            warm = [svc.submit(rhs_host, block=True)
                    for _ in range(max(B, 1))]
            for f in warm:
                f.result(timeout=600)
            done_t = []          # completion stamps (done callbacks —
            #                      list.append is atomic under the GIL)
            t0 = _time.perf_counter()
            futs = []
            for k in range(reqs):
                fut = svc.submit(
                    rhs_host * (1.0 + 0.1 * (k % max(B, 1))),
                    block=True)
                fut.add_done_callback(
                    lambda f: done_t.append(_time.perf_counter()))
                futs.append(fut)
            lats = [f.result(timeout=600)[1].serve["latency_ms"]
                    for f in futs]
            wall = _time.perf_counter() - t0
        out = {"closed_loop": True, "latency_basis": "submit"}
        if lats:
            out["latency_ms"] = {
                "p50": round(_metrics.percentile(lats, 50), 3),
                "p99": round(_metrics.percentile(lats, 99), 3),
                "max": round(max(lats), 3)}
        open_lats = [(t - t0) * 1e3 for t in done_t]
        if open_lats:
            out["open_loop_latency_ms"] = {
                "basis": "intended_arrival_t0",
                "p50": round(_metrics.percentile(open_lats, 50), 3),
                "p99": round(_metrics.percentile(open_lats, 99), 3),
                "max": round(max(open_lats), 3)}
        if wall > 0:
            out["service_sps"] = round(reqs / wall, 3)
        return out
    except Exception as e:            # noqa: BLE001 — latency detail is
        return {"latency_error": repr(e)[:120]}   # optional, the gate
        #                                           metric is b32_sps


def _bench_farm(on_tpu, tenants=3, rounds=6):
    """Multi-tenant farm throughput (serve/farm.py): ``tenants``
    distinct graded-Poisson operators round-robined through one
    SolverFarm under a byte budget capped at 75% of the resident set —
    every round pays real eviction/readmission traffic, which is the
    number the farm gate protects. Reports aggregate solves/sec across
    tenants, per-tenant p99 latency, the eviction/readmission counts
    and the registry hit/miss/rebuild counters (readmission must stay
    on the rebuild path: misses == tenants)."""
    import numpy as np
    import jax.numpy as jnp
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.serve.farm import SolverFarm
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.utils.sample_problem import poisson3d
    base = int(os.environ.get("AMGCL_TPU_BENCH_FARM_N", "0")) \
        or (24 if on_tpu else 8)
    tenants = max(int(tenants), 2)
    rounds = max(int(rounds), 2)
    with SolverFarm(metrics_port=-9) as farm:
        rhs_by = {}
        for k in range(tenants):
            A, rhs = poisson3d(base + 2 * k)
            name = "t%d" % k
            farm.register(name, A, solver=CG(maxiter=100, tol=1e-6),
                          precond=AMGParams(dtype=jnp.float32,
                                            coarse_enough=200))
            rhs_by[name] = np.asarray(rhs)
        total = farm.stats()["pool"]["used_bytes"]
        farm.set_max_bytes(int(total * 0.75))
        # warm one round outside the measured window (cold compiles)
        for name, rhs in rhs_by.items():
            farm.solve(name, rhs)
        t0 = time.perf_counter()
        futs = []
        for _ in range(rounds):
            futs += [(name, farm.submit(name, rhs, block=True))
                     for name, rhs in rhs_by.items()]
        iters_max = 0
        for name, fut in futs:
            _x, rep = fut.result(timeout=farm.timeout_s + 600)
            iters_max = max(iters_max, int(rep.iters))
        wall = time.perf_counter() - t0
        stats = farm.stats()
    nreq = rounds * tenants
    out = {
        "tenants": tenants, "rounds": rounds, "n_base": base,
        "requests": nreq, "wall_s": round(wall, 4),
        "agg_sps": round(nreq / wall, 3) if wall > 0 else None,
        "evictions": stats["evictions"],
        "readmissions": stats["readmissions"],
        "registry": {k: stats["registry"][k]
                     for k in ("hits", "misses", "rebuilds")},
        "iters_max": iters_max,
        "pool_bytes": stats["pool"]["total_bytes"],
        "per_tenant": [
            {"tenant": r["tenant"], "requests": r["requests"],
             "p99_ms": (r.get("latency_ms") or {}).get("p99"),
             "slo_trips": r["slo_trips"],
             "unhealthy": r["unhealthy"]}
            for r in stats["tenants"]],
    }
    # the acceptance invariant, recorded where the gate can see it:
    # readmissions never paid a fresh setup
    out["rebuild_only_readmission"] = \
        stats["registry"]["misses"] <= tenants
    return out


def main_farm(args=None):
    """``bench.py --farm [T ...]``: measure the multi-tenant farm
    throughput (T tenants round-robin under an eviction-forcing byte
    budget) and emit ONE ``bench_farm`` JSONL record — the
    AMGCL_TPU_GATE_FARM metric is ``agg_sps``."""
    import jax
    nums = [int(a) for a in (args or []) if a.isdigit()]
    tenants = nums[0] if nums else 3
    rounds = nums[1] if len(nums) > 1 else 6
    on_tpu = jax.default_backend() == "tpu"
    rec = _bench_farm(on_tpu, tenants=tenants, rounds=rounds)
    dev0 = jax.devices()[0]
    print("farm (%d tenant(s) x %d round(s), base n=%d^3, %s): "
          "%.2f solves/s aggregate, %d eviction(s), %d readmission(s)"
          % (rec["tenants"], rec["rounds"], rec["n_base"],
             dev0.platform, rec["agg_sps"] or 0.0, rec["evictions"],
             rec["readmissions"]))
    for row in rec["per_tenant"]:
        print("  %-6s %3d request(s)  p99 %sms  slo_trips %d"
              % (row["tenant"], row["requests"], row["p99_ms"],
                 row["slo_trips"]))
    reg = rec["registry"]
    print("  registry: %d hit / %d miss / %d rebuild  "
          "(rebuild-only readmission: %s)"
          % (reg["hits"], reg["misses"], reg["rebuilds"],
             rec["rebuild_only_readmission"]))
    from amgcl_tpu.telemetry.comm import hw_provenance
    out = {"event": "bench_farm", **rec,
           "device": str(dev0), "device_platform": dev0.platform,
           "device_kind": getattr(dev0, "device_kind", None),
           "provenance": hw_provenance(),
           "commit": _git_head()}
    _stdout_sink.emit(out)
    _sink.emit(dict(out))
    return 0


def main_throughput(args=None):
    """``bench.py --throughput [B ...]``: measure the serving throughput
    curve (stacked multi-RHS solves/sec per batch size vs the un-chained
    single-solve rate) and emit ONE ``bench_throughput`` JSONL record.
    Problem size: AMGCL_TPU_THROUGHPUT_N, defaulting to the headline
    bench size on TPU and a small CPU-friendly size elsewhere."""
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.utils.sample_problem import poisson3d
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.cg import CG

    bs = tuple(int(a) for a in (args or []) if a.isdigit()) or (1, 8, 32)
    on_tpu = jax.default_backend() == "tpu"
    n = int(os.environ.get("AMGCL_TPU_THROUGHPUT_N", "0")) \
        or (_N if on_tpu else 24)
    A, rhs = poisson3d(n)
    solver = make_solver(A, AMGParams(dtype=jnp.float32),
                         CG(maxiter=100, tol=1e-6))
    rec = _bench_throughput(solver, jnp.asarray(rhs, jnp.float32),
                            on_tpu, bs)
    dev0 = jax.devices()[0]
    print("throughput (n=%d^3, %s): single un-chained %.2f solves/s"
          % (n, dev0.platform, rec["single_unchained_sps"]))
    for row in rec["rows"]:
        lat = row.get("latency_ms") or {}
        print("  B=%-3d  %8.4f s/batch  %8.2f solves/s  (%.2fx single)%s"
              % (row["B"], row["batch_s"], row["solves_per_sec"],
                 row["speedup_vs_single"],
                 "  serve p50 %.1fms p99 %.1fms"
                 % (lat["p50"], lat["p99"]) if lat else ""))
    from amgcl_tpu.telemetry.comm import hw_provenance
    out = {"event": "bench_throughput", "n": n, **rec,
           "device": str(dev0), "device_platform": dev0.platform,
           "device_kind": getattr(dev0, "device_kind", None),
           "provenance": hw_provenance(),
           "commit": _git_head()}
    _stdout_sink.emit(out)
    _sink.emit(dict(out))
    return 0


# ===========================================================================
# scaling harness: weak+strong sweeps over the mesh, gated round-over-round
# ===========================================================================

_MULTICHIP_LATEST = os.path.join(_REPO, "MULTICHIP_LATEST.json")


def _scaling_problem(n, scale):
    """3D Poisson on an (n*scale, n, n) grid, slow dim stretched: rows
    scale linearly with ``scale`` while the strip-partition halo (the
    +-n^2 band reach) stays constant — the weak-scaling ladder, built by
    the SAME fixture the tests and audits use (poisson3d's ``nx``
    parameter). Rows divide every mesh size that divides n^3."""
    from amgcl_tpu.utils.sample_problem import poisson3d
    return poisson3d(n, nx=n * scale)


def _scaling_measure(solver_key, A, rhs, mesh, maxiter, tol, reps):
    """One (solver, mesh, problem) cell: warm once, then median-of-reps
    timed solves. Returns rows/iters/solve seconds/per-iteration
    seconds (the efficiency metric — iteration counts move with problem
    size, per-iteration time is the comparable quantity)."""
    import numpy as np
    import jax.numpy as jnp
    t_setup = 0.0
    if solver_key in ("dist_cg", "dist_cg_pipelined"):
        from amgcl_tpu.parallel.dist_matrix import DistDiaMatrix
        from amgcl_tpu.parallel.dist_solver import dist_cg
        Ad = DistDiaMatrix.from_csr(A, mesh, jnp.float64)
        dinv = jnp.asarray(A.diagonal(invert=True))
        rhs_d = jnp.asarray(rhs)
        pip = solver_key == "dist_cg_pipelined"

        def run():
            return dist_cg(Ad, mesh, rhs_d, dinv=dinv, maxiter=maxiter,
                           tol=tol, pipelined=pip)
    else:
        from amgcl_tpu.parallel.dist_amg import DistAMGSolver
        from amgcl_tpu.models.amg import AMGParams
        from amgcl_tpu.solver.cg import CG
        t0 = time.perf_counter()
        s = DistAMGSolver(A, mesh, AMGParams(),
                          CG(maxiter=maxiter, tol=tol))
        t_setup = time.perf_counter() - t0

        def run():
            x, info = s(rhs)
            return x, info.iters, info.resid
    out = run()                                  # compile + warm
    ts = []
    for _ in range(max(int(reps), 1)):
        t0 = time.perf_counter()
        out = run()
        ts.append(time.perf_counter() - t0)
    iters = max(int(out[1]), 1)
    solve_s = float(np.median(ts))
    row = {"rows": int(A.nrows), "iters": iters,
           "solve_s": round(solve_s, 5),
           "t_iter_s": round(solve_s / iters, 6)}
    if t_setup:
        row["setup_s"] = round(t_setup, 3)
    return row


def scaling_record(devices=None, base_n=None, solvers=None, maxiter=None,
                   tol=1e-6, reps=None):
    """The structured multichip record: weak + strong sweeps per
    distributed solver over the device ladder, measured comm
    attribution + per-shard imbalance at the largest mesh, and the
    collective census cross-checked against the declared
    ``DIST_CG_COLLECTIVES`` contract. Callable with small parameters
    from tests; ``bench.py --scaling`` drives it with the env defaults
    and emits/persists the result."""
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.parallel.mesh import make_mesh
    from amgcl_tpu.telemetry import comm as C
    from amgcl_tpu.telemetry.ledger import DIST_CG_COLLECTIVES

    def _env_int(name, default):
        try:
            return int(os.environ.get(name, default))
        except ValueError:
            return int(default)

    base_n = base_n or _env_int("AMGCL_TPU_SCALING_N", 12)
    maxiter = maxiter or _env_int("AMGCL_TPU_SCALING_MAXITER", 50)
    reps = reps or _env_int("AMGCL_TPU_SCALING_REPS", 3)
    nd_avail = len(jax.devices())
    if devices is None:
        raw = os.environ.get("AMGCL_TPU_SCALING_DEVICES", "1,2,4,8")
        devices = [int(v) for v in raw.split(",") if v.strip()]
    devices = sorted(d for d in set(int(d) for d in devices)
                     if 1 <= d <= nd_avail
                     and (base_n ** 3) % d == 0)
    if not devices:
        devices = [1]
    if solvers is None:
        raw = os.environ.get("AMGCL_TPU_SCALING_SOLVERS",
                             "dist_cg,dist_cg_pipelined,dist_amg")
        solvers = [s.strip() for s in raw.split(",") if s.strip()]
    nd_max = devices[-1]
    prov = C.hw_provenance(make_mesh(nd_max))
    rec = {"event": "multichip_scaling", "schema": 2,
           "metric": "multichip_scaling",
           "base_n": base_n, "devices": devices,
           "maxiter": maxiter, "tol": tol, "reps": reps,
           "device_platform": prov.get("device_platform"),
           "device_kind": prov.get("device_kind"),
           "provenance": prov, "solvers": {}}

    # strong problem = the base grid; weak ladder scales x with nd
    A_strong, rhs_strong = _scaling_problem(base_n, 1)
    weak_cache = {1: (A_strong, rhs_strong)}

    def weak_problem(nd):
        if nd not in weak_cache:
            weak_cache[nd] = _scaling_problem(base_n, nd)
        return weak_cache[nd]

    for key in solvers:
        srec = {"weak": {"devices": devices, "cells": []},
                "strong": {"devices": devices, "cells": []}}
        if key in DIST_CG_COLLECTIVES:
            srec["collectives"] = dict(DIST_CG_COLLECTIVES[key])
        for nd in devices:
            mesh = make_mesh(nd)
            Aw, fw = weak_problem(nd)
            srec["weak"]["cells"].append(
                {"devices": nd, **_scaling_measure(
                    key, Aw, fw, mesh, maxiter, tol, reps)})
            srec["strong"]["cells"].append(
                {"devices": nd, **_scaling_measure(
                    key, A_strong, rhs_strong, mesh, maxiter, tol,
                    reps)})
        for mode in ("weak", "strong"):
            cells = srec[mode]["cells"]
            t0_, tN = cells[0]["t_iter_s"], cells[-1]["t_iter_s"]
            if t0_ and tN:
                eff = t0_ / tN
                if mode == "strong":
                    eff /= max(devices[-1] / devices[0], 1)
                srec[mode]["efficiency"] = round(eff, 4)
        rec["solvers"][key] = srec

    # comm attribution + per-shard imbalance at the largest mesh on the
    # weak (headline) problem — DIA strip operator, the dist_cg path
    mesh_max = make_mesh(nd_max)
    try:
        from amgcl_tpu.parallel.dist_matrix import DistDiaMatrix
        Aw, _fw = weak_problem(nd_max)
        Ad = DistDiaMatrix.from_csr(Aw, mesh_max, jnp.float64)
        attr = C.comm_attribution(Ad, mesh_max, solver="dist_cg")
        rec["comm"] = {k: v for k, v in attr.items()
                       if not k.startswith("_")}
        rec["imbalance"] = C.dist_resources(Ad, nd_max)
        spread = C.measure_shard_spread(Ad, mesh_max)
        if spread:
            rec["imbalance"]["measured"] = {
                "per_shard_us": spread["per_shard_us"],
                "spread": spread["spread"]}
    except Exception as e:
        rec["comm"] = {"error": repr(e)[:200]}

    # collective-census cross-check: the traced dist bodies vs the SAME
    # DIST_CG_COLLECTIVES table the comm model prices from
    if nd_max >= 2:
        try:
            from amgcl_tpu.analysis import jaxpr_audit as _ja
            census = {}
            ok = True
            for pip in (False, True):
                arec = _ja.audit_dist_cg(pipelined=pip, mesh=mesh_max)
                errs = [f for f in _ja.check_dist(arec)
                        if f["severity"] == "error"]
                census[arec["entry"].rsplit(".", 1)[1]] = {
                    "census": arec.get("collectives"),
                    "match": not errs}
                ok = ok and not errs
            rec["collectives_census"] = {"ok": ok, "bodies": census}
        except Exception as e:
            rec["collectives_census"] = {"ok": None,
                                         "error": repr(e)[:200]}

    # headline: the gate's round-over-round quantities (dist_cg at the
    # largest mesh; the first configured solver when dist_cg is absent)
    head_key = "dist_cg" if "dist_cg" in rec["solvers"] \
        else (solvers[0] if solvers else None)
    head = {"devices": nd_max}
    if head_key:
        srec = rec["solvers"][head_key]
        head["solver"] = head_key
        head["weak_efficiency"] = srec["weak"].get("efficiency")
        head["strong_efficiency"] = srec["strong"].get("efficiency")
        head["iters"] = srec["weak"]["cells"][-1]["iters"]
    pi = (rec.get("comm") or {}).get("per_iteration") or {}
    head["comm_fraction"] = pi.get("comm_fraction")
    head["wire_gbps"] = pi.get("wire_gbps")
    imb = (rec.get("imbalance") or {}).get("imbalance") or {}
    head["imbalance"] = imb.get("factor")
    rec["headline"] = head
    return rec


def main_scaling(args=None):
    """``bench.py --scaling``: run the weak+strong scaling sweep on the
    available mesh (8 virtual CPU devices are forced when the host
    platform is in play — the flag is a no-op on TPU), print the
    ladder, emit ONE structured ``multichip_scaling`` JSONL record and
    persist it to ``MULTICHIP_LATEST.json`` — the candidate
    ``--gate``/``--check`` score against the previous round's committed
    ``MULTICHIP_r*.json`` under ``AMGCL_TPU_GATE_MULTICHIP``."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_enable_x64", True)

    rec = scaling_record()
    for key, srec in rec["solvers"].items():
        for mode in ("weak", "strong"):
            cells = srec[mode]["cells"]
            print("%s %s scaling: %s" % (key, mode, "  ".join(
                "nd=%d %.0f rows %.1fus/it" % (
                    c["devices"], c["rows"], c["t_iter_s"] * 1e6)
                for c in cells)))
            if srec[mode].get("efficiency") is not None:
                print("  %s efficiency (per-iteration): %.3f"
                      % (mode, srec[mode]["efficiency"]))
    head = rec["headline"]
    print("headline (nd=%d): weak eff %s, comm fraction %s, "
          "imbalance %s" % (head["devices"], head.get("weak_efficiency"),
                            head.get("comm_fraction"),
                            head.get("imbalance")))
    rec["commit"] = _git_head()
    _stdout_sink.emit(rec)
    _sink.emit(dict(rec))
    _sink.write_json_atomic(_MULTICHIP_LATEST, _sink.stamp(dict(rec)))
    base = _multichip_baseline()
    if base is not None:
        ok, checks = run_multichip_gate(rec, base)
        print("multichip gate vs %s: %s" % (
            base.get("path", "baseline"), "ok" if ok else "REGRESSION"))
        for c in checks:
            if c.get("status") != "ok":
                # the measured pair rides the failure line — a status
                # name alone sends the reader back to the JSON
                print("  %s: %s (candidate %s vs baseline %s, limit %s)"
                      % (c["check"], c["status"], c.get("candidate"),
                         c.get("last_good"), c.get("limit")))
    return 0


def multichip_tolerances():
    """Multichip gate tolerances:

      AMGCL_TPU_GATE_MULTICHIP — minimum allowed fraction of the
                              baseline's scaling efficiency (default
                              0.8: the candidate regresses when its
                              weak/strong per-iteration efficiency
                              drops below 80% of the previous round's);
                              0 disables every multichip check
      AMGCL_TPU_GATE_COMM_FRAC — maximum allowed ratio of the
                              baseline's measured comm fraction
                              (default 1.3, plus a 0.05 absolute slack
                              so near-zero fractions don't gate on
                              noise)
    """
    def _f(name, default):
        try:
            return float(os.environ.get(name, default))
        except ValueError:
            return float(default)

    return {"efficiency": _f("AMGCL_TPU_GATE_MULTICHIP", 0.8),
            "comm_frac": _f("AMGCL_TPU_GATE_COMM_FRAC", 1.3)}


def run_multichip_gate(candidate, baseline, tol=None):
    """Compare two structured multichip records round-over-round:
    scaling efficiency (higher is better, min-fraction floor) and
    measured comm fraction (lower is better, max-ratio ceiling +
    absolute slack). Platform-mismatched pairs skip every ratio — the
    provenance tag makes a CPU-fallback candidate vs a TPU baseline a
    platform change, not a regression (the same rule the bench gate
    applies to solve time)."""
    tol = tol or multichip_tolerances()
    checks = []
    if tol["efficiency"] <= 0:
        return True, [{"check": "multichip", "status": "skipped",
                       "reason": "disabled (AMGCL_TPU_GATE_MULTICHIP=0)"}]
    plat_c = _record_platform(candidate)
    plat_b = _record_platform(baseline)
    plat_skip = None
    if plat_c is not None and plat_b is not None and plat_c != plat_b:
        plat_skip = "platform_mismatch: candidate=%s baseline=%s" \
            % (plat_c, plat_b)
    hc = candidate.get("headline") or {}
    hb = baseline.get("headline") or {}

    def higher_better(name, cv, bv):
        if plat_skip is not None:
            checks.append({"check": name, "status": "skipped",
                           "reason": plat_skip, "candidate": cv,
                           "last_good": bv})
        elif cv is None or bv is None:
            checks.append({"check": name, "status": "skipped",
                           "candidate": cv, "last_good": bv})
        else:
            floor = bv * tol["efficiency"]
            checks.append({"check": name, "candidate": cv,
                           "last_good": bv, "limit": round(floor, 6),
                           "status": "ok" if cv >= floor
                           else "regression"})

    higher_better("weak_efficiency", hc.get("weak_efficiency"),
                  hb.get("weak_efficiency"))
    higher_better("strong_efficiency", hc.get("strong_efficiency"),
                  hb.get("strong_efficiency"))
    cf_c, cf_b = hc.get("comm_fraction"), hb.get("comm_fraction")
    if plat_skip is not None:
        checks.append({"check": "comm_fraction", "status": "skipped",
                       "reason": plat_skip, "candidate": cf_c,
                       "last_good": cf_b})
    elif cf_c is None or cf_b is None:
        checks.append({"check": "comm_fraction", "status": "skipped",
                       "candidate": cf_c, "last_good": cf_b})
    else:
        limit = cf_b * tol["comm_frac"] + 0.05
        checks.append({"check": "comm_fraction", "candidate": cf_c,
                       "last_good": cf_b, "limit": round(limit, 6),
                       "status": "ok" if cf_c <= limit
                       else "regression"})
    ok = not any(c["status"] == "regression" for c in checks)
    return ok, checks


def _multichip_candidate():
    """This round's scaling record (``--scaling`` writes it):
    ``AMGCL_TPU_GATE_MULTICHIP_CANDIDATE`` path override, else
    ``MULTICHIP_LATEST.json``. (None, src) when unreadable/absent."""
    path = os.environ.get("AMGCL_TPU_GATE_MULTICHIP_CANDIDATE",
                          _MULTICHIP_LATEST)
    try:
        with open(path) as f:
            return json.load(f), path
    except Exception:
        return None, path


def _multichip_baseline():
    """The previous round's committed structured multichip record —
    the newest schema-carrying ``MULTICHIP_r*.json`` (legacy dryrun
    logs carry no metrics to gate on)."""
    m = _load_metrics()
    rows = [r for r in m.multichip_history(_REPO)
            if not r.get("legacy_dryrun")]
    return rows[-1] if rows else None


def multichip_gate_record():
    """The multichip arm of ``--gate``/``--check``: None when the
    feature is unused (no candidate AND no structured baseline), a
    gate sub-record otherwise."""
    tol = multichip_tolerances()
    cand, src = _multichip_candidate()
    base = _multichip_baseline()
    if cand is None and base is None:
        return None
    if cand is None:
        return {"ok": True, "status": "no_candidate",
                "candidate_src": src, "tolerances": tol}
    if base is None:
        return {"ok": True, "status": "no_baseline",
                "candidate_src": src, "tolerances": tol}
    ok, checks = run_multichip_gate(cand, base, tol)
    out = {"ok": ok, "candidate_src": src,
           "baseline": base.get("path"), "tolerances": tol,
           "checks": checks}
    if not ok:
        # same contract as the bench gate: the failure record carries
        # the measured pairs + the cross-run attribution
        out["failed"] = gate_failures(checks)
        out["attribution"] = gate_attribution(cand, base)
    return out


# ===========================================================================
# storm: open-loop load harness + saturation record, gated round-over-round
# ===========================================================================

_STORM_LATEST = os.path.join(_REPO, "STORM_LATEST.json")


def _storm_env_f(name, default):
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return float(default)


def main_storm(args=None):
    """``bench.py --storm [--smoke] [--trace PATH]``: the OPEN-LOOP
    load harness. Builds a small multi-tenant SolverFarm, runs a seeded
    Poisson offered-load ladder (rates from ``AMGCL_TPU_STORM_RATES``
    or auto-calibrated from a quick closed-loop warm burst), then one
    mixed poisson/burst/ramp profile storm near the sustainable rate —
    every request timestamped at its SCHEDULED arrival so latency
    includes the queueing a closed-loop harness hides. Emits ONE
    schema-versioned ``bench_storm`` record (latency-vs-offered-load
    curve, saturation knee, goodput accounting, per-phase span
    attribution, scraped gauge series) and writes ``STORM_LATEST.json``
    — the ``AMGCL_TPU_GATE_STORM`` candidate. ``--smoke`` is the seeded
    ~10 s CI variant ``--check`` runs."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.serve import storm as S
    from amgcl_tpu.serve.farm import SolverFarm
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.telemetry import load as L
    from amgcl_tpu.telemetry.comm import hw_provenance
    from amgcl_tpu.utils.sample_problem import poisson3d

    args = list(args or [])
    smoke = "--smoke" in args
    trace_path = os.environ.get("AMGCL_TPU_STORM_TRACE")
    if "--trace" in args:
        i = args.index("--trace")
        trace_path = args[i + 1] if i + 1 < len(args) else trace_path
    on_tpu = jax.default_backend() == "tpu"
    seed = int(os.environ.get("AMGCL_TPU_STORM_SEED", "0"))
    base = int(os.environ.get("AMGCL_TPU_STORM_N", "0")) \
        or (24 if on_tpu else 8)
    dur = _storm_env_f("AMGCL_TPU_STORM_DURATION_S", 0) \
        or (1.5 if smoke else 6.0)
    drain = _storm_env_f("AMGCL_TPU_STORM_DRAIN_S", 30.0)
    slo_ms = _storm_env_f("AMGCL_TPU_STORM_SLO_MS", 0) or None
    fault_plan = os.environ.get("AMGCL_TPU_STORM_FAULT_PLAN")
    n_tenants = 2

    with SolverFarm(metrics_port=0, flush_ms=5.0) as farm:
        rhs_by = {}
        for k in range(n_tenants):
            A, rhs = poisson3d(base + 2 * k)
            name = "t%d" % k
            farm.register(name, A, solver=CG(maxiter=100, tol=1e-6),
                          precond=AMGParams(dtype=jnp.float32,
                                            coarse_enough=200))
            rhs_by[name] = np.asarray(rhs)
        tenants = tuple(sorted(rhs_by))

        def rhs_for(tenant, rid):
            # mixed-content requests without a per-submit device trip
            return rhs_by[tenant] * (1.0 + 0.01 * (rid % 17))

        # warm EVERY tenant and every power-of-two bucket width the
        # storm can pack (1..batch) outside the measured window — an
        # open-loop storm against cold XLA compiles measures the
        # compiler, and ONE mid-rung bucket compile stalls the queue
        # long enough to poison the whole rung's percentiles
        for name, rhs in rhs_by.items():
            b = 1
            while b <= farm.batch:
                futs = [farm.submit(name, rhs, block=True)
                        for _ in range(b)]
                for f in futs:
                    f.result(timeout=600)
                b *= 2
        rates_env = os.environ.get("AMGCL_TPU_STORM_RATES")
        if rates_env:
            rates = [float(x) for x in rates_env.split(",")
                     if x.strip()]
        else:
            # auto-calibrate: the warm closed-loop service rate of a
            # short burst anchors the ladder so the top rung sits past
            # saturation on any hardware. TWO bursts: the first pays
            # the partial-bucket compiles its batch widths trigger,
            # only the second (warm) one is the measurement
            t0 = time.perf_counter()
            for _ in range(2):
                t0 = time.perf_counter()
                futs = [farm.submit(name, rhs, block=True)
                        for name, rhs in rhs_by.items()
                        for _ in range(3)]
                for f in futs:
                    f.result(timeout=600)
            closed_sps = (3 * n_tenants) \
                / max(time.perf_counter() - t0, 1e-6)
            anchor = max(closed_sps, 0.5)
            mult = (0.5, 1.0, 2.0) if smoke \
                else (0.4, 0.8, 1.2, 1.8, 2.5)
            rates = [round(anchor * m, 3) for m in mult]
        rungs = S.run_ladder(farm, rates, dur, rhs_for,
                             tenants=tenants, seed=seed,
                             drain_timeout_s=drain,
                             scrape_every_s=0.2,
                             fault_plan=fault_plan)
        # the mixed-phase profile storm near the sustainable rate:
        # per-phase span attribution + the Perfetto timeline source
        curve = L.ladder_curve(rungs)
        knee = L.detect_knee(curve, slo_p99_ms=slo_ms)
        ms_rate = knee.get("max_sustainable_rps") \
            or (rates[len(rates) // 2] if rates else 1.0)
        pdur = dur * (0.7 if smoke else 1.0)
        phases = [S.poisson_phase(0.8 * ms_rate, pdur),
                  S.burst_phase(0.5 * ms_rate, pdur,
                                burst_every_s=max(pdur / 3, 0.4),
                                burst_len=4),
                  S.ramp_phase(0.5 * ms_rate, 1.5 * ms_rate, pdur)]
        sched = S.build_schedule(phases, tenants=tenants, seed=seed)
        prof = S.run_storm(farm, sched, rhs_for,
                           drain_timeout_s=drain, scrape_every_s=0.2,
                           label="profile", fault_plan=fault_plan)
    by_phase = {}
    for s in prof["samples"]:
        by_phase.setdefault(s["phase"], []).append(s)
    prof_summary = {
        "phases": [{"kind": p["kind"], "rate_rps": p["rate_rps"],
                    "duration_s": p["duration_s"]} for p in phases],
        "summary": prof["summary"],
        "per_phase": {ph: L.summarize_samples(rows)
                      for ph, rows in sorted(by_phase.items())},
    }
    record = L.build_record(rungs, slo_p99_ms=slo_ms,
                            profile=prof_summary)
    # the concurrently scraped /metrics gauge time-series rides the
    # record (bounded), not just its rollup — queue-depth divergence is
    # visible in the raw series
    record["gauge_series"] = prof["gauges"][:400]
    if trace_path:
        trace = L.storm_timeline_trace(prof["samples"], prof["gauges"])
        with open(trace_path, "w") as f:
            json.dump(trace, f)
        print("storm timeline written to %s" % trace_path)
    dev0 = jax.devices()[0]
    kn = record["knee"]
    print("storm (%d tenant(s), base n=%d^3, %s, seed %d): "
          "%d request(s) over %d rung(s) + profile"
          % (len(tenants), base, dev0.platform, seed,
             record["goodput"]["requests"], len(rates)))
    for row in record["curve"]:
        print("  offered %8.2f rps  goodput %8s rps  p99 %8s ms  "
              "shed %s" % (row["offered_rps"],
                           row.get("goodput_rps"), row.get("p99_ms"),
                           row.get("shed_rate")))
    print("  knee: %s (max sustainable %s rps%s)"
          % (kn.get("reason") or "not reached",
             kn.get("max_sustainable_rps"),
             ", knee at %s rps" % kn["knee_offered_rps"]
             if kn.get("knee_offered_rps") else ""))
    out = {"event": "bench_storm", "record": record,
           "rates": rates, "duration_s": dur, "seed": seed,
           "smoke": smoke, "tenants": list(tenants), "n_base": base,
           "fault_plan": fault_plan,
           "device": str(dev0), "device_platform": dev0.platform,
           "device_kind": getattr(dev0, "device_kind", None),
           "provenance": hw_provenance(), "commit": _git_head()}
    _stdout_sink.emit(out)
    _sink.emit(dict(out))
    with open(_STORM_LATEST, "w") as f:
        json.dump(out, f, indent=1)
    return 0


def storm_tolerances():
    """Storm gate tolerances:

      AMGCL_TPU_GATE_STORM — minimum allowed fraction of the baseline's
                          max sustainable rate (default 0.7: the
                          candidate regresses when the rate its goodput
                          sustains below the knee drops under 70% of
                          the previous round's); 0 disables every storm
                          check
      AMGCL_TPU_GATE_STORM_P99 — maximum allowed ratio of the
                          baseline's p99 latency at the REFERENCE
                          offered load (the lowest ladder rung; default
                          1.5). Skipped when the two rounds' reference
                          rates differ by more than 25% — a ladder
                          recalibration changes the question, not the
                          answer.
    """
    return {"rate": _storm_env_f("AMGCL_TPU_GATE_STORM", 0.7),
            "p99": _storm_env_f("AMGCL_TPU_GATE_STORM_P99", 1.5)}


def run_storm_gate(candidate, baseline, tol=None):
    """Compare two ``bench_storm`` records round-over-round: max
    sustainable rate (higher is better, min-fraction floor) and p99 at
    the reference offered load (lower is better, max-ratio ceiling,
    comparability-gated on the reference rate). Platform-mismatched
    pairs skip every ratio via ``hw_provenance``/``device_platform`` —
    the multichip-gate rule."""
    tol = tol or storm_tolerances()
    if tol["rate"] <= 0:
        return True, [{"check": "storm", "status": "skipped",
                       "reason": "disabled (AMGCL_TPU_GATE_STORM=0)"}]
    checks = []
    plat_c = _record_platform(candidate)
    plat_b = _record_platform(baseline)
    plat_skip = None
    if plat_c is not None and plat_b is not None and plat_c != plat_b:
        plat_skip = "platform_mismatch: candidate=%s baseline=%s" \
            % (plat_c, plat_b)
    rc = candidate.get("record") or {}
    rb = baseline.get("record") or {}
    mc = (rc.get("knee") or {}).get("max_sustainable_rps")
    mb = (rb.get("knee") or {}).get("max_sustainable_rps")
    if plat_skip is not None:
        checks.append({"check": "storm_max_rps", "status": "skipped",
                       "reason": plat_skip, "candidate": mc,
                       "last_good": mb})
    elif mc is None or mb is None:
        checks.append({"check": "storm_max_rps", "status": "skipped",
                       "candidate": mc, "last_good": mb})
    else:
        floor = mb * tol["rate"]
        checks.append({"check": "storm_max_rps", "candidate": mc,
                       "last_good": mb, "limit": round(floor, 6),
                       "status": "ok" if mc >= floor
                       else "regression"})
    refc = rc.get("reference") or {}
    refb = rb.get("reference") or {}
    pc, pb = refc.get("p99_ms"), refb.get("p99_ms")
    ratec, rateb = refc.get("offered_rps"), refb.get("offered_rps")
    if plat_skip is not None:
        checks.append({"check": "storm_ref_p99", "status": "skipped",
                       "reason": plat_skip, "candidate": pc,
                       "last_good": pb})
    elif pc is None or pb is None or not ratec or not rateb:
        checks.append({"check": "storm_ref_p99", "status": "skipped",
                       "candidate": pc, "last_good": pb})
    elif abs(ratec - rateb) > 0.25 * max(ratec, rateb):
        checks.append({"check": "storm_ref_p99", "status": "skipped",
                       "reason": "reference_rate_mismatch: "
                                 "candidate=%s baseline=%s rps"
                                 % (ratec, rateb),
                       "candidate": pc, "last_good": pb})
    else:
        limit = pb * tol["p99"]
        checks.append({"check": "storm_ref_p99", "candidate": pc,
                       "last_good": pb, "limit": round(limit, 6),
                       "status": "ok" if pc <= limit
                       else "regression"})
    ok = not any(c["status"] == "regression" for c in checks)
    return ok, checks


def _storm_candidate():
    """This round's storm record (``--storm`` writes it):
    ``AMGCL_TPU_GATE_STORM_CANDIDATE`` path override, else
    ``STORM_LATEST.json``. (None, src) when unreadable/absent."""
    path = os.environ.get("AMGCL_TPU_GATE_STORM_CANDIDATE",
                          _STORM_LATEST)
    try:
        with open(path) as f:
            return json.load(f), path
    except Exception:
        return None, path


def _storm_baseline():
    """The previous round's committed storm record — the newest
    ``STORM_r*.json``."""
    m = _load_metrics()
    rows = m.storm_history(_REPO)
    return rows[-1] if rows else None


def storm_gate_record():
    """The storm arm of ``--gate``/``--check``: None when the feature
    is unused (no candidate AND no baseline), a gate sub-record
    otherwise — the multichip-arm contract."""
    tol = storm_tolerances()
    cand, src = _storm_candidate()
    base = _storm_baseline()
    if cand is None and base is None:
        return None
    if cand is None:
        return {"ok": True, "status": "no_candidate",
                "candidate_src": src, "tolerances": tol}
    if base is None:
        return {"ok": True, "status": "no_baseline",
                "candidate_src": src, "tolerances": tol}
    ok, checks = run_storm_gate(cand, base, tol)
    out = {"ok": ok, "candidate_src": src,
           "baseline": base.get("path"), "tolerances": tol,
           "checks": checks}
    if not ok:
        out["failed"] = gate_failures(checks)
    return out


# ===========================================================================
# regression gate: compare a candidate bench record against the last-good
# ===========================================================================

def gate_tolerances():
    """Gate tolerances, env-tunable so a caller can tighten them as
    the bench trajectory stabilizes:

      AMGCL_TPU_GATE_ITERS  — allowed ABSOLUTE iteration increase (def 2)
      AMGCL_TPU_GATE_TIME   — allowed solve-time ratio (default 1.25:
                              chained timings still jitter ~10-15% across
                              chip sessions, see BENCH_r0*.json)
      AMGCL_TPU_GATE_BYTES  — allowed peak-ledger-bytes ratio (def 1.10)
      AMGCL_TPU_GATE_THROUGHPUT — minimum allowed fraction of the
                              baseline's B=32 serving throughput
                              (default 0.75: the candidate regresses
                              when its b32 solves/sec drop below 75% of
                              last-good); skipped across
                              device_platform mismatches like the time
                              ratio
      AMGCL_TPU_GATE_HEALTH — 1 (default): fail when a previously-clean
                              record's candidate trips any health guard
                              (breakdown/NaN/stagnation/divergence);
                              0 disables the health check
      AMGCL_TPU_GATE_SETUP  — minimum allowed fraction of the baseline's
                              setup_vs_baseline (default 0.7: higher is
                              better, the candidate regresses when its
                              setup speed ratio drops below 70% of
                              last-good); rebuild_s is gated alongside
                              at the AMGCL_TPU_GATE_TIME ratio (lower
                              is better). 0 disables both setup checks;
                              both skip across device_platform
                              mismatches like the time ratio.
      AMGCL_TPU_GATE_FARM   — minimum allowed fraction of the baseline's
                              multi-tenant farm throughput (bench_farm
                              agg_sps; default 0.7 — eviction traffic
                              jitters more than the single-operator
                              path); platform-mismatch-skipped like the
                              other time gates. The same check also
                              fails a candidate whose readmissions left
                              the rebuild path (rebuild_only_readmission
                              false) regardless of speed.
      AMGCL_TPU_GATE_MEMDRIFT — allowed measured-vs-ledger drift-ratio
                              growth for the memwatch record (default
                              1.25: the candidate's |drift−1| may be at
                              most 1.25× the baseline's, floored at the
                              declared join tolerance so a clean
                              baseline does not gate noise); the leak
                              check itself is absolute — any leaked
                              owner bytes fail the round regardless.
      AMGCL_TPU_GATE_XRAY   — allowed predicted-vs-measured divergence
                              of the executed-reorder gain (the
                              ``bench --xray`` join the worker's xray
                              stage records; default 0.25: the
                              measured/predicted ratio must stay within
                              25% of 1). Skipped across device_platform
                              mismatches like the time ratio, and for
                              CPU-fallback joins that could only match
                              end-to-end (informational). 0 disables.
    """
    def _f(name, default):
        try:
            return float(os.environ.get(name, default))
        except ValueError:
            return float(default)

    return {"iters": _f("AMGCL_TPU_GATE_ITERS", 2),
            "time": _f("AMGCL_TPU_GATE_TIME", 1.25),
            "bytes": _f("AMGCL_TPU_GATE_BYTES", 1.10),
            "throughput": _f("AMGCL_TPU_GATE_THROUGHPUT", 0.75),
            "setup": _f("AMGCL_TPU_GATE_SETUP", 0.7),
            "farm": _f("AMGCL_TPU_GATE_FARM", 0.7),
            "memdrift": _f("AMGCL_TPU_GATE_MEMDRIFT", 1.25),
            "xray": _f("AMGCL_TPU_GATE_XRAY", 0.25)}


def _record_health_flags(rec):
    """Tripped health-guard names of a bench record (sorted list), or
    None when the record predates health telemetry (comparison
    skipped)."""
    h = rec.get("health")
    if not isinstance(h, dict):
        return None
    flags = h.get("flags")
    if flags is None:
        ok = h.get("ok")
        return None if ok is None else ([] if ok else ["unhealthy"])
    return sorted(str(f) for f in flags)


def _record_ledger_bytes(rec):
    """Peak hierarchy bytes of a bench record: the ledger summary when the
    record carries one, else the hierarchy stats' total (older records),
    else None (comparison skipped)."""
    led = rec.get("ledger") or {}
    v = led.get("hierarchy_bytes")
    if v is None:
        v = (rec.get("hierarchy") or {}).get("bytes")
    return v


def _record_platform(rec):
    """Device platform of a bench/scaling record — the ONE place every
    gate's platform-mismatch skip reads. Resolution order: the
    top-level field, the hardware-provenance stamp (newer records carry
    ``provenance.device_platform`` uniformly), then the CPU-fallback
    marker for records predating the split."""
    p = rec.get("device_platform")
    if p is None:
        p = (rec.get("provenance") or {}).get("device_platform")
    if p is None and rec.get("fallback"):
        return "cpu"
    return p


def run_gate(candidate, last_good, tol=None):
    """Compare ``candidate`` against ``last_good`` under the tolerances.

    Returns (ok, checks): one check row per metric — iterations (absolute
    slack), solve time and peak ledger bytes (ratios), plus the health
    check (tripped-guard count must not exceed the baseline's; env
    AMGCL_TPU_GATE_HEALTH=0 opts out). A metric missing on either side
    is 'skipped', not a regression (pre-ledger records carry no byte
    accounting, pre-health records no guard decode).

    The time/bytes ratios only compare records from the SAME
    ``device_platform``: a CPU-fallback candidate scored against a TPU
    last-good (or vice versa) is a platform change, not a perf
    regression — those checks report 'skipped' with the mismatch
    (BENCH_r05 compared a CPU 2.10 s run against a TPU 0.069 s baseline
    and the ratio meant nothing). Iteration count and health flags stay
    compared — the math is platform-independent."""
    tol = tol or gate_tolerances()
    checks = []

    def check(name, cand, base, limit, skip_reason=None):
        if skip_reason is not None:
            checks.append({"check": name, "status": "skipped",
                           "reason": skip_reason,
                           "candidate": cand, "last_good": base})
            return
        if cand is None or base is None:
            checks.append({"check": name, "status": "skipped",
                           "candidate": cand, "last_good": base})
            return
        checks.append({"check": name, "candidate": cand,
                       "last_good": base, "limit": round(limit, 6),
                       "status": "ok" if cand <= limit else "regression"})

    plat_c, plat_b = _record_platform(candidate), _record_platform(last_good)
    plat_skip = None
    if plat_c is not None and plat_b is not None and plat_c != plat_b:
        plat_skip = "platform_mismatch: candidate=%s last_good=%s" \
            % (plat_c, plat_b)
    it0 = last_good.get("iters")
    check("iters", candidate.get("iters"), it0,
          it0 + tol["iters"] if it0 is not None else 0)
    t0 = last_good.get("value")
    check("solve_time", candidate.get("value"), t0,
          t0 * tol["time"] if t0 is not None else 0,
          skip_reason=plat_skip)
    b0 = _record_ledger_bytes(last_good)
    check("ledger_bytes", _record_ledger_bytes(candidate), b0,
          b0 * tol["bytes"] if b0 is not None else 0,
          skip_reason=plat_skip)
    # serving throughput (bench_throughput / the worker's throughput
    # stage): HIGHER is better, so the check inverts — regression when
    # the candidate's B=32 solves/sec fall below the tolerance fraction
    # of the baseline's. Skipped across platforms and for records that
    # predate the metric.
    tp_c = (candidate.get("throughput") or {}).get("b32_sps")
    tp_b = (last_good.get("throughput") or {}).get("b32_sps")
    if tp_c is None and tp_b is None:
        pass          # neither record carries the metric: no check row
    elif plat_skip is not None:
        checks.append({"check": "throughput_b32", "status": "skipped",
                       "reason": plat_skip,
                       "candidate": tp_c, "last_good": tp_b})
    elif tp_c is None or tp_b is None:
        checks.append({"check": "throughput_b32", "status": "skipped",
                       "candidate": tp_c, "last_good": tp_b})
    else:
        floor = tp_b * tol["throughput"]
        checks.append({"check": "throughput_b32", "candidate": tp_c,
                       "last_good": tp_b, "limit": round(floor, 6),
                       "status": "ok" if tp_c >= floor
                       else "regression"})
    # multi-tenant farm throughput (bench_farm / the worker's farm
    # stage): higher-is-better like throughput_b32, same platform and
    # pre-metric skips. A candidate whose readmissions left the rebuild
    # path regresses outright — speed cannot buy back a broken registry.
    fm_c = (candidate.get("farm") or {}).get("agg_sps")
    fm_b = (last_good.get("farm") or {}).get("agg_sps")
    if fm_c is None and fm_b is None:
        pass          # neither record carries the metric: no check row
    elif plat_skip is not None:
        checks.append({"check": "farm_sps", "status": "skipped",
                       "reason": plat_skip,
                       "candidate": fm_c, "last_good": fm_b})
    elif fm_c is None or fm_b is None:
        checks.append({"check": "farm_sps", "status": "skipped",
                       "candidate": fm_c, "last_good": fm_b})
    else:
        floor = fm_b * tol.get("farm", 0.7)
        rebuild_ok = (candidate.get("farm") or {}).get(
            "rebuild_only_readmission", True)
        row = {"check": "farm_sps", "candidate": fm_c,
               "last_good": fm_b, "limit": round(floor, 6),
               "status": "ok" if (fm_c >= floor and rebuild_ok)
               else "regression"}
        if not rebuild_ok:
            row["reason"] = "readmission paid a fresh setup " \
                "(rebuild_only_readmission false)"
        checks.append(row)
    # setup speed + same-sparsity rebuild (ROADMAP item 2): both skip on
    # platform mismatch and on records predating the metrics.
    # setup_vs_baseline is higher-is-better (like throughput), the
    # rebuild time lower-is-better (like solve time).
    if tol.get("setup", 0) > 0:
        sv_c, sv_b = candidate.get("setup_vs_baseline"), \
            last_good.get("setup_vs_baseline")
        if sv_c is not None or sv_b is not None:
            if plat_skip is not None or sv_c is None or sv_b is None:
                checks.append({"check": "setup_vs_baseline",
                               "status": "skipped",
                               "reason": plat_skip,
                               "candidate": sv_c, "last_good": sv_b})
            else:
                floor = sv_b * tol["setup"]
                checks.append({
                    "check": "setup_vs_baseline", "candidate": sv_c,
                    "last_good": sv_b, "limit": round(floor, 6),
                    "status": "ok" if sv_c >= floor else "regression"})
        rb_c, rb_b = candidate.get("rebuild_s"), last_good.get("rebuild_s")
        if rb_c is not None or rb_b is not None:
            check("rebuild_s", rb_c, rb_b,
                  rb_b * max(tol["time"], 1.0) if rb_b is not None else 0,
                  skip_reason=plat_skip)
    # measured-vs-ledger drift (the memwatch record, ISSUE 18):
    # |drift_ratio − 1| may grow at most tol["memdrift"]× over the
    # baseline's, floored at the declared join tolerance so a clean
    # baseline (drift 1.0) does not gate measurement noise. Platform-
    # skipped: TPU padding/layout legitimately moves measured away
    # from the analytic model.
    md_c = (candidate.get("memwatch") or {}).get("drift_ratio")
    md_b = (last_good.get("memwatch") or {}).get("drift_ratio")
    if md_c is None and md_b is None:
        pass          # neither record carries the metric: no check row
    elif plat_skip is not None:
        checks.append({"check": "memwatch_drift", "status": "skipped",
                       "reason": plat_skip,
                       "candidate": md_c, "last_good": md_b})
    elif md_c is None or md_b is None:
        checks.append({"check": "memwatch_drift", "status": "skipped",
                       "candidate": md_c, "last_good": md_b})
    else:
        try:
            from amgcl_tpu.telemetry.memwatch import declared_tolerance
            floor_tol = declared_tolerance()
        except Exception:
            floor_tol = 0.25
        limit = max(abs(md_b - 1.0) * tol.get("memdrift", 1.25),
                    floor_tol)
        checks.append({"check": "memwatch_drift",
                       "candidate": round(abs(md_c - 1.0), 6),
                       "last_good": round(abs(md_b - 1.0), 6),
                       "limit": round(limit, 6),
                       "status": "ok" if abs(md_c - 1.0) <= limit
                       else "regression"})
    # predicted-vs-measured reorder gain (the bench --xray join, ISSUE
    # 20): the candidate's measured gain must stay within tol["xray"]
    # of its OWN prediction — a drifting join means the executed
    # reorder no longer delivers what the advisor priced, i.e. either
    # the cost model or the execution seam regressed. Checked against
    # the candidate alone (the ratio is self-relative); the last_good
    # side only decides whether the metric exists for this trajectory.
    xtol = tol.get("xray", 0.25)
    xj_c = (candidate.get("xray") or {}).get("join") or {}
    xj_b = (last_good.get("xray") or {}).get("join") or {}
    xr_c, xr_b = xj_c.get("ratio"), xj_b.get("ratio")
    if (xr_c is None and xr_b is None) or xtol <= 0:
        pass          # neither record carries the join: no check row
    elif plat_skip is not None:
        checks.append({"check": "xray_join", "status": "skipped",
                       "reason": plat_skip,
                       "candidate": xr_c, "last_good": xr_b})
    elif xr_c is None:
        checks.append({"check": "xray_join", "status": "skipped",
                       "candidate": xr_c, "last_good": xr_b})
    elif xj_c.get("informational") and xj_c.get("fallback"):
        checks.append({"check": "xray_join", "status": "skipped",
                       "reason": "cpu-fallback end-to-end join is "
                       "informational (format winners differ between "
                       "the orderings, so time does not track the "
                       "byte model off-TPU)",
                       "candidate": xr_c, "last_good": xr_b})
    else:
        checks.append({"check": "xray_join",
                       "candidate": round(abs(xr_c - 1.0), 6),
                       "last_good": round(abs(xr_b - 1.0), 6)
                       if xr_b is not None else None,
                       "limit": round(xtol, 6),
                       "status": "ok" if abs(xr_c - 1.0) <= xtol
                       else "regression"})
    if os.environ.get("AMGCL_TPU_GATE_HEALTH", "1") != "0":
        # flag IDENTITIES, not counts: any guard the baseline did not
        # trip is a regression (a candidate swapping a warning-level
        # stagnation for a fatal breakdown must not pass on 1 <= 1)
        h0 = _record_health_flags(last_good)
        hc = _record_health_flags(candidate)
        if h0 is None or hc is None:
            checks.append({"check": "health_flags", "status": "skipped",
                           "candidate": hc, "last_good": h0})
        else:
            new = sorted(set(hc) - set(h0))
            checks.append({"check": "health_flags", "candidate": hc,
                           "last_good": h0, "new_flags": new,
                           "status": "ok" if not new else "regression"})
    ok = not any(c["status"] == "regression" for c in checks)
    return ok, checks


def _gate_last_good():
    """Gate baseline record: AMGCL_TPU_GATE_LAST_GOOD overrides the repo
    BENCH_LAST_GOOD.json (tests and ad-hoc comparisons)."""
    path = os.environ.get("AMGCL_TPU_GATE_LAST_GOOD", _LAST_GOOD_PATH)
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return None


def main_gate(args=None):
    """``bench.py --gate [candidate.json]``: exit 0 when the candidate
    (default: the last-good record itself — the self-consistency run CI
    gets) stays within tolerances of the last-good record, 1 on a
    regression, 2 on an unreadable candidate. Emits ONE JSONL record
    either way."""
    tol = gate_tolerances()
    lg = _gate_last_good()
    cand_src = "last_good"
    cand = lg
    if args:
        cand_src = args[0]
        try:
            with open(cand_src) as f:
                cand = json.load(f)
        except Exception as e:
            rec = {"event": "bench_gate", "ok": False,
                   "error": "unreadable candidate %r: %r" % (cand_src, e)}
            _stdout_sink.emit(rec)
            _sink.emit(dict(rec))
            return 2
    if lg is None or cand is None:
        rec = {"event": "bench_gate", "ok": True, "status": "no_baseline",
               "tolerances": tol}
        _stdout_sink.emit(rec)
        _sink.emit(dict(rec))
        return 0
    ok, checks = run_gate(cand, lg, tol)
    rec = {"event": "bench_gate", "ok": ok, "candidate_src": cand_src,
           "tolerances": tol, "checks": checks, "commit": _git_head()}
    if not ok:
        # failed checks with their measured candidate/baseline pairs in
        # one place, plus the automatic cross-run attribution — the
        # post-hoc `--why` answer rides the failure record itself
        rec["failed"] = gate_failures(checks)
        rec["attribution"] = gate_attribution(cand, lg)
    # multichip arm: this round's --scaling record vs the previous
    # round's committed MULTICHIP_r*.json (AMGCL_TPU_GATE_MULTICHIP)
    mc = multichip_gate_record()
    if mc is not None:
        rec["multichip"] = mc
        ok = ok and mc["ok"]
        rec["ok"] = ok
    # storm arm: this round's --storm record vs the previous round's
    # committed STORM_r*.json (AMGCL_TPU_GATE_STORM)
    st = storm_gate_record()
    if st is not None:
        rec["storm"] = st
        ok = ok and st["ok"]
        rec["ok"] = ok
    _stdout_sink.emit(rec)
    _sink.emit(dict(rec))
    return 0 if ok else 1


def gate_failures(checks):
    """The regression rows of a gate run, with the measured
    candidate/baseline pair each (so post-hoc tooling never re-derives
    them from the tolerance and the limit)."""
    return [{"check": c["check"], "candidate": c.get("candidate"),
             "baseline": c.get("last_good"), "limit": c.get("limit"),
             **({"reason": c["reason"]} if c.get("reason") else {})}
            for c in checks if c.get("status") == "regression"]


def gate_attribution(cand, base):
    """Automatic cross-run attribution of a gate failure: the
    ``telemetry/diff.py`` record of candidate-vs-baseline, stage rows
    bounded for the JSONL event. Never raises — a broken diff must not
    mask the gate verdict."""
    try:
        dm = _load_diff()
        d = dm.compact(dm.diff(base, cand))
        print(dm.format_diff(d), file=sys.stderr)
        return d
    except Exception as e:     # noqa: BLE001
        return {"error": repr(e)[:200]}


# ===========================================================================
# why: cross-run regression attribution (stdlib-only, telemetry/diff.py)
# ===========================================================================

def main_why(args=None):
    """``bench.py --why A.json B.json``: structured attribution of the
    delta between two records of the same kind — A is the baseline /
    older run, B the candidate / newer one. Wraps ``telemetry/diff.py``
    (stage join over the ledger stage keys + roofline rows, exact
    iterations-vs-per-iteration wall split, compile/comm call-outs).
    Exit 2 on unreadable/mismatched inputs; exit 0 otherwise — the
    attribution is a report, the GATE is the verdict."""
    args = [a for a in (args or []) if not a.startswith("-")]
    if len(args) < 2:
        print("usage: bench.py --why A.json B.json", file=sys.stderr)
        return 2
    recs = []
    for path in args[:2]:
        try:
            with open(path) as f:
                recs.append(json.load(f))
        except Exception as e:
            print("unreadable record %r: %r" % (path, e),
                  file=sys.stderr)
            return 2
    dm = _load_diff()
    d = dm.diff(recs[0], recs[1])
    print(dm.format_diff(d))
    rec = {"event": "bench_why", "a": args[0], "b": args[1],
           "diff": dm.compact(d), "commit": _git_head()}
    _stdout_sink.emit(rec)
    _sink.emit(dict(rec))
    return 2 if d.get("error") else 0


# ===========================================================================
# trend: cross-round trajectory + percentile rollups (stdlib-only)
# ===========================================================================

def trend_summary(metrics_mod=None):
    """The cross-PR trend over the committed ``BENCH_r*.json`` rounds:
    {"rows": per-round headline fields, "rollups": p50/p90/p99 per
    column}. Pre-ledger/pre-roofline rounds contribute gaps, never
    errors."""
    m = metrics_mod or _load_metrics()
    history = m.bench_history(_REPO)
    rows = m.trend(history)
    # the raw records ride along (underscored: not for the JSONL
    # record) so --trend's why-attribution reuses them instead of
    # re-reading every BENCH_r*.json from disk
    return {"rows": rows, "rollups": m.trend_rollups(rows),
            "_history": history}


def _annotate_trend_why(rows, history):
    """Attach the ``why`` column to trend rows IN PLACE: for each round
    whose solve time regressed beyond the gate's time tolerance against
    the previous round (same platform), the top attributed contributor
    of ``telemetry/diff.py``; None (rendered '-') everywhere else,
    including rounds whose predecessor predates per-stage data (the
    label then degrades to the coarse iterations/per-iteration bucket
    the wall split can still name)."""
    dm = _load_diff()
    limit = gate_tolerances()["time"]
    prev_row = prev_rec = None
    for rec, row in zip(history, rows):
        row.setdefault("why", None)
        if prev_row is not None:
            t0, t1 = prev_row.get("solve_s"), row.get("solve_s")
            if t0 and t1 and t1 > t0 * limit:
                row["why"] = dm.why(prev_rec, rec)
        prev_row, prev_rec = row, rec


def main_trend(args=None):
    """``bench.py --trend [sink.jsonl]``: print the cross-round table
    (BENCH_r01.. on disk) + rollups, optionally aggregate a telemetry
    JSONL file's solve/bench events too; ``--prom PATH`` writes the
    rollups as Prometheus exposition text. Emits ONE JSONL record."""
    m = _load_metrics()
    args = list(args or [])
    prom_path = None
    if "--prom" in args:
        i = args.index("--prom")
        prom_path = args[i + 1] if i + 1 < len(args) else None
        del args[i:i + 2]
    summ = trend_summary(m)
    # the why column: each round-over-round regression beyond the
    # gate's time tolerance gets the top attributed stage from
    # telemetry/diff.py ('-' gap when the older record predates
    # per-stage data or the platforms differ)
    try:
        _annotate_trend_why(summ["rows"], summ["_history"])
    except Exception:       # noqa: BLE001 — attribution is a bonus
        pass                # column; the table must still render
    print(m.format_trend(summ["rows"],
                         m.TREND_FIELDS + [("why", "why")]))
    rollups = dict(summ["rollups"])
    rec = {"event": "bench_trend", "rows": summ["rows"],
           "rollups": summ["rollups"], "commit": _git_head()}
    # multichip trajectory alongside the BENCH_r* table: structured
    # rounds carry efficiency/comm-fraction/imbalance, legacy dryrun
    # rounds degrade to device-count-only rows with gaps
    mc_hist = m.multichip_history(_REPO)
    if mc_hist:
        mc_rows = m.trend(mc_hist, m.MULTICHIP_TREND_FIELDS)
        print("\nmultichip trajectory (MULTICHIP_r*.json):")
        print(m.format_trend(mc_rows, m.MULTICHIP_TREND_FIELDS))
        rec["multichip_rows"] = mc_rows
        mc_roll = m.trend_rollups(mc_rows, m.MULTICHIP_TREND_FIELDS)
        for name, r in mc_roll.items():
            rollups["multichip_" + name] = r
    # storm trajectory: max sustainable rate + reference-load p99 per
    # committed STORM_r*.json round
    st_hist = m.storm_history(_REPO)
    if st_hist:
        st_rows = m.trend(st_hist, m.STORM_TREND_FIELDS)
        print("\nstorm trajectory (STORM_r*.json):")
        print(m.format_trend(st_rows, m.STORM_TREND_FIELDS))
        rec["storm_rows"] = st_rows
        st_roll = m.trend_rollups(st_rows, m.STORM_TREND_FIELDS)
        for name, r in st_roll.items():
            rollups["storm_" + name] = r
    if args:
        sink_records = m.iter_jsonl(args[0])
        ev_roll = m.rollup_events(sink_records)
        rec["sink"] = {"path": args[0], "records": len(sink_records),
                       "rollups": ev_roll}
        rollups.update(ev_roll)
        if ev_roll:
            print("\nsink rollups (%s, %d records):"
                  % (args[0], len(sink_records)))
            for name in sorted(ev_roll):
                r = ev_roll[name]
                print("  %-28s n=%-4d p50=%-10.4g p90=%-10.4g "
                      "p99=%.4g" % (name, r["count"], r["p50"],
                                    r["p90"], r["p99"]))
    if prom_path:
        with open(prom_path, "w") as f:
            f.write(m.prometheus_text(rollups))
        print("\nprometheus text written to %s" % prom_path)
    _stdout_sink.emit(rec)
    _sink.emit(dict(rec))
    return 0


# ===========================================================================
# vecbench: fused vector kernels vs their composed counterparts
# ===========================================================================

def main_vecbench(args=None):
    """``bench.py --vecbench [n ...]``: time the fused vector-algebra
    primitives (ops/fused_vec.py) against the composed axpby+dot
    reference per vector size and emit ONE ``bench_vecbench`` JSONL
    record — so the fusion win is tracked round-over-round like the
    solve metric. Each arm chains ``reps`` data-dependent applications
    inside one jitted scan (both carries thread every output, so
    neither arm can dead-code its updates) and reports median
    per-application microseconds."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from amgcl_tpu.ops import fused_vec as fv

    sizes = [int(a) for a in (args or []) if a.isdigit()]
    on_tpu = jax.default_backend() == "tpu"
    if not sizes:
        sizes = [1 << k for k in ((16, 18, 20, 22) if on_tpu
                                  else (14, 16, 18))]
    reps = 32 if on_tpu else 8
    repeats = 5

    def timeit(step, init, ops):
        # the carry AND the operand vectors ride as jit ARGUMENTS: a
        # closed-over init would let XLA constant-fold the whole chain
        # (measuring nothing), and closure operands embed megabytes of
        # MLIR constants (see _timed_chain)
        def many(st, ops):
            out, _ = lax.scan(lambda c, _: (step(c, ops), None),
                              step(st, ops), None, length=reps - 1)
            return out[-1]
        f = jax.jit(many)
        jax.block_until_ready(f(init, ops))     # compile + warm
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(f(init, ops))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) / reps

    rows = []
    for n in sizes:
        rng = np.random.RandomState(7)
        p, q, x, r = (jnp.asarray(rng.standard_normal(n), jnp.float32)
                      for _ in range(4))
        alpha = jnp.float32(0.37)
        mode = fv._pallas_mode(x)
        path = "xla" if mode is None else (
            "pallas-interpret" if mode else "pallas")

        # -- xr_update: the CG tail -------------------------------------
        def xr_fused(st, ops):
            xc, rc, rr = st
            pp, qq = ops
            a = alpha * (1 + 0 * rr)    # data-depend on the prior dot
            return fv.xr_update(a, pp, qq, xc, rc)

        def xr_composed(st, ops):
            xc, rc, rr = st
            pp, qq = ops
            a = alpha * (1 + 0 * rr)
            xn = xc + a * pp
            rn = rc - a * qq
            return xn, rn, jnp.vdot(rn, rn)

        init_xr = (x, r, jnp.float32(0))
        t_f = timeit(xr_fused, init_xr, (p, q))
        t_c = timeit(xr_composed, init_xr, (p, q))

        # -- axpby_dot --------------------------------------------------
        def ax_fused(st, ops):
            z, zz = st
            (pp,) = ops
            a = alpha * (1 + 0 * zz)
            return fv.axpby_dot(a, pp, 0.5, z)

        def ax_composed(st, ops):
            z, zz = st
            (pp,) = ops
            a = alpha * (1 + 0 * zz)
            zn = a * pp + 0.5 * z
            return zn, jnp.vdot(zn, zn)

        init_ax = (x, jnp.float32(0))
        a_f = timeit(ax_fused, init_ax, (p,))
        a_c = timeit(ax_composed, init_ax, (p,))

        # -- stacked (n, B) tier: one fused pass retires B columns ------
        Bb = 8
        Pb, Qb, Xb, Rb = (jnp.asarray(
            rng.standard_normal((n, Bb)), jnp.float32) for _ in range(4))

        def xr_batched(st, ops):
            xc, rc, rr = st
            pp, qq = ops
            a = alpha * (1 + 0 * rr)    # (Bb,) per-column scalars
            return fv.xr_update(a, pp, qq, xc, rc)

        init_b = (Xb, Rb, jnp.zeros(Bb, jnp.float32))
        t_b = timeit(xr_batched, init_b, (Pb, Qb))
        rows.append({
            "n": n, "path": path,
            "xr_b8_us": round(t_b * 1e6, 3),
            "xr_b8_per_rhs_us": round(t_b / Bb * 1e6, 3),
            # per-rhs win of the stacked pass vs B single fused passes
            "xr_b8_vs_single": round(t_f / max(t_b / Bb, 1e-12), 3),
            "xr_update_us": round(t_f * 1e6, 3),
            "xr_composed_us": round(t_c * 1e6, 3),
            "xr_speedup": round(t_c / max(t_f, 1e-12), 3),
            "axpby_dot_us": round(a_f * 1e6, 3),
            "axpby_composed_us": round(a_c * 1e6, 3),
            "axpby_speedup": round(a_c / max(a_f, 1e-12), 3)})
        print("n=%-9d %-17s xr %8.2f vs %8.2f us (%.2fx)   axpby_dot "
              "%8.2f vs %8.2f us (%.2fx)"
              % (n, path, rows[-1]["xr_update_us"],
                 rows[-1]["xr_composed_us"], rows[-1]["xr_speedup"],
                 rows[-1]["axpby_dot_us"], rows[-1]["axpby_composed_us"],
                 rows[-1]["axpby_speedup"]))
    dev0 = jax.devices()[0]
    from amgcl_tpu.telemetry.comm import hw_provenance
    rec = {"event": "bench_vecbench", "rows": rows,
           "fused_enabled": fv.fused_vec_enabled(),
           "device": str(dev0), "device_platform": dev0.platform,
           "device_kind": getattr(dev0, "device_kind", None),
           "provenance": hw_provenance(),
           "commit": _git_head()}
    _stdout_sink.emit(rec)
    _sink.emit(dict(rec))
    return 0


# ===========================================================================
# tier-1 check: run the ROADMAP pytest line, emit DOTS_PASSED as JSONL
# ===========================================================================

_DOTS_RE = re.compile(r"^[.FEsx]+( *\[ *[0-9]+%\])?$")

# the ROADMAP tier-1 invocation, minus the shell plumbing
_TIER1_ARGS = ["-m", "pytest", "-q", "-m", "not slow",
               "--continue-on-collection-errors", "-p", "no:cacheprovider",
               "-p", "no:xdist", "-p", "no:randomly"]


def count_dots(text: str) -> int:
    """DOTS_PASSED: '.' characters on pytest -q progress lines — the same
    grep the ROADMAP tier-1 line applies to its log (char class kept
    identical on purpose, quirks included, so the two metrics never
    disagree)."""
    return sum(line.count(".") for line in text.splitlines()
               if _DOTS_RE.match(line.strip()))


def _xray_record(n, bw, local, seed):
    """Build the ``bench_xray`` record for one permuted-banded operator
    (the measurement body shared by ``--xray`` and the bench worker's
    xray stage — one copy of the chained-SpMV protocol, so the gate's
    ``xray_join`` check always scores the same experiment the CLI
    prints)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.telemetry import structure as _structure
    from amgcl_tpu.telemetry.comm import hw_provenance
    from amgcl_tpu.ops import device as dev
    from amgcl_tpu.utils.adapters import cuthill_mckee, permute

    A, _A0, _perm = _structure.permuted_banded(n, bw=bw, seed=seed,
                                               local=local or None)
    rcm = cuthill_mckee(A)
    B = permute(A, rcm)
    on_tpu = jax.default_backend() == "tpu"
    # the prediction: exactly the advisor row cli --xray would print
    # for this operator (candidate tables identity vs RCM)
    adv = _structure.advise(A, variants=("rcm",), on_tpu=on_tpu)
    best = adv.get("best") or {}
    best_fmt = best.get("format")
    predicted = (best.get("per_format") or {}).get(best_fmt)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(n).astype(np.float32))

    def build(mat, fmt):
        """Device matrix for one candidate format, None when the
        format declines this structure (exactly what the X-ray table
        records as ineligible)."""
        try:
            if fmt == "ell":
                return dev.csr_to_ell(mat)
            if fmt == "dia":
                # the DIA SpMV unrolls one fused multiply-add per
                # diagonal — thousands of diagonals (the scrambled
                # identity ordering) would build an absurd XLA graph,
                # the same reason auto rejects it
                if len(dev._dia_offsets(mat)) > 512:
                    return None
                return dev.csr_to_dia(mat)
            if fmt == "well":
                from amgcl_tpu.ops.unstructured import \
                    csr_to_windowed_ell
                return csr_to_windowed_ell(mat, max_win_bytes=4 << 20)
            if fmt == "dwin":
                from amgcl_tpu.ops.densewin import csr_to_dense_window
                return csr_to_dense_window(mat)
        except Exception:
            return None

    chain = 16

    def time_spmv(M, reps=7):
        """Per-SpMV seconds, measured as a CHAIN of data-dependent
        applications inside one dispatch — a single spmv at these
        sizes is µs-scale and would drown in per-call dispatch
        overhead (the bench _timed_chain lesson). Min-of-reps: the
        joined quantity is a RATIO of two such measurements, and on a
        shared host the best case is the one uncontaminated by
        interference (median would fold ambient load into whichever
        side ran during a busy window)."""
        if M is None:
            return None

        def chained(v):
            for _ in range(chain):       # square operator: y feeds x
                v = dev.spmv(M, v)
            return v

        f = jax.jit(chained)
        jax.block_until_ready(f(x))          # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            ts.append(time.perf_counter() - t0)
        return float(min(ts)) / chain

    rows = []
    best_id = best_rcm = None
    matched = {}
    per_format_pred = best.get("per_format") or {}
    for fmt in ("ell", "dia", "well", "dwin"):
        t_id = time_spmv(build(A, fmt))
        t_rcm = time_spmv(build(B, fmt))
        row = {"format": fmt,
               "t_identity_s": round(t_id, 7) if t_id else None,
               "t_rcm_s": round(t_rcm, 7) if t_rcm else None}
        if t_id and t_rcm:
            row["gain"] = round(t_id / t_rcm, 4)
            matched[fmt] = row["gain"]
            if per_format_pred.get(fmt):
                row["predicted_gain"] = per_format_pred[fmt]
        rows.append(row)
        if t_id is not None and (best_id is None or t_id < best_id):
            best_id = t_id
        if t_rcm is not None and (best_rcm is None or t_rcm < best_rcm):
            best_rcm = t_rcm
    measured = matched.get(best_fmt)
    e2e = round(best_id / best_rcm, 4) if best_id and best_rcm else None
    prov = hw_provenance()
    join = {"format": best_fmt, "predicted_gain": predicted,
            "measured_gain": measured,
            "informational": prov.get("platform_tag") != "ici"}
    if measured is None and e2e is not None:
        # the matched pair could not be built on one side — fall back
        # to the cross-format end-to-end gain, flagged as such
        join["fallback"] = "end_to_end"
        measured = e2e
        join["measured_gain"] = measured
        predicted = best.get("gain")
        join["predicted_gain"] = predicted
    if predicted and measured:
        join["ratio"] = round(measured / predicted, 4)
        join["within_25pct"] = bool(abs(join["ratio"] - 1.0) <= 0.25)
    rec = {"event": "bench_xray", "metric": "xray_reorder_gain",
           "value": measured, "unit": "x", "n": n, "bw": bw,
           "local": local, "seed": seed, "provenance": prov,
           "device_platform": prov.get("device_platform"),
           "advisor": {"predicted_gain": best.get("gain"),
                       "predicted_format_gain": predicted,
                       "best_format": best_fmt,
                       "densify": best.get("densify")},
           "end_to_end": {"measured_gain": e2e,
                          "predicted_gain": best.get("gain")},
           "formats": rows, "join": join, "commit": _git_head()}
    return rec


def main_xray(args=None):
    """``bench.py --xray``: the advisor-validation microbenchmark
    (ISSUE 14 satellite) — ONE unstructured operator (the
    permuted-banded fixture from telemetry/structure.py: a band
    scrambled by a block-local symmetric permutation, the matrix class
    the reorder advisor exists for), SpMV measured per candidate
    device format under the identity ordering and under RCM, joined
    against the X-ray's PREDICTED reorder gain. The headline join is
    MECHANISM-MATCHED: the advisor's winning format measured on both
    orderings (same packing, so time tracks the byte model on any
    platform — DIA's shifted multiply-adds scale with ndiags whether
    the bottleneck is HBM or cache); the cross-format end-to-end gain
    (best identity format vs best reordered format) rides along as
    ``end_to_end``. Emits ONE ``bench_xray`` record (platform-stamped
    via hw_provenance; informational on the CPU fallback — the
    cross-format mapping is only roofline-faithful where the SpMV is
    HBM-bound). Exit 1 only when nothing could be measured."""
    n = int(os.environ.get("AMGCL_TPU_XRAY_N", "4096"))
    # bw 16 keeps the RCM-recovered band at ~33 diagonals — still
    # inside auto's CPU max_diags=40 so the advisor genuinely picks
    # DIA, and in the same XLA lowering regime as the scrambled
    # identity's ~160 (below ~16 diagonals the whole DIA chain fuses
    # into one pass and the per-diagonal cost drops ~40%, which would
    # bias the matched join)
    bw = int(os.environ.get("AMGCL_TPU_XRAY_BW", "16"))
    local = int(os.environ.get("AMGCL_TPU_XRAY_LOCAL", "32"))
    rec = _xray_record(n, bw, local, seed=7)
    _stdout_sink.emit(rec)
    _sink.emit(dict(rec))
    return 0 if rec["value"] is not None else 1


def main_check(targets=None):
    """Run the tier-1 pytest line in a subprocess (CPU-forced, like the
    driver) and emit ONE JSONL record carrying DOTS_PASSED, the return
    code and the duration — to stdout and the process-global sink. The
    bench regression gate rides along (AMGCL_TPU_GATE_IN_CHECK=0 opts
    out): the record gains a ``gate`` field and a gate regression fails
    the check, so CI inherits the gate for free. The gate candidate
    defaults to the last-good record itself (a self-consistency pass);
    point AMGCL_TPU_GATE_CANDIDATE at a fresh bench record to score a
    new run.

    ``targets``: optional pytest paths/flags replacing the default
    ``tests/`` target (lets callers check a subset quickly)."""
    timeout = float(os.environ.get("AMGCL_TPU_CHECK_TIMEOUT", "870"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable] + _TIER1_ARGS \
        + (list(targets) if targets else ["tests/"])
    t0 = time.time()
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout, cwd=_REPO, env=env)
        rc, text = r.returncode, r.stdout + "\n" + r.stderr
        err = None
    except subprocess.TimeoutExpired as e:
        rc = -1
        text = (e.stdout or b"").decode("utf-8", "replace") if isinstance(
            e.stdout, bytes) else (e.stdout or "")
        err = "pytest timed out after %.0fs" % timeout
    rec = {"event": "tier1_check", "metric": "tier1_dots_passed",
           "value": count_dots(text), "unit": "tests",
           "rc": rc, "duration_s": round(time.time() - t0, 1),
           "commit": _git_head()}
    if err:
        rec["error"] = err
    gate_ok = True
    if os.environ.get("AMGCL_TPU_GATE_IN_CHECK", "1") != "0":
        lg = _gate_last_good()
        cand = lg
        cand_src = "last_good"
        cpath = os.environ.get("AMGCL_TPU_GATE_CANDIDATE")
        if cpath:
            cand_src = cpath
            try:
                with open(cpath) as f:
                    cand = json.load(f)
            except Exception:
                cand = None
        if cpath and cand is None:
            # an unreadable EXPLICIT candidate is a failure regardless of
            # the baseline — the caller asked to score it (same contract
            # as `--gate <path>`'s exit 2)
            gate_ok = False
            rec["gate"] = {"ok": False, "status": "unreadable_candidate",
                           "candidate_src": cand_src}
        elif lg is None:
            gate_ok = True
            rec["gate"] = {"ok": True, "status": "no_baseline"}
        else:
            gate_ok, checks = run_gate(cand, lg)
            rec["gate"] = {"ok": gate_ok, "candidate_src": cand_src,
                           "checks": checks}
            if not gate_ok:
                # failed checks carry their measured pairs, and the
                # cross-run attribution section is appended to every
                # gate failure — CI names the culprit stage itself
                rec["gate"]["failed"] = gate_failures(checks)
                rec["gate"]["attribution"] = gate_attribution(cand, lg)
        # the CI record carries the efficiency summaries of the record it
        # gated (roofline frac + compile totals travel with the gate
        # verdict), plus the cross-round trend rollups — pre-roofline
        # records simply lack the fields
        for key in ("roofline", "compile"):
            if isinstance(cand, dict) and isinstance(cand.get(key), dict):
                src = cand[key]
                rec[key] = src.get("totals", src) \
                    if key == "compile" else {
                        k: src.get(k) for k in
                        ("gbps", "gflops", "frac_hbm_peak", "bound")
                        if src.get(k) is not None}
        # multichip arm rides --check exactly like --gate: a scaling
        # efficiency / comm-fraction regression fails CI
        mc = multichip_gate_record()
        if mc is not None:
            rec["multichip"] = mc
            gate_ok = gate_ok and mc["ok"]
        # storm arm rides --check the same way: a max-sustainable-rate
        # or reference-p99 regression (AMGCL_TPU_GATE_STORM) fails CI
        st = storm_gate_record()
        if st is not None:
            rec["storm_gate"] = st
            gate_ok = gate_ok and st["ok"]
    replay_ok = True
    if os.environ.get("AMGCL_TPU_FLIGHT", "1") != "0":
        # determinism self-check (telemetry/flight.py): dump a replay
        # bundle of a small headline-config solve, replay it, and
        # require report parity — so "a bundle replays identically on
        # the same platform" is gated every round, not asserted once.
        # A gate failure additionally persists the bundle into
        # AMGCL_TPU_FLIGHT_DIR (when set): the failing round leaves a
        # replayable artifact behind, not just ratios.
        r_timeout = float(os.environ.get("AMGCL_TPU_CHECK_TIMEOUT",
                                         "870")) / 2
        cmd2 = [sys.executable, "-m", "amgcl_tpu.telemetry.flight",
                "--selftest"]
        keep_dir = os.environ.get("AMGCL_TPU_FLIGHT_DIR")
        if not gate_ok and keep_dir:
            # a `check/` SUBdirectory: the persisted bundle must not
            # consume one of the incident dir's bounded dump slots
            cmd2 += ["--dir", os.path.join(keep_dir, "check")]
        try:
            rr = subprocess.run(cmd2, capture_output=True, text=True,
                                timeout=r_timeout, cwd=_REPO,
                                env=dict(os.environ,
                                         JAX_PLATFORMS="cpu"))
            rrec = json.loads(rr.stdout.strip().splitlines()[-1])
            replay_ok = bool(rrec.get("ok")) and rr.returncode == 0
            rec["selfreplay"] = {
                "ok": replay_ok, "n": rrec.get("n"),
                "reason": rrec.get("reason"),
                "parity": rrec.get("parity"),
                "bundle": rrec.get("bundle")}
            if not replay_ok and rrec.get("error"):
                rec["selfreplay"]["error"] = rrec["error"]
        except Exception as e:
            replay_ok = False
            rec["selfreplay"] = {"ok": False, "error": repr(e)[:300]}
    recovery_ok = True
    if os.environ.get("AMGCL_TPU_GATE_RECOVERY", "1") != "0":
        # chaos-matrix gate (amgcl_tpu/faults/chaos.py): every injected
        # fault scenario (numeric x allocation x device x serve) must
        # either recover with solution parity or fail cleanly (typed
        # error + flight bundle) under the global deadline — a hang or
        # an unclean failure fails the round, the flight-selftest
        # pattern applied to the whole fault-tolerance layer.
        try:
            c_timeout = float(os.environ.get("AMGCL_TPU_CHAOS_TIMEOUT",
                                             "900"))
        except ValueError:
            c_timeout = 900.0
        try:
            cr = subprocess.run(
                [sys.executable, "-m", "amgcl_tpu.faults",
                 "--selftest"],
                capture_output=True, text=True, timeout=c_timeout + 60,
                cwd=_REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
            crec = json.loads(cr.stdout.strip().splitlines()[-1])
            recovery_ok = bool(crec.get("ok")) and cr.returncode == 0
            rec["recovery"] = {
                "ok": recovery_ok,
                "scenarios": crec.get("total"),
                "recovered": crec.get("recovered"),
                "clean_fail": crec.get("clean_fail"),
                "hangs": crec.get("hangs"),
                "failures": crec.get("failures"),
                "wall_s": crec.get("wall_s")}
            if not recovery_ok:
                # the actionable payload: the failing scenario rows
                rec["recovery"]["failed_scenarios"] = [
                    s for s in crec.get("scenarios", [])
                    if not s.get("ok")]
        except Exception as e:
            recovery_ok = False
            rec["recovery"] = {"ok": False, "error": repr(e)[:300]}
    storm_ok = True
    if os.environ.get("AMGCL_TPU_STORM_IN_CHECK", "1") != "0":
        # seeded storm smoke (serve/storm.py): a ~10 s open-loop load
        # pass on the CPU mesh, so every round carries a measured
        # load-under-traffic datapoint (curve + knee + goodput). The
        # subprocess's last stdout line is the bench_storm record; it
        # also refreshes STORM_LATEST.json for the storm gate arm.
        s_timeout = _storm_env_f("AMGCL_TPU_STORM_TIMEOUT", 600.0)
        try:
            sr = subprocess.run(
                [sys.executable, os.path.join(_REPO, "bench.py"),
                 "--storm", "--smoke"],
                capture_output=True, text=True, timeout=s_timeout,
                cwd=_REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
            srec = json.loads(sr.stdout.strip().splitlines()[-1])
            body = srec.get("record") or {}
            knee = body.get("knee") or {}
            storm_ok = sr.returncode == 0 and bool(body.get("curve"))
            rec["storm"] = {
                "ok": storm_ok,
                "requests": (body.get("goodput") or {}).get("requests"),
                "good_frac": (body.get("goodput") or {}).get(
                    "good_frac"),
                "max_sustainable_rps": knee.get("max_sustainable_rps"),
                "saturated": knee.get("saturated"),
                "knee_reason": knee.get("reason"),
                "ref_p99_ms": (body.get("reference") or {}).get(
                    "p99_ms"),
            }
        except Exception as e:
            storm_ok = False
            rec["storm"] = {"ok": False, "error": repr(e)[:300]}
    memwatch_ok = True
    if os.environ.get("AMGCL_TPU_MEMWATCH_IN_CHECK", "1") != "0":
        # seeded memory-observatory selftest (telemetry/memwatch.py):
        # builds a small farm tenant on the CPU mesh, joins measured
        # live-array bytes against the ledger model per level, then
        # runs register->evict->register cycles and fails on bytes
        # that do not return to baseline (the leak gate). The record's
        # drift_ratio also feeds the AMGCL_TPU_GATE_MEMDRIFT gate arm.
        try:
            m_timeout = float(os.environ.get(
                "AMGCL_TPU_MEMWATCH_TIMEOUT", "600"))
        except ValueError:
            m_timeout = 600.0
        try:
            mr = subprocess.run(
                [sys.executable, "-m", "amgcl_tpu.telemetry.memwatch",
                 "--selftest"],
                capture_output=True, text=True, timeout=m_timeout,
                cwd=_REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
            mrec = json.loads(mr.stdout.strip().splitlines()[-1])
            memwatch_ok = bool(mrec.get("ok")) and mr.returncode == 0
            rec["memwatch"] = {
                "ok": memwatch_ok,
                "drift_ratio": mrec.get("drift_ratio"),
                "baseline_bytes": mrec.get("baseline_bytes"),
                "leaked_bytes": mrec.get("leaked_bytes"),
                "checks": mrec.get("checks"),
                "wall_s": mrec.get("wall_s")}
            if not memwatch_ok:
                # the actionable payload: findings + per-owner rows
                rec["memwatch"]["findings"] = mrec.get("findings")
                rec["memwatch"]["owners"] = mrec.get("owners")
        except Exception as e:
            memwatch_ok = False
            rec["memwatch"] = {"ok": False, "error": repr(e)[:300]}
    analysis_ok = True
    if os.environ.get("AMGCL_TPU_ANALYSIS_IN_CHECK", "1") != "0":
        # static-analysis gate (amgcl_tpu/analysis): AST lint vs the
        # committed ANALYSIS_BASELINE.json findings budget + the jaxpr
        # contract audit (collective census, fused-tier engagement,
        # dtype/donation discipline). A subprocess, like the pytest
        # run: the audit forces its own 8-virtual-device CPU topology.
        a_timeout = float(os.environ.get("AMGCL_TPU_ANALYSIS_TIMEOUT",
                                         "600"))
        try:
            ar = subprocess.run(
                [sys.executable, "-m", "amgcl_tpu.analysis", "--json"],
                capture_output=True, text=True, timeout=a_timeout,
                cwd=_REPO, env=dict(os.environ))
            arec = json.loads(ar.stdout.strip().splitlines()[-1])
            audit = arec.get("audit", {})
            analysis_ok = bool(arec.get("ok")) and ar.returncode == 0
            rec["analysis"] = {
                "ok": analysis_ok,
                "lint_total": arec["lint"]["total"],
                "lint_new": len(arec["lint"]["new"]),
                "lint_suppressed": arec["lint"]["suppressed"],
                "stale_suppressions":
                    len(arec["lint"]["stale_suppressions"]),
                "rules": arec["lint"]["rules"],
                "audit_records": len(audit.get("records", [])),
                "audit_errors": audit.get("errors", 0),
            }
            conc = arec.get("concurrency") or {}
            if conc:
                # concurrency contract analyzer counts (lock-order /
                # guarded-by / cv- / handoff-discipline) — new findings
                # fail the round through the shared arec["ok"] gate
                rec["analysis"]["concurrency"] = {
                    "total": conc.get("total", 0),
                    "new": len(conc.get("new", [])),
                    "suppressed": conc.get("suppressed", 0),
                    "modules": len(conc.get("modules", [])),
                    "rules": conc.get("rules", []),
                }
            if not analysis_ok:
                # the actionable payload rides the CI record
                rec["analysis"]["new_findings"] = (
                    arec["lint"]["new"] + list(conc.get("new", [])))
                rec["analysis"]["audit_findings"] = [
                    f for f in audit.get("findings", [])
                    if f.get("severity") == "error"]
        except Exception as e:
            analysis_ok = False
            rec["analysis"] = {"ok": False, "error": repr(e)[:300]}
    try:
        rec["trend"] = trend_summary()["rollups"]
    except Exception as e:
        rec["trend"] = {"error": repr(e)[:200]}
    _stdout_sink.emit(rec)
    _sink.emit(dict(rec))
    return 0 if (rc == 0 and gate_ok and analysis_ok
                 and replay_ok and recovery_ok and storm_ok
                 and memwatch_ok) else 1


if __name__ == "__main__":
    if "--check" in sys.argv:
        extra = sys.argv[sys.argv.index("--check") + 1:]
        sys.exit(main_check(extra))
    elif "--gate" in sys.argv:
        extra = sys.argv[sys.argv.index("--gate") + 1:]
        sys.exit(main_gate(extra))
    elif "--why" in sys.argv:
        extra = sys.argv[sys.argv.index("--why") + 1:]
        sys.exit(main_why(extra))
    elif "--xray" in sys.argv:
        extra = sys.argv[sys.argv.index("--xray") + 1:]
        sys.exit(main_xray(extra))
    elif "--trend" in sys.argv:
        extra = sys.argv[sys.argv.index("--trend") + 1:]
        sys.exit(main_trend(extra))
    elif "--vecbench" in sys.argv:
        extra = sys.argv[sys.argv.index("--vecbench") + 1:]
        sys.exit(main_vecbench(extra))
    elif "--throughput" in sys.argv:
        extra = sys.argv[sys.argv.index("--throughput") + 1:]
        sys.exit(main_throughput(extra))
    elif "--farm" in sys.argv:
        extra = sys.argv[sys.argv.index("--farm") + 1:]
        sys.exit(main_farm(extra))
    elif "--storm" in sys.argv:
        extra = sys.argv[sys.argv.index("--storm") + 1:]
        sys.exit(main_storm(extra))
    elif "--scaling" in sys.argv:
        extra = sys.argv[sys.argv.index("--scaling") + 1:]
        sys.exit(main_scaling(extra))
    else:
        sys.exit(main_worker())
