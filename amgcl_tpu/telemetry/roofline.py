"""Roofline attribution — joining measured stage times to the resource
models.

PR 1 gave us *how long* a stage took (utils/profiler scopes), PR 2 *how
much* it should have cost (ledger.cycle_cost_model's per-stage FLOPs and
HBM bytes). This module joins the two into the number that actually says
whether a memory-bound sparse kernel is healthy: achieved GB/s (and
GFLOP/s) per V-cycle stage, per level, and per Krylov iteration, against
the device's peaks:

* :func:`device_peaks` — HBM GB/s + peak FLOP/s per platform:
  a public-figure table for TPUs (keyed on ``device_kind``, same table
  family as bench.py's), ``AMGCL_TPU_PEAK_GBPS`` / ``AMGCL_TPU_PEAK_FLOPS``
  env overrides for anything, and a MEASURED fallback on CPU/unknown
  backends (a stream triad for bandwidth, one dense matmul for FLOPs) so
  roofline fractions stay meaningful in CPU CI instead of comparing
  against a TPU number.
* :func:`measure_stages` — drive every stage of one multigrid cycle
  (mirroring ``Hierarchy.cycle``, fused legs included) standalone under a
  device-synced profiler, one scope occurrence per repetition at
  ``level<i>/<stage>``.
* :func:`roofline` — the join: per-stage achieved GB/s / GFLOP/s,
  arithmetic intensity, compute- vs memory-bound classification against
  the machine balance, fraction of the governing peak, and ranked
  bottleneck findings for ``telemetry.diagnose()``.
* :func:`xla_stage_check` — per-stage cross-check of the model bytes
  against XLA's own compiled cost analysis (``cli.py --roofline`` prints
  it). The model is a streaming floor: gather/roll-paying lowerings
  (DIA on CPU XLA) legitimately report more bytes accessed; dense and
  scaled-residual stages agree to ~1%.
* :func:`solve_roofline` — the per-Krylov-iteration variant from one
  solve's wall time and the ledger's iteration model (``bench.py``
  calls it; solves no longer carry it). The iteration model prices
  the fused tiers at their single-stream cost (fused V-cycle legs via
  ``cycle_cost_model``'s ``down_fused``/``up_fused`` rows, fused vector
  algebra via ``KRYLOV_VEC_STREAMS_FUSED``) — no double counting of
  intermediates the fused kernels never write, so achieved-GB/s numbers
  stay honest as kernels merge.
* :func:`counter_map` — the achieved-GB/s counter track for
  ``Profiler.to_chrome_trace(counters=...)``.

Everything returned is JSON-clean. Measurement reps:
``AMGCL_TPU_ROOFLINE_REPS`` (default 3).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from amgcl_tpu.telemetry import ledger as _ledger

#: (device_kind substring, HBM GB/s, dense-peak FLOP/s) per chip, from the
#: Google Cloud TPU documentation's per-version pages (e.g. "TPU v5e":
#: 819 GB/s HBM, 197 TFLOP/s bf16). The FLOPs column is the dense-unit
#: (MXU) bf16 peak, an upper bound a sparse kernel will not approach: the
#: roofline's compute ceiling, not a target. The one table of peaks in the
#: repo; a TPU whose device_kind is not in it is an error. Substring order
#: matters (v5p before v5).
TPU_PEAKS = [
    ("v6", 1640.0, 918e12),
    ("v5p", 2765.0, 459e12),
    ("v5 lite", 819.0, 197e12),
    ("v5e", 819.0, 197e12),
    ("v5", 2765.0, 459e12),
    ("v4", 1228.0, 275e12),
    ("v3", 900.0, 123e12),
    ("v2", 700.0, 45e12),
]


def _env_float(name: str) -> Optional[float]:
    v = os.environ.get(name)
    if not v:
        return None
    try:
        return float(v)
    except ValueError:
        return None


def _measure_stream_gbps(n: int = 1 << 23, reps: int = 5) -> float:
    """STREAM-triad bandwidth of the default device: ``a + 2.5 b`` over
    two ``n``-element f32 arrays (3 streams = 12n bytes), median of
    ``reps`` synced runs."""
    import time
    import jax
    import jax.numpy as jnp
    a = jnp.ones(n, jnp.float32)
    b = jnp.full(n, 0.5, jnp.float32)
    f = jax.jit(lambda a, b: a + 2.5 * b)
    jax.block_until_ready(f(a, b))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(a, b))
        ts.append(time.perf_counter() - t0)
    return 12.0 * n / float(np.median(ts)) / 1e9


def _measure_matmul_flops(m: int = 768, reps: int = 5) -> float:
    """Dense f32 matmul FLOP/s of the default device — the measured
    compute ceiling for the CPU fallback."""
    import time
    import jax
    import jax.numpy as jnp
    A = jnp.ones((m, m), jnp.float32)
    f = jax.jit(lambda A: A @ A)
    jax.block_until_ready(f(A))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(A))
        ts.append(time.perf_counter() - t0)
    return 2.0 * m ** 3 / float(np.median(ts))


_peaks_cache: Optional[Dict[str, Any]] = None


def device_peaks(refresh: bool = False) -> Dict[str, Any]:
    """``{"gbps", "flops", "platform", "device_kind", "source"}`` for the
    default device. Resolution order per number: env override
    (``AMGCL_TPU_PEAK_GBPS`` in GB/s, ``AMGCL_TPU_PEAK_FLOPS`` in
    FLOP/s), the TPU table, and off the TPU a one-time measured value
    (cached process-global — the stream/matmul probes cost ~0.1 s once).
    A TPU whose ``device_kind`` is not in :data:`TPU_PEAKS` raises: a
    measured rate is not a peak."""
    global _peaks_cache
    if _peaks_cache is not None and not refresh:
        return _peaks_cache
    out: Dict[str, Any] = {"gbps": None, "flops": None,
                           "platform": None, "device_kind": None,
                           "source": {}}
    try:
        import jax
        dev0 = jax.devices()[0]
        out["platform"] = dev0.platform
        out["device_kind"] = getattr(dev0, "device_kind", None)
    except Exception:
        pass
    env_g = _env_float("AMGCL_TPU_PEAK_GBPS")
    env_f = _env_float("AMGCL_TPU_PEAK_FLOPS")
    if env_g is not None:
        out["gbps"], out["source"]["gbps"] = env_g, "env"
    if env_f is not None:
        out["flops"], out["source"]["flops"] = env_f, "env"
    kind = (out["device_kind"] or "").lower()
    if out["platform"] == "tpu":
        for key, gbps, flops in TPU_PEAKS:
            if key in kind:
                if out["gbps"] is None:
                    out["gbps"], out["source"]["gbps"] = gbps, "table"
                if out["flops"] is None:
                    out["flops"], out["source"]["flops"] = flops, "table"
                break
        else:
            if out["gbps"] is None or out["flops"] is None:
                raise ValueError("no published peaks for TPU device_kind "
                                 "%r in roofline.TPU_PEAKS"
                                 % out["device_kind"])
    if out["gbps"] is None:
        try:
            out["gbps"] = round(_measure_stream_gbps(), 2)
            out["source"]["gbps"] = "measured-stream"
        except Exception:
            pass
    if out["flops"] is None:
        try:
            out["flops"] = float("%.4g" % _measure_matmul_flops())
            out["source"]["flops"] = "measured-matmul"
        except Exception:
            pass
    _peaks_cache = out
    return out


#: (device_kind substring, aggregate per-chip ICI GB/s) — the public
#: Cloud figures (total inter-chip interconnect bandwidth per chip), the
#: wire ceiling for the comm attribution (telemetry/comm.py). Substring
#: order matters (v5p before v5).
TPU_ICI_GBPS = [
    ("v6", 448.0),
    ("v5p", 600.0),
    ("v5 lite", 200.0),
    ("v5e", 200.0),
    ("v4", 300.0),
]


def ici_peak_gbps() -> Optional[float]:
    """Aggregate per-chip ICI bandwidth ceiling: env override
    (``AMGCL_TPU_PEAK_ICI_GBPS``) first, then the public-figure table by
    ``device_kind``; None on CPU/unknown backends — a host-virtual mesh
    moves collectives through shared memory and has no meaningful wire
    peak (the comm attribution tags those runs via provenance instead
    of comparing against a fictitious number)."""
    env = _env_float("AMGCL_TPU_PEAK_ICI_GBPS")
    if env is not None:
        return env
    pk = device_peaks()
    if pk.get("platform") != "tpu":
        return None
    kind = (pk.get("device_kind") or "").lower()
    for key, gbps in TPU_ICI_GBPS:
        if key in kind:
            return gbps
    return None


# ---------------------------------------------------------------------------
# stage measurement
# ---------------------------------------------------------------------------

def _stage_plan(hier, seed: int = 0) -> List[Tuple[int, str, Any, tuple]]:
    """``[(level, stage, fn, args)]`` mirroring exactly the work
    ``Hierarchy.cycle`` runs per stage — fused down/up legs included when
    engaged, so what gets measured is what the solve runs. ``fn`` takes
    the hierarchy as its first argument (jit argument, not closure
    constant). Inputs chain level to level (the restricted rhs feeds the
    next level) so shapes and sparsity are the real ones."""
    import jax
    import jax.numpy as jnp
    from amgcl_tpu.ops import device as dev

    plan: List[Tuple[int, str, Any, tuple]] = []
    levels = hier.levels
    nl = len(levels)
    rng = np.random.RandomState(seed)

    def rand_vec(n, dtype):
        return jnp.asarray(rng.standard_normal(n), dtype)

    f = None
    for i, lv in enumerate(levels):
        A = lv.A
        if A is None:                 # device_filter placeholder level
            continue
        n, _ = _ledger._vec_dims(A)
        if f is None or int(f.shape[0]) != n:
            f = rand_vec(n, A.dtype)
        if i == nl - 1:
            if hier.coarse is not None:
                def coarse_f(h, ff):
                    return h.coarse.solve(ff)
            else:
                def coarse_f(h, ff, i=i):
                    return h.levels[i].relax.apply(h.levels[i].A, ff)
            plan.append((i, "coarse_solve", coarse_f, (f,)))
            break
        fused_down = (hier.npre == 1 and lv.down is not None
                      and getattr(lv.down, "w", None) is not None)
        if fused_down:
            def down_f(h, ff, i=i):
                return h.levels[i].down.zero(ff)
            plan.append((i, "down_fused", down_f, (f,)))
            u, fc = jax.jit(down_f)(hier, f)
        else:
            def pre_f(h, ff, i=i):
                lvl = h.levels[i]
                if h.npre > 0:
                    u = lvl.relax.apply(lvl.A, ff)
                    for _ in range(h.npre - 1):
                        u = lvl.relax.apply_pre(lvl.A, ff, u)
                else:
                    u = dev.clear(ff)
                return u
            plan.append((i, "pre_smooth", pre_f, (f,)))
            u = jax.jit(pre_f)(hier, f)
            if lv.down is not None:
                def res_f(h, ff, uu, i=i):
                    return h.levels[i].down(ff, uu)
            else:
                def res_f(h, ff, uu, i=i):
                    lvl = h.levels[i]
                    return dev.spmv(lvl.R, dev.residual(ff, lvl.A, uu))
            plan.append((i, "restrict", res_f, (f, u)))
            fc = jax.jit(res_f)(hier, f, u)
        uc = rand_vec(int(fc.shape[0]), fc.dtype)
        if lv.up is not None and hier.npost >= 1:
            def up_f(h, ff, uu, ucc, i=i):
                return h.levels[i].up(ff, uu, ucc)
            plan.append((i, "up_fused", up_f, (f, u, uc)))
            extra = hier.npost - 1
        else:
            def pro_f(h, uu, ucc, i=i):
                return uu + dev.spmv(h.levels[i].P, ucc)
            plan.append((i, "prolong", pro_f, (u, uc)))
            extra = hier.npost
        if extra > 0:
            def post_f(h, ff, uu, i=i, extra=extra):
                for _ in range(extra):
                    uu = h.levels[i].relax.apply_post(h.levels[i].A,
                                                      ff, uu)
                return uu
            plan.append((i, "post_smooth", post_f, (f, u)))
        f = fc
    return plan


def measure_stages(hier, reps: Optional[int] = None, prof=None, seed: int = 0):
    """Run every stage of one cycle standalone, ``reps`` timed
    repetitions each under a device-synced profiler scope
    ``level<i>/<stage>`` (compile + warmup happen OUTSIDE the scopes).
    Returns the profiler — :func:`roofline` joins its per-scope times to
    the cost model, and its per-occurrence events feed the Perfetto
    export."""
    import jax
    from amgcl_tpu.utils.profiler import Profiler
    if reps is None:
        try:
            reps = int(os.environ.get("AMGCL_TPU_ROOFLINE_REPS", "3"))
        except ValueError:
            reps = 3
    reps = max(int(reps), 1)
    prof = prof if prof is not None else Profiler.device()
    for lvl, stage, fn, args in _stage_plan(hier, seed=seed):
        jf = jax.jit(fn)
        jax.block_until_ready(jf(hier, *args))
        for _ in range(reps):
            with prof.scope("level%d" % lvl):
                with prof.scope(stage):
                    jax.block_until_ready(jf(hier, *args))
    return prof


def scope_times(prof) -> Dict[str, Tuple[float, int]]:
    """``{scope_path: (total_s, count)}`` from a profiler tree."""
    out: Dict[str, Tuple[float, int]] = {}

    def walk(node, path):
        for name, ch in node.children.items():
            p = path + "/" + name if path else name
            out[p] = (ch.total, ch.count)
            walk(ch, p)

    walk(prof.root, "")
    return out


def _stage_lookup(times: Dict[str, Tuple[float, int]], level: int,
                  stage: str) -> Optional[Tuple[float, int]]:
    """Find ``level<i>/<stage>`` by path suffix, so profilers that nest
    the measurement under outer scopes (a CLI run) still join."""
    suffix = "level%d/%s" % (level, stage)
    for path, tc in times.items():
        if path == suffix or path.endswith("/" + suffix):
            return tc
    return None


def _model_for(srow: Dict[str, Any], stage: str, npost: int,
               up_fused: bool) -> Optional[Dict[str, float]]:
    """Model cost of a MEASURED stage: direct for the five model stages,
    composed for the fused legs (down_fused = pre_smooth + restrict;
    up_fused = prolong + the first of the npost post-sweeps, the
    remaining post_smooth shrinking accordingly)."""
    if stage in srow:
        cost = dict(srow[stage])
        if stage == "post_smooth" and up_fused and npost > 1:
            frac = (npost - 1) / float(npost)
            cost = {"flops": cost["flops"] * frac,
                    "bytes": cost["bytes"] * frac}
        return cost
    if stage == "down_fused" and "pre_smooth" in srow:
        return _ledger._add(srow["pre_smooth"], srow["restrict"])
    if stage == "up_fused" and "prolong" in srow:
        cost = dict(srow["prolong"])
        ps = srow.get("post_smooth")
        if ps and npost > 0:
            cost = {"flops": cost["flops"] + ps["flops"] / float(npost),
                    "bytes": cost["bytes"] + ps["bytes"] / float(npost)}
        return cost
    return None


def _classify(flops: float, bytes_: float,
              peaks: Dict[str, Any]) -> Tuple[Optional[float], str]:
    """(machine balance flop/byte, 'memory'|'compute') from the peaks."""
    balance = None
    pk_f, pk_g = peaks.get("flops"), peaks.get("gbps")
    if pk_f and pk_g:
        balance = pk_f / (pk_g * 1e9)
    intensity = flops / bytes_ if bytes_ else 0.0
    bound = "compute" if balance is not None and intensity > balance \
        else "memory"
    return balance, bound


def roofline(hier, prof=None, peaks: Optional[Dict[str, Any]] = None,
             reps: Optional[int] = None) -> Dict[str, Any]:
    """The join: measured per-stage seconds (``prof`` — measured fresh
    via :func:`measure_stages` when None) x ``ledger.cycle_cost_model``
    -> achieved GFLOP/s and GB/s per stage and level, classification
    against the machine balance, fraction of the governing peak, and
    ranked bottlenecks."""
    if prof is None:
        prof = measure_stages(hier, reps=reps)
    peaks = peaks or device_peaks()
    model = _ledger.cycle_cost_model(hier)
    times = scope_times(prof)
    rows: List[Dict[str, Any]] = []
    tot_t = tot_flops = tot_bytes = 0.0
    for srow in model["stages"]:
        if srow.get("skipped"):
            continue
        lvl = srow["level"]
        visits = srow.get("visits", 1)
        up_fused = _stage_lookup(times, lvl, "up_fused") is not None
        for stage in ("down_fused", "pre_smooth", "restrict",
                      "coarse_solve", "up_fused", "prolong",
                      "post_smooth"):
            tc = _stage_lookup(times, lvl, stage)
            if tc is None:
                continue
            total_s, count = tc
            t = total_s / max(count, 1)
            cost = _model_for(srow, stage, getattr(hier, "npost", 1),
                              up_fused)
            if cost is None:
                continue
            flops, bytes_ = float(cost["flops"]), float(cost["bytes"])
            balance, bound = _classify(flops, bytes_, peaks)
            gflops = flops / t / 1e9 if t > 0 else None
            gbps = bytes_ / t / 1e9 if t > 0 else None
            row: Dict[str, Any] = {
                "level": lvl, "stage": stage, "visits": visits,
                "t_s": t, "model_flops": int(flops),
                "model_bytes": int(bytes_),
                "intensity": round(flops / bytes_, 4) if bytes_ else None,
                "gflops": round(gflops, 3) if gflops is not None else None,
                "gbps": round(gbps, 3) if gbps is not None else None,
                "bound": bound,
            }
            frac = None
            if bound == "memory" and gbps is not None and peaks.get("gbps"):
                frac = gbps / peaks["gbps"]
            elif gflops is not None and peaks.get("flops"):
                frac = gflops * 1e9 / peaks["flops"]
            row["frac_peak"] = round(frac, 4) if frac is not None else None
            rows.append(row)
            tot_t += t * visits
            tot_flops += flops * visits
            tot_bytes += bytes_ * visits
    out: Dict[str, Any] = {"peaks": peaks, "stages": rows,
                           "cycle_s": round(tot_t, 6)}
    balance, bound = _classify(tot_flops, tot_bytes, peaks)
    if balance is not None:
        out["machine_balance_flop_per_byte"] = round(balance, 4)
    if tot_t > 0:
        gbps = tot_bytes / tot_t / 1e9
        out["total"] = {
            "model_flops": int(tot_flops), "model_bytes": int(tot_bytes),
            "gflops": round(tot_flops / tot_t / 1e9, 3),
            "gbps": round(gbps, 3), "bound": bound,
            "frac_peak": round(gbps / peaks["gbps"], 4)
            if peaks.get("gbps") else None,
        }
    out["bottlenecks"] = findings(out, hier)
    return out


def findings(rf: Dict[str, Any], hier=None,
             frac_threshold: float = 0.25,
             max_items: int = 3) -> List[Dict[str, Any]]:
    """Ranked bottlenecks as ``telemetry.diagnose()``-style findings:
    stages below ``frac_threshold`` of their governing peak, worst
    time-share first. The suggestion names the likeliest cause — a
    disabled fused leg for the down/up stages on DIA levels, gather
    overhead otherwise."""
    rows = [r for r in rf.get("stages", [])
            if r.get("frac_peak") is not None
            and r["frac_peak"] < frac_threshold]
    cycle_s = rf.get("cycle_s") or sum(
        r["t_s"] * r.get("visits", 1) for r in rf.get("stages", [])) or 1.0
    rows.sort(key=lambda r: -(r["t_s"] * r.get("visits", 1)))
    out = []
    for r in rows[:max_items]:
        share = r["t_s"] * r.get("visits", 1) / cycle_s
        sev = "warning" if (r["frac_peak"] < 0.10 and share > 0.15) \
            else "info"
        peak_name = "HBM peak" if r["bound"] == "memory" \
            else "compute peak"
        msg = ("level %d %s at %.0f%% of %s (%.2f GB/s, %.1f%% of cycle "
               "time)" % (r["level"], r["stage"],
                          100 * r["frac_peak"], peak_name,
                          r["gbps"] or 0.0, 100 * share))
        sugg = None
        if hier is not None and r["level"] < len(hier.levels):
            lv = hier.levels[r["level"]]
            if r["stage"] in ("pre_smooth", "restrict") \
                    and lv.down is None:
                sugg = "fused down-leg disabled on this level — check " \
                       "AMGCL_TPU_FUSED_VCYCLE / AMGCL_TPU_PALLAS and " \
                       "the probe decline log"
            elif r["stage"] in ("prolong", "post_smooth") \
                    and lv.up is None:
                sugg = "fused up-leg disabled on this level — check " \
                       "AMGCL_TPU_FUSED_VCYCLE / AMGCL_TPU_PALLAS and " \
                       "the probe decline log"
        if sugg is None:
            sugg = "memory-bound stage far off the roofline: check the " \
                   "storage format (ledger by_format), gather overhead, " \
                   "per-dispatch latency at this level's size, and that " \
                   "the fused vector tier is engaged " \
                   "(AMGCL_TPU_FUSED_VEC)" \
                if r["bound"] == "memory" else \
                "compute-bound stage off peak: dense coarse levels this " \
                "small are dispatch-latency dominated"
        out.append({"severity": sev, "code": "roofline_stage",
                    "message": msg, "suggestion": sugg})
    return out


def counter_map(rf: Dict[str, Any],
                track: str = "achieved_gbps") -> Dict[str, Dict[str, float]]:
    """``Profiler.to_chrome_trace(counters=...)`` mapping: the achieved
    GB/s of each stage keyed by its ``level<i>/<stage>`` scope path."""
    by_path = {}
    for r in rf.get("stages", []):
        if r.get("gbps") is not None:
            by_path["level%d/%s" % (r["level"], r["stage"])] = r["gbps"]
    return {track: by_path}


def solve_roofline(per_iteration: Dict[str, Any], iters: int,
                   wall_s: float,
                   peaks: Optional[Dict[str, Any]] = None,
                   first_call: bool = False) -> Optional[Dict[str, Any]]:
    """Whole-solve roofline from the ledger's per-Krylov-iteration model
    and one solve's wall time — the cheap, measurement-free variant.
    Wall time includes dispatch
    and fetch overhead (and compile, when ``first_call`` — flagged), so
    this is a lower bound on the achieved rate."""
    flops = per_iteration.get("flops")
    bytes_ = per_iteration.get("bytes")
    if not flops or not bytes_ or not wall_s or wall_s <= 0 or iters <= 0:
        return None
    peaks = peaks or device_peaks()
    t_iter = wall_s / iters
    gflops = flops / t_iter / 1e9
    gbps = bytes_ / t_iter / 1e9
    balance, bound = _classify(float(flops), float(bytes_), peaks)
    out: Dict[str, Any] = {
        "per_iteration_s": round(t_iter, 6),
        "gflops": round(gflops, 3), "gbps": round(gbps, 3),
        "intensity": round(flops / bytes_, 4), "bound": bound,
        "peaks": {k: peaks.get(k) for k in ("gbps", "flops", "source")},
    }
    if peaks.get("gbps"):
        out["frac_hbm_peak"] = round(gbps / peaks["gbps"], 4)
    if peaks.get("flops"):
        out["frac_flops_peak"] = round(gflops * 1e9 / peaks["flops"], 6)
    if first_call:
        out["first_call"] = True      # wall includes jit trace + compile
    return out


def xla_stage_check(hier, plan=None,
                    tolerance: float = 0.05) -> List[Dict[str, Any]]:
    """Per-stage model-bytes vs XLA's compiled ``bytes accessed``
    (``ledger.xla_cost_analysis`` of exactly the stage functions the
    measurement runs). ``within_tol`` marks agreement at ``tolerance``
    (the ledger's ~5% contract); stages whose lowering materializes
    gathers/rolls (DIA on CPU XLA) legitimately exceed the streaming
    floor and report their ratio for inspection. Empty list when the
    backend exposes no cost analysis."""
    import functools
    model = _ledger.cycle_cost_model(hier)
    srows = {r["level"]: r for r in model["stages"]}
    plan = plan or _stage_plan(hier)
    fused_up_levels = {p[0] for p in plan if p[1] == "up_fused"}
    rows = []
    for lvl, stage, fn, args in plan:
        srow = srows.get(lvl)
        if srow is None:
            continue
        cost = _model_for(srow, stage, getattr(hier, "npost", 1),
                          lvl in fused_up_levels)
        if cost is None:
            continue
        xc = _ledger.xla_cost_analysis(functools.partial(fn, hier), *args)
        if not xc or not xc.get("bytes_accessed"):
            continue
        ratio = cost["bytes"] / xc["bytes_accessed"]
        rows.append({
            "level": lvl, "stage": stage,
            "model_bytes": int(cost["bytes"]),
            "xla_bytes": int(xc["bytes_accessed"]),
            "ratio": round(ratio, 4),
            "within_tol": bool(abs(ratio - 1.0) <= tolerance),
        })
    return rows


def format_roofline(rf: Dict[str, Any],
                    xla_rows: Optional[List[Dict[str, Any]]] = None) -> str:
    """Human-readable roofline table (the CLI's ``--roofline``
    rendering)."""
    pk = rf.get("peaks", {})
    src = pk.get("source", {})
    head = "Roofline (peaks: %s GB/s HBM [%s], %s FLOP/s [%s]" % (
        pk.get("gbps"), src.get("gbps", "?"),
        ("%.3g" % pk["flops"]) if pk.get("flops") else "?",
        src.get("flops", "?"))
    if rf.get("machine_balance_flop_per_byte") is not None:
        head += "; balance %.2f F/B" % rf["machine_balance_flop_per_byte"]
    lines = [head + "):",
             "level  stage         t/visit    model MB   achieved GB/s"
             "   GFLOP/s    F/B  bound    %peak",
             "-" * 92]
    xla_by = {(r["level"], r["stage"]): r for r in (xla_rows or [])}
    for r in rf.get("stages", []):
        lines.append(
            "%5d  %-12s %8.1f us %9.3f %15.2f %9.2f %6.2f  %-7s %6s"
            % (r["level"], r["stage"], r["t_s"] * 1e6,
               r["model_bytes"] / 1e6, r["gbps"] or 0.0,
               r["gflops"] or 0.0, r["intensity"] or 0.0, r["bound"],
               ("%.1f%%" % (100 * r["frac_peak"]))
               if r.get("frac_peak") is not None else "-"))
        xr = xla_by.get((r["level"], r["stage"]))
        if xr is not None:
            lines.append(
                "       %-12s model %.3f MB vs XLA %.3f MB  (ratio "
                "%.3f%s)" % ("  xla-check:", xr["model_bytes"] / 1e6,
                             xr["xla_bytes"] / 1e6, xr["ratio"],
                             ", ok" if xr["within_tol"]
                             else " — gather/roll lowering exceeds the "
                                  "streaming floor"))
    tot = rf.get("total")
    if tot:
        lines.append("-" * 92)
        lines.append(
            "cycle: %.1f us/visit-sum, %.2f GB/s achieved (%s-bound%s)"
            % (rf.get("cycle_s", 0.0) * 1e6, tot["gbps"], tot["bound"],
               (", %.1f%% of HBM peak" % (100 * tot["frac_peak"]))
               if tot.get("frac_peak") is not None else ""))
    for f in rf.get("bottlenecks", []):
        lines.append("  [%s] %s" % (f["severity"].upper(), f["message"]))
        if f.get("suggestion"):
            lines.append("      -> %s" % f["suggestion"])
    return "\n".join(lines)
