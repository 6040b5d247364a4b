"""Distributed observability (ISSUE 10): measured comm attribution via
comm-ablated stand-ins, per-shard imbalance, the structured multichip
scaling record, and the AMGCL_TPU_GATE_MULTICHIP gate."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest
import jax.numpy as jnp

from amgcl_tpu.utils.sample_problem import poisson3d
from amgcl_tpu.parallel.mesh import make_mesh
from amgcl_tpu.parallel.dist_matrix import DistDiaMatrix
from amgcl_tpu.parallel.dist_ell import build_dist_ell
from amgcl_tpu.telemetry import comm as C
from amgcl_tpu.telemetry.ledger import (DIST_CG_COLLECTIVES,
                                        COMM_STAGE_CONTRACTS)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import bench  # noqa: E402  (repo-root module)


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8)


@pytest.fixture(scope="module")
def dia16(mesh8):
    A, _ = poisson3d(16)                 # 4096 rows, divides 8
    return A, DistDiaMatrix.from_csr(A, mesh8, jnp.float64)


# ---------------------------------------------------------------------------
# imbalance: structural tables
# ---------------------------------------------------------------------------

def test_imbalance_unit():
    assert C.imbalance([3, 3, 3, 3])["factor"] == 1.0
    r = C.imbalance([4, 1, 1, 2])
    assert r["factor"] == pytest.approx(2.0)
    assert r["max"] == 4.0
    assert C.imbalance([])["factor"] == 1.0


def test_shard_costs_skewed_strip_partition():
    """A deliberately skewed strip partition reports its real load
    factor; the even partition of the same matrix is near-balanced, and
    both conserve total nnz."""
    A, _ = poisson3d(8)                  # 512 rows
    n = A.nrows
    even = C.shard_costs(A.ptr, C.even_bounds(n, 8))
    assert sum(r["nnz"] for r in even) == A.nnz
    assert C.imbalance([r["nnz"] for r in even])["factor"] < 1.1
    # skew: shard 0 takes half the rows, the rest split the remainder
    bounds = [0, n // 2] + [n // 2 + (n // 2) * k // 7
                            for k in range(1, 8)]
    skewed = C.shard_costs(A.ptr, bounds)
    assert sum(r["nnz"] for r in skewed) == A.nnz
    assert C.imbalance([r["nnz"] for r in skewed])["factor"] > 1.5


def test_dia_shard_table(dia16):
    A, Ad = dia16
    dist = C.dist_resources(Ad, 8)
    assert dist["format"] == "DistDiaMatrix"
    assert dist["pattern"] == "ring"
    assert dist["halo_width"] == 256     # the +-n^2 band of 16^3
    rows = dist["per_shard"]
    assert len(rows) == 8
    assert all(r["rows"] == 512 for r in rows)
    # per-shard in-range counts must sum to the whole-matrix in-range
    # count (each diagonal stores n - |offset| values inside the matrix)
    total = sum(A.nrows - abs(off) for off in Ad.offsets)
    assert sum(r["nnz"] for r in rows) == total
    # edge shards exchange one side only
    assert rows[0]["halo_elems"] == 256
    assert rows[3]["halo_elems"] == 512
    f = dist["imbalance"]["factor"]
    assert 1.0 <= f < 1.1


def test_ell_dist_resources(mesh8):
    A, _ = poisson3d(8)
    Ae = build_dist_ell(A, mesh8, jnp.float64)
    dist = C.dist_resources(Ae, 8)
    assert dist["pattern"] == "all_to_all"
    assert dist["padding_uniform"] is True
    assert dist["imbalance"]["factor"] == 1.0
    assert len(dist["per_shard"]) == 8


def test_dist_amg_ledger_skewed_partition(mesh8):
    """min_per_shard concentrates a level on fewer shards — the ledger's
    useful-work shard table must report the resulting imbalance (the
    device buffers stay padding-uniform, the nnz table does not)."""
    from amgcl_tpu.parallel.dist_amg import DistAMGSolver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.cg import CG
    A, rhs = poisson3d(12)               # 1728 rows
    s = DistAMGSolver(A, mesh8, AMGParams(coarse_enough=50),
                      CG(maxiter=5),
                      replicate_below=256, min_per_shard=432)
    led = s.resource_ledger()
    dist = led["dist"]
    lvl0 = dist["levels"][0]
    nz = [r["nnz"] for r in lvl0["per_shard"]]
    assert len(nz) == 8
    assert sum(1 for v in nz if v == 0) == 4     # concentrated on 4
    assert lvl0["imbalance"]["factor"] > 1.5
    assert dist["imbalance_factor"] >= lvl0["imbalance"]["factor"]
    assert dist["provenance"]["device_platform"] == "cpu"


# ---------------------------------------------------------------------------
# measured comm attribution
# ---------------------------------------------------------------------------

def test_measure_comm_join_invariants(dia16, mesh8):
    """The ablation pair partitions each stage by construction:
    comm_us == max(measured − ablated, 0), fraction in [0, 1], every
    measured time positive."""
    _, Ad = dia16
    rec = C.measure_comm(Ad, mesh8, reps=2)
    keys = {r["stage"] for r in rec["rows"]}
    assert keys == {"halo", "psum", "iteration"}
    for r in rec["rows"]:
        assert r["t_us"] > 0 and r["ablated_us"] > 0
        assert r["comm_us"] >= 0
        # the three fields are independently rounded to 1e-3 us
        assert r["comm_us"] == pytest.approx(
            max(r["t_us"] - r["ablated_us"], 0.0), abs=2e-3)
        assert 0.0 <= r["comm_fraction"] <= 1.0
        assert r["contract"] in COMM_STAGE_CONTRACTS


def test_comm_attribution_model_join(dia16, mesh8):
    _, Ad = dia16
    rec = C.comm_attribution(Ad, mesh8, solver="dist_cg", reps=2)
    pi = rec["per_iteration"]
    assert pi["collectives"] == DIST_CG_COLLECTIVES["dist_cg"]
    assert pi["model"]["msgs"] > 0 and pi["model"]["bytes"] > 0
    assert pi["comm_fraction"] is not None
    prov = rec["provenance"]
    assert prov["device_platform"] == "cpu"
    assert prov["platform_tag"] == "cpu-fallback"
    # the host-virtual-mesh caveat is always a finding on CPU meshes
    codes = {f["code"] for f in rec["findings"]}
    assert "comm_platform" in codes
    # formatter renders without raising
    assert "Comm attribution" in C.format_comm(rec)


def test_comm_attribution_ell_pipelined(mesh8):
    A, _ = poisson3d(8)
    Ae = build_dist_ell(A, mesh8, jnp.float64)
    rec = C.comm_attribution(Ae, mesh8, solver="dist_cg_pipelined",
                             reps=2)
    assert rec["per_iteration"]["collectives"] == \
        DIST_CG_COLLECTIVES["dist_cg_pipelined"]
    assert {r["stage"] for r in rec["stages"]} == \
        {"halo", "psum", "iteration"}


def test_measured_shard_spread(dia16, mesh8):
    _, Ad = dia16
    spread = C.measure_shard_spread(Ad, mesh8, reps=2)
    assert len(spread["per_shard_us"]) == 8
    assert all(t > 0 for t in spread["per_shard_us"])
    assert spread["spread"]["factor"] >= 1.0
    # ELL buffers are padding-uniform: no per-shard split to measure
    A, _ = poisson3d(8)
    Ae = build_dist_ell(A, mesh8, jnp.float64)
    assert C.measure_shard_spread(Ae, mesh8, reps=1) is None


def test_dist_cg_report_carries_dist(dia16, mesh8):
    from amgcl_tpu.parallel.dist_solver import dist_cg
    A, Ad = dia16
    dinv = jnp.asarray(A.diagonal(invert=True))
    out = dist_cg(Ad, mesh8, jnp.asarray(np.ones(A.nrows)), dinv=dinv,
                  maxiter=5, tol=1e-12)
    res = out.report.resources
    assert res["dist"]["imbalance"]["factor"] >= 1.0
    assert len(res["dist"]["per_shard"]) == 8
    prov = out.report.extra["provenance"]
    assert prov["device_count"] == 8
    assert prov["platform_tag"] == "cpu-fallback"


def test_diagnose_folds_comm_findings():
    from amgcl_tpu.telemetry.health import diagnose
    report = types.SimpleNamespace(health=None, resid=1e-8, iters=7,
                                   convergence_rate=0.1, extra={})
    comm_rec = {"solver": "dist_cg", "devices": 8,
                "per_iteration": {"comm_fraction": 0.9},
                "provenance": {"platform_tag": "cpu-fallback"}}
    codes = {f["code"] for f in diagnose(report, comm=comm_rec)}
    assert "comm_bound" in codes
    assert "comm_platform" in codes


# ---------------------------------------------------------------------------
# audit: measured census == contract, ablated census == 0
# ---------------------------------------------------------------------------

def test_audit_comm_stage_census(mesh8):
    from amgcl_tpu.analysis import jaxpr_audit as ja
    recs = ja.audit_comm_stages(mesh8)
    assert len(recs) == 14               # 7 contracts x (measured, ablated)
    findings = [f for r in recs for f in ja.check_comm_stages(r)]
    assert findings == []
    for r in recs:
        if r["ablated"]:
            cen = r["collectives"]
            assert all(cen[k] == 0 for k in
                       ("psum", "ppermute", "all_gather", "all_to_all"))


def test_audit_comm_negative_injection(mesh8):
    """A collective surviving in an 'ablated' stand-in must fail the
    check — both on a fabricated record and on a really-traced body."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from amgcl_tpu.parallel.mesh import ROWS_AXIS
    from amgcl_tpu.analysis import jaxpr_audit as ja

    fake = {"entry": "telemetry.comm_psum_ablated", "stage": "psum",
            "ablated": True, "devices": 8,
            "collectives": {"psum": 1, "ppermute": 0, "all_gather": 0,
                            "all_to_all": 0, "psum_elems": [1]}}
    errs = ja.check_comm_stages(fake)
    assert len(errs) == 1 and errs[0]["severity"] == "error"

    # trace an injected bad stand-in for real and run the same check
    def bad_ablated(a, b):
        return lax.psum(jnp.vdot(a, b), ROWS_AXIS)   # the poison

    fn = shard_map(bad_ablated, mesh=mesh8,
                   in_specs=(P(ROWS_AXIS), P(ROWS_AXIS)),
                   out_specs=P(), check_vma=False)
    x = jnp.ones(4096)
    jx = jax.make_jaxpr(fn)(x, x)
    rec = {"entry": "telemetry.comm_psum_ablated", "stage": "psum",
           "ablated": True, "devices": 8,
           "collectives": ja.collective_census(jx.jaxpr)}
    errs = ja.check_comm_stages(rec)
    assert len(errs) == 1
    # a measured stage whose census drifted from the contract fails too
    drifted = {"entry": "telemetry.comm_psum", "stage": "psum",
               "ablated": False, "devices": 8,
               "collectives": {"psum": 2, "ppermute": 0,
                               "all_gather": 0, "all_to_all": 0,
                               "psum_elems": [1, 1]}}
    assert len(ja.check_comm_stages(drifted)) == 1


# ---------------------------------------------------------------------------
# scaling record + multichip gate
# ---------------------------------------------------------------------------

def test_scaling_record_schema(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_COMM_REPS", "2")
    rec = bench.scaling_record(devices=[1, 2], base_n=8,
                               solvers=["dist_cg"], maxiter=10, reps=1)
    assert rec["event"] == "multichip_scaling"
    assert rec["schema"] == 2
    assert rec["provenance"]["device_platform"] == "cpu"
    assert rec["device_platform"] == "cpu"
    srec = rec["solvers"]["dist_cg"]
    assert srec["collectives"] == DIST_CG_COLLECTIVES["dist_cg"]
    assert [c["devices"] for c in srec["weak"]["cells"]] == [1, 2]
    assert srec["weak"]["cells"][1]["rows"] == \
        2 * srec["weak"]["cells"][0]["rows"]
    assert [c["rows"] for c in srec["strong"]["cells"]] == [512, 512]
    assert srec["weak"]["efficiency"] is not None
    head = rec["headline"]
    for key in ("weak_efficiency", "strong_efficiency",
                "comm_fraction", "imbalance", "devices"):
        assert key in head
    assert head["comm_fraction"] is not None
    assert rec["imbalance"]["imbalance"]["factor"] >= 1.0
    assert rec["collectives_census"]["ok"] is True


def _mk_record(weak=0.8, strong=0.5, comm=0.2, platform="cpu"):
    return {"schema": 2, "headline": {
        "weak_efficiency": weak, "strong_efficiency": strong,
        "comm_fraction": comm, "imbalance": 1.05, "devices": 8},
        "provenance": {"device_platform": platform},
        "path": "MULTICHIP_r01.json"}


def test_multichip_gate_unit(monkeypatch):
    monkeypatch.delenv("AMGCL_TPU_GATE_MULTICHIP", raising=False)
    monkeypatch.delenv("AMGCL_TPU_GATE_COMM_FRAC", raising=False)
    base = _mk_record()
    ok, checks = bench.run_multichip_gate(_mk_record(weak=0.85), base)
    assert ok
    # injected efficiency regression fails
    ok, checks = bench.run_multichip_gate(_mk_record(weak=0.4), base)
    assert not ok
    assert [c for c in checks if c["check"] == "weak_efficiency"][0][
        "status"] == "regression"
    # comm-fraction blowup fails (beyond ratio + abs slack)
    ok, checks = bench.run_multichip_gate(_mk_record(comm=0.6), base)
    assert not ok
    # platform mismatch skips every ratio instead of comparing
    ok, checks = bench.run_multichip_gate(
        _mk_record(weak=0.1, platform="tpu"), base)
    assert ok
    assert all(c["status"] == "skipped" for c in checks)
    # kill switch
    monkeypatch.setenv("AMGCL_TPU_GATE_MULTICHIP", "0")
    ok, checks = bench.run_multichip_gate(_mk_record(weak=0.01), base)
    assert ok and checks[0]["status"] == "skipped"


def test_multichip_gate_wiring(tmp_path, monkeypatch):
    """--gate/--check read the candidate from MULTICHIP_LATEST.json (or
    the env override) and the baseline from the newest structured
    MULTICHIP_r*.json; a regressed candidate flips ok to False."""
    cand = _mk_record(weak=0.3)
    p = tmp_path / "cand.json"
    p.write_text(json.dumps(cand))
    monkeypatch.setenv("AMGCL_TPU_GATE_MULTICHIP_CANDIDATE", str(p))
    monkeypatch.delenv("AMGCL_TPU_GATE_MULTICHIP", raising=False)
    monkeypatch.setattr(bench, "_multichip_baseline",
                        lambda: _mk_record(weak=0.8))
    rec = bench.multichip_gate_record()
    assert rec["ok"] is False
    assert any(c["status"] == "regression" for c in rec["checks"])
    # no candidate + no structured baseline = feature unused, no arm
    monkeypatch.setenv("AMGCL_TPU_GATE_MULTICHIP_CANDIDATE",
                       str(tmp_path / "missing.json"))
    monkeypatch.setattr(bench, "_multichip_baseline", lambda: None)
    assert bench.multichip_gate_record() is None


def test_multichip_history_mixed(tmp_path):
    from amgcl_tpu.telemetry import metrics as m
    legacy = {"n_devices": 8, "rc": 0, "ok": True, "tail": "dryrun..."}
    (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps(legacy))
    (tmp_path / "MULTICHIP_r02.json").write_text(
        json.dumps(_mk_record()))
    rows = m.multichip_history(str(tmp_path))
    assert [r["round"] for r in rows] == [1, 2]
    assert rows[0]["legacy_dryrun"] is True
    trend = m.trend(rows, m.MULTICHIP_TREND_FIELDS)
    assert trend[0]["devices"] == 8          # legacy keeps the count
    assert trend[0]["weak_eff"] is None      # ... and gaps elsewhere
    assert trend[1]["weak_eff"] == 0.8
    assert "multichip" not in m.format_trend([], m.MULTICHIP_TREND_FIELDS)


def test_record_platform_reads_provenance():
    assert bench._record_platform(
        {"provenance": {"device_platform": "tpu"}}) == "tpu"
    assert bench._record_platform(
        {"device_platform": "cpu",
         "provenance": {"device_platform": "tpu"}}) == "cpu"
    assert bench._record_platform({"fallback": "cpu (...)"}) == "cpu"


def test_live_dist_gauges():
    from amgcl_tpu.telemetry.live import (LiveRegistry,
                                          publish_dist_gauges)
    reg = LiveRegistry()
    publish_dist_gauges(reg, devices=8, comm_fraction=0.25)
    assert reg.get("dist_mesh_devices") == 8.0
    assert reg.get("dist_comm_fraction") == 0.25
    text = reg.prometheus()
    assert "amgcl_tpu_dist_mesh_devices 8.0" in text
    assert "amgcl_tpu_dist_comm_fraction 0.25" in text


@pytest.mark.serial
def test_cli_dist_report_smoke(tmp_path):
    """`cli --mesh 8 --dist-report` end to end on the 8-virtual-device
    mesh: per-shard + comm tables printed, dist_report event emitted."""
    out = tmp_path / "dist.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               AMGCL_TPU_COMM_REPS="2")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    r = subprocess.run(
        [sys.executable, "-m", "amgcl_tpu.cli", "-n", "10",
         "--mesh", "8", "--dist-report", "--telemetry", str(out)],
        capture_output=True, text=True, timeout=900, cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Comm attribution" in r.stdout
    assert "Per-shard ledger" in r.stdout
    events = [json.loads(line) for line in out.read_text().splitlines()]
    by = {e.get("event") for e in events}
    assert "dist_report" in by
    dr = [e for e in events if e.get("event") == "dist_report"][0]
    assert dr["comm"]["per_iteration"]["collectives"] in (
        DIST_CG_COLLECTIVES["dist_cg"],
        DIST_CG_COLLECTIVES["dist_cg_pipelined"])
