"""Operator X-ray tests (ISSUE 14): structure analytics on known
matrices, the to_device('auto') format-decision ledger (winner + reason
incl. budget-starved picks), the predict-only reorder-gain advisor, the
host-purity contract (no jax, compile_watch delta 0), and the
surfacing seams (hierarchy_stats fold, doctor fold, rollup specs,
cli/bench --xray)."""

import json

import numpy as np
import pytest

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.telemetry import structure as st
from amgcl_tpu.utils.sample_problem import poisson3d


def _amg(A, coarse_enough=50):
    from amgcl_tpu.models.amg import AMG, AMGParams
    return AMG(A, AMGParams(coarse_enough=coarse_enough))


# ---------------------------------------------------------------------------
# window-tiling parity with the real packer
# ---------------------------------------------------------------------------

def test_tile_windows_host_matches_packer():
    """The X-ray's O(n) window mirror must agree exactly with
    ops.unstructured.tile_windows (the packer the predictions price)."""
    from amgcl_tpu.ops.unstructured import tile_windows
    mats = [poisson3d(8)[0], st.permuted_banded(2048, bw=4, seed=1)[0]]
    # a matrix with empty rows (ptr[i] == ptr[i+1])
    ptr = np.array([0, 2, 2, 3], np.int64)
    mats.append(CSR(ptr, np.array([0, 2, 1], np.int32),
                    np.ones(3), 3))
    for A in mats:
        for tile in (1024, 64):      # windowed-ELL and dense-window
            a = tile_windows(A, tile)
            b = st.tile_windows_host(A, tile)
            assert a[0] == b[0] and a[4] == b[4]
            np.testing.assert_array_equal(a[3], b[3])


def test_fingerprint_matches_registry_scheme():
    A1 = poisson3d(6)[0]
    A2 = CSR(A1.ptr.copy(), A1.col.copy(), A1.val.copy(), A1.ncols)
    from amgcl_tpu.serve.registry import sparsity_fingerprint
    assert st.fingerprint(A1) == sparsity_fingerprint(A2)


# ---------------------------------------------------------------------------
# structure metrics on known matrices
# ---------------------------------------------------------------------------

def test_seven_point_stencil_metrics():
    """7-point stencil: exactly 7 occupied diagonals, near-zero ELL
    padding (boundary rows only), and the advisor reports no gain —
    the structure is already as banded as it gets."""
    A, _ = poisson3d(8)
    met = st.structure_metrics(A)
    assert met["diagonals"]["ndiags"] == 7
    # occupied offsets are exactly {0, ±1, ±8, ±64}
    offs = sorted(o for o, _, _ in met["diagonals"]["occupancy_top"])
    assert offs == [-64, -8, -1, 0, 1, 8, 64]
    # the main diagonal is fully occupied
    top = {o: c for o, c, _ in met["diagonals"]["occupancy_top"]}
    assert top[0] == A.nrows
    assert met["ell"]["k"] == 7 and met["ell"]["k_padded"] == 8
    # padding vs the raw max row length is only the Dirichlet boundary
    assert met["ell"]["pad_frac"] == pytest.approx(
        1.0 - A.nnz / (A.nrows * 7), abs=1e-4)
    assert met["ell"]["pad_frac"] < 0.15
    assert met["bandwidth"]["max"] == 64
    adv = st.advise(A, variants=("rcm",))
    best = adv.get("best")
    assert best is None or best["gain"] <= 1.02, \
        "advisor must report no gain on an already-banded stencil"
    # and no reorder_gain finding fires
    xray = {"levels": [{"level": 0, "metrics": met, "advisor": adv}],
            "summary": {}}
    codes = [f["code"] for f in st.structure_findings(xray)]
    assert "reorder_gain" not in codes


def test_permuted_banded_rcm_recovers_band():
    """Randomly-permuted banded matrix: RCM recovers the band, and the
    predicted ndiags / window densification is asserted."""
    A, A0, _perm = st.permuted_banded(4096, bw=4, seed=0)
    met = st.structure_metrics(A)
    assert met["diagonals"]["ndiags"] > 500          # scrambled
    adv = st.advise(A, variants=("rcm",))
    best = adv["best"]
    assert best["gain"] > 1.5
    nd_id, nd_rcm = best["densify"]["ndiags"]
    assert nd_id > 500
    assert nd_rcm <= 4 * (2 * 4 + 1)                 # band recovered
    # window span shrinks from full width toward the aligned band
    # (starts floor to the 1024 DMA alignment, so the recovered band
    # still pays up to two alignment quanta)
    win_id, win_rcm = best["densify"]["window_win"]
    assert win_id == 4096 and win_rcm < win_id
    wf_id, wf_rcm = best["densify"]["window_fill"]
    assert wf_rcm > wf_id
    bw_id, bw_rcm = best["densify"]["bandwidth_max"]
    assert bw_rcm < bw_id / 10


def test_block_structured_density_curve():
    """Block-structured CSR: the (8, 128) tile-granularity density
    curve pins exactly — dense 8x128 blocks on a block diagonal give
    128 occupied granules out of 1024, each completely full."""
    n = 1024
    rows_l, cols_l = [], []
    for band in range(n // 8):                  # 8-row bands
        c0 = 128 * (band % 8)                   # one 8x128 block each
        r = np.repeat(np.arange(band * 8, band * 8 + 8), 128)
        c = np.tile(np.arange(c0, c0 + 128), 8)
        rows_l.append(r)
        cols_l.append(c)
    rows = np.concatenate(rows_l)
    cols = np.concatenate(cols_l)
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    ptr = np.zeros(n + 1, np.int64)
    np.add.at(ptr, rows + 1, 1)
    A = CSR(np.cumsum(ptr), cols.astype(np.int32),
            np.ones(len(cols), np.float32), n)
    met = st.structure_metrics(A)
    curve = {c["granule"]: c for c in met["window"]["density_curve"]}
    # one tile (n=1024), win = 1024: 128x8 = 1024 granules of (8, 128)
    assert met["window"]["tiles"] == 1 and met["window"]["win"] == 1024
    assert curve["8x128"]["occupied_frac"] == pytest.approx(
        128 / 1024.0)
    assert curve["8x128"]["fill_in_occupied"] == pytest.approx(1.0)
    assert curve["1x1"]["occupied_frac"] == pytest.approx(
        A.nnz / (1024.0 * 1024.0))


# ---------------------------------------------------------------------------
# the format-decision ledger
# ---------------------------------------------------------------------------

def test_decision_recorded_on_auto_conversion():
    from amgcl_tpu.ops import device as dev
    A, _ = poisson3d(8)
    M = dev.to_device(A, "auto")
    dec = M._format_decision
    assert dec["fmt"] == "dia" and dec["reason"] == "cost"
    fmts = [c["format"] for c in dec["candidates"]]
    assert fmts == ["dense", "dia", "dwin", "well", "ell"]
    assert dec["margin"] is not None and dec["margin"] > 1.0
    # the DIA byte model is exact: predicted stored == built stored
    assert dec["built_bytes"] == dec["stored_bytes"]
    # every ineligible candidate names its reason
    for c in dec["candidates"]:
        assert c["eligible"] or c.get("why")


def test_decision_forced_reason():
    from amgcl_tpu.ops import device as dev
    A, _ = poisson3d(6)
    M = dev.to_device(A, "dia")
    assert M._format_decision["reason"] == "forced"
    M = dev.to_device(A, "dense")
    assert M._format_decision["reason"] == "forced"


def test_hierarchy_collects_decisions():
    amg = _amg(poisson3d(8)[0])
    decs = amg._format_decisions
    assert len(decs) == len(amg.host_levels)
    assert decs[0] is not None and decs[0]["fmt"] == "dia"
    assert all(d is None or d["reason"] in ("cost", "budget", "forced")
               for d in decs)


def test_rebuild_carries_decisions_over():
    A, _ = poisson3d(8)
    amg = _amg(A)
    before = [d and d["fmt"] for d in amg._format_decisions]
    amg.structure_report()
    assert amg._structure_cache is not None
    amg.rebuild(A.val.copy())
    # cache invalidated, decisions carried (refresh_values path)
    assert amg._structure_cache is None
    assert [d and d["fmt"] for d in amg._format_decisions] == before


def test_dense_window_budget_vs_window_reason():
    """The satellite fix: a dense-window decline distinguishes 'budget'
    (starved by earlier draws on the shared pool) from 'window'
    (structurally too wide for any budget)."""
    from amgcl_tpu.ops.densewin import csr_to_dense_window
    from amgcl_tpu.telemetry.ledger import DeviceMemoryBudget
    A, _ = poisson3d(8)
    # learn this matrix's dense-window footprint from a free dry run
    probe = {}
    assert csr_to_dense_window(
        A, budget=DeviceMemoryBudget(0), why=probe) is None
    need = probe["need_bytes"]
    assert need > 0
    # pool large enough in total, but drained by an earlier charge
    budget = DeviceMemoryBudget(2 * need)
    assert budget.try_charge(2 * need - 1024, "earlier_level")
    why = {}
    assert csr_to_dense_window(A, budget=budget, why=why) is None
    assert why["why"] == "budget"
    assert why["need_bytes"] == need
    # pool too small in total: structural, not budget starvation
    why = {}
    assert csr_to_dense_window(
        A, budget=DeviceMemoryBudget(1024), why=why) is None
    assert why["why"] == "window"


def test_candidate_table_budget_reason_and_decision():
    A, _ = poisson3d(8)
    need = st.fast_facts(A)["dwin_bytes"]
    cands = st.candidate_table(A, on_tpu=True,
                               budget_remaining=need // 2,
                               budget_total=10 * need)
    dwin = next(c for c in cands if c["format"] == "dwin")
    assert not dwin["eligible"] and dwin["why"] == "budget"
    # the realistic starved shape: auto fell THROUGH dwin (which it
    # prefers for gather-freedom, whatever the byte ranking) to a
    # later format — the pick is budget-starved, not a cost win
    for fallback in ("well", "ell"):
        assert st.decision_record(cands, fallback)["reason"] == "budget"
    # a winner auto prefers OVER dwin (dia wins before the budget is
    # even consulted) stays a cost win
    assert st.decision_record(cands, "dia")["reason"] == "cost"
    assert st.decision_record(cands, "ell",
                              forced=True)["reason"] == "forced"


def _fe_rcm(n=2048, seed=33):
    from amgcl_tpu.ops.unstructured import fe_like_problem
    from amgcl_tpu.utils.adapters import cuthill_mckee, permute
    A, _ = fe_like_problem(n=n, nnz_target=n * 25, seed=seed)
    return permute(A, cuthill_mckee(A))


def test_candidate_table_prices_well_scan_on_tpu():
    """With on_tpu, windowed ELL of <= 32-bit scalar values is priced by
    its bytes plus the lane-gather scan (entry vregs x mean x rows, at
    WELL_SCAN_STEP_S each, as bytes at HBM rate), ELL by XLA's gather;
    off TPU every price is the predicted bytes."""
    A = _fe_rcm()
    scan = st.well_scan(A)
    on = {c["format"]: c for c in st.candidate_table(A, on_tpu=True)}
    off = {c["format"]: c for c in st.candidate_table(A, on_tpu=False)}
    well = on["well"]
    assert well["kernel"] == "pallas"
    assert well["entry_vregs"] == scan["entry_vregs"]
    assert well["scan_xrows_mean"] == scan["scan_xrows_mean"]
    assert well["price"] == int(
        well["predicted"]["bytes"] + scan["steps"] * st.WELL_SCAN_STEP_S
        * st.TPU_HBM_BYTES_PER_S)
    assert on["ell"]["price"] > on["ell"]["predicted"]["bytes"]
    assert all(c["price"] == c["predicted"]["bytes"]
               and "kernel" not in c for c in off.values())
    # a 64-bit operator takes XLA's gather, priced per stored slot
    well64 = next(c for c in st.candidate_table(A, itemsize=8, on_tpu=True)
                  if c["format"] == "well")
    assert well64["kernel"] == "xla" and "entry_vregs" not in well64


def test_decision_record_carries_well_kernel(monkeypatch):
    """A windowed-ELL decision records which SpMV the built matrix runs
    (kernel, and kernel_why when it is XLA's), its entry vregs and the
    mean x rows the kernel scans per vreg."""
    import jax.numpy as jnp
    from amgcl_tpu.ops import device as dev
    A = _fe_rcm(n=4096)
    M = dev.to_device(A, "auto", jnp.float32, dense_cutoff=256)
    dec = M._format_decision
    assert dec["fmt"] == "well"
    assert (dec["kernel"], dec["kernel_why"]) == ("xla", "not on TPU")
    assert dec["entry_vregs"] == M.cols_local.shape[0]
    assert dec["scan_xrows_mean"] == st.well_scan(A)["scan_xrows_mean"]
    monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    dec = dev.to_device(A, "auto", jnp.float32,
                        dense_cutoff=256)._format_decision
    assert dec["kernel"] == "pallas" and "kernel_why" not in dec


# ---------------------------------------------------------------------------
# host-purity contract (STRUCTURE_CONTRACTS)
# ---------------------------------------------------------------------------

def test_structure_audit_contract():
    from amgcl_tpu.analysis import jaxpr_audit as ja
    rec = ja.audit_structure(m=6)
    assert rec["jax_imports"] == 0, rec.get("jax_import_names")
    assert not rec.get("skipped"), rec
    assert rec["new_traces"] == 0
    assert rec["new_backend_compiles"] == 0
    assert ja.check_structure(rec) == []


def test_structure_report_compile_watch_delta_zero():
    from amgcl_tpu.telemetry import compile_watch as cw
    amg = _amg(poisson3d(8)[0])
    before = cw.snapshot()["totals"]
    xray = amg.structure_report(advise=True)
    st.structure_findings(xray)
    st.format_xray(xray)
    after = cw.snapshot()["totals"]
    assert after["traces"] == before["traces"]
    assert after["backend_compiles"] == before["backend_compiles"]


# ---------------------------------------------------------------------------
# surfacing: hierarchy_stats fold, doctor fold, gauges, rollups, diff
# ---------------------------------------------------------------------------

def test_hierarchy_stats_folds_structure():
    amg = _amg(poisson3d(8)[0])
    assert "structure" not in amg.hierarchy_stats()["levels"][0]
    amg.structure_report()
    stats = amg.hierarchy_stats()
    srow = stats["levels"][0]["structure"]
    assert srow["ndiags"] == 7
    assert srow["decision"]["fmt"] == "dia"
    assert stats["structure"]["formats"].startswith("dia")
    # JSON-clean (rides the 'hierarchy' telemetry event)
    json.dumps(stats)


def test_diagnose_folds_structure_findings(monkeypatch):
    from amgcl_tpu.telemetry.health import diagnose
    # keep the identity order: with the executed reorder the hierarchy is
    # built already permuted and there is no gain left to advise
    monkeypatch.setenv("AMGCL_TPU_REORDER", "off")
    A, _, _ = st.permuted_banded(2048, bw=4, seed=0)
    amg = _amg(A, coarse_enough=40)
    xray = amg.structure_report(advise=True)
    findings = diagnose(None, structure=xray)
    codes = [f.get("code") for f in findings]
    assert "reorder_gain" in codes
    f = next(f for f in findings if f["code"] == "reorder_gain")
    assert f["predicted_gain"] > 1.15
    assert "reorder" in f["suggestion"].lower() or \
        "Reordered" in f["suggestion"]


def test_publish_xray_gauges():
    from amgcl_tpu.telemetry.live import LiveRegistry, \
        publish_xray_gauges
    reg = LiveRegistry()
    publish_xray_gauges(reg, {"padding_waste_frac": 0.25,
                              "predicted_reorder_gain": 2.5,
                              "dia_fill": 1.1})
    text = reg.prometheus()
    assert "xray_padding_waste_frac 0.25" in text
    assert "xray_predicted_reorder_gain 2.5" in text
    assert "xray_dia_fill 1.1" in text


def test_rollup_specs_pick_up_new_events():
    from amgcl_tpu.telemetry import metrics
    recs = [
        {"event": "structure",
         "summary": {"padding_waste_frac": 0.2, "dia_fill": 1.1,
                     "predicted_reorder_gain": 2.0,
                     "window_fill": 0.5, "bandwidth_max": 10}},
        {"event": "bench_xray",
         "join": {"predicted_gain": 2.0, "measured_gain": 1.8,
                  "ratio": 0.9}},
    ]
    out = metrics.rollup_events(recs)
    assert out["structure.padding_waste_frac"]["last"] == 0.2
    assert out["bench_xray.measured_gain"]["last"] == 1.8
    assert out["bench_xray.gain_ratio"]["last"] == 0.9


def test_diff_names_format_decision_changes():
    from amgcl_tpu.telemetry import diff as dmod
    a = {"metric": "solve", "value": 1.0, "iters": 5,
         "device_platform": "cpu",
         "structure": {"formats": "ell/dense", "reasons": "cost/cost"}}
    b = {"metric": "solve", "value": 1.0, "iters": 5,
         "device_platform": "cpu",
         "structure": {"formats": "dia/dense",
                       "reasons": "budget/cost"}}
    d = dmod.diff(a, b)
    assert d["structure"]["changed"]
    codes = [f["code"] for f in dmod.findings(d)]
    assert "cross_run_format" in codes
    assert "format decisions" in dmod.format_diff(d)
    # identical summaries produce no call-out
    assert "structure" not in dmod.diff(a, dict(a))


# ---------------------------------------------------------------------------
# cli / bench surfaces
# ---------------------------------------------------------------------------

def test_cli_xray_smoke(capsys):
    from amgcl_tpu import cli
    rc = cli.main(["-n", "8", "--xray", "--doctor",
                   "-p", "precond.coarse_enough=50"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "Operator X-ray:" in out
    assert "Format-decision ledger" in out
    assert "Convergence doctor" in out


def test_bench_xray_smoke(monkeypatch):
    import bench
    emitted = []
    monkeypatch.setattr(bench._stdout_sink, "emit",
                        lambda rec, **kw: emitted.append(dict(rec)))
    monkeypatch.setenv("AMGCL_TPU_XRAY_N", "1024")
    monkeypatch.setenv("AMGCL_TPU_XRAY_BW", "3")
    rc = bench.main_xray()
    rec = emitted[-1]
    json.dumps(rec)
    assert rc == 0
    assert rec["event"] == "bench_xray"
    assert rec["join"]["predicted_gain"] > 1.0
    assert rec["join"]["measured_gain"] is not None
    assert rec["provenance"]["platform_tag"] in ("ici", "cpu-fallback")
    # per-format rows: ELL always measures on both sides
    ell = next(r for r in rec["formats"] if r["format"] == "ell")
    assert ell["t_identity_s"] and ell["t_rcm_s"]


def test_bench_worker_summary_shape():
    """The compact summary bench.py embeds on every worker record is
    JSON-clean and carries the attribution fields the trend reads."""
    amg = _amg(poisson3d(8)[0])
    summ = st.xray_summary(amg.structure_report(advise=False))
    json.dumps(summ)
    assert summ["formats"].startswith("dia")
    assert summ["reasons"].startswith("cost")
    assert summ["padding_waste_frac"] is not None
    assert summ["fingerprint"]
