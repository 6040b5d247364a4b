"""Executed reorder seam (ISSUE 20): the advisor's RCM/CM permutation is
APPLIED at build time — hierarchy + transfers absorb it, rhs/x0 are
permuted in and x un-permuted out — and must be semantically invisible:
solution parity in f64, batched (n, B) pass-through, rebuild/farm plan
reuse through the fingerprint cache, ledger-driven format winners
flipping on the permuted-banded fixture, and the windowed-ELL SpMV on
the reordered operator."""

import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from amgcl_tpu.models.amg import AMG, AMGParams
from amgcl_tpu.models.make_solver import make_solver
from amgcl_tpu.ops import device as dev
from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops.unstructured import csr_to_windowed_ell
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.telemetry import structure as st


def _fixture(n=512, bw=4, seed=0):
    A, A0, perm = st.permuted_banded(n, bw=bw, seed=seed)
    rng = np.random.RandomState(seed + 1)
    return A, A0, rng.rand(n)


# -- the plan and its fingerprint cache --------------------------------------

def test_reorder_plan_shape_and_cache(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_REORDER", "rcm")
    A, _, _ = _fixture()
    p1 = st.reorder_plan(A)
    assert p1 is not None
    n = A.nrows
    assert sorted(p1["perm"].tolist()) == list(range(n))
    np.testing.assert_array_equal(p1["iperm"][p1["perm"]], np.arange(n))
    assert p1["variant"] == "rcm"
    assert p1["fingerprint"] == st.fingerprint(A)
    assert p1["val_perm"].shape == (A.val.size,)
    # same pattern, fresh object -> SAME plan object (fingerprint keyed)
    B = CSR(A.ptr, A.col, A.val * 3.0, A.ncols)
    assert st.reorder_plan(B) is p1


def test_reorder_off_and_identity_decline(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_REORDER", "0")
    A, A0, _ = _fixture()
    assert st.reorder_plan(A) is None
    # auto declines the already-banded matrix: no predicted gain
    monkeypatch.setenv("AMGCL_TPU_REORDER", "auto")
    assert st.reorder_plan(A0) is None
    # ...but takes the scrambled one
    plan = st.reorder_plan(A)
    assert plan is not None and plan["predicted_gain"] >= st.GAIN_FLOOR


# -- solution parity through the solver seam ---------------------------------

def _solve(A, rhs, mode, monkeypatch, **kw):
    monkeypatch.setenv("AMGCL_TPU_REORDER", mode)
    s = make_solver(A, AMGParams(dtype=jnp.float64),
                    CG(maxiter=200, tol=1e-12), **kw)
    x, info = s(rhs)
    return s, np.asarray(x, np.float64), info


def test_solution_parity_f64(monkeypatch):
    A, _, rhs = _fixture()
    s_id, x_id, i_id = _solve(A, rhs, "0", monkeypatch)
    s_r, x_r, i_r = _solve(A, rhs, "rcm", monkeypatch)
    assert s_id.precond._reorder is None
    assert s_r.precond._reorder is not None
    # permutation changes reduction orders, so parity is to machine
    # precision (documented in DESIGN §21), not bit-for-bit
    np.testing.assert_allclose(x_r, x_id, rtol=1e-9, atol=1e-12)
    assert abs(int(i_r.iters) - int(i_id.iters)) <= 2
    # the residual reported is for the ORIGINAL-order system
    r = rhs - A.spmv(x_r)
    assert np.linalg.norm(r) / np.linalg.norm(rhs) < 1e-10


def test_batched_rhs_passthrough(monkeypatch):
    A, _, _ = _fixture()
    rng = np.random.RandomState(9)
    Rhs = rng.rand(A.nrows, 3)
    s_id, X_id, _ = _solve(A, Rhs, "0", monkeypatch)
    s_r, X_r, _ = _solve(A, Rhs, "rcm", monkeypatch)
    assert X_r.shape == Rhs.shape
    np.testing.assert_allclose(X_r, X_id, rtol=1e-9, atol=1e-12)


# -- rebuild / farm reuse ----------------------------------------------------

def test_rebuild_reuses_plan_values_only(monkeypatch):
    """AMG-level values-only rebuild: callers hand back values in the
    ORIGINAL ordering (time-dependent loops never learn about the
    permutation); val_perm maps them into the permuted frame the
    hierarchy lives in, and the cached plan survives the refresh."""
    monkeypatch.setenv("AMGCL_TPU_REORDER", "rcm")
    A, _, _ = _fixture()
    amg = AMG(A, AMGParams(dtype=jnp.float64))
    plan = amg._reorder
    assert plan is not None
    amg.rebuild(A.val * 2.0)
    assert amg._reorder is plan                # no recompute
    hl0 = amg.host_levels[0][0]
    np.testing.assert_array_equal(
        np.asarray(hl0.val),
        np.asarray(A.val)[plan["val_perm"]] * 2.0)


def test_rebuild_accepts_original_order_csr(monkeypatch):
    A, _, rhs = _fixture()
    s, x1, _ = _solve(A, rhs, "rcm", monkeypatch)
    plan = s.precond._reorder
    A2 = CSR(A.ptr, A.col, A.val * 2.0, A.ncols)
    s.rebuild(A2)
    assert s.precond._reorder is plan
    x2, _ = s(rhs)
    np.testing.assert_allclose(np.asarray(x2), x1 / 2.0,
                               rtol=1e-9, atol=1e-12)


def test_same_pattern_builds_share_plan(monkeypatch):
    """The farm/registry reuse path: a re-registration of a same-pattern
    operator finds the permutation already computed (module cache keyed
    by the SAME fingerprint serve/registry.py uses)."""
    monkeypatch.setenv("AMGCL_TPU_REORDER", "rcm")
    A, _, _ = _fixture()
    B = CSR(A.ptr, A.col, A.val * 5.0, A.ncols)
    amg1 = AMG(A, AMGParams(dtype=jnp.float64))
    amg2 = AMG(B, AMGParams(dtype=jnp.float64))
    assert amg1._reorder is not None
    assert amg2._reorder is amg1._reorder


def test_release_readmit_roundtrip(monkeypatch):
    A, _, rhs = _fixture()
    s, x1, _ = _solve(A, rhs, "rcm", monkeypatch)
    s.release_device()
    s.readmit()
    x2, _ = s(rhs)
    np.testing.assert_allclose(np.asarray(x2), x1, rtol=1e-9,
                               atol=1e-12)


# -- ledger-driven auto-format ----------------------------------------------

def test_decision_winner_flips_on_reorder(monkeypatch):
    """On the permuted-banded fixture the identity layout cannot pack
    diagonals (thousands of them) while the reordered one is a clean
    band: the ledger-ranked auto pick flips format and the chosen
    layout's predicted bytes drop."""
    from amgcl_tpu.utils.adapters import permute
    A, _, _ = st.permuted_banded(4096, bw=4, seed=0)
    plan = st.reorder_plan(A, mode="rcm")
    Ar = permute(A, plan["perm"])
    M_id = dev.to_device(A, "auto", jnp.float64)
    M_r = dev.to_device(Ar, "auto", jnp.float64)
    d_id, d_r = M_id._format_decision, M_r._format_decision
    assert d_r["fmt"] != d_id["fmt"]

    def _pred(dec):
        row = [c for c in dec["candidates"]
               if c["format"] == dec["fmt"]][0]
        return row["predicted"]["bytes"]

    assert _pred(d_r) < _pred(d_id)


def test_decision_records_reorder_provenance(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_REORDER", "rcm")
    A, _, _ = _fixture(n=1024)
    amg = AMG(A, AMGParams(dtype=jnp.float64))
    decs = amg._format_decisions
    assert decs, "level decisions missing"
    prov = decs[0].get("reorder")
    assert prov and prov["variant"] == "rcm"
    assert prov["fingerprint"] == st.fingerprint(A)


# -- windowed-ELL SpMV on the reordered operator -----------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_windowed_ell_spmv_reordered(dtype):
    _, A0, _ = _fixture(n=2048)
    W = csr_to_windowed_ell(A0, dtype)
    assert W is not None and W.block == (1, 1)
    x = jnp.asarray(np.random.RandomState(2).rand(A0.ncols), dtype)
    y = np.asarray(W.mv(x), np.float64)
    y_host = A0.spmv(np.asarray(x, np.float64))
    tol = 1e-12 if dtype == jnp.float64 else 1e-5
    np.testing.assert_allclose(y, y_host, rtol=tol,
                               atol=tol * np.abs(y_host).max())


def test_windowed_ell_spmv_jit_and_stacked():
    """The jitted SpMV equals the eager one, and a stacked (n, B) operand
    through the device seam equals the per-column products."""
    _, A0, _ = _fixture(n=2048)
    W = csr_to_windowed_ell(A0, jnp.float32)
    X = jnp.asarray(np.random.RandomState(3).rand(A0.ncols, 3),
                    jnp.float32)
    y_eager = np.asarray(W.mv(X[:, 0]))
    y_jit = np.asarray(jax.jit(lambda M, v: M.mv(v))(W, X[:, 0]))
    np.testing.assert_allclose(y_jit, y_eager, rtol=1e-6,
                               atol=1e-6 * np.abs(y_eager).max())
    Y = np.asarray(dev.spmv(W, X))
    for j in range(3):
        yj = np.asarray(W.mv(X[:, j]))
        np.testing.assert_allclose(Y[:, j], yj, rtol=1e-6,
                                   atol=1e-6 * np.abs(yj).max())


# -- flight-recorder replay parity under reorder -----------------------------

def test_replay_parity_reordered(monkeypatch, tmp_path):
    """A bundle dumped from a reordered solve replays with identical
    layout: provenance (fingerprint + advisor variant) is in the
    manifest and parity holds on the same platform."""
    from amgcl_tpu.telemetry import flight
    flight._reset_for_tests()
    monkeypatch.setenv("AMGCL_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("AMGCL_TPU_REORDER", "rcm")
    A, _, rhs = _fixture()
    s = make_solver(A, AMGParams(dtype=jnp.float64),
                    CG(maxiter=200, tol=1e-12))
    x, info = s(rhs)
    assert s.precond._reorder is not None
    path = flight.dump("reorder_parity", bundle=s, rhs=rhs,
                       report=info)
    assert path
    with open(os.path.join(path, "manifest.json")) as f:
        man = json.load(f)
    prov = man.get("reorder")
    assert prov and prov["variant"] == "rcm"
    assert prov["fingerprint"] == st.fingerprint(A)
    result = flight.run_replay(path)
    assert result["ok"], result
    rows = {c["check"]: c for c in result["parity"]["checks"]}
    assert rows["iters"]["status"] == "ok"
    assert rows["resid"]["status"] == "ok"
    flight._reset_for_tests()


# -- the TPU price of windowed ELL: the kernel's scan ------------------------

def _fe(n=2048, seed=31):
    from amgcl_tpu.ops.unstructured import fe_like_problem
    return fe_like_problem(n=n, nnz_target=n * 25, seed=seed)[0]


def test_reorder_plan_on_tpu_prices_the_scan():
    """On TPU the advisor prices windowed ELL by its lane-gather scan:
    identity order scans many more x rows per entry vreg than the
    Cuthill-McKee order, so the plan executes the reorder. Off TPU the
    price is bytes alone, identity and reordered cost the same, and no
    reorder runs."""
    A = _fe()
    adv = st.advise(A, on_tpu=True)
    assert adv["identity"]["best"] == "well"
    assert adv["best"]["format"] == "well"
    assert adv["best"]["gain"] >= st.GAIN_FLOOR
    plan = st.reorder_plan(A, on_tpu=True, mode="auto")
    assert plan is not None and plan["variant"] in ("rcm", "cm")
    assert plan["predicted_gain"] == adv["best"]["gain"]
    # the plan cache keys on the platform: the CPU decision is its own
    assert st.reorder_plan(A, on_tpu=False, mode="auto") is None
    assert "best" not in st.advise(A, on_tpu=False)


def test_setup_span_counts_well_kernels(monkeypatch):
    """setup/hierarchy carries how many windowed-ELL operators take the
    lane-gather kernel (well_pallas) and how many XLA's gather
    (well_xla)."""
    from amgcl_tpu.telemetry import tracing
    from amgcl_tpu.ops.unstructured import WindowedEllMatrix
    monkeypatch.setenv("AMGCL_TPU_REORDER", "rcm")
    A = _fe(n=4096, seed=32)
    counts = {}
    for hook in ("0", "1"):
        monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", hook)
        amg = AMG(A, AMGParams(coarse_enough=500))
        attrs = tracing.RECORDER.spans("setup/hierarchy")[-1][5]
        counts[hook] = (attrs["well_pallas"], attrs["well_xla"])
        n_well = sum(isinstance(M, WindowedEllMatrix)
                     for lv in amg.hierarchy.levels
                     for M in (lv.A, lv.P, lv.R))
        assert n_well >= 1 and sum(counts[hook]) == n_well
    assert counts["0"][0] == 0 and counts["1"][1] == 0
