"""Windowed-ELL unstructured SpMV: packing, the device seams (SpMV,
residual, smoother, fused dots), and end-to-end AMG solves on FE-style
irregular matrices
(reference capability: general-sparsity device SpMV,
amgcl/backend/cuda.hpp:60-843)."""

import numpy as np
import jax.numpy as jnp
import pytest

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops import device as dev
from amgcl_tpu.ops.unstructured import (
    WindowedEllMatrix, csr_to_windowed_ell, fe_like_problem, _TILE,
    _WIN_ALIGN)
from amgcl_tpu.utils.adapters import cuthill_mckee, permute


def _small_fe(n=3000, seed=1):
    A, rhs = fe_like_problem(n=n, nnz_target=n * 18, seed=seed)
    return A, rhs


def test_windowed_ell_matches_host_spmv():
    A, _ = _small_fe()
    perm = cuthill_mckee(A)
    Ap = permute(A, perm)
    W = csr_to_windowed_ell(Ap, jnp.float64)
    assert W is not None
    x = np.random.RandomState(0).rand(A.nrows)
    y_ref = Ap.spmv(x)
    y = np.asarray(W.mv(jnp.asarray(x)))
    np.testing.assert_allclose(y, y_ref, rtol=1e-12)


def test_windowed_ell_f32_matches_host_spmv():
    A, _ = _small_fe(n=2500, seed=2)
    perm = cuthill_mckee(A)
    Ap = permute(A, perm)
    W = csr_to_windowed_ell(Ap, jnp.float32)
    x = np.random.RandomState(1).rand(A.nrows).astype(np.float32)
    y_ref = Ap.spmv(x.astype(np.float64))
    y = np.asarray(W.mv(jnp.asarray(x)))
    # scale-aware atol: the 1/h² fixture weights span ~3 orders, so rows
    # with catastrophic cancellation bound the f32 error absolutely (by
    # ~max|y|·eps·√k), not relatively
    np.testing.assert_allclose(y, y_ref, rtol=2e-4,
                               atol=1e-4 * np.abs(y_ref).max())


def test_rcm_shrinks_windows():
    A, _ = _small_fe(n=4000, seed=3)
    W_raw = csr_to_windowed_ell(A, jnp.float32)
    perm = cuthill_mckee(A)
    W_rcm = csr_to_windowed_ell(permute(A, perm), jnp.float32)
    assert W_rcm is not None
    # RCM must genuinely shrink the per-tile column span on a kNN graph
    # (review r3: the pre-fix window computation made this vacuous)
    if W_raw is not None:
        assert W_rcm.win < W_raw.win
    assert W_rcm.win < 4000 // _TILE * _WIN_ALIGN + 2 * _WIN_ALIGN


def test_to_device_auto_picks_windowed_for_banded_irregular():
    A, _ = _small_fe(n=4096, seed=4)
    Ap = permute(A, cuthill_mckee(A))
    M = dev.to_device(Ap, "auto", jnp.float32, dense_cutoff=256)
    # irregular (not DIA-eligible at CPU thresholds) but banded -> windowed
    assert isinstance(M, WindowedEllMatrix)
    x = np.random.RandomState(2).rand(A.nrows)
    want = Ap.spmv(x)
    np.testing.assert_allclose(
        np.asarray(M.mv(jnp.asarray(x, dtype=jnp.float32))),
        want, rtol=2e-4, atol=1e-4 * np.abs(want).max())


def _windowed_fixture(n=2500, seed=7):
    A, _ = _small_fe(n=n, seed=seed)
    Ap = permute(A, cuthill_mckee(A))
    W = csr_to_windowed_ell(Ap, jnp.float32)
    rng = np.random.RandomState(seed)
    x = rng.rand(Ap.nrows).astype(np.float32)
    f = rng.rand(Ap.nrows).astype(np.float32)
    w = rng.rand(Ap.nrows).astype(np.float32)
    return Ap, W, x, f, w


def test_windowed_residual_seam():
    Ap, W, x, f, _ = _windowed_fixture()
    r_ref = f - Ap.spmv(x.astype(np.float64))
    r = np.asarray(dev.residual(jnp.asarray(f), W, jnp.asarray(x)))
    np.testing.assert_allclose(r, r_ref, rtol=5e-4, atol=5e-4)


def test_windowed_scaled_correction_composes():
    """Windowed ELL has no fused correction kernel: the seam declines
    and the smoother composes residual and scale."""
    from amgcl_tpu.relaxation.base import ScaledResidualSmoother
    Ap, W, x, f, w = _windowed_fixture(seed=8)
    assert dev.scaled_correction(W, jnp.asarray(w), jnp.asarray(f),
                                 jnp.asarray(x)) is None
    ref = x + w * (f - Ap.spmv(x.astype(np.float64)))
    got = np.asarray(ScaledResidualSmoother(jnp.asarray(w)).apply_pre(
        W, jnp.asarray(f), jnp.asarray(x)))
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_windowed_spmv_dots_seam():
    Ap, W, x, _, w = _windowed_fixture(seed=9)
    y_ref = Ap.spmv(x.astype(np.float64))
    y, yy, yx, yw = dev.spmv_dots(W, jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(float(yy), y_ref @ y_ref, rtol=1e-3)
    np.testing.assert_allclose(float(yx), y_ref @ x, rtol=1e-3)
    np.testing.assert_allclose(float(yw), y_ref @ w, rtol=1e-3)
    # w=None leg returns yw=None and the same pairs
    y2, yy2, yx2, yw2 = dev.spmv_dots(W, jnp.asarray(x))
    assert yw2 is None
    np.testing.assert_allclose(float(yx2), float(yx), rtol=1e-6)


def test_windowed_seams_under_interpret_hook(monkeypatch):
    """With the CI interpret hook on, the windowed-ELL seams run the
    lane-gather kernel (``well_spmv``, interpret mode) and agree with the
    host."""
    monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    Ap, W, x, f, w = _windowed_fixture(seed=10)
    r = np.asarray(dev.residual(jnp.asarray(f), W, jnp.asarray(x)))
    np.testing.assert_allclose(
        r, f - Ap.spmv(x.astype(np.float64)), rtol=5e-4, atol=5e-4)
    y, yy, yx, yw = dev.spmv_dots(W, jnp.asarray(x), jnp.asarray(w))
    y_ref = Ap.spmv(x.astype(np.float64))
    np.testing.assert_allclose(float(yx), y_ref @ x, rtol=1e-3)
    from amgcl_tpu.relaxation.base import ScaledResidualSmoother
    sm = ScaledResidualSmoother(jnp.asarray(w))
    got = np.asarray(sm.apply_pre(W, jnp.asarray(f), jnp.asarray(x)))
    ref = x + w * (f - Ap.spmv(x.astype(np.float64)))
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def _block_fixture(n_pt=1500, b=3, seed=12):
    """Block-valued FE-style fixture: scalar kNN Laplacian re-blocked."""
    A, _ = _small_fe(n=n_pt * b, seed=seed)
    Ap = permute(A, cuthill_mckee(A))
    Ab = Ap.to_block(b)
    W = csr_to_windowed_ell(Ab, jnp.float32)
    assert W is not None and W.block == (b, b)
    rng = np.random.RandomState(seed)
    x = rng.rand(n_pt * b).astype(np.float32)
    f = rng.rand(n_pt * b).astype(np.float32)
    S = rng.rand(n_pt, b, b).astype(np.float32) * 0.1
    return Ab, W, x, f, S


def test_windowed_block_spmv_matches_host():
    Ab, W, x, _, _ = _block_fixture()
    y_ref = Ab.unblock().spmv(x.astype(np.float64))
    y = np.asarray(W.mv(jnp.asarray(x)))
    np.testing.assert_allclose(y, y_ref, rtol=5e-4, atol=5e-4)


def test_windowed_block_residual_and_correction():
    from amgcl_tpu.relaxation.base import ScaledResidualSmoother
    Ab, W, x, f, S = _block_fixture(seed=13)
    ax = Ab.unblock().spmv(x.astype(np.float64))
    r_ref = f - ax
    r = np.asarray(dev.residual(jnp.asarray(f), W, jnp.asarray(x)))
    np.testing.assert_allclose(r, r_ref, rtol=5e-4, atol=5e-4)
    b = W.block[0]
    corr_ref = x + np.einsum(
        "nij,nj->ni", S, r_ref.reshape(-1, b)).reshape(-1)
    sm = ScaledResidualSmoother(jnp.asarray(S), block=b)
    got = np.asarray(sm.apply_pre(W, jnp.asarray(f), jnp.asarray(x)))
    np.testing.assert_allclose(got, corr_ref, rtol=5e-4, atol=5e-4)


def test_windowed_block_spmv_dots_seam():
    Ab, W, x, _, _ = _block_fixture(seed=16)
    w = np.random.RandomState(16).rand(x.shape[0]).astype(np.float32)
    y_ref = Ab.unblock().spmv(x.astype(np.float64))
    y, yy, yx, yw = dev.spmv_dots(W, jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(float(yy), y_ref @ y_ref, rtol=1e-3)
    np.testing.assert_allclose(float(yx), y_ref @ x, rtol=1e-3)
    np.testing.assert_allclose(float(yw), y_ref @ w, rtol=1e-3)


def test_windowed_block_seams_under_interpret_hook(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    Ab, W, x, f, S = _block_fixture(seed=14)
    r = np.asarray(dev.residual(jnp.asarray(f), W, jnp.asarray(x)))
    ax = Ab.unblock().spmv(x.astype(np.float64))
    np.testing.assert_allclose(r, f - ax, rtol=5e-4, atol=5e-4)
    from amgcl_tpu.relaxation.base import ScaledResidualSmoother
    sm = ScaledResidualSmoother(jnp.asarray(S), block=W.block[0])
    got = np.asarray(sm.apply_pre(W, jnp.asarray(f), jnp.asarray(x)))
    b = W.block[0]
    ref = x + np.einsum("nij,nj->ni", S,
                        (f - ax).reshape(-1, b)).reshape(-1)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_block_solver_windowed_end_to_end():
    """make_block_solver on an RCM-banded problem: the block windowed-ELL
    device format carries the whole solve."""
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.bicgstab import BiCGStab
    b = 2
    A, rhs = _small_fe(n=2000 * b, seed=15)
    Ap = permute(A, cuthill_mckee(A))
    rhs_p = rhs[cuthill_mckee(A)]
    Ab = Ap.to_block(b)
    M = dev.to_device(Ab, "auto", jnp.float32)
    assert isinstance(M, WindowedEllMatrix) and M.block == (b, b)
    solve = make_solver(Ab, AMGParams(dtype=jnp.float64),
                        BiCGStab(tol=1e-8))
    x, info = solve(rhs_p)
    r = rhs_p - Ap.spmv(np.asarray(x, np.float64))
    assert np.linalg.norm(r) / np.linalg.norm(rhs_p) < 1e-6


def test_windowed_bf16_values():
    """bfloat16 operator values in windowed ELL (the HBM-halving
    hierarchy option): packing, SpMV, and residual stay within bf16
    accuracy of the f64 reference."""
    Ap, _, x, f, _ = _windowed_fixture(seed=17)
    Wb = csr_to_windowed_ell(Ap, jnp.bfloat16)
    assert Wb is not None and Wb.dtype == jnp.bfloat16
    y_ref = Ap.spmv(x.astype(np.float64))
    y = np.asarray(Wb.mv(jnp.asarray(x)), np.float64)
    denom = np.abs(y_ref).max()
    assert np.abs(y - y_ref).max() / denom < 3e-2      # bf16 epsilon
    r = np.asarray(dev.residual(jnp.asarray(f), Wb, jnp.asarray(x)),
                   np.float64)
    assert np.abs(r - (f - y_ref)).max() / denom < 3e-2


def test_transfers_take_windowed_format():
    """Hierarchy P/R go through auto format selection: on an RCM-banded
    problem with explicit transfers (Ruge-Stuben) they must pick the
    windowed-ELL device format, like the level operators."""
    from amgcl_tpu.models.amg import AMG, AMGParams
    from amgcl_tpu.coarsening.ruge_stuben import RugeStuben
    A, _ = _small_fe(n=6000, seed=18)
    Ap = permute(A, cuthill_mckee(A))
    amg = AMG(Ap, AMGParams(coarsening=RugeStuben()))
    lv0 = amg.hierarchy.levels[0]
    assert isinstance(lv0.P, WindowedEllMatrix), type(lv0.P).__name__
    assert isinstance(lv0.R, WindowedEllMatrix), type(lv0.R).__name__


def test_amg_solve_fe_like():
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.cg import CG
    A, rhs = _small_fe(n=5000, seed=5)
    Ap = permute(A, cuthill_mckee(A))
    rhs_p = rhs[cuthill_mckee(A)]
    solve = make_solver(Ap, AMGParams(dtype=jnp.float64), CG(tol=1e-8))
    x, info = solve(rhs_p)
    r = rhs_p - Ap.spmv(np.asarray(x))
    assert np.linalg.norm(r) / np.linalg.norm(rhs_p) < 1e-6


# -- the lane-gather kernel (well_spmv), interpret mode -----------------------

def _kernel_case(case):
    """(host CSR, value dtype) for one kernel case."""
    import scipy.sparse as sp
    if case in ("rcm_fe_4096", "bf16"):
        A, _ = fe_like_problem(n=4096, nnz_target=4096 * 25, seed=21)
        A = permute(A, cuthill_mckee(A))
        return A, jnp.bfloat16 if case == "bf16" else jnp.float32
    rng = np.random.RandomState(22)
    n = 1000 if case == "rows_not_multiple_of_128" else 1536
    band = sp.random(n, n, density=0.01, random_state=rng, format="csr")
    M = (sp.diags(np.arange(1.0, n + 1)) + sp.triu(sp.tril(band, 300),
                                                   -300)).tolil()
    if case == "empty_rows":
        for r in (0, 5, 700, 1535):
            M.rows[r], M.data[r] = [], []
    elif case == "max_k_row":
        # one row reaches the maximum K (all of its 2x128-column band)
        M[900, 640:896] = rng.rand(256)
    M = M.tocsr()
    M.sort_indices()
    return CSR.from_scipy(M), jnp.float32


@pytest.mark.parametrize("case", ["rcm_fe_4096", "rows_not_multiple_of_128",
                                  "empty_rows", "max_k_row", "bf16"])
def test_well_kernel_matches_host(case):
    from amgcl_tpu.ops.unstructured import well_spmv
    A, vdt = _kernel_case(case)
    W = csr_to_windowed_ell(A, vdt)
    assert W is not None
    x = np.random.RandomState(3).rand(A.ncols).astype(np.float32)
    # the reference multiplies the values the device holds
    Ah = CSR(A.ptr, A.col, np.asarray(jnp.asarray(A.val, vdt), np.float64),
             A.ncols)
    ref = Ah.spmv(x.astype(np.float64))
    y = np.asarray(well_spmv(W.scan, W.window_starts, W.cols_local,
                             W.vals, jnp.asarray(x), n_out=A.nrows,
                             win=W.win, kv=W.kv, interpret=True), np.float64)
    np.testing.assert_allclose(y, ref, rtol=1e-5,
                               atol=1e-5 * np.abs(ref).max())
    if case == "empty_rows":
        assert y[[0, 5, 700, 1535]].tolist() == [0.0] * 4
    if case == "max_k_row":
        k = np.diff(A.ptr)
        assert k.argmax() == 900 and W.kv == -(-k.max() // 8)
    # padding never widens a vreg's scan: every slot's x row (real or
    # padding) lies in [lo, hi) of its vreg, and an empty vreg scans
    # nothing
    rows = np.asarray(W.cols_local).reshape(W.cols_local.shape[0], -1) >> 7
    lo, hi = np.asarray(W.scan).reshape(-1, 2).T
    full = hi > lo
    assert np.all(rows[full] >= lo[full, None])
    assert np.all(rows[full] < hi[full, None])
    assert np.all(lo[~full] == 0) and np.all(hi[~full] == 0)


def test_well_kernel_residual_seam(monkeypatch):
    """The residual seam composes mv, so under the interpret hook it
    runs the kernel; the result matches the host residual."""
    from amgcl_tpu.ops import unstructured
    monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    Ap, W, x, f, _ = _windowed_fixture(seed=23)
    assert W.kernel_status() == ("pallas", None)
    calls = []
    real = unstructured.well_spmv
    monkeypatch.setattr(unstructured, "well_spmv",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    r = np.asarray(dev.residual(jnp.asarray(f), W, jnp.asarray(x)))
    assert calls
    np.testing.assert_allclose(r, f - Ap.spmv(x.astype(np.float64)),
                               rtol=5e-4, atol=5e-4)


def test_well_kernel_gate():
    """Without the TPU or the interpret hook, and for block values or
    64-bit operators, windowed ELL keeps XLA's gather and says why."""
    Ap, W, _, _, _ = _windowed_fixture(seed=24)
    assert W.kernel_status() == ("xla", "not on TPU")
    W64 = csr_to_windowed_ell(Ap, jnp.float64)
    assert W64.kernel_status()[0] == "xla"
    _, Wb, _, _, _ = _block_fixture(n_pt=600, seed=25)
    assert Wb.kernel_status() == ("xla", "block values")


def test_well_scan_stats_match_structure_pricing():
    """The packer's entry vregs and mean scan are the ones the structure
    advisor prices (telemetry/structure.well_scan)."""
    from amgcl_tpu.telemetry import structure as st
    Ap, W, _, _, _ = _windowed_fixture(seed=26)
    got = st.well_scan(Ap)
    assert W.scan_stats == {"entry_vregs": got["entry_vregs"],
                            "scan_xrows_mean": got["scan_xrows_mean"]}
    assert W.cols_local.shape[0] == got["entry_vregs"]
