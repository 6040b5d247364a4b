"""Fused vector-algebra tier (ISSUE 5): agreement of the compound
primitives (Pallas kernels and XLA fallback) with the plain composition
across dtypes and awkward lengths, seam behavior (plain / psum-marked /
opaque inner products), health-guard parity with the tier on and off,
the fused spmv_dots psum acceptance, and the pipelined-CG comm model."""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from amgcl_tpu.ops import device as dev
from amgcl_tpu.ops import fused_vec as fv
from amgcl_tpu.ops.csr import CSR

_LENS = [0, 1, 5, 1000, 8195]      # incl. odd / non-tile-aligned / empty


def _vecs(n, dtype, k, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.standard_normal(n), dtype)
                 for _ in range(k))


def _tol(dtype):
    return dict(rtol=2e-5, atol=1e-5) if jnp.dtype(dtype) == jnp.float32 \
        else dict(rtol=1e-12, atol=1e-12)


# -- agreement: fused (kernel where it applies) vs plain composition --------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("n", _LENS)
@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_axpby_dot_agrees(monkeypatch, dtype, n, kernels):
    if kernels:
        monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    x, y = _vecs(n, dtype, 2)
    z, zz = fv.axpby_dot(0.3, x, -1.2, y)
    ref = 0.3 * x - 1.2 * y
    np.testing.assert_allclose(np.asarray(z), np.asarray(ref), **_tol(dtype))
    np.testing.assert_allclose(float(zz), float(jnp.vdot(ref, ref)),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("n", _LENS)
@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_xr_update_agrees(monkeypatch, dtype, n, kernels):
    if kernels:
        monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    p, q, x, r = _vecs(n, dtype, 4)
    xn, rn, rr = fv.xr_update(0.7, p, q, x, r)
    xr, rr_ref = x + 0.7 * p, r - 0.7 * q
    np.testing.assert_allclose(np.asarray(xn), np.asarray(xr),
                               **_tol(dtype))
    np.testing.assert_allclose(np.asarray(rn), np.asarray(rr_ref),
                               **_tol(dtype))
    np.testing.assert_allclose(float(rr), float(jnp.vdot(rr_ref, rr_ref)),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("n", _LENS)
@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_bicgstab_tail_agrees(monkeypatch, dtype, n, kernels):
    if kernels:
        monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    ph, sh, s, t, x, rhat = _vecs(n, dtype, 6)
    xn, rn, rr, rhr = fv.bicgstab_tail(0.4, ph, 0.2, sh, s, t, x, rhat)
    x_ref = x + 0.4 * ph + 0.2 * sh
    r_ref = s - 0.2 * t
    np.testing.assert_allclose(np.asarray(xn), np.asarray(x_ref),
                               **_tol(dtype))
    np.testing.assert_allclose(np.asarray(rn), np.asarray(r_ref),
                               **_tol(dtype))
    np.testing.assert_allclose(float(rr), float(jnp.vdot(r_ref, r_ref)),
                               **_tol(dtype))
    np.testing.assert_allclose(float(rhr), float(jnp.vdot(rhat, r_ref)),
                               **_tol(dtype))


@pytest.mark.parametrize("n", [0, 5, 1000])
def test_multi_stack_block_dots_agree(n):
    x, y, z = _vecs(n, jnp.float64, 3)
    d1, d2 = fv.multi_dot(x, (x, y))
    assert np.allclose(float(d1), float(jnp.vdot(x, x)))
    assert np.allclose(float(d2), float(jnp.vdot(x, y)))
    V = jnp.stack([x, y, z]) if n else jnp.zeros((3, 0))
    sd = fv.stack_dots(V, y)
    ref = np.array([float(jnp.vdot(v, y)) for v in V])
    np.testing.assert_allclose(np.asarray(sd), ref, rtol=1e-12, atol=1e-12)
    B = fv.block_dots(V, V)
    refB = np.array([[float(jnp.vdot(a, b)) for b in V] for a in V])
    np.testing.assert_allclose(np.asarray(B), refB, rtol=1e-12,
                               atol=1e-12)


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_residual_dot_agrees(monkeypatch, kernels):
    import scipy.sparse as sp
    if kernels:
        monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    n = 100
    L = sp.diags([-np.ones(n - 1), 2.05 * np.ones(n), -np.ones(n - 1)],
                 [-1, 0, 1]).tocsr()
    f, x = _vecs(n, jnp.float32, 2)
    for fmt in ("dia", "ell"):
        A = dev.to_device(CSR.from_scipy(L), fmt, jnp.float32)
        r, rr = fv.residual_dot(f, A, x)
        r_ref = dev.residual(f, A, x)
        np.testing.assert_allclose(np.asarray(r), np.asarray(r_ref),
                                   rtol=2e-5, atol=1e-5)
        np.testing.assert_allclose(
            float(rr), float(jnp.vdot(r_ref, r_ref)), rtol=2e-5,
            atol=1e-5)


def test_opt_out_restores_composition(monkeypatch):
    """AMGCL_TPU_FUSED_VEC=0: no kernel runs even under the interpret
    hook, and the results are the plain composition's bit-for-bit."""
    monkeypatch.setenv("AMGCL_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("AMGCL_TPU_FUSED_VEC", "0")
    assert not fv.fused_vec_enabled()
    assert fv._pallas_mode(jnp.zeros(8, jnp.float32)) is None
    p, q, x, r = _vecs(1000, jnp.float32, 4)
    xn, rn, rr = fv.xr_update(0.7, p, q, x, r)
    assert np.array_equal(np.asarray(xn),
                          np.asarray(dev.axpby(0.7, p, 1.0, x)))
    assert np.array_equal(np.asarray(rn),
                          np.asarray(dev.axpby(-0.7, q, 1.0, r)))
    assert float(rr) == float(jnp.vdot(rn, rn))


# -- df32 pairs: the primitives stay usable on the refinement's hi/lo legs --

def test_df32_pair_through_fused_ops():
    """Applying the (linear) fused update to the hi and lo legs of a
    df32 pair recombines to the f64 result at f32-grade accuracy — and
    strictly better than dropping the lo leg — so the fused tier
    composes with the double-float refinement (ops/dfloat.py)."""
    from amgcl_tpu.ops.dfloat import df_decompose
    rng = np.random.RandomState(3)
    a64 = rng.standard_normal(4097) * (1 + rng.rand(4097) * 1e-3)
    b64 = rng.standard_normal(4097)
    xhi, xlo = df_decompose(a64)
    yhi, ylo = df_decompose(b64)
    zhi, _ = fv.axpby_dot(0.3, jnp.asarray(xhi), -1.2, jnp.asarray(yhi))
    zlo, _ = fv.axpby_dot(0.3, jnp.asarray(xlo), -1.2, jnp.asarray(ylo))
    z64 = 0.3 * a64 - 1.2 * b64
    got = np.asarray(zhi, np.float64) + np.asarray(zlo, np.float64)
    err_pair = np.linalg.norm(got - z64) / np.linalg.norm(z64)
    err_hi = np.linalg.norm(np.asarray(zhi, np.float64) - z64) \
        / np.linalg.norm(z64)
    assert err_pair < 1e-6
    assert err_pair <= err_hi
    # the pair dot: <x, y> from the cross terms of one multi_dot read
    d_hh, d_hl = fv.multi_dot(jnp.asarray(xhi, jnp.float64),
                              (jnp.asarray(yhi, jnp.float64),
                               jnp.asarray(ylo, jnp.float64)))
    (d_lh,) = fv.multi_dot(jnp.asarray(xlo, jnp.float64),
                           (jnp.asarray(yhi, jnp.float64),))
    ref = float(np.vdot(a64, b64))
    assert abs(float(d_hh + d_hl + d_lh) - ref) < 1e-6 * abs(ref) + 1e-9


# -- inner-product seams ----------------------------------------------------

def test_opaque_seam_composes_through_ip():
    """A custom (unmarked) inner product must be called — never bypassed
    by a kernel — so custom seams keep custom semantics."""
    calls = []

    def weird_ip(a, b):
        calls.append(1)
        return 2.0 * jnp.vdot(a, b)

    p, q, x, r = _vecs(1000, jnp.float64, 4)
    _, rn, rr = fv.xr_update(0.7, p, q, x, r, ip=weird_ip)
    assert calls, "opaque seam was bypassed"
    assert np.allclose(float(rr), 2.0 * float(jnp.vdot(rn, rn)))
    sd = fv.stack_dots(jnp.stack([p, q]), x, ip=weird_ip)
    assert np.allclose(np.asarray(sd),
                       [2 * float(jnp.vdot(p, x)),
                        2 * float(jnp.vdot(q, x))])


def test_psum_seam_merges_reductions():
    """Under shard_map with the psum-marked distributed dot, the fused
    primitives return globally-reduced values (matching the serial
    math), via ONE stacked psum."""
    from jax import shard_map
    from amgcl_tpu.parallel.mesh import make_mesh
    from amgcl_tpu.parallel.dist_matrix import dist_inner_product
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(8)
    n = 8 * 32
    p, q, x, r = _vecs(n, jnp.float64, 4)
    V = jnp.stack([p, q, r])

    def body(pl_, ql_, xl_, rl_, Vl_):
        xn, rn, rr = fv.xr_update(0.7, pl_, ql_, xl_, rl_,
                                  ip=dist_inner_product)
        dots = fv.multi_dot(rl_, (rl_, xl_), ip=dist_inner_product)
        sd = fv.stack_dots(Vl_, xl_, ip=dist_inner_product)
        B = fv.block_dots(Vl_, Vl_, ip=dist_inner_product)
        return xn, rn, rr, dots[0], dots[1], sd, B

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P("rows"), P("rows"), P("rows"), P("rows"),
                             P(None, "rows")),
                   out_specs=(P("rows"), P("rows"), P(), P(), P(), P(),
                              P()),
                   check_vma=False)
    xn, rn, rr, d0, d1, sd, B = jax.jit(fn)(p, q, x, r, V)
    rn_ref = r - 0.7 * q
    np.testing.assert_allclose(np.asarray(xn), np.asarray(x + 0.7 * p))
    np.testing.assert_allclose(float(rr),
                               float(jnp.vdot(rn_ref, rn_ref)))
    np.testing.assert_allclose(float(d0), float(jnp.vdot(r, r)))
    np.testing.assert_allclose(float(d1), float(jnp.vdot(r, x)))
    np.testing.assert_allclose(np.asarray(sd),
                               [float(jnp.vdot(v, x)) for v in V])
    np.testing.assert_allclose(
        np.asarray(B),
        [[float(jnp.vdot(a, b)) for b in V] for a in V])


def test_spmv_dots_accepts_psum_seam():
    """ISSUE 5 satellite: spmv_dots with the psum-marked distributed dot
    returns globally-reduced dots (local-shard fusion + one collective)
    instead of falling back to the unfused per-dot seam calls."""
    from jax import shard_map
    from amgcl_tpu.parallel.mesh import make_mesh
    from amgcl_tpu.parallel.dist_matrix import dist_inner_product
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(8)
    nloc, nd = 32, 8
    n = nloc * nd
    x, w = _vecs(n, jnp.float64, 2)
    d = jnp.asarray(np.random.RandomState(5).rand(n) + 1.0)

    def body(dl, xl, wl):
        A_loc = dev.DiaMatrix((0,), dl[None, :], (nloc, nloc))
        y, yy, yx, yw = dev.spmv_dots(A_loc, xl, wl,
                                      ip=dist_inner_product)
        return y, yy, yx, yw

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P("rows"), P("rows"), P("rows")),
                   out_specs=(P("rows"), P(), P(), P()),
                   check_vma=False)
    y, yy, yx, yw = jax.jit(fn)(d, x, w)
    y_ref = d * x            # block-diagonal: the diagonal operator
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_allclose(float(yy), float(jnp.vdot(y_ref, y_ref)))
    np.testing.assert_allclose(float(yx), float(jnp.vdot(y_ref, x)))
    np.testing.assert_allclose(float(yw), float(jnp.vdot(y_ref, w)))


# -- health-guard parity with the tier on/off -------------------------------

def _neumann(n):
    import scipy.sparse as sp
    main = 2.0 * np.ones(n)
    main[0] = main[-1] = 1.0
    L = sp.diags([-np.ones(n - 1), main, -np.ones(n - 1)],
                 [-1, 0, 1]).tocsr()
    return dev.to_device(CSR.from_scipy(L), "ell", jnp.float64)


def _poisson1d(n):
    import scipy.sparse as sp
    L = sp.diags([-np.ones(n - 1), 2.0 * np.ones(n), -np.ones(n - 1)],
                 [-1, 0, 1]).tocsr()
    return dev.to_device(CSR.from_scipy(L), "dia", jnp.float64)


@pytest.mark.parametrize("fused", ["0", "1"])
def test_guard_parity_recorded(monkeypatch, fused):
    """Breakdown (singular system), NaN propagation (guards off) and
    divergence-trip behavior must be IDENTICAL with the fused tier on
    and off — same flags, same trip iteration, same early exit. The
    parametrization records both arms; the cross-arm equality is
    asserted in test_guard_parity_cross below with explicit env
    control."""
    monkeypatch.setenv("AMGCL_TPU_FUSED_VEC", fused)
    got = _guard_scenarios()
    assert got["cg_breakdown"]["breakdown"] is not None
    assert got["bicgstab_breakdown"]["breakdown"] is not None
    assert got["richardson_divergence"]["diverged"]
    assert not np.isfinite(got["cg_nan_guard_off"])


def _guard_scenarios():
    """Run the guard-relevant scenarios under the CURRENT env; returns
    decoded health per scenario."""
    from amgcl_tpu.solver import CG, BiCGStab, Richardson
    from amgcl_tpu.telemetry import health as H
    out = {}
    A = _neumann(8)
    b = jnp.ones(8, jnp.float64)
    x, it, res, hs = CG(maxiter=50, tol=1e-8).solve(A, lambda r: r, b)
    out["cg_breakdown"] = H.decode(hs.flags, hs.first_it)
    out["cg_breakdown"]["iters"] = int(it)
    x, it, res, hs = BiCGStab(maxiter=50, tol=1e-8).solve(
        A, lambda r: r, b)
    out["bicgstab_breakdown"] = H.decode(hs.flags, hs.first_it)
    out["bicgstab_breakdown"]["iters"] = int(it)
    # guards off: the historical NaN-exit failure signal must survive
    x, it, res = CG(maxiter=50, tol=1e-8, guard=False).solve(
        A, lambda r: r, b)
    out["cg_nan_guard_off"] = float(res)
    # divergence: over-relaxed Richardson on an SPD system grows the
    # residual monotonically — the divergence guard must trip and exit
    Ap = _poisson1d(64)
    bp = jnp.ones(64, jnp.float64)
    x, it, res, hs = Richardson(maxiter=200, tol=1e-10, damping=1.3).solve(
        Ap, lambda r: r, bp)
    out["richardson_divergence"] = H.decode(hs.flags, hs.first_it)
    out["richardson_divergence"]["iters"] = int(it)
    return out


def test_guard_parity_cross(monkeypatch):
    """The decisive check: the same scenarios, run back to back with
    AMGCL_TPU_FUSED_VEC=0 and =1 — flags, trip iterations and iteration
    counts must agree exactly; residuals to solver tolerance."""
    monkeypatch.setenv("AMGCL_TPU_FUSED_VEC", "1")
    on = _guard_scenarios()
    monkeypatch.setenv("AMGCL_TPU_FUSED_VEC", "0")
    off = _guard_scenarios()
    for key in ("cg_breakdown", "bicgstab_breakdown",
                "richardson_divergence"):
        assert on[key]["flags"] == off[key]["flags"], key
        assert on[key]["iters"] == off[key]["iters"], key
        assert on[key].get("breakdown") == off[key].get("breakdown"), key
    assert np.isnan(on["cg_nan_guard_off"]) \
        == np.isnan(off["cg_nan_guard_off"])


@pytest.mark.parametrize("fused", ["0", "1"])
def test_solver_residual_parity(monkeypatch, fused):
    """Fused and unfused paths agree on the final residual to solver
    tolerance (acceptance criterion), across CG / BiCGStab / IDRs."""
    import scipy.sparse as sp
    from amgcl_tpu.solver import CG, BiCGStab, IDRs
    monkeypatch.setenv("AMGCL_TPU_FUSED_VEC", fused)
    n = 128
    L = sp.diags([-np.ones(n - 1), 2.1 * np.ones(n), -np.ones(n - 1)],
                 [-1, 0, 1]).tocsr()
    A = dev.to_device(CSR.from_scipy(L), "dia", jnp.float64)
    b = jnp.asarray(np.random.RandomState(0).rand(n))
    host = L.toarray()
    for slv in (CG(maxiter=200, tol=1e-8), BiCGStab(maxiter=200, tol=1e-8),
                IDRs(s=2, maxiter=200, tol=1e-8)):
        x, it, res = slv.solve(A, lambda r: r, b)[:3]
        true = np.linalg.norm(np.asarray(b) - host @ np.asarray(x)) \
            / np.linalg.norm(np.asarray(b))
        assert true < 5e-8, (type(slv).__name__, fused, true)


# -- models / CLI -----------------------------------------------------------

def test_iteration_model_fused_bytes_drop():
    """The fused iteration model charges strictly fewer vector bytes
    than the composed one, with identical FLOPs (fusion moves bytes,
    not arithmetic)."""
    from amgcl_tpu.telemetry.ledger import krylov_iteration_model
    d = dev.DiaMatrix((0,), jnp.ones((1, 4096), jnp.float32),
                      (4096, 4096))
    for name in ("CG", "BiCGStab", "Richardson", "IDRs"):
        f = krylov_iteration_model(name, d, fused=True)
        u = krylov_iteration_model(name, d, fused=False)
        assert f["bytes"] < u["bytes"], name
        assert f["flops"] == u["flops"], name
        assert f["fused_vec"] and not u["fused_vec"]


def test_vecbench_cli():
    """bench.py --vecbench runs end to end and emits the record."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_vec", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.main_vecbench(["1024"]) == 0
