"""Double-float outer residual (ops/dfloat.py) + refine_dtype='df32'
(reference capability: mixed-precision refinement, mixing.hpp's spirit
— re-designed f64-free for the TPU, where float64 is software-emulated)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from amgcl_tpu.ops import device as dev
from amgcl_tpu.ops.dfloat import (two_sum, two_prod, df_decompose,
                                  df_add_vec, dia_residual_df)
from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.utils.sample_problem import poisson3d


def test_two_sum_exact():
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(1000), jnp.float32)
    b = jnp.asarray(rng.randn(1000) * 1e-6, jnp.float32)
    s, e = two_sum(a, b)
    got = np.asarray(s, np.float64) + np.asarray(e, np.float64)
    want = np.asarray(a, np.float64) + np.asarray(b, np.float64)
    np.testing.assert_array_equal(got, want)


def test_two_prod_exact():
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(1000), jnp.float32)
    b = jnp.asarray(rng.randn(1000), jnp.float32)
    p, e = two_prod(a, b)
    got = np.asarray(p, np.float64) + np.asarray(e, np.float64)
    want = np.asarray(a, np.float64) * np.asarray(b, np.float64)
    np.testing.assert_array_equal(got, want)


def test_df_residual_beats_f32_floor():
    """The compensated residual of a near-solution must match the f64
    residual to far below the plain-f32 evaluation floor."""
    A, rhs = poisson3d(16)
    Ad = dev.to_device(A, "dia", jnp.float32)
    A_lo = dev.csr_to_dia_remainder(A, Ad)
    # a high-quality solution: f64 solve on the host
    import scipy.sparse as sp
    import scipy.sparse.linalg as spla
    As = sp.csr_matrix((A.val, A.col, A.ptr), shape=A.shape)
    x64 = spla.spsolve(As.tocsc(), rhs)
    xh, xl = df_decompose(x64)
    r64 = rhs - As @ x64
    b_hi = jnp.asarray(rhs, jnp.float32)
    r_df = np.asarray(dia_residual_df(
        Ad.offsets, Ad.data, A_lo.data, b_hi,
        jnp.zeros_like(b_hi), jnp.asarray(xh), jnp.asarray(xl)),
        np.float64)
    # plain f32 residual for comparison
    r_f32 = np.asarray(
        dev.residual(b_hi, Ad, jnp.asarray(xh)), np.float64)
    err_df = np.linalg.norm(r_df - r64)
    err_f32 = np.linalg.norm(r_f32 - r64)
    # b rounded to f32 shifts both by the same ~eps32*||b||; the df
    # evaluation must recover the A x part to ~eps32^2 while plain f32
    # is floored at ~eps32*||A||*||x||
    assert err_df < 1e-3 * err_f32 + 1e-10, (err_df, err_f32)


def test_df_add_vec_carries_low_part():
    xh = jnp.asarray([1.0], jnp.float32)
    xl = jnp.asarray([0.0], jnp.float32)
    d = jnp.asarray([1e-9], jnp.float32)
    nh, nl = df_add_vec(xh, xl, d)
    got = float((np.asarray(nh, np.float64)
                 + np.asarray(nl, np.float64))[0])
    assert abs(got - (1.0 + 1e-9)) < 1e-14


def test_refine_df32_end_to_end():
    """df32 refinement reaches the same true-residual class as float64
    refinement on the structured Poisson system."""
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.cg import CG
    A, rhs = poisson3d(20)
    s_df = make_solver(A, AMGParams(dtype=jnp.float32),
                       CG(maxiter=100, tol=1e-7), refine=3,
                       refine_dtype="df32")
    assert s_df.refine_mode == "df32"
    x, info = s_df(rhs)
    x = np.asarray(x, np.float64)
    tr = np.linalg.norm(rhs - A.spmv(x)) / np.linalg.norm(rhs)
    assert tr < 2e-7, tr
    # and beats the no-refinement f32 floor
    s0 = make_solver(A, AMGParams(dtype=jnp.float32),
                     CG(maxiter=100, tol=1e-7), refine=0)
    x0, _ = s0(rhs)
    tr0 = np.linalg.norm(rhs - A.spmv(np.asarray(x0, np.float64))) \
        / np.linalg.norm(rhs)
    assert tr < tr0 or tr < 1e-7


def test_refine_df32_bicgstab():
    """df32 refinement through a solver WITHOUT the abstol kwarg (the
    has_abstol=False leg of the shared loop)."""
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.bicgstab import BiCGStab
    A, rhs = poisson3d(16)
    s = make_solver(A, AMGParams(dtype=jnp.float32),
                    BiCGStab(maxiter=100, tol=1e-7), refine=3,
                    refine_dtype="df32")
    assert s.refine_mode == "df32"
    x, info = s(rhs)
    tr = np.linalg.norm(rhs - A.spmv(np.asarray(x, np.float64))) \
        / np.linalg.norm(rhs)
    assert tr < 2e-7, tr


def test_refine_df32_selfcheck_fallback_is_recorded(monkeypatch):
    """A failed df32 self-check falls back to float64 refinement, warns,
    and says so on the bundle (refine_fallback)."""
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.cg import CG
    A, _ = poisson3d(8)
    ok = make_solver(A, AMGParams(dtype=jnp.float32), CG(), refine=1,
                     refine_dtype="df32")
    assert ok.refine_mode == "df32" and not ok.refine_fallback
    monkeypatch.setattr(make_solver, "_df32_selfcheck", lambda self, A: False)
    with pytest.warns(UserWarning, match="self-check"):
        s = make_solver(A, AMGParams(dtype=jnp.float32), CG(), refine=1,
                        refine_dtype="df32")
    assert s.refine_mode == "float64" and s.refine_fallback


def test_refine_df32_needs_dia():
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.ops.unstructured import fe_like_problem
    A, _ = fe_like_problem(n=800, nnz_target=8000, seed=1)
    with pytest.raises(ValueError, match="df32"):
        make_solver(A, AMGParams(dtype=jnp.float32), CG(), refine=2,
                    refine_dtype="df32", matrix_format="ell")
