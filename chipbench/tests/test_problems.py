"""The frozen generators reproduce the program's generators array for
array at small sizes."""

import numpy as np
import pytest

from chipbench.problems import fe_like, poisson3d


def same(a, b):
    assert a.shape == b.shape
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.indices, b.indices)
    np.testing.assert_array_equal(a.data, b.data)
    assert a.data.dtype == b.data.dtype == np.float64


@pytest.mark.parametrize("n", [1, 2, 5, 16])
def test_poisson3d_matches_program(n):
    from amgcl_tpu.utils.sample_problem import poisson3d as prog
    same(poisson3d.build({"n": n}), prog(n)[0].to_scipy())


@pytest.mark.parametrize("rows,nnz,seed", [(500, 10000, 0), (3000, 90000, 0),
                                           (2000, 60000, 7)])
def test_fe_like_matches_program(rows, nnz, seed):
    from amgcl_tpu.ops.unstructured import fe_like_problem as prog
    got = fe_like.build({"rows": rows, "nnz_target": nnz,
                         "matrix_seed": seed})
    same(got, prog(n=rows, nnz_target=nnz, seed=seed)[0].to_scipy())
