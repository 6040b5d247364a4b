"""The least-work function: a hand count, and the same count whatever
format stores the operator."""

import numpy as np
import pytest

from chipbench import work
from chipbench.problems import poisson3d


def two_level(solver="CG", refine=0):
    return {"levels": [{"rows": 8, "a_values": 20, "smoother_values": 8,
                        "p_values": 10, "r_values": 10},
                       {"rows": 2, "a_values": 4}],
            "value_bytes": 4, "vector_bytes": 4, "npre": 1, "npost": 1,
            "ncycle": 1, "pre_cycles": 1, "solver": solver,
            "refine": refine}


def test_hand_count_two_levels():
    d = two_level()
    # down leg: A, smoother, R values; f in, coarse f out
    down = (20 + 8 + 10) * 4 + 8 * 4 + 2 * 4
    # up leg: P, A, smoother values; f and coarse u in, u out
    up = (10 + 20 + 8) * 4 + 8 * 4 + 2 * 4 + 8 * 4
    coarse = 4 * 4 + 2 * 2 * 4
    assert work.cycle_work(d)["bytes"] == down + up + coarse
    # CG: one operator pass (values, x in, y out), 11 vector streams,
    # one cycle
    it = 20 * 4 + 2 * 8 * 4 + 11 * 8 * 4 + down + up + coarse
    assert work.iteration_work(d)["bytes"] == it
    got = work.least_work(d, 3)
    assert got["bytes"] == 3 * it + 2 * 8 * 4
    values = 3 * (20 + (20 + 8 + 10) + (10 + 20 + 8) + 4)
    assert got["flops"] == 2 * values
    # refinement: one residual of A against a double-width iterate
    ref = work.least_work(two_level(refine=3), 3)
    assert ref["bytes"] == got["bytes"] + 20 * 4 + 8 * (2 * 4 + 8)
    # BiCGStab: two operator passes and two cycles per iteration
    bi = work.iteration_work(two_level("BiCGStab"))["bytes"]
    assert bi == 2 * (20 * 4 + 2 * 8 * 4) + 15 * 8 * 4 \
        + 2 * (down + up + coarse)


def test_least_seconds_names_its_bound():
    peak = {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e9}
    assert work.least_seconds({"bytes": 4e9, "flops": 1e9}, peak) \
        == (4.0, "memory")
    assert work.least_seconds({"bytes": 1e9, "flops": 3e9}, peak) \
        == (3.0, "compute")


def test_same_count_for_dia_ell_and_csr():
    from amgcl_tpu.ops import device as dev
    from amgcl_tpu.ops.csr import CSR
    import jax.numpy as jnp
    A = poisson3d.build({"n": 9})
    csr = CSR.from_scipy(A)
    dia = dev.to_device(csr, "dia", jnp.float32)
    ell = dev.to_device(csr, "ell", jnp.float32)
    assert type(dia).__name__ == "DiaMatrix"
    assert type(ell).__name__ == "EllMatrix"
    # DIA pads the boundary rows' missing neighbours and ELL pads short
    # rows: neither padding, nor an index array, counts
    assert work.count_values(dia) == work.count_values(ell) == A.nnz
    assert work.count_values(jnp.asarray(A.toarray(), jnp.float32)) \
        == A.nnz


@pytest.mark.parametrize("fmt", ["dia", "ell"])
def test_describe_reads_the_hierarchy(fmt):
    from amgcl_tpu import make_solver
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.solver.cg import CG
    import jax.numpy as jnp
    A = poisson3d.build({"n": 10})
    s = make_solver(A, AMGParams(dtype=jnp.float32, coarse_enough=100,
                                 matrix_format=fmt), CG(tol=1e-6))
    d = work.describe(s)
    assert d["levels"][0] == {**d["levels"][0], "rows": 1000,
                              "a_values": A.nnz}
    assert d["solver"] == "CG" and d["value_bytes"] == 4
    assert len(d["levels"]) == len(s.precond.hierarchy.levels)
    assert np.all([lv["a_values"] > 0 for lv in d["levels"]])
