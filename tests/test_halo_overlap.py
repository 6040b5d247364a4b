"""Comm/compute overlap structure of the sharded SpMVs (round-2 review
item 8; reference: amgcl/mpi/distributed_matrix.hpp:520-534).

XLA overlaps a collective with compute only when some compute does NOT
consume the collective's result. These tests assert that property on the
compiled HLO: the bulk (interior/local) product must not transitively
depend on the halo exchange."""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.parallel.mesh import make_mesh, ROWS_AXIS
from amgcl_tpu.parallel.dist_matrix import DistDiaMatrix, dia_halo_mv
from amgcl_tpu.utils.sample_problem import poisson3d


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8)


def _hlo_collective_independent_flops(txt, collective_ops):
    """Parse optimized HLO; return (n_heavy_total, n_heavy_independent):
    heavy instructions (fusion/dot/reduce/multiply) and how many of them
    do NOT transitively depend on any collective."""
    deps = {}
    kinds = {}
    order = []
    for m in re.finditer(
            r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*[\w\[\],{}\s]*?"
            r"([\w\-]+)\((.*)$", txt, re.M):
        name, op, rest = m.group(1), m.group(2), m.group(3)
        operands = re.findall(r"%([\w\.\-]+)", rest)
        deps[name] = operands
        kinds[name] = op
        order.append(name)
    tainted = set()
    for name in order:
        k = kinds[name]
        if any(c in k for c in collective_ops) \
                or any(d in tainted for d in deps[name]):
            tainted.add(name)
    heavy = [n for n in order
             if kinds[n] in ("fusion", "dot", "reduce", "multiply")]
    indep = [n for n in heavy if n not in tainted]
    return len(heavy), len(indep)


def test_dia_halo_mv_interior_independent_of_ppermute(mesh8):
    A, _ = poisson3d(16)
    M = DistDiaMatrix.from_csr(A, mesh8, jnp.float32)

    fn = shard_map(
        lambda d, x: dia_halo_mv(d, M.offsets, x),
        mesh=mesh8, in_specs=(P(None, ROWS_AXIS), P(ROWS_AXIS)),
        out_specs=P(ROWS_AXIS), check_vma=False)
    x = jnp.ones(A.nrows, jnp.float32)
    txt = jax.jit(fn).lower(M.data, x).compile().as_text()
    assert "collective-permute" in txt
    heavy, indep = _hlo_collective_independent_flops(
        txt, ("collective-permute",))
    assert heavy > 0
    # the interior product (the bulk of the FLOPs) must be schedulable
    # concurrently with the exchange
    assert indep > 0, "every compute op consumes the collective: no overlap"


def test_dist_ell_local_product_independent_of_all_to_all(mesh8):
    from amgcl_tpu.parallel.dist_ell import build_dist_ell
    A, _ = poisson3d(16)
    dA = build_dist_ell(A, mesh8, jnp.float32)

    def body(lc, lv, rc, rv, si, x):
        from amgcl_tpu.parallel.dist_ell import DistEllMatrix
        m = DistEllMatrix(lc, lv, rc, rv, si, dA.shape, dA.nloc, dA.ncloc)
        return m.shard_mv(x)

    sp = P(ROWS_AXIS, None, None)
    fn = shard_map(body, mesh=mesh8,
                   in_specs=(sp, sp, sp, sp, sp, P(ROWS_AXIS)),
                   out_specs=P(ROWS_AXIS), check_vma=False)
    x = jnp.ones(dA.shape[1], jnp.float32)
    txt = jax.jit(fn).lower(dA.loc_cols, dA.loc_vals, dA.rem_cols,
                            dA.rem_vals, dA.send_idx, x).compile().as_text()
    assert "all-to-all" in txt
    heavy, indep = _hlo_collective_independent_flops(txt, ("all-to-all",))
    assert indep > 0, "local ELL product consumes the collective"


def test_overlapped_dia_mv_matches_reference_product(mesh8):
    """Numerics: the interior/edge split must be exact."""
    A, _ = poisson3d(16)
    M = DistDiaMatrix.from_csr(A, mesh8, jnp.float64)
    x = np.random.RandomState(0).rand(A.nrows)

    fn = shard_map(
        lambda d, v: dia_halo_mv(d, M.offsets, v),
        mesh=mesh8, in_specs=(P(None, ROWS_AXIS), P(ROWS_AXIS)),
        out_specs=P(ROWS_AXIS), check_vma=False)
    y = np.asarray(jax.jit(fn)(M.data, jnp.asarray(x)))
    np.testing.assert_allclose(y, A.spmv(x), rtol=1e-12)


def test_dia_halo_mv_reach_beyond_neighbour(mesh8):
    """w > nl: a diagonal reaching past the immediate neighbour slab must
    fall back to the gather path, not silently clamp (round-3 advice)."""
    rng = np.random.default_rng(0)
    nd, nl = 8, 4
    n = nd * nl
    offs = (0, 6)            # reach 6 > nl=4: crosses TWO shards
    data = rng.standard_normal((len(offs), n)).astype(np.float32)
    x = rng.standard_normal(n).astype(np.float32)
    # dense reference with zero-filled shift semantics
    want = np.zeros(n, np.float32)
    for k, s in enumerate(offs):
        src = np.zeros(n, np.float32)
        if s >= 0:
            src[: n - s] = x[s:]
        else:
            src[-s:] = x[: n + s]
        want += data[k] * src

    fn = shard_map(
        lambda d, v: dia_halo_mv(d, offs, v),
        mesh=mesh8, in_specs=(P(None, ROWS_AXIS), P(ROWS_AXIS)),
        out_specs=P(ROWS_AXIS), check_vma=False)
    got = jax.jit(fn)(jnp.asarray(data), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
