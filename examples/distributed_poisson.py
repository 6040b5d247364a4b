"""Distributed AMG-CG over a device mesh with subdomain deflation — the
reference's examples/mpi/mpi_solver.cpp + runtime_sdd.cpp. Run on any
device count (virtual CPU mesh works):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/distributed_poisson.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from amgcl_tpu.models.amg import AMGParams
from amgcl_tpu.parallel.mesh import make_mesh
from amgcl_tpu.parallel.dist_amg import DistAMGSolver
from amgcl_tpu.parallel.deflation import DistDeflatedSolver
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.utils.sample_problem import poisson3d

A, rhs = poisson3d(24)
mesh = make_mesh()
print("mesh:", mesh)

s = DistAMGSolver(A, mesh, AMGParams(dtype=jnp.float64), CG(tol=1e-8))
x, info = s(rhs)
print("distributed AMG-CG: %d iterations, resid %.2e" % (info.iters,
                                                         info.resid))

d = DistDeflatedSolver(A, mesh, AMGParams(dtype=jnp.float64), CG(tol=1e-8))
x, info = d(rhs)
print("with subdomain deflation: %d iterations" % info.iters)

# strip-parallel SETUP (the mpi_solver.cpp per-rank pattern): the
# hierarchy itself is built distributed — each shard owns a row strip,
# transposes route triples, SpGEMM fetches remote rows, and no process
# ever assembles the global matrix. Under jax.distributed each controller
# passes only its own strips (None elsewhere) — see
# tests/test_multihost.py::test_two_process_strip_ingestion.
from amgcl_tpu.parallel.dist_setup import StripAMGSolver

st = StripAMGSolver(A, mesh, AMGParams(dtype=jnp.float64), CG(tol=1e-8))
x, info = st(rhs)
print("strip-parallel setup: %d iterations, peak strip nnz %d of %d"
      % (info.iters, st.stats["peak_strip_nnz"], A.nnz))

# coarse-level REPARTITIONING (the parmetis/ptscotch role): scramble the
# row order so every shard couples with every other, then let the k-way
# partitioner (parallel/partition.py) re-localize the coarse levels; the
# replicated tail can also be row-sharded across the mesh (rep_rowshard)
import numpy as np
from amgcl_tpu.utils.adapters import permute

rng = np.random.RandomState(0)
perm = rng.permutation(A.nrows)
As, rs = permute(A, perm), np.asarray(rhs)[perm]
sp_ = DistAMGSolver(As, mesh, AMGParams(dtype=jnp.float64,
                                        coarse_enough=100),
                    CG(tol=1e-8), replicate_below=150,
                    repartition=0.1, rep_rowshard=True)
x, info = sp_(rs)
print("scrambled + repartitioned: %d iterations; levels repartitioned: %s"
      % (info.iters, [(k, round(b, 2), round(a, 2))
                      for (k, b, a) in sp_.repartition_report]))
