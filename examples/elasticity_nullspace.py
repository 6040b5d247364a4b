"""Elasticity: the Serena / Nullspace tutorial recipe (reference:
docs/tutorial/Serena.rst, Nullspace.rst) on an in-memory Q1 plane-stress
assembly (the tutorials' SuiteSparse matrices are not redistributable).

The tutorial's escalation ladder, reproduced step by step:
1. scalar defaults — converges but slowly (the vector character is lost);
2. symmetric diagonal scaling (adapter::scaled_problem) — equilibrates
   the badly scaled rows;
3. block value type (2x2) — one aggregate lambda per mesh NODE;
4. near-nullspace: rigid body modes from coordinates — the SA hierarchy
   reproduces rotations, the usual elasticity game-changer.

Run: JAX_PLATFORMS=cpu python examples/elasticity_nullspace.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np
import scipy.sparse as sp
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.models.make_solver import make_solver
from amgcl_tpu.models.amg import AMGParams
from amgcl_tpu.coarsening.smoothed_aggregation import SmoothedAggregation
from amgcl_tpu.coarsening.rigid_body_modes import rigid_body_modes
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.utils.adapters import Scaled


def q1_elasticity2d(nx=48, E=1.0, nu=0.3, contrast=1e3):
    """Genuine Q1 plane-stress elasticity on an nx x nx quad mesh (2x2
    Gauss assembly of B^T D B), Dirichlet on the left edge, a stiff
    inclusion in one quadrant — rotations really are in the near-kernel
    here, so rigid-body modes matter (the Serena situation)."""
    nn1 = nx + 1
    D = E / (1 - nu * nu) * np.array(
        [[1.0, nu, 0.0], [nu, 1.0, 0.0], [0.0, 0.0, (1 - nu) / 2]])
    # 2x2 Gauss points on [-1,1]^2; element is the unit square (J = I/2)
    gp = np.array([-1.0, 1.0]) / np.sqrt(3.0)
    Ke = np.zeros((8, 8))
    for xi in gp:
        for eta in gp:
            dN = 0.25 * np.array([          # dN/dxi, dN/deta per node
                [-(1 - eta), -(1 - xi)],
                [(1 - eta), -(1 + xi)],
                [(1 + eta), (1 + xi)],
                [-(1 + eta), (1 - xi)]])
            dNdx = dN * 2.0                 # J^-1 for an h=1 square /2
            B = np.zeros((3, 8))
            B[0, 0::2] = dNdx[:, 0]
            B[1, 1::2] = dNdx[:, 1]
            B[2, 0::2] = dNdx[:, 1]
            B[2, 1::2] = dNdx[:, 0]
            Ke += 0.25 * B.T @ D @ B        # det(J) * weight
    # element -> global scatter, vectorized over all elements
    ex, ey = np.meshgrid(np.arange(nx), np.arange(nx), indexing="ij")
    n00 = (ex * nn1 + ey).ravel()
    enodes = np.stack([n00, n00 + nn1, n00 + nn1 + 1, n00 + 1], axis=1)
    edofs = np.stack([enodes * 2, enodes * 2 + 1],
                     axis=2).reshape(-1, 8)
    scale = np.ones(len(edofs))
    scale[(ex.ravel() < nx // 2) & (ey.ravel() < nx // 2)] = contrast
    rows = np.repeat(edofs, 8, axis=1).ravel()
    cols = np.tile(edofs, (1, 8)).ravel()
    vals = (scale[:, None, None] * Ke[None]).ravel()
    ndof = 2 * nn1 * nn1
    K = sp.coo_matrix((vals, (rows, cols)), shape=(ndof, ndof)).tocsr()
    # Dirichlet on the left edge (ix = 0): pin both components
    free = np.ones(ndof, bool)
    fixed_nodes = np.arange(nn1)            # nodes with ix == 0
    free[fixed_nodes * 2] = False
    free[fixed_nodes * 2 + 1] = False
    keep = np.flatnonzero(free)
    K = K[keep][:, keep].tocsr()
    K.sort_indices()
    X, Y = np.meshgrid(np.arange(nn1, dtype=float),
                       np.arange(nn1, dtype=float), indexing="ij")
    coords = np.stack([X.ravel(), Y.ravel()], axis=1)[keep[::2] // 2]
    return CSR.from_scipy(K), np.ones(K.shape[0]), coords


A, rhs, coords = q1_elasticity2d(48)
tol = 1e-8

# -- 1. scalar defaults ------------------------------------------------------
solve = make_solver(A, AMGParams(dtype=jnp.float64, coarse_enough=500),
                    CG(maxiter=500, tol=tol))
x, info = solve(rhs)
print("1. scalar defaults:            %3d iterations" % info.iters)

# -- 2. + symmetric diagonal scaling ----------------------------------------
scaled = Scaled(
    A, lambda M: make_solver(
        M, AMGParams(dtype=jnp.float64, coarse_enough=500),
        CG(maxiter=500, tol=tol)))
x, info = scaled(rhs)
print("2. + diagonal scaling:         %3d iterations" % info.iters)

# -- 3. + block value type ---------------------------------------------------
solve = make_solver(
    A.to_block(2), AMGParams(dtype=jnp.float64, coarse_enough=500),
    CG(maxiter=500, tol=tol))
x, info = solve(rhs)
print("3. block (2x2) values:         %3d iterations" % info.iters)

# -- 4. + rigid body modes ---------------------------------------------------
B = rigid_body_modes(coords)          # (2n, 3): translations + rotation
solve = make_solver(
    A, AMGParams(dtype=jnp.float64, coarse_enough=500,
                 coarsening=SmoothedAggregation(nullspace=B)),
    CG(maxiter=500, tol=tol))
x, info = solve(rhs)
print("4. rigid-body nullspace:       %3d iterations" % info.iters)
r = np.linalg.norm(rhs - A.spmv(np.asarray(x))) / np.linalg.norm(rhs)
print("   true residual: %.2e" % r)

# -- 5. distributed (NullspaceMPI.rst analogue) ------------------------------
# run with XLA_FLAGS=--xla_force_host_platform_device_count=8 to see it
if len(jax.devices()) > 1:
    from amgcl_tpu.parallel.mesh import make_mesh
    from amgcl_tpu.parallel.dist_amg import DistAMGSolver

    s = DistAMGSolver(
        A, make_mesh(),
        AMGParams(dtype=jnp.float64, coarse_enough=500,
                  coarsening=SmoothedAggregation(nullspace=B)),
        CG(maxiter=500, tol=tol))
    x, info = s(rhs)
    print("5. distributed over %d devices: %3d iterations"
          % (len(jax.devices()), info.iters))
