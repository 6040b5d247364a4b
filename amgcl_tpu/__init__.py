"""amgcl_tpu — a TPU-native algebraic multigrid / iterative solver framework.

Brand-new implementation (not a port) of the capability contract of
ddemidov/amgcl (see /root/repo/SURVEY.md): AMG hierarchies are constructed on
the host in a canonical CSR format and *moved* to the device; the solve phase
runs entirely as jitted XLA programs over a tiny device algebra
(spmv/residual/axpby/dot/...), mirroring the reference's backend contract
(reference: amgcl/backend/interface.hpp:189-249) but expressed as JAX
functions over TPU-friendly sparse formats (ELL / DIA) instead of OpenMP CRS.

Package layout:
  ops/        host CSR build format + device algebra + Pallas kernels
  coarsening/ aggregation-based and classic coarsening policies
  relaxation/ smoothers (Jacobi, SPAI, Chebyshev, ILU family, ...)
  solver/     Krylov solvers (CG, BiCGStab(L), GMRES variants, IDR(s), ...)
  models/     top-level compositions: amg, make_solver, coupled-physics
  parallel/   distributed (mesh-sharded) layer: halo exchange, psum dots
  utils/      params/config, IO (MatrixMarket/binary), profiler, samples
"""

__version__ = "0.1.0"

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.models.amg import AMG, AMGParams
from amgcl_tpu.models.make_solver import make_solver
from amgcl_tpu.models.block_solver import make_block_solver
from amgcl_tpu.models.deflated import deflated_solver
from amgcl_tpu.models.runtime import make_solver_from_config
from amgcl_tpu.models.preconditioner import AsPreconditioner, \
    DummyPreconditioner

from amgcl_tpu.serve import SolverService

__all__ = ["CSR", "AMG", "AMGParams", "make_solver", "make_block_solver",
           "deflated_solver", "make_solver_from_config", "AsPreconditioner",
           "DummyPreconditioner", "SolverService", "__version__"]
