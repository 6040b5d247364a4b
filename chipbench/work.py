"""The least HBM bytes and operations one solve needs.

Counted from the problem and the cycle parameters: each level's rows and
the nonzero values of its operator, smoother and transfer operators, the
coarse operator, the cycle shape (npre, npost, ncycle, pre_cycles), the
Krylov method, refinement and the iterations. Values are counted as
nonzeros: no index arrays and no padding, so one operator reads the same
work whether the program stores it as DIA, ELL, CSR or dense windows.

The bytes are a floor: every pass streams each operand once. Per level
and cycle visit, a down leg reads the level operator, the smoother state,
the restriction and the right-hand side and writes the coarse right-hand
side (a sweep from a zero guess needs no operator pass of its own, and
its result can be recomputed in the up leg); an up leg reads the
prolongation, the level operator, the smoother state, the right-hand side
and the coarse correction and writes the iterate. Each sweep beyond the
first streams the operator and the smoother state and reads and writes
the iterate once more. The coarse solve reads at least the coarse
operator's values, since a factorisation holds no fewer. Operations count
one multiply-add per stored value read.
"""

from __future__ import annotations

from typing import Any, Dict

#: per outer iteration of the methods the cells run: (operator
#: applications, preconditioner applications, vector streams). The
#: streams are those of every update and dot of the iteration riding as
#: few passes as its data dependencies allow, beside the operator
#: applications' own input and output vectors: CG = rho (r, z) 2 + p
#: update 3 + x/r update 6 = 11; BiCGStab = p update 4 + s update 3 +
#: x/r tail 8 = 15. A cell with another method adds its row.
KRYLOV = {"CG": (1, 1, 11), "BiCGStab": (2, 2, 15)}


def count_values(tree) -> int:
    """Nonzero floating-point values held by the array leaves of a
    pytree; integer leaves (indices, offsets, aggregates) do not count."""
    import jax
    import numpy as np
    total = 0
    for leaf in jax.tree.leaves(tree):
        dt = getattr(leaf, "dtype", None)
        if dt is None or not np.issubdtype(np.dtype(dt), np.inexact):
            continue
        total += int(np.count_nonzero(np.asarray(jax.device_get(leaf))))
    return total


def describe(bundle) -> Dict[str, Any]:
    """The counts :func:`least_work` needs, read from a ``make_solver``
    bundle's device hierarchy."""
    import numpy as np
    hier = bundle.precond.hierarchy
    levels = []
    for i, lv in enumerate(hier.levels):
        row = {"rows": int(lv.A.shape[0]), "a_values": count_values(lv.A)}
        if i < len(hier.levels) - 1:
            row.update(smoother_values=count_values(lv.relax),
                       p_values=count_values(lv.P),
                       r_values=count_values(lv.R))
        levels.append(row)
    return {"levels": levels,
            "value_bytes": int(np.dtype(bundle.precond_dtype).itemsize),
            "vector_bytes": int(np.dtype(bundle.solver_dtype).itemsize),
            "npre": hier.npre, "npost": hier.npost, "ncycle": hier.ncycle,
            "pre_cycles": hier.pre_cycles,
            "solver": type(bundle.solver).__name__,
            "refine": int(bundle.refine)}


def cycle_work(desc: Dict[str, Any]) -> Dict[str, int]:
    """Bytes and values of one multigrid cycle."""
    vb, ab = desc["vector_bytes"], desc["value_bytes"]
    npre, npost = desc["npre"], desc["npost"]
    levels = desc["levels"]
    nbytes = values = 0
    for i, lv in enumerate(levels):
        visits = desc["ncycle"] ** i
        n = lv["rows"]
        if i == len(levels) - 1:
            vals = lv["a_values"]
            nbytes += visits * (vals * ab + 2 * n * vb)
            values += visits * vals
            continue
        nc = levels[i + 1]["rows"]
        sweep_vals = lv["a_values"] + lv["smoother_values"]
        extra = max(npre - 1, 0) + max(npost - 1, 0)
        vals = lv["r_values"] + lv["p_values"] + extra * sweep_vals
        vecs = extra * 3 * n
        # down leg: residual (of the first sweep) and restriction
        if npre >= 1:
            vals += sweep_vals
        vecs += n + nc
        # up leg: prolongation, correction and the first post-sweep
        if npost >= 1:
            vals += sweep_vals
            vecs += 2 * n + nc + (n if npre >= 2 else 0)
        else:
            vecs += 2 * n + nc
        nbytes += visits * (vals * ab + vecs * vb)
        values += visits * vals
    return {"bytes": nbytes, "values": values}


def iteration_work(desc: Dict[str, Any]) -> Dict[str, int]:
    """Bytes and values of one outer Krylov iteration."""
    vb, ab = desc["vector_bytes"], desc["value_bytes"]
    n = desc["levels"][0]["rows"]
    a0 = desc["levels"][0]["a_values"]
    spmv, papp, streams = KRYLOV[desc["solver"]]
    cyc = cycle_work(desc)
    k = papp * desc["pre_cycles"]
    return {"bytes": spmv * (a0 * ab + 2 * n * vb) + streams * n * vb
            + k * cyc["bytes"],
            "values": spmv * a0 + k * cyc["values"]}


def least_work(desc: Dict[str, Any], iters: int) -> Dict[str, int]:
    """Least bytes and operations of one solve of ``iters`` Krylov
    iterations (refinement passes included): the iterations, the read of
    the right-hand side and the write of the solution, and with
    refinement one residual of the operator in working precision against
    a double-width iterate."""
    vb, ab = desc["vector_bytes"], desc["value_bytes"]
    n = desc["levels"][0]["rows"]
    it = iteration_work(desc)
    nbytes = iters * it["bytes"] + 2 * n * vb
    values = iters * it["values"]
    if desc["refine"] > 0:
        a0 = desc["levels"][0]["a_values"]
        nbytes += a0 * ab + n * (2 * vb + 8)
        values += a0
    return {"bytes": int(nbytes), "flops": int(2 * values)}


def least_seconds(work: Dict[str, int], peak: Dict[str, float]):
    """(least seconds at the chip's peaks, the bound that sets them)."""
    mem = work["bytes"] / peak["hbm_bytes_per_s"]
    comp = work["flops"] / peak["flops_per_s"]
    return (mem, "memory") if mem >= comp else (comp, "compute")
