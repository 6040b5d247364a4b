"""Mesh-sharded distance-2 MIS aggregation.

The reference's distributed PMIS coarsening is 1131 lines of rank-boundary
ownership resolution with dynamic messaging
(amgcl/mpi/coarsening/pmis.hpp:49-1131). On a TPU mesh the same algorithm
is data-parallel max-plus propagation: each round's root election and
distance-1/2 captures are masked row-max gathers over the strength
adjacency, and the ONLY communication is the same static halo exchange the
SpMV uses (one ``all_to_all`` per gather). Ownership resolution is free:
priorities are globally unique, so every shard deterministically agrees on
the winner of every boundary contest — no handshake, no retries.

``sharded_aggregates(A, eps, mesh)`` is a drop-in for
``plain_aggregates``: the per-entry strength filter runs on the host
(embarrassingly parallel, same cost class as one matrix pass), the MIS
rounds — the iterative, communication-heavy part that pmis.hpp spends its
complexity on — run jitted on the mesh, and the aggregate keys come back
for the host to compress and feed the tentative prolongation.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.parallel.mesh import ROWS_AXIS, put_sharded
from amgcl_tpu.parallel.dist_ell import DistEllMatrix, build_dist_ell


def _gather_all(dS: DistEllMatrix, x_local):
    """Neighbor values of every local row over the halo plan:
    (nloc, K1 + K2) — local columns first, then halo columns."""
    send = jnp.take(x_local, dS.send_idx[0], axis=0)
    halo = lax.all_to_all(send, ROWS_AXIS, 0, 0, tiled=False).reshape(-1)
    gl = jnp.take(x_local, dS.loc_cols[0], axis=0)
    gr = jnp.take(halo, dS.rem_cols[0], axis=0)
    return jnp.concatenate([gl, gr], axis=1)


def _mis_shard_body(dS: DistEllMatrix, prio, rounds: int):
    """Runs inside shard_map. prio: (1, nloc) unique positive int32 per
    global row (0 on padding rows). Returns per-shard aggregate keys."""
    prio = prio[0]
    valid = jnp.concatenate(
        [dS.loc_vals[0] > 0, dS.rem_vals[0] > 0], axis=1)

    def row_max(x):
        return jnp.max(jnp.where(valid, _gather_all(dS, x), 0), axis=1)

    has_nbr = jnp.any(valid, axis=1)

    def cond(carry):
        key, und, r = carry
        # one scalar psum per round stops at convergence (typically ~5-10
        # rounds on stencil graphs) instead of burning the full cap's
        # collectives on an all-decided mask
        return (r < rounds) & (lax.psum(und.sum(), ROWS_AXIS) > 0)

    def body(carry):
        key, und, r = carry
        p_und = jnp.where(und, prio, 0)
        # closed 2-hop max of undecided priorities: a node wins exactly
        # when it holds the maximum of its distance-2 neighborhood
        m1 = row_max(p_und)
        m2 = jnp.maximum(row_max(jnp.maximum(m1, p_und)), m1)
        winners = und & (prio >= m2)
        key = jnp.where(winners, prio, key)
        # distance-1 capture: adopt the best adjacent new root
        pw = jnp.where(winners, prio, 0)
        w1 = row_max(pw)
        d1 = und & ~winners & (w1 > 0)
        key = jnp.where(d1, w1, key)
        # distance-2 capture: adopt the key of the best captured neighbor
        cap = winners | d1
        kcap = jnp.where(cap, key, 0)
        pcap = jnp.where(cap, prio, 0)
        best_p = row_max(pcap)
        pg = jnp.where(valid, _gather_all(dS, pcap), 0)
        kg = jnp.where(valid, _gather_all(dS, kcap), 0)
        hit = (pg > 0) & (pg == best_p[:, None])
        k2 = jnp.max(jnp.where(hit, kg, 0), axis=1)
        d2 = und & ~cap & (best_p > 0)
        key = jnp.where(d2, k2, key)
        und = und & ~(winners | d1 | d2)
        return (key, und, r + 1)

    key0 = jnp.zeros_like(prio)
    key, und, _ = lax.while_loop(cond, body, (key0, has_nbr, 0))
    # pathological leftovers become their own roots
    key = jnp.where(und, prio, key)
    return key


@lru_cache(maxsize=32)
def _compiled_mis(mesh, shape, nloc, ncloc, rounds):
    s = P(ROWS_AXIS, None, None)
    dS_spec = DistEllMatrix(s, s, s, s, s, shape, nloc, ncloc)

    def run(dS, prio):
        return _mis_shard_body(dS, prio, rounds)

    fn = shard_map(run, mesh=mesh, in_specs=(dS_spec, P(ROWS_AXIS, None)),
                   out_specs=P(ROWS_AXIS), check_vma=False)
    # observed jit (telemetry/compile_watch.py): runs once per strip
    # setup, but the lru_cache above makes it a process-lived entry
    # point — keep its compiles attributable
    from amgcl_tpu.telemetry.compile_watch import watched_jit
    return watched_jit(fn, name="parallel.dist_mis")


def sharded_aggregates(A: CSR, eps_strong: float, mesh, rounds: int = 40):
    """Drop-in for ``plain_aggregates`` running the MIS rounds on the mesh.
    Returns (agg, n_agg) in the host convention (-1 for isolated rows)."""
    from amgcl_tpu.coarsening.aggregates import strength_graph, _priority

    S = strength_graph(A, eps_strong)
    n = S.shape[0]
    Sc = CSR(S.indptr.astype(np.int64), S.indices.astype(np.int32),
             np.ones(S.nnz), n)
    dS = build_dist_ell(Sc, mesh, jnp.float32)
    nd = mesh.shape[ROWS_AXIS]
    n_pad = dS.nloc * nd
    prio = np.zeros(n_pad, dtype=np.int32)
    prio[:n] = _priority(n).astype(np.int32)
    prio_sh = put_sharded(prio.reshape(nd, dS.nloc), mesh, jnp.int32)
    fn = _compiled_mis(mesh, dS.shape, dS.nloc, dS.ncloc, int(rounds))
    key = np.asarray(fn(dS, prio_sh))[:n]
    agg = np.full(n, -1, dtype=np.int64)
    live = key > 0
    uniq, inv = np.unique(key[live], return_inverse=True)
    agg[live] = inv
    return agg, len(uniq)


def make_mesh_aggregator(mesh, rounds: int = 40):
    """An ``aggregator`` hook for the coarsening policies: aggregation runs
    sharded on this mesh (used by DistAMGSolver(device_mis=True))."""
    return lambda A, eps: sharded_aggregates(A, eps, mesh, rounds)
