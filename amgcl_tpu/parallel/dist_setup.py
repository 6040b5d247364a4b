"""Strip-parallel hierarchy construction for GENERAL (unstructured) matrices.

The reference builds the whole distributed hierarchy per-rank: each MPI rank
owns a row strip, and the setup-phase products run as remote-row fetch +
local product (distributed SpGEMM, amgcl/mpi/distributed_matrix.hpp:856-1066)
and triple routing (distributed transpose, amgcl/mpi/distributed_matrix.hpp:
559-716) inside mpi::amg's step_down (amgcl/mpi/amg.hpp:163-330). This module
is the TPU-native rendition of that architecture:

- the SOLVE phase is unchanged — the sharded shard_map program of
  dist_amg.py over DistEllMatrix levels;
- the SETUP phase runs strip-at-a-time on the host with the reference's
  fetch/route communication structure, so the per-strip working set is
  O(nnz/nd + halo) instead of O(nnz) — no step ever assembles a global
  matrix (level arrays are placed shard-by-shard via put_sharded_parts);
- aggregation is the already-mesh-sharded MIS (parallel/dist_mis.py), fed
  strip-built strength graphs, so the communication-heavy rounds run jitted
  on the mesh.

Under single-controller JAX the strip "communication" is in-process slicing
behind the :class:`LocalComm` seam; a multi-controller comm realizes the
same five primitives over ``jax.distributed`` so each process only ever
holds its own strips (the strip-ingestion pattern of the reference's
examples/mpi/mpi_solver.cpp:190-238).

Coarse-level numbering keeps locality by construction: each shard numbers
the MIS roots it owns contiguously from an exclusive prefix of per-shard
root counts, so coarse row blocks stay aligned with the fine row blocks
that produced them — the role of the reference's repartitioners
(amgcl/mpi/partition/*.hpp) falls out of the numbering for aggregation-type
coarsening.
"""

from __future__ import annotations

import copy
import functools
from typing import Any, Optional

import numpy as np
import scipy.sparse as sp
import jax.numpy as jnp

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.parallel.mesh import ROWS_AXIS, put_sharded_parts

__all__ = [
    "LocalComm", "split_strips", "strip_transpose", "strip_spgemm",
    "strip_sa_hierarchy", "StripAMGSolver",
]


# ===========================================================================
# communication seam
# ===========================================================================

# Shared with the serial builder (and every coarsening policy) since r5;
# re-exported here because the strip route's callers import it from this
# module. The strip builder catches exactly this — not arbitrary
# ValueErrors — and closes the hierarchy with the replicated tail, the
# same way the serial build stops (models/amg.py stall guard).
from amgcl_tpu.coarsening.stall import CoarseningStall  # noqa: E402


class LocalComm:
    """Single-controller realization of the strip-exchange primitives.

    Every method takes/returns PER-SHARD lists (index = shard id).
    :class:`MultihostComm` implements the same interface where each
    process holds only its own shards' entries (``None`` elsewhere) and
    the data moves over jax.distributed."""

    def __init__(self, nd: int):
        self.nd = int(nd)
        self.my_shards = list(range(self.nd))

    def max_scalar(self, per_shard) -> float:
        """Global max of one scalar per owned shard (MPI_Allreduce MAX).
        -inf when nothing is owned anywhere (the allreduce identity)."""
        return float(max((v for v in per_shard if v is not None),
                         default=-np.inf))

    def _vals_meta(self, vals_per_shard):
        """(is_complex, is_int) of the value payload, from owned non-None
        entries only — safe for a process that owns no shards."""
        kinds = {np.asarray(vals_per_shard[s]).dtype.kind
                 for s in self.my_shards if vals_per_shard[s] is not None}
        return bool(kinds & {"c"}), bool(kinds & {"i", "u"})

    def alltoall(self, buckets):
        """buckets[src][dst] = (rows, cols, vals) destined for shard dst,
        for each OWNED src (None elsewhere); returns recv[dst][src] for
        each owned dst (the reference's Isend/Irecv triple exchange,
        distributed_matrix.hpp:559-716)."""
        return [[buckets[s][d] for s in range(self.nd)]
                for d in range(self.nd)]

    def allgather_concat(self, per_shard):
        """Concatenate one 1-D array per owned shard across every shard
        (MPI_Allgatherv); every caller sees the same global array."""
        return np.concatenate([np.asarray(per_shard[s])
                               for s in range(self.nd)])

    def fetch_rows(self, strips, nloc, gids_per_shard):
        """Remote-row fetch (the reference's SpGEMM prologue,
        distributed_matrix.hpp:856-940): for each owned requesting shard,
        the scipy CSR stack of global rows ``gids`` (sorted unique) served
        by their owners."""
        out = []
        for gids in gids_per_shard:
            if gids is None:
                out.append(None)
                continue
            gids = np.asarray(gids)
            if len(gids) == 0:
                out.append(None)
                continue
            owner = np.minimum(gids // nloc, self.nd - 1)
            parts = []
            for o in range(self.nd):
                sel = gids[owner == o]
                if len(sel):
                    parts.append(strips[o][sel - o * nloc])
            out.append(sp.vstack(parts, format="csr") if parts else None)
        return out

    def fetch_vals(self, vals_per_shard, nloc, gids_per_shard):
        """Same as fetch_rows for one value per global row (duplicate and
        unsorted ids allowed)."""
        out = []
        ref_dt = np.asarray(
            next(v for v in vals_per_shard if v is not None)).dtype
        for gids in gids_per_shard:
            if gids is None:
                out.append(None)
                continue
            gids = np.asarray(gids)
            if len(gids) == 0:
                out.append(np.zeros(0, ref_dt))
                continue
            owner = np.minimum(gids // nloc, self.nd - 1)
            res = np.empty(len(gids), ref_dt)
            for o in range(self.nd):
                sel = owner == o
                if sel.any():
                    res[sel] = np.asarray(
                        vals_per_shard[o])[gids[sel] - o * nloc]
            out.append(res)
        return out


class MultihostComm(LocalComm):
    """Multi-controller realization over ``jax.distributed``: each process
    holds only its addressable shards' strips; small reductions ride
    ``process_allgather`` and the bulk triple exchange is ONE device
    ``all_to_all`` over the rows mesh, so no process ever materializes
    another process's strip (reference role: the Isend/Irecv exchanges of
    distributed_matrix.hpp; ingestion pattern of
    examples/mpi/mpi_solver.cpp:190-238)."""

    def __init__(self, mesh):
        import jax
        self.mesh = mesh
        self.nd = int(mesh.shape[ROWS_AXIS])
        pid = jax.process_index()
        devs = list(np.asarray(mesh.devices).reshape(-1))
        self.my_shards = [i for i, d in enumerate(devs)
                          if d.process_index == pid]

    # -- small fixed-shape allreduce helpers --------------------------------

    def _allgather_np(self, arr, combine):
        from jax.experimental import multihost_utils
        a = np.asarray(arr)
        g = np.asarray(multihost_utils.process_allgather(a))
        # jax versions disagree on whether the process axis is stacked
        # (nproc, *shape) or tiled ((nproc*n0, ...)); normalize to stacked
        g = g.reshape((-1,) + a.shape)
        return combine(g, axis=0)

    def max_scalar(self, per_shard) -> float:
        vals = [v for v in per_shard if v is not None]
        loc = max(vals) if vals else -np.inf
        return float(self._allgather_np(np.float64(loc), np.max))

    def _vals_meta(self, vals_per_shard):
        # flags must agree across processes even when this one owns no
        # shards on the rows axis — reduce them over process_allgather
        cplx, isint = LocalComm._vals_meta(self, vals_per_shard)
        flags = self._allgather_np(np.int64([cplx, isint]), np.max)
        return bool(flags[0]), bool(flags[1])

    def _allgather_var(self, arr):
        """Allgatherv of one variable-length 1-D array per process.
        Lengths ride a separate int64 gather — never the payload dtype,
        which could not represent large counts exactly (float32 payloads
        round above 2^24)."""
        from jax.experimental import multihost_utils
        arr = np.asarray(arr)
        lens = np.asarray(
            multihost_utils.process_allgather(np.int64(arr.shape[0])))
        lens = lens.reshape(-1)
        n = int(lens.max())
        if n == 0:
            return arr
        pad = np.zeros(n, dtype=arr.dtype)
        pad[:arr.shape[0]] = arr
        g = np.asarray(multihost_utils.process_allgather(pad))
        return np.concatenate([g[p, :int(lens[p])]
                               for p in range(g.shape[0])])

    def allgather_concat(self, per_shard):
        loc = np.concatenate(
            [np.asarray(per_shard[s]) for s in self.my_shards]) \
            if self.my_shards else np.zeros(0, np.int64)
        return self._allgather_var(loc)

    # -- bulk exchange: ONE device all_to_all over the mesh -----------------

    # elements per (src,dst) slot per exchange round: bounds the padded
    # payload at nd * _CHUNK_CAP * 24B per shard per round; larger
    # messages stream over several rounds of the SAME compiled program
    # (a single global max chunk would inflate every nd^2 slot to the
    # size of the one largest message)
    _CHUNK_CAP = 1 << 16

    def alltoall(self, buckets):
        nd = self.nd
        # global max message + value dtype agreement
        loc_max = max((len(buckets[s][d][0]) for s in self.my_shards
                       for d in range(nd)), default=0)
        M = max(int(self._allgather_np(np.int64(loc_max), np.max)), 1)
        has_cplx = any(np.asarray(buckets[s][d][2]).dtype.kind == "c"
                       for s in self.my_shards for d in range(nd))
        has_cplx = bool(self._allgather_np(np.int64(has_cplx), np.max))
        vdt = np.complex128 if has_cplx else np.float64
        # power-of-two chunk, capped: quantized so _compiled_alltoall's
        # distinct jit compilations stay ~log2(range)
        C = min(1 << (M - 1).bit_length(), self._CHUNK_CAP)
        rounds = -(-M // C)

        cnt = np.zeros((nd, nd), np.int64)
        for s in self.my_shards:
            for d in range(nd):
                cnt[s, d] = len(np.asarray(buckets[s][d][0]))
        cnt = self._allgather_np(cnt, np.sum)     # zeros elsewhere

        fn = _compiled_alltoall(self.mesh, C, "c" if has_cplx else "f")
        pieces = {d: [([], [], []) for _ in range(nd)]
                  for d in self.my_shards}
        for t in range(rounds):
            lo = t * C
            idx_parts = [None] * nd
            val_parts = [None] * nd
            for s in self.my_shards:
                ip = np.zeros((nd, C, 2), np.int64)
                vp = np.zeros((nd, C), vdt)
                for d in range(nd):
                    r, c, v = buckets[s][d]
                    k = max(0, min(len(np.asarray(r)) - lo, C))
                    if k:
                        ip[d, :k, 0] = np.asarray(r)[lo:lo + k]
                        ip[d, :k, 1] = np.asarray(c)[lo:lo + k]
                        vp[d, :k] = np.asarray(v)[lo:lo + k]
                idx_parts[s] = ip
                val_parts[s] = vp
            idx_sh = put_sharded_parts(idx_parts, self.mesh, jnp.int64)
            val_sh = put_sharded_parts(
                val_parts, self.mesh,
                jnp.complex128 if has_cplx else jnp.float64)
            idx_r, val_r = fn(idx_sh, val_sh)
            got_i = {sh.index[0].start or 0: np.asarray(sh.data)[0]
                     for sh in idx_r.addressable_shards}
            got_v = {sh.index[0].start or 0: np.asarray(sh.data)[0]
                     for sh in val_r.addressable_shards}
            for d in self.my_shards:
                for s in range(nd):
                    k = max(0, min(int(cnt[s, d]) - lo, C))
                    if k:
                        rs, cs, vs = pieces[d][s]
                        rs.append(got_i[d][s, :k, 0])
                        cs.append(got_i[d][s, :k, 1])
                        vs.append(got_v[d][s, :k])

        out = [None] * nd
        z = np.zeros(0, np.int64)
        for d in self.my_shards:
            seg = []
            for s in range(nd):
                rs, cs, vs = pieces[d][s]
                seg.append((
                    np.concatenate(rs) if rs else z,
                    np.concatenate(cs) if cs else z,
                    np.concatenate(vs) if vs else np.zeros(0, vdt)))
            out[d] = seg
        return out

    # -- fetch = route requests, serve, route responses ---------------------

    def _route_requests(self, nloc, gids_per_shard):
        nd = self.nd
        req = [None] * nd
        uniq = [None] * nd
        for s in self.my_shards:
            gids = np.asarray(gids_per_shard[s]) \
                if gids_per_shard[s] is not None else np.zeros(0, np.int64)
            u = np.unique(gids)
            uniq[s] = u
            owner = np.minimum(u // nloc, nd - 1) if len(u) else u
            bk = []
            for o in range(nd):
                sel = u[owner == o] if len(u) else u
                bk.append((sel, np.zeros(len(sel), np.int64),
                           np.zeros(len(sel))))
            req[s] = bk
        return req, uniq

    def fetch_vals(self, vals_per_shard, nloc, gids_per_shard):
        nd = self.nd
        req, uniq = self._route_requests(nloc, gids_per_shard)
        recv_req = self.alltoall(req)
        resp = [None] * nd
        for o in self.my_shards:
            vals_o = np.asarray(vals_per_shard[o])
            bk = []
            for s in range(nd):
                want = np.asarray(recv_req[o][s][0], np.int64)
                served = vals_o[want - o * nloc] if len(want) else \
                    np.zeros(0, vals_o.dtype)
                bk.append((want, np.zeros(len(want), np.int64), served))
            resp[o] = bk
        recv = self.alltoall(resp)
        has_cplx, has_int = self._vals_meta(vals_per_shard)
        out = [None] * nd
        for s in self.my_shards:
            gids = np.asarray(gids_per_shard[s]) \
                if gids_per_shard[s] is not None else None
            if gids is None or len(gids) == 0:
                out[s] = np.zeros(0) if gids is not None else None
                continue
            got_g = np.concatenate([np.asarray(recv[s][o][0], np.int64)
                                    for o in range(nd)])
            got_v = np.concatenate([np.asarray(recv[s][o][2])
                                    for o in range(nd)])
            order = np.argsort(got_g)
            pos = order[np.searchsorted(got_g[order], gids)]
            vals = got_v[pos]
            if not has_cplx:
                vals = vals.real
            # integer payloads (aggregate ids) ride the float channel;
            # values are exact integers well below 2^53
            if has_int:
                vals = np.rint(vals.real).astype(np.int64)
            out[s] = vals
        return out

    def fetch_rows(self, strips, nloc, gids_per_shard):
        nd = self.nd
        req, uniq = self._route_requests(nloc, gids_per_shard)
        recv_req = self.alltoall(req)
        resp = [None] * nd
        for o in self.my_shards:
            S = strips[o]
            bk = []
            for s in range(nd):
                want = np.asarray(recv_req[o][s][0], np.int64)
                if len(want):
                    sub = S[want - o * nloc].tocoo()
                    gid_of = want[sub.row]
                    bk.append((gid_of, sub.col.astype(np.int64), sub.data))
                else:
                    bk.append((np.zeros(0, np.int64), np.zeros(0, np.int64),
                               np.zeros(0)))
            resp[o] = bk
        recv = self.alltoall(resp)
        ncols = None
        for s in self.my_shards:
            ncols = strips[s].shape[1]
            break
        out = [None] * nd
        for s in self.my_shards:
            gids = gids_per_shard[s]
            if gids is None or len(np.asarray(gids)) == 0:
                out[s] = None
                continue
            gids = np.asarray(gids)
            gg = np.concatenate([np.asarray(recv[s][o][0], np.int64)
                                 for o in range(nd)])
            cc = np.concatenate([np.asarray(recv[s][o][1], np.int64)
                                 for o in range(nd)])
            vv = np.concatenate([np.asarray(recv[s][o][2])
                                 for o in range(nd)])
            if not any(np.iscomplexobj(np.asarray(strips[t].data))
                       for t in self.my_shards):
                vv = vv.real
            rows_rel = np.searchsorted(gids, gg)   # gids sorted unique
            M = sp.coo_matrix((vv, (rows_rel, cc)),
                              shape=(len(gids), ncols)).tocsr()
            M.sum_duplicates()
            M.sort_indices()
            out[s] = M
        return out


@functools.lru_cache(maxsize=64)
def _compiled_alltoall(mesh, C, kind):
    """One jitted shard_map all_to_all for (nd, nd, C, ...) payloads."""
    import jax
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def run(idx, val):
        i = lax.all_to_all(idx[0], ROWS_AXIS, 0, 0, tiled=False)
        v = lax.all_to_all(val[0], ROWS_AXIS, 0, 0, tiled=False)
        return i[None], v[None]

    fn = shard_map(run, mesh=mesh,
                   in_specs=(P(ROWS_AXIS), P(ROWS_AXIS)),
                   out_specs=(P(ROWS_AXIS), P(ROWS_AXIS)),
                   check_vma=False)
    # observed jit (telemetry/compile_watch.py): every strip-setup
    # triple product funnels its exchanges through this cached program
    from amgcl_tpu.telemetry.compile_watch import watched_jit
    return watched_jit(fn, name="parallel.dist_exchange")


# ===========================================================================
# strip primitives: split / transpose / SpGEMM
# ===========================================================================

def split_strips(A, nd: int):
    """Row-strip a host matrix: per-shard scipy CSR with GLOBAL columns,
    strip s = rows [s*nloc, min((s+1)*nloc, n)). Only the entry point for
    single-host matrices — multi-host ingestion hands per-process strips
    straight to strip_sa_hierarchy without this call."""
    if isinstance(A, CSR):
        assert not A.is_block, "strip the unblocked matrix"
        A = A.to_scipy()
    A = sp.csr_matrix(A)
    n = A.shape[0]
    nloc = -(-n // nd)
    return [A[min(s * nloc, n): min((s + 1) * nloc, n)]
            for s in range(nd)], nloc


def strip_transpose(strips, nloc_in, nloc_out, shape_out, comm: LocalComm):
    """Distributed transpose by triple routing (reference:
    distributed_matrix.hpp:559-716): entry (i, j, v) of strip s is routed to
    the owner of row j in the OUTPUT partition and lands as (j, i, v)."""
    nd = comm.nd
    buckets = [None] * nd
    for s in comm.my_shards:
        S = strips[s]
        r0 = s * nloc_in
        rows_g = np.repeat(np.arange(S.shape[0]), np.diff(S.indptr)) + r0
        dst = np.minimum(S.indices // nloc_out, nd - 1)
        bk = []
        for d in range(nd):
            sel = dst == d
            bk.append((S.indices[sel], rows_g[sel], S.data[sel]))
        buckets[s] = bk
    recv = comm.alltoall(buckets)
    n_out, m_out = shape_out
    out = [None] * nd
    for d in comm.my_shards:
        r0, r1 = min(d * nloc_out, n_out), min((d + 1) * nloc_out, n_out)
        rr = np.concatenate([np.asarray(t[0]) for t in recv[d]])
        cc = np.concatenate([np.asarray(t[1]) for t in recv[d]])
        vv = np.concatenate([np.asarray(t[2]) for t in recv[d]])
        T = sp.coo_matrix((vv, (rr - r0, cc)),
                          shape=(r1 - r0, m_out)).tocsr()
        T.sum_duplicates()
        T.sort_indices()
        out[d] = T
    return out


def strip_spgemm(A_strips, B_strips, nloc_B, comm: LocalComm):
    """C = A @ B with A row-stripped and B row-stripped by A's column
    partition: fetch the B rows each strip's columns touch, then multiply
    locally (reference: distributed_matrix.hpp:856-1066). Returns C strips
    on A's row partition."""
    nd = comm.nd
    ucols = [None] * nd
    ncols_B = None
    for s in comm.my_shards:
        S = A_strips[s]
        ucols[s] = np.unique(S.indices) if S.nnz else np.zeros(0, np.int64)
        ncols_B = B_strips[s].shape[1]
    B_sub = comm.fetch_rows(B_strips, nloc_B, ucols)
    out = [None] * nd
    for s in comm.my_shards:
        S = A_strips[s]
        if S.nnz == 0 or B_sub[s] is None:
            out[s] = sp.csr_matrix((S.shape[0], ncols_B))
            continue
        # remap columns into the fetched row block
        pos = np.searchsorted(ucols[s], S.indices)
        Sl = sp.csr_matrix((S.data, pos, S.indptr),
                           shape=(S.shape[0], len(ucols[s])))
        C = (Sl @ B_sub[s]).tocsr()
        C.sum_duplicates()
        C.sort_indices()
        out[s] = C
    return out


# ===========================================================================
# per-level SA construction on strips
# ===========================================================================

def _strip_diag(strips, nloc, my_shards=None):
    """Per-strip diagonal values (value at (i, r0+i))."""
    out = [None] * len(strips)
    for s in (range(len(strips)) if my_shards is None else my_shards):
        S = strips[s]
        r0 = s * nloc
        m_s = S.shape[0]
        rows = np.repeat(np.arange(m_s), np.diff(S.indptr))
        d = np.zeros(m_s, S.data.dtype)
        hit = S.indices == rows + r0
        d[rows[hit]] = S.data[hit]
        out[s] = d
    return out


def _strip_filtered(strips, nloc, eps, comm, need_filtered=True):
    """Strength filter + weak-entry lumping per strip (the serial
    ``smoothed_aggregation._filtered`` with halo diagonal fetch).
    Returns (Af_strips, Dfinv_strips, strong_offdiag_masks, ucols);
    ``need_filtered=False`` (plain aggregation) skips assembling the
    lumped Af/Dfinv — only the strength masks are produced."""
    nd = comm.nd
    dloc = _strip_diag(strips, nloc, comm.my_shards)
    ucols = [None] * nd
    for s in comm.my_shards:
        S = strips[s]
        ucols[s] = np.unique(S.indices) if S.nnz else np.zeros(0, np.int64)
    dj_per = comm.fetch_vals(dloc, nloc, ucols)
    Af = [None] * nd
    Dfinv = [None] * nd
    strong_masks = [None] * nd
    for s in comm.my_shards:
        S = strips[s]
        r0 = s * nloc
        m_s = S.shape[0]
        rows = np.repeat(np.arange(m_s), np.diff(S.indptr))
        di = np.abs(dloc[s])
        dj = np.abs(dj_per[s])[np.searchsorted(ucols[s], S.indices)] \
            if S.nnz else np.zeros(0)
        is_dia = S.indices == rows + r0
        strong = (np.abs(S.data) ** 2 > eps * eps * di[rows] * dj)
        strong_masks[s] = (strong & ~is_dia, rows)
        if not need_filtered:
            continue
        keep = strong | is_dia
        # lump removed entries onto the diagonal
        removed = np.bincount(rows[~keep], weights=S.data[~keep].real,
                              minlength=m_s).astype(S.data.dtype)
        if np.iscomplexobj(S.data):
            removed = removed + 1j * np.bincount(
                rows[~keep], weights=S.data[~keep].imag, minlength=m_s)
        data = S.data[keep].copy()
        col = S.indices[keep]
        ptr = np.concatenate(
            [[0], np.cumsum(np.bincount(rows[keep], minlength=m_s))])
        F = sp.csr_matrix((data, col, ptr), shape=S.shape)
        frows = np.repeat(np.arange(m_s), np.diff(F.indptr))
        fdia = F.indices == frows + r0
        F.data[fdia] += removed[frows[fdia]]
        dF = np.zeros(m_s, F.data.dtype)
        dF[frows[fdia]] = F.data[fdia]
        Af[s] = F
        Dfinv[s] = np.where(dF != 0, 1.0 / np.where(dF != 0, dF, 1), 1.0)
    return Af, Dfinv, strong_masks, ucols


def _strip_mis_aggregates(strips, strong_masks, n, nloc, mesh, comm,
                          rounds=40):
    """Mesh-sharded MIS over the strip-built strength graph; coarse ids
    numbered per-owner from an exclusive prefix (locality-preserving).
    Returns (agg strips with -1 for isolated, nc)."""
    import jax
    from amgcl_tpu.coarsening.aggregates import _priority
    from amgcl_tpu.parallel.dist_ell import build_dist_ell_strips
    from amgcl_tpu.parallel.dist_mis import _compiled_mis

    nd = comm.nd
    # symmetrized strength adjacency, strip-wise: local strong pattern OR
    # its routed transpose
    pat = [None] * nd
    for s in comm.my_shards:
        S = strips[s]
        mask, rows = strong_masks[s]
        pat[s] = sp.csr_matrix(
            (np.ones(int(mask.sum()), np.int8),
             (rows[mask], S.indices[mask])), shape=S.shape)
    patT = strip_transpose(pat, nloc, nloc, (n, n), comm)
    triples = [None] * nd
    for s in comm.my_shards:
        G = ((pat[s] + patT[s]) > 0).astype(np.float32).tocsr()
        G.sort_indices()
        rows = np.repeat(np.arange(G.shape[0]), np.diff(G.indptr))
        triples[s] = (rows, G.indices.astype(np.int64), G.data)
    dS = build_dist_ell_strips(triples, mesh, (n, n), jnp.float32,
                               nloc=nloc, comm=comm)

    prio_full = _priority(n).astype(np.int32)
    prio_parts = [None] * nd
    for s in comm.my_shards:
        r0, r1 = min(s * nloc, n), min((s + 1) * nloc, n)
        p = np.zeros(dS.nloc, np.int32)
        p[: r1 - r0] = prio_full[r0:r1]
        prio_parts[s] = p
    prio_sh = put_sharded_parts(prio_parts, mesh, jnp.int32)
    fn = _compiled_mis(mesh, dS.shape, dS.nloc, dS.ncloc, int(rounds))
    from amgcl_tpu.parallel.mesh import host_full
    key_g = np.asarray(host_full(fn(dS, prio_sh)))

    # Coarse numbering: every process derives the same global cid map from
    # the (allgathered) MIS keys — O(n) ints, the same cost class as the
    # priority permutation itself. Roots (key == own priority) are numbered
    # per-owner contiguous, so coarse blocks stay aligned with the fine
    # blocks that produced them; captured rows adopt their root's cid via
    # the priority-inverse.
    inv = np.empty(n, np.int64)
    inv[prio_full - 1] = np.arange(n)
    keyv = key_g[: nd * dS.nloc].reshape(nd, dS.nloc)
    cid_full = np.full(n, -1, np.int64)
    nc = 0
    for s in range(nd):
        r0, r1 = min(s * nloc, n), min((s + 1) * nloc, n)
        k = keyv[s, : r1 - r0]
        roots = (k == prio_full[r0:r1]) & (k > 0)
        idx = np.flatnonzero(roots) + r0
        cid_full[idx] = nc + np.arange(len(idx))
        nc += len(idx)
    agg = [None] * nd
    for s in comm.my_shards:
        r0, r1 = min(s * nloc, n), min((s + 1) * nloc, n)
        k = keyv[s, : r1 - r0]
        root_row = inv[np.maximum(k, 1) - 1]
        agg[s] = np.where(k > 0, cid_full[root_row], -1).astype(np.int64)
    return agg, int(nc)


def _strip_sa_level(strips, n, nloc, mesh, comm, eps, relax,
                    mis_rounds=40, smooth=True, ac_scale=1.0):
    """One aggregation level on strips: (P_strips, Ac_strips, nc, nloc_c).
    ``smooth=True`` is smoothed aggregation (P = (I - w D^-1 Af) P_tent,
    Gershgorin omega); ``smooth=False`` is plain aggregation (P = P_tent,
    ``ac_scale`` applies the reference's 1/over_interp Galerkin scaling,
    aggregation.hpp:71-160). R is NOT formed here — between two sharded
    levels the caller transposes P (strip_transpose); at the
    replicated-tail boundary the local S.T suffices (TransitionOps), so a
    distributed transpose there would be wasted traffic.

    Mirrors the serial policies + galerkin exactly (same strength filter,
    same omega, same MIS — iteration counts match the serial device_mis
    build up to a permutation of coarse unknowns)."""
    nd = comm.nd
    Af, Dfinv, strong_masks, ucols = _strip_filtered(
        strips, nloc, eps, comm, need_filtered=smooth)
    agg, nc = _strip_mis_aggregates(strips, strong_masks, n, nloc, mesh,
                                    comm, mis_rounds)
    if nc == 0:
        raise CoarseningStall("empty coarse level (all rows isolated)")
    nloc_c = -(-nc // nd)

    P_strips = [None] * nd
    if smooth:
        # omega = relax * 4/3 / rho(Df^-1 Af), Gershgorin
        # (builtin.hpp:775-820)
        rho_loc = [None] * nd
        for s in comm.my_shards:
            absrow = np.asarray(np.abs(Af[s]).sum(axis=1)).ravel()
            rho_loc[s] = float(np.max(np.abs(Dfinv[s]) * absrow)) \
                if len(absrow) else 0.0
        rho = comm.max_scalar(rho_loc)
        omega = relax * (4.0 / 3.0) / max(rho, 1e-30)

        # P strip: row i of (I - omega Df^-1 Af) P_tent. P_tent[j] =
        # e_{agg_j} for agg_j >= 0, so P entries come straight from Af:
        # coef_ij = delta_ij - omega * Dfinv_i * Af_ij, col = agg_j.
        agg_cols = [None] * nd
        for s in comm.my_shards:
            F = Af[s]
            agg_cols[s] = np.unique(F.indices) if F.nnz \
                else np.zeros(0, np.int64)
        agg_j_per = comm.fetch_vals(agg, nloc, agg_cols)
        for s in comm.my_shards:
            F = Af[s]
            r0 = s * nloc
            m_s = F.shape[0]
            rows = np.repeat(np.arange(m_s), np.diff(F.indptr))
            aj = agg_j_per[s][np.searchsorted(agg_cols[s], F.indices)] \
                if F.nnz else np.zeros(0, np.int64)
            coef = -omega * Dfinv[s][rows] * F.data
            coef = coef + (F.indices == rows + r0)  # the identity term
            live = aj >= 0
            Pm = sp.coo_matrix(
                (coef[live], (rows[live], aj[live])),
                shape=(m_s, nc)).tocsr()
            Pm.sum_duplicates()
            Pm.sort_indices()
            P_strips[s] = Pm
    else:
        # plain aggregation: P_tent rows are unit vectors at the row's
        # aggregate — strictly strip-local
        for s in comm.my_shards:
            a = agg[s]
            live = np.flatnonzero(a >= 0)
            Pm = sp.coo_matrix(
                (np.ones(len(live)), (live, a[live])),
                shape=(len(a), nc)).tocsr()
            Pm.sort_indices()
            P_strips[s] = Pm

    # Ac = P^T (A P): local product per strip, triples routed to the coarse
    # owner (this is the distributed Galerkin SpGEMM,
    # distributed_matrix.hpp:856-1066 + mpi/amg.hpp:163-330)
    AP = strip_spgemm(strips, P_strips, nloc, comm)
    buckets = [None] * nd
    for s in comm.my_shards:
        L = (P_strips[s].T.tocsr() @ AP[s]).tocoo()   # (nc, nc) local part
        dst = np.minimum(L.row // nloc_c, nd - 1)
        bk = []
        for d in range(nd):
            sel = dst == d
            bk.append((L.row[sel], L.col[sel], L.data[sel]))
        buckets[s] = bk
    recv = comm.alltoall(buckets)
    Ac_strips = [None] * nd
    for d in comm.my_shards:
        r0, r1 = min(d * nloc_c, nc), min((d + 1) * nloc_c, nc)
        rr = np.concatenate([np.asarray(t[0]) for t in recv[d]])
        cc = np.concatenate([np.asarray(t[1]) for t in recv[d]])
        vv = np.concatenate([np.asarray(t[2]) for t in recv[d]])
        if ac_scale != 1.0:
            vv = vv * ac_scale
        Ac = sp.coo_matrix((vv, (rr - r0, cc)),
                           shape=(r1 - r0, nc)).tocsr()
        Ac.sum_duplicates()
        Ac.sort_indices()
        Ac_strips[d] = Ac
    return P_strips, Ac_strips, nc, nloc_c


# ===========================================================================
# smoothers + hierarchy assembly
# ===========================================================================

def _strip_smoother(relax, strips, n, nloc, mesh, comm, dtype):
    """Strip-local DistSmoother state: the row-local families plus
    SPAI-1 (whose Gram rows come from the same remote-row fetch the
    SpGEMM uses). The truly global factorizations (ilu*, gauss_seidel)
    need the assembled matrix and are served by the serial-build
    DistAMGSolver."""
    from amgcl_tpu.parallel.dist_amg import DistSmoother
    from amgcl_tpu.relaxation.spai0 import Spai0
    from amgcl_tpu.relaxation.jacobi import DampedJacobi
    from amgcl_tpu.relaxation.chebyshev import Chebyshev
    from amgcl_tpu.relaxation.spai1 import Spai1

    nd = comm.nd

    def parts_of(vec_strips, fill=0.0):
        host_dt = np.result_type(
            *([np.asarray(vec_strips[s]).dtype for s in comm.my_shards]
              + [np.float64]))
        out = [None] * nd
        for s in comm.my_shards:
            p = np.full(nloc, fill, host_dt)
            v = vec_strips[s]
            p[:len(v)] = v
            out[s] = p
        return put_sharded_parts(out, mesh, dtype)

    def invsafe(d):
        return np.where(d != 0, 1.0 / np.where(d != 0, d, 1), 1.0)

    if isinstance(relax, Spai0):
        # m_i = a_ii / sum_j |a_ij|^2 (spai0.hpp:49-117) — row-local
        dia = _strip_diag(strips, nloc, comm.my_shards)
        sc = [None] * nd
        for s in comm.my_shards:
            S = strips[s]
            rows = np.repeat(np.arange(S.shape[0]), np.diff(S.indptr))
            denom = np.bincount(rows, weights=(np.abs(S.data) ** 2).real,
                                minlength=S.shape[0])
            sc[s] = dia[s] / np.where(denom != 0, denom, 1.0)
        return DistSmoother("diag", parts_of(sc))
    if isinstance(relax, DampedJacobi):
        dia = _strip_diag(strips, nloc, comm.my_shards)
        sc = [None if dia[s] is None else relax.damping * invsafe(dia[s])
              for s in range(nd)]
        return DistSmoother("diag", parts_of(sc))
    if isinstance(relax, Chebyshev):
        if relax.power_iters:
            raise ValueError(
                "strip setup supports Gershgorin chebyshev only "
                "(power_iters=0)")
        dia = _strip_diag(strips, nloc, comm.my_shards) if relax.scale \
            else None
        loc = [None] * nd
        for s in comm.my_shards:
            absrow = np.asarray(np.abs(strips[s]).sum(axis=1)).ravel()
            if relax.scale:
                absrow = np.abs(invsafe(dia[s])) * absrow
            loc[s] = float(absrow.max()) if len(absrow) else 0.0
        rho = comm.max_scalar(loc)
        a, b = rho * relax.lower, rho
        dinv_sh = None
        if relax.scale:
            dinv_sh = parts_of(
                [None if d is None else invsafe(d) for d in dia])
        return DistSmoother("cheb", dinv_sh, theta=(a + b) / 2,
                            delta=(b - a) / 2, degree=relax.degree)
    if isinstance(relax, Spai1):
        # row-wise least squares over A's pattern (spai1.hpp:54): row i's
        # normal equations need B = A A^T restricted to J_i x J_i — every
        # needed A row is in this strip's column set, so ONE remote-row
        # fetch serves the whole Gram block. Same padded batched solve as
        # the serial build — per-row results are identical.
        from amgcl_tpu.relaxation.spai1 import (gather_sparse_entries,
                                                padded_pattern,
                                                pattern_normal_solve)
        ucols = [None] * nd
        for s in comm.my_shards:
            S = strips[s]
            ucols[s] = np.unique(S.indices) if S.nnz \
                else np.zeros(0, np.int64)
        Rsub = comm.fetch_rows(strips, nloc, ucols)
        M_strips = [None] * nd
        for s in comm.my_shards:
            S = strips[s]          # only the pattern is read; values come
            m_s = S.shape[0]       # from the fetched rows R
            if S.nnz == 0:
                M_strips[s] = sp.csr_matrix(S.shape)
                continue
            R = Rsub[s].astype(np.float64)   # rows ucols[s] of A
            posJ = np.searchsorted(ucols[s], S.indices)
            Jp, valid, rows, pos, K = padded_pattern(S.indptr, posJ)
            B = (R @ R.T).tocsr()            # strip-local Gram
            # rhs c[i, k] = A[J_ik, i_global] = R[posJ_ik, r0 + i]
            gcols = np.repeat(s * nloc + np.arange(m_s), K)
            c = gather_sparse_entries(R, Jp.ravel(), gcols).reshape(m_s, K)
            mvals = pattern_normal_solve(Jp, valid, B, c)
            M_strips[s] = sp.csr_matrix(
                (mvals[rows, pos], S.indices.copy(), S.indptr.copy()),
                shape=S.shape)
        Msp = _strips_to_dist_ell(M_strips, mesh, (n, n), dtype, nloc,
                                  nloc, comm)
        return DistSmoother("spai1", Msp=Msp)
    raise ValueError(
        "smoother %s has no strip-parallel build; use spai0/damped_jacobi/"
        "chebyshev/spai1, or the serial-build DistAMGSolver for ilu/gs"
        % type(relax).__name__)


def _strips_to_dist_ell(strips, mesh, shape, dtype, nloc, ncloc, comm):
    from amgcl_tpu.parallel.dist_ell import build_dist_ell_strips
    triples = [None] * comm.nd
    for s in comm.my_shards:
        S = strips[s]
        rows = np.repeat(np.arange(S.shape[0]), np.diff(S.indptr))
        triples[s] = (rows, S.indices.astype(np.int64), S.data)
    return build_dist_ell_strips(triples, mesh, shape, dtype, nloc, ncloc,
                                 comm=comm)


def _gather_strips(strips, shape, nloc, comm):
    """Assemble strips into one host CSR (used ONLY at the replicated-tail
    boundary, where the level is already small). Under multi-controller
    the tail triples are allgathered through the public comm interface —
    every process then runs the same replicated serial build."""
    nd = comm.nd
    rr = [None] * nd
    cc = [None] * nd
    vv = [None] * nd
    for s in comm.my_shards:
        S = strips[s].tocoo()
        rr[s] = S.row.astype(np.int64) + s * nloc
        cc[s] = S.col.astype(np.int64)
        vv[s] = S.data
    rr = comm.allgather_concat(rr)
    cc = comm.allgather_concat(cc)
    vv = comm.allgather_concat(vv)
    M = sp.coo_matrix((vv, (rr, cc)), shape=shape).tocsr()
    M.sum_duplicates()
    M.sort_indices()
    return CSR(M.indptr.astype(np.int64), M.indices.astype(np.int32),
               M.data, shape[1])


def strip_sa_hierarchy(strips, n, mesh, prm, comm=None,
                       replicate_below: int = 4096, mis_rounds: int = 40,
                       max_sharded_levels: int = 30, precond_dtype=None,
                       rep_rowshard: bool = False):
    """Build the distributed hierarchy from row strips. Returns
    (DistHierarchy, level_sizes, stats). No global matrix is ever
    assembled while levels stay sharded; the replicated tail (below
    ``replicate_below`` rows) is gathered and built serially, as
    DistAMGSolver does."""
    from amgcl_tpu.coarsening.smoothed_aggregation import \
        SmoothedAggregation
    from amgcl_tpu.models.amg import AMG, Hierarchy as SerialHierarchy
    from amgcl_tpu.parallel.dist_amg import (DistLevel, DistHierarchy,
                                             TransitionOps)

    nd = mesh.shape[ROWS_AXIS]
    if comm is None:
        import jax
        comm = MultihostComm(mesh) if jax.process_count() > 1 \
            else LocalComm(nd)
    from amgcl_tpu.coarsening.aggregation import Aggregation
    c = prm.coarsening
    if isinstance(c, SmoothedAggregation):
        smooth, ac_scale = True, 1.0
        if c.power_iters:
            raise ValueError("strip setup uses the Gershgorin omega "
                             "(power_iters=0)")
    elif isinstance(c, Aggregation):
        smooth, ac_scale = False, 1.0 / float(c.over_interp)
    else:
        raise ValueError("strip setup implements smoothed_aggregation "
                         "and aggregation; got %s" % type(c).__name__)
    if c.nullspace is not None or c.block_size != 1:
        raise ValueError("strip setup supports scalar aggregation only "
                         "(no nullspace, block_size=1)")
    if c.aggregator is not None:
        raise ValueError(
            "strip setup always aggregates with its own mesh-sharded MIS;"
            " a custom aggregator hook would be silently ignored — drop "
            "it or use the serial-build DistAMGSolver")
    dtype = precond_dtype or prm.dtype   # sharded operator dtype
    strips0, nloc0, n0 = strips, -(-n // nd), n   # finest level, for top_A
    eps = float(c.eps_strong)
    nloc = -(-n // nd)
    sizes = [n]
    levels = []

    def owned_peak(ss):
        return max((ss[s].nnz for s in comm.my_shards), default=0)

    stats = {"peak_strip_nnz": owned_peak(strips),
             "level_strip_nnz": []}

    while (n >= replicate_below and n > prm.coarse_enough
           and len(levels) + 1 < prm.max_levels
           and len(levels) < max_sharded_levels):
        try:
            P_s, Ac_s, nc, nloc_c = _strip_sa_level(
                strips, n, nloc, mesh, comm, eps,
                getattr(c, "relax", 1.0), mis_rounds,
                smooth=smooth, ac_scale=ac_scale)
        except CoarseningStall:
            break       # coarsening stalled: serial build breaks too
            # (any OTHER error propagates — a silent truncation here would
            # masquerade as a performance regression)
        if nc >= n:
            break
        dA = _strips_to_dist_ell(strips, mesh, (n, n), dtype, nloc, nloc,
                                 comm)
        sm = _strip_smoother(prm.relax, strips, n, nloc, mesh, comm, dtype)
        levels.append([dA, sm, P_s, nloc, n])
        stats["level_strip_nnz"].append(owned_peak(strips))
        stats["peak_strip_nnz"] = max(stats["peak_strip_nnz"],
                                      owned_peak(Ac_s))
        strips, n, nloc = Ac_s, nc, nloc_c
        eps *= 0.5
        sizes.append(n)

    # wire DistLevels: P/R between consecutive SHARDED levels become
    # DistEllMatrix; the last sharded level's P/R become TransitionOps
    dist_levels = []
    for k, (dA, sm, P_s, nloc_k, n_k) in enumerate(levels):
        dP = dR = None
        if k + 1 < len(levels):
            nloc_next = levels[k + 1][3]
            n_next = levels[k + 1][4]
            dP = _strips_to_dist_ell(P_s, mesh, (n_k, n_next), dtype,
                                     nloc_k, nloc_next, comm)
            R_s = strip_transpose(P_s, nloc_k, nloc_next, (n_next, n_k),
                                  comm)
            dR = _strips_to_dist_ell(R_s, mesh, (n_next, n_k), dtype,
                                     nloc_next, nloc_k, comm)
        dist_levels.append(DistLevel(dA, dP, dR, sm))

    # replicated serial tail from the gathered coarse strips
    prm_tail = copy.copy(prm)
    prm_tail.coarsening = copy.deepcopy(c)
    prm_tail.coarsening.eps_strong = eps
    # the user's depth bound covers sharded + replicated levels together
    prm_tail.max_levels = max(prm.max_levels - len(levels), 1)
    A_tail = _gather_strips(strips, (n, n), nloc, comm)
    rep_amg = AMG(A_tail, prm_tail)
    rep = SerialHierarchy(rep_amg.hierarchy.levels,
                          rep_amg.hierarchy.coarse,
                          prm.npre, prm.npost, prm.ncycle, 1)

    top_A = None
    trans = None
    if levels:
        # TransitionOps strip-wise: P rows are already fine-partitioned;
        # R per shard = (P strip)^T — column-restricted by construction
        _, _, P_s, nloc_b, n_b = levels[-1]
        K1 = max(1, int(comm.max_scalar(
            [None if P_s[s] is None else
             (int(np.diff(P_s[s].indptr).max()) if P_s[s].nnz else 0)
             for s in range(nd)])))
        K2 = max(1, int(comm.max_scalar(
            [None if P_s[s] is None else
             (int((P_s[s].T.tocsr()).getnnz(axis=1).max())
              if P_s[s].nnz else 0) for s in range(nd)])))
        pc_parts = [None] * nd
        pv_parts = [None] * nd
        rc_parts = [None] * nd
        rv_parts = [None] * nd
        from amgcl_tpu.parallel.dist_ell import pack_rows_ell
        for s in comm.my_shards:
            S = P_s[s]
            rows = np.repeat(np.arange(S.shape[0]), np.diff(S.indptr))
            pc_parts[s], pv_parts[s] = pack_rows_ell(
                rows, S.indices, S.data, nloc_b, K1)
            T = S.T.tocsr()
            trows = np.repeat(np.arange(T.shape[0]), np.diff(T.indptr))
            rc_parts[s], rv_parts[s] = pack_rows_ell(
                trows, T.indices, T.data, n, K2)
        put = lambda parts, dt: put_sharded_parts(parts, mesh, dt)
        trans = TransitionOps(put(pc_parts, jnp.int32),
                              put(pv_parts, dtype),
                              put(rc_parts, jnp.int32),
                              put(rv_parts, dtype))
    else:
        # no sharded levels: top_A is only the Krylov operator — always
        # solver precision (the preconditioner runs through `rep`)
        top_A = _strips_to_dist_ell(strips, mesh, (n, n), prm.dtype, nloc,
                                    nloc, comm)

    if dist_levels and jnp.dtype(dtype) != jnp.dtype(prm.dtype):
        # mixing.hpp seam: the Krylov loop tracks a solver-precision
        # system matrix; the narrowed operators serve only the cycle
        top_A = _strips_to_dist_ell(strips0, mesh, (n0, n0), prm.dtype,
                                    nloc0, nloc0, comm)
    hier = DistHierarchy(dist_levels, rep, trans, top_A, prm.npre,
                         prm.npost, prm.ncycle, prm.pre_cycles,
                         rep_rowshard=rep_rowshard)
    return hier, sizes, stats


class StripAMGSolver:
    """mpi::make_solver with a DISTRIBUTED setup: the hierarchy is built
    strip-parallel (strip_sa_hierarchy) and solved with the same SPMD
    program as DistAMGSolver. Accepts either a whole matrix (split
    in-process) or pre-split per-shard strips (multi-host ingestion:
    no process ever holds the global matrix)."""

    def __init__(self, A_or_strips, mesh, prm: Optional[Any] = None,
                 solver: Any = None, n: Optional[int] = None,
                 replicate_below: int = 4096, comm=None,
                 mis_rounds: int = 40, precond_dtype=None,
                 rep_rowshard: bool = False):
        import jax
        from amgcl_tpu.models.amg import AMGParams
        self.mesh = mesh
        self.prm = prm or AMGParams()
        from amgcl_tpu.solver.cg import CG
        self.solver = solver or CG()
        nd = mesh.shape[ROWS_AXIS]
        if comm is None:
            comm = MultihostComm(mesh) if jax.process_count() > 1 \
                else LocalComm(nd)
        if isinstance(A_or_strips, (list, tuple)):
            strips = list(A_or_strips)
            if n is None:
                raise ValueError("pass n= (global rows) with strips")
            if len(strips) != nd:
                raise ValueError(
                    "need one strip slot per mesh device (None for "
                    "shards owned by other processes)")
            # the whole strip algebra assumes the ceil(n/nd) row blocks of
            # build_dist_ell (owner = row // nloc); a floor-based MPI-style
            # split would silently misalign every diagonal and halo plan
            nloc0 = -(-int(n) // nd)
            for s in comm.my_shards:
                S = strips[s]
                if S is None:
                    raise ValueError("strip %d is owned by this process "
                                     "but is None" % s)
                want = min((s + 1) * nloc0, int(n)) - min(s * nloc0, int(n))
                if S.shape[0] != want:
                    raise ValueError(
                        "strip %d has %d rows; the ceil(n/nd) partition "
                        "requires %d (rows [%d, %d)) — re-split with "
                        "split_strips' convention"
                        % (s, S.shape[0], want, min(s * nloc0, int(n)),
                           min((s + 1) * nloc0, int(n))))
        else:
            strips, _ = split_strips(A_or_strips, nd)
            n = sum(S.shape[0] for S in strips)
            if len(comm.my_shards) != nd:
                strips = [strips[s] if s in set(comm.my_shards) else None
                          for s in range(nd)]
        self.hier, self.sizes, self.stats = strip_sa_hierarchy(
            strips, n, mesh, self.prm, comm=comm,
            replicate_below=replicate_below, mis_rounds=mis_rounds,
            precond_dtype=precond_dtype, rep_rowshard=rep_rowshard)
        self.n = int(n)
        first_A = self.hier.levels[0].A if self.hier.levels \
            else self.hier.top_A
        self.n_pad = first_A.nloc * nd
        self._compiled = None

    # the compiled SPMD solve program is identical to the serial-setup one
    def _build_compiled(self):
        from amgcl_tpu.parallel.dist_amg import DistAMGSolver
        return DistAMGSolver._build_compiled(self)

    def __call__(self, rhs, x0=None):
        from amgcl_tpu.parallel.dist_amg import DistAMGSolver
        return DistAMGSolver.__call__(self, rhs, x0)

    # ... and so is the resource ledger __call__ attaches to the report
    # (hier/prm/mesh carry everything the comm/memory models read)
    def resource_ledger(self):
        from amgcl_tpu.parallel.dist_amg import DistAMGSolver
        return DistAMGSolver.resource_ledger(self)

    def __repr__(self):
        lines = ["StripAMGSolver over %d devices (strip-parallel setup)"
                 % self.mesh.shape[ROWS_AXIS]]
        for i, m in enumerate(self.sizes):
            lines.append("%5d %12d" % (i, m))
        return "\n".join(lines)
