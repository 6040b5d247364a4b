"""Row-block distributed matrices and the halo-exchange SpMV.

TPU rendition of the reference's ``distributed_matrix`` (A split into a
local part and a remote part by column ownership, with an overlapped halo
exchange feeding the remote SpMV — amgcl/mpi/distributed_matrix.hpp:316-557).
On a TPU mesh the comm pattern is static at trace time: the host-side
partitioner computes which neighbor slices each shard needs, and the device
program exchanges them with ``lax.ppermute`` (ICI neighbor traffic), then
runs the local SpMV — XLA overlaps the permute with the local compute the
same way the reference overlaps Isend/Irecv with the local product.

Round-1 scope: banded matrices (DIA) whose halo is a fixed-width edge
exchange with the two ring neighbors. The general scattered-column ELL case
(arbitrary comm pattern via all_to_all) follows the same structure and is
layered on next.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import register_pytree_node_class

from amgcl_tpu.ops.csr import CSR
from amgcl_tpu.ops.device import csr_to_dia
from jax.lax import axis_size as _axis_size
from amgcl_tpu.parallel.mesh import ROWS_AXIS


@register_pytree_node_class
class DistDiaMatrix:
    """Banded matrix sharded by row blocks over the ``rows`` mesh axis.

    data: (ndiag, n) global diagonal storage, sharded on the row dimension;
    offsets static. ``halo`` = max |offset| = the edge width exchanged with
    ring neighbors each SpMV."""

    def __init__(self, offsets, data, shape):
        self.offsets = tuple(int(o) for o in offsets)
        self.data = data
        self.shape = (int(shape[0]), int(shape[1]))

    @property
    def halo(self) -> int:
        if not self.offsets:
            return 0
        return max(max(self.offsets), -min(self.offsets), 0)

    def halo_comm(self, nd: int):
        """Wire model of ONE halo-exchange SpMV over ``nd`` shards (the
        ledger hook, telemetry/ledger.comm_model): the ring exchange in
        dia_halo_mv moves the w-row edge slab in each direction between
        every adjacent pair — 2(nd−1) messages of w elements. The thin-
        slab all_gather fallbacks move more; this models the production
        regime (w ≤ shard size)."""
        nd = int(nd)
        w = self.halo
        if nd <= 1 or w == 0:
            return {"pattern": "ring", "msgs": 0, "bytes": 0}
        itemsize = np.dtype(self.data.dtype).itemsize \
            if self.data is not None else 4
        msgs = 2 * (nd - 1)
        return {"pattern": "ring", "msgs": msgs,
                "bytes": msgs * w * itemsize, "halo_width": w}

    def tree_flatten(self):
        return (self.data,), (self.offsets, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        offsets, shape = aux
        return cls(offsets, children[0], shape)

    @classmethod
    def from_csr(cls, A: CSR, mesh, dtype=jnp.float32) -> "DistDiaMatrix":
        """Host CSR -> device-sharded DIA. Rows must divide the mesh size
        (pad upstream if needed)."""
        assert not A.is_block
        n = A.nrows
        nd = mesh.shape[ROWS_AXIS]
        assert n % nd == 0, "rows must divide the mesh for round-1 DIA"
        dia = csr_to_dia(A, dtype)      # single source of the DIA packing
        out = cls(dia.offsets, dia.data, A.shape)
        if out.halo > n // nd:
            raise ValueError(
                "halo width %d exceeds the shard size %d — the ring "
                "exchange only reaches immediate neighbors; use fewer "
                "devices or a narrower band" % (out.halo, n // nd))
        sharding = NamedSharding(mesh, P(None, ROWS_AXIS))
        # numpy in, sharded out: the direct per-device path, no reshard
        # compile, multi-controller-safe (see mesh.put_with_sharding)
        from amgcl_tpu.parallel.mesh import put_with_sharding
        out.data = put_with_sharding(np.asarray(out.data), sharding)
        return out

    # -- the per-shard kernel (runs inside shard_map) -----------------------

    def shard_mv(self, data_local, x_local):
        """Overlapped halo SpMV on one shard (see dia_halo_mv)."""
        return dia_halo_mv(data_local, self.offsets, x_local)


def _ring_exchange(x_l, w, nd):
    """The real edge exchange: one ppermute per direction between every
    adjacent shard pair — (prev_tail, next_head), each ``w`` elements."""
    fwd = [(i, i + 1) for i in range(nd - 1)]
    bwd = [(i + 1, i) for i in range(nd - 1)]
    return (lax.ppermute(x_l[-w:], ROWS_AXIS, fwd),
            lax.ppermute(x_l[:w], ROWS_AXIS, bwd))


def _local_exchange(x_l, w, nd):
    """Comm-ablated stand-in for :func:`_ring_exchange`
    (telemetry/comm.py): identical shapes, dtypes and downstream compute,
    ZERO collectives — timing the two variants of the same SpMV isolates
    the collective's wall share. Numerically wrong at the shard edges on
    purpose; never dispatched by a solve (the ablation audit pins its
    collective census to exactly 0)."""
    return x_l[:w], x_l[-w:]


def _gather_ring(x_l, nd):
    """Whole-vector gather of the thin-slab fallback path."""
    return lax.all_gather(x_l, ROWS_AXIS, tiled=True)


def _gather_local(x_l, nd):
    """Comm-ablated stand-in for :func:`_gather_ring`: same output shape
    from a local tile, zero collectives (see _local_exchange)."""
    return jnp.tile(x_l, nd)


def _maybe_stall_exchange():
    """Fault seam (faults/inject.py): a ``dist.delay`` rule stalls the
    halo-exchange SpMV by ``delay_ms`` — a slow-interconnect simulation
    for the serve/SLO layers. Fires at TRACE time (once per compiled
    exchange program), never as a host callback inside the device loop:
    the comm-stage census contracts (ledger.COMM_STAGE_CONTRACTS) and
    the host-sync lint forbid runtime callbacks at this seam. One env
    read when no plan is armed."""
    import os
    if not os.environ.get("AMGCL_TPU_FAULT_PLAN"):
        return
    try:
        from amgcl_tpu.faults import inject as _inject
        spec = _inject.should_fire("dist.delay", target="dia_halo")
        if spec is not None and spec.get("delay_ms", 0) > 0:
            import time
            time.sleep(float(spec["delay_ms"]) / 1e3)
    except Exception:
        pass


def dia_halo_mv(data_l, flat_offs, x_l, exchange=_ring_exchange,
                gather=_gather_ring):
    """y = A x on one shard with comm/compute overlap.

    The reference overlaps explicitly: start_exchange → local SpMV →
    finish_exchange → remote SpMV (amgcl/mpi/distributed_matrix.hpp:520-534).
    The XLA rendition makes the same split at the DATA-DEPENDENCE level:
    the interior product (all rows, zero-filled shifts — wrong only in the
    first/last ``w`` rows) reads ONLY x_local, so it shares no operands
    with the ppermute and XLA's async-collective scheduler can run it
    while the edge exchange is in flight; the exact edge rows (2w of them,
    a sliver) are recomputed from the halo and spliced in. A naive
    concat(halo, x, halo) formulation would make EVERY fused
    multiply-add a consumer of the collective and serialize the step
    (structure asserted by tests/test_distributed overlap-HLO test).

    ``exchange``/``gather`` are the collective seams: the defaults issue
    the real ppermute/all_gather; telemetry/comm.py passes the local
    same-shape stand-ins to measure the comm-ablated variant of exactly
    this program."""
    _maybe_stall_exchange()
    w = max(max(flat_offs), -min(flat_offs), 0) if flat_offs else 0
    nl = x_l.shape[0]
    acc_dt = jnp.result_type(data_l.dtype, x_l.dtype)
    if w == 0:
        return sum(data_l[k] * x_l for k in range(len(flat_offs))) \
            if flat_offs else jnp.zeros(nl, acc_dt)

    nd = _axis_size(ROWS_AXIS)
    if nd > 1 and w > nl:
        # Diagonal reach exceeds one neighbour slab: a single ring
        # exchange cannot supply the halo (x_l[-w:] would clamp to nl
        # elements and silently misalign every subsequent slice).  Only
        # reachable on very thin coarse slabs, so assembling the global
        # vector is cheap — gather it and slice at the shard's global
        # row offset.
        xg = gather(x_l, nd)
        base = lax.axis_index(ROWS_AXIS) * nl
        xe = jnp.pad(xg, (w, w))
        y = jnp.zeros(nl, dtype=acc_dt)
        for k, s in enumerate(flat_offs):
            y = y + data_l[k] * lax.dynamic_slice(xe, (w + base + s,),
                                                  (nl,))
        return y
    if nd == 1 or 2 * w >= nl:
        # degenerate split: plain haloed product (single shard, or shard
        # too thin for an interior region)
        if nd == 1:
            xe = jnp.pad(x_l, (w, w))
        else:
            prev_tail, next_head = exchange(x_l, w, nd)
            xe = jnp.concatenate([prev_tail, x_l, next_head])
        y = jnp.zeros(nl, dtype=acc_dt)
        for k, s in enumerate(flat_offs):
            y = y + data_l[k] * lax.dynamic_slice(xe, (w + s,), (nl,))
        return y

    prev_tail, next_head = exchange(x_l, w, nd)          # in flight ...

    # ... while the interior streams: zero-filled local shifts, valid for
    # rows [w, nl-w).  On TPU the interior takes the Pallas DIA kernel —
    # its semantics ARE the zero-filled shift product, and the pallas_call
    # consumes only x_l, so it still shares no operands with the ppermutes
    # and overlaps the exchange exactly like the XLA loop.
    from amgcl_tpu.ops.pallas_spmv import pallas_mode, dia_spmv
    ip = pallas_mode(data_l.dtype, x_l.dtype)
    if ip is not None:
        y0 = dia_spmv(flat_offs, data_l, x_l, interpret=ip)
    else:
        xp = jnp.pad(x_l, (w, w))
        y0 = jnp.zeros(nl, dtype=acc_dt)
        for k, s in enumerate(flat_offs):
            y0 = y0 + data_l[k] * lax.dynamic_slice(xp, (w + s,), (nl,))

    # exact edge rows from the received halo (2w rows, O(w·ndiag) work)
    xe = jnp.concatenate([prev_tail, x_l, next_head])
    lo = jnp.zeros(w, dtype=acc_dt)
    hi = jnp.zeros(w, dtype=acc_dt)
    for k, s in enumerate(flat_offs):
        lo = lo + data_l[k, :w] * lax.dynamic_slice(xe, (w + s,), (w,))
        hi = hi + data_l[k, nl - w:] * lax.dynamic_slice(
            xe, (nl + s,), (w,))
    return jnp.concatenate([lo, y0[w:nl - w], hi])


def dist_inner_product(x_local, y_local):
    """Local dot + psum over the rows axis — the distributed InnerProduct
    seam (reference: amgcl/mpi/inner_product.hpp:45-67)."""
    return lax.psum(jnp.vdot(x_local, y_local), ROWS_AXIS)


# the psum marker the fused tiers key on (ops/device.spmv_dots,
# ops/fused_vec): "this seam is local-vdot + psum over THIS axis", so a
# fused kernel may compute the shard-local partial and globalize all its
# dots in one stacked collective instead of composing through the seam
dist_inner_product.psum_axis = ROWS_AXIS
