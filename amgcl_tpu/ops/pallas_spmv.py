"""Pallas TPU kernel for the DIA SpMV — the hot op of the solve phase.

Why a kernel at all: the XLA lowering of the DIA product is ``ndiag``
dynamic-slices of x plus fused multiply-adds; whether x is re-read from HBM
once or ``ndiag`` times is up to the fuser. This kernel makes the access
pattern explicit: each grid step DMAs one x window (tile + halo) from HBM
into VMEM once, then applies every diagonal with static slices from VMEM —
guaranteed single-read of x and stream-through of the diagonal data
(pallas guide: Async DMA / double-buffering patterns).

The kernel is the DEFAULT on TPU for <=32-bit dtypes (``AMGCL_TPU_PALLAS=0``
opts out; f64 always takes the XLA path — Mosaic's f64 vector support is
partial). Measured on v5e at 128^3 Poisson: the composed V-cycle drops from
36ms (XLA, whose many-diagonal DIA products fall off the fusion path and
pay per-kernel launch overhead) to 5.9ms. Correctness is additionally
covered in interpret mode on CPU.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from amgcl_tpu.telemetry.compile_watch import watched_jit as _watched_jit

from amgcl_tpu.telemetry.tracing import phase as _tel_phase


def pallas_enabled() -> bool:
    """Default ON (the kernel is 6x faster than XLA's lowering for the
    composed V-cycle on v5e — many-diagonal DIA products fall off XLA's
    fusion path inside large programs and pay ~60us launch overhead per
    diagonal). AMGCL_TPU_PALLAS=0 opts out."""
    return os.environ.get("AMGCL_TPU_PALLAS", "1") != "0"


# -- thread-local Pallas opt-out (stacked/vmapped traces) -------------------
#
# The batched multi-RHS traces (serve/batched.py, Hierarchy.apply's 2-D
# branch) vmap over bodies whose hand kernels carry exact 1-D shapes, so
# they must trace the XLA lowerings instead. A process-env override
# would RACE concurrent traces on other threads (the serve worker thread
# compiles batched buckets while the main thread may be tracing a
# single-rhs program); this thread-local is exact: it scopes to the
# tracing thread for the duration of the context.

import contextlib
import threading

_TLS = threading.local()


@contextlib.contextmanager
def pallas_disabled():
    """Disable every Pallas gate on THIS thread for the duration of a
    trace (re-entrant)."""
    prev = getattr(_TLS, "disabled", 0)
    _TLS.disabled = prev + 1
    try:
        yield
    finally:
        _TLS.disabled = prev


def pallas_locally_disabled() -> bool:
    return getattr(_TLS, "disabled", 0) > 0


def pallas_interpret_forced() -> bool:
    """AMGCL_TPU_PALLAS_INTERPRET=1 routes the DIA dispatch seams through
    the Pallas kernels in interpret mode on NON-TPU backends — a test hook
    so CI exercises the production wiring (hierarchy/smoother/Krylov seams
    through pallas_call), not just the kernels in isolation."""
    return os.environ.get("AMGCL_TPU_PALLAS_INTERPRET") == "1"


def min_ndiag() -> int:
    """AMGCL_TPU_PALLAS_MIN_NDIAG: smallest diagonal count that still
    takes the Pallas DIA kernels (see DiaMatrix._pallas_mode). Read per
    call — cheap, and lets a chip session A/B without reimporting."""
    try:
        return int(os.environ.get("AMGCL_TPU_PALLAS_MIN_NDIAG", "0"))
    except ValueError:
        return 0


# Every probe-compile / value-check decline this process has seen:
# (kernel name, one-line reason). Always recorded (cheap), so bench.py
# can embed the decline list in the artifact — the supervisor discards
# worker stderr, which made an empty ``fused_levels`` undiagnosable from
# the committed JSON alone.
PROBE_DECLINES: list = []


def probe_report(name, exc=None, note=""):
    """Record a probe-compile / value-check decline; with
    AMGCL_TPU_PROBE_VERBOSE=1 also print it (default is a silent XLA
    fallback) — the chip-session debugging hook. A declined kernel is
    otherwise invisible outside the bench's missing fused tiers (round-5
    chip lesson: the first real v5e session spent its opening hour
    discovering WHICH kernel Mosaic rejected)."""
    if note:
        reason = note
    elif exc is not None:
        # the useful Mosaic line can sit deep inside the compiler's
        # message — extract it so the decline log is diagnosable
        import re
        txt = str(exc)
        m = re.search(r"(Mosaic failed[^\n]*|Internal: AOT PJRT "
                      r"error:[^\n]*|verification error[^\n]*|"
                      r"Unimplemented[^\n]*|NotImplemented[^\n]*)", txt)
        reason = (m.group(0) if m else repr(exc).splitlines()[0])[:300]
    else:
        reason = ""
    PROBE_DECLINES.append((name, reason))
    if os.environ.get("AMGCL_TPU_PROBE_VERBOSE") != "1":
        return
    import sys
    import traceback
    print("[amgcl-tpu probe] %s declined%s"
          % (name, ": " + note if note else ""), file=sys.stderr)
    if exc is not None:
        traceback.print_exception(type(exc), exc, exc.__traceback__,
                                  file=sys.stderr)


def pallas_mode(*dtypes):
    """None = use the XLA path; else the ``interpret`` flag to pass the
    kernels (False on real TPU, True under the CI interpret hook). All
    participating dtypes must be <= 32-bit (Mosaic's f64 vector support
    is partial)."""
    import jax
    if not pallas_enabled() or pallas_locally_disabled():
        return None
    if any(jnp.dtype(d).itemsize > 4 for d in dtypes):
        return None
    if jax.default_backend() == "tpu":
        return False
    return True if pallas_interpret_forced() else None


# Double-buffered x-window DMA for the DIA kernels: OPT-IN
# (AMGCL_TPU_DIA_DB=1) — the serial DIA
# kernel has a real-chip measurement behind it (round 2: 6x vs XLA) and
# keeps its EXACT original geometry (1-D scratch, ref slices); the
# prefetch variant must prove itself in a chip-session A/B before
# becoming default. Snapshotted at import; the kernels also accept an
# explicit ``db`` static arg so tests can exercise both modes without
# stale-trace hazards.
_DIA_DB = os.environ.get("AMGCL_TPU_DIA_DB", "0") == "1"

# VMEM budget for _resolve_tile's auto mode, in ESTIMATE units (window
# scratch + pipelined operand blocks). Mosaic's real scoped-vmem stack
# runs ~4x the naive operand estimate (r5 bench: a bf16 33-diagonal
# level estimated 4.7 MB and hit the 16 MB limit at 21.3 MB), so the
# estimate cap is 3 MB — which also happens to land every measured
# level on its empirically-best tile (L0 32768 == 74 us plateau,
# L1 8192, L2 2048)
_TILE_VMEM_BUDGET = 3 << 20


def _resolve_tile(offsets, tile, itemsize, ndiag):
    """Row-tile size for the DIA kernels.

    Explicit ``tile`` wins. ``None`` reads AMGCL_TPU_DIA_TILE: an integer
    fixes it; 'auto' picks the smallest 1024-multiple with window
    redundancy (tile + 2H)/tile <= 1.25 — the r5 chip session measured
    dia_spmv at tile=2048 within 6% of the redundancy model's prediction
    on the 128^3 fine level (each tile re-DMAs the +-16384 z-halo, 17.5x
    its own rows), so the halo, not the row count, must set the tile —
    halved until the window + pipelined blocks fit the VMEM budget.
    Resolved at trace time: the first call per static signature binds the
    env value (A/B arms need fresh processes, like AMGCL_TPU_DIA_DB)."""
    if tile is not None:
        return int(tile)
    # default 'auto' since the r5 v5e sweep: level-0 spmv 316 us at
    # tile=2048 vs 74 us at 32768+ (the halo amortizes); explicit
    # AMGCL_TPU_DIA_TILE pins a fixed size for A/B runs
    v = os.environ.get("AMGCL_TPU_DIA_TILE", "auto")
    if v != "auto":
        return int(v)
    H = max((abs(int(o)) for o in offsets), default=0)
    t = max(2048, -(-8 * H // 1024) * 1024)
    while t > 2048:
        # window scratch (doubled when db) + diag block + ~3 vector tiles
        # (f/w/out), all double-buffered by the pallas pipeline
        use = (t + 2 * H + 2048) * itemsize * (2 if _DIA_DB else 1) \
            + 2 * (ndiag + 3) * t * itemsize
        if use <= _TILE_VMEM_BUDGET:
            break
        t = max(2048, (t // 2048) * 1024)
    return t


def window_dma(pl, dma, i, n_tiles, nbuf):
    """Shared slot machinery for per-tile window-DMA double buffering
    (used by the DIA kernels here — one copy of the race-prone part).
    ``dma(tile_idx, slot)`` builds the async-copy descriptor. Serial
    (nbuf=1): start+wait tile i. Double (nbuf=2): tile i+1's transfer is
    issued before waiting on tile i's, riding under this tile's compute
    (grid steps are sequential and scratch persists across them).
    Returns the slot holding tile i's window."""
    if nbuf == 1:
        dma(i, 0).start()
        dma(i, 0).wait()
        return 0
    ii = jnp.asarray(i, jnp.int32)
    slot = jax.lax.rem(ii, np.int32(2))
    nxt = jax.lax.rem(ii + np.int32(1), np.int32(2))

    @pl.when(i == 0)
    def _warm():
        dma(0, 0).start()

    @pl.when(i + 1 < n_tiles)
    def _prefetch():
        dma(i + 1, nxt).start()

    dma(i, slot).wait()
    return slot


def _dia_dma(pl, pltpu, x_hbm, xw, sem, i, tile, win, n_tiles):
    """Per-tile window DMA; returns a REF holding tile i's window, so
    the serial path reads through exactly the original 1-D ref slices
    (the measured kernel) and the double-buffered path through an
    ``at[slot]`` view."""
    serial = len(xw.shape) == 1

    def dma(tile_idx, slot):
        dst = xw if serial else xw.at[slot]
        dsem = sem if serial else sem.at[slot]
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(tile_idx * tile, win)], dst, dsem)

    slot = window_dma(pl, dma, i, n_tiles, 1 if serial else 2)
    return xw if serial else xw.at[slot]


def _dia_scratch(pltpu, win, dtype, db):
    if db:
        return [pltpu.VMEM((2, win), dtype), pltpu.SemaphoreType.DMA((2,))]
    # the round-2-measured geometry, bit-for-bit
    return [pltpu.VMEM((win,), dtype), pltpu.SemaphoreType.DMA]


def _dia_window(offsets, data, x, tile, interpret):
    """Shared tile/window geometry + padded operands for the DIA kernels.

    Returns (base, win, n_pad, xp, dpad). BOTH dia_spmv and _dia_fused
    must read x through exactly this geometry — any sizing fix here
    services every kernel (round-1 finding: wide operators need
    ``max(n_pad - tile + win, m + base)``)."""
    # Mosaic requires 1-D DMA slice starts/shapes aligned to the
    # 1024-element tiling, so the row tile must be a multiple of it on
    # real hardware (interpret mode has no such constraint)
    if tile % 1024 and not interpret:
        raise ValueError("tile must be a multiple of 1024, got %d" % tile)
    n = data.shape[1]
    m = x.shape[0]
    lo = min(offsets + (0,))
    base = -lo if lo < 0 else 0
    # every tile reads scratch[base + d : base + d + tile], so the window
    # must extend max(offsets) beyond the tile regardless of how n and m
    # compare (wide matrices read far to the right of the tile's rows)
    hi = max(max(offsets + (0,)), 0)
    n_pad = -(-n // tile) * tile
    # Mosaic requires 1-D DMA slice shapes (and starts) aligned to the
    # 1024-element tiling; tile is a multiple of 1024, so round the halo
    # window up and size the padded x so the last tile's window is in range
    win = -(-(tile + base + hi) // 1024) * 1024
    # wide rectangular operators: x (length m) can exceed the tile window
    # span, so size the scratch source for BOTH (round-1 advisor finding:
    # dynamic_update_slice trace failure when m > n_pad + hi)
    xp = jnp.zeros(max(n_pad - tile + win, m + base), x.dtype)
    xp = jax.lax.dynamic_update_slice(xp, x, (base,))
    dpad = jnp.pad(data, ((0, 0), (0, n_pad - n)))
    return base, win, n_pad, xp, dpad


@functools.partial(_watched_jit, name="ops.dia_spmv",
                   static_argnames=("offsets", "tile", "interpret",
                                    "db"))
def dia_spmv(offsets, data, x, tile=None, interpret: bool = False,
             db=None):
    """y = A x for DIA storage. offsets: static tuple; data: (ndiag, n);
    x: (m,). Rows padded up to a tile multiple; result sliced back.
    ``db`` overrides the AMGCL_TPU_DIA_DB window double-buffering flag
    (None = the import-time snapshot); ``tile=None`` resolves via
    AMGCL_TPU_DIA_TILE (see _resolve_tile)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    db = _DIA_DB if db is None else bool(db)
    n = data.shape[1]
    ndiag = len(offsets)
    tile = _resolve_tile(offsets, tile, x.dtype.itemsize, ndiag)
    base, win, n_pad, xp, dpad = _dia_window(offsets, data, x, tile,
                                             interpret)

    def kernel(x_hbm, d_ref, o_ref, scratch, sem):
        i = pl.program_id(0)
        row = _dia_dma(pl, pltpu, x_hbm, scratch, sem, i, tile, win,
                       n_pad // tile)
        acc = jnp.zeros((tile,), dtype=o_ref.dtype)
        for k, d in enumerate(offsets):
            seg = row[pl.ds(base + d, tile)]
            acc = acc + d_ref[k, :] * seg
        o_ref[:] = acc

    grid = (n_pad // tile,)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),               # x stays in HBM
            # np.int32 keeps the index map i32 under jax_enable_x64 — a bare
            # Python 0 traces as i64 there and Mosaic cannot legalize the
            # mixed-width func.return
            pl.BlockSpec((ndiag, tile),
                         lambda i: (np.int32(0), i)),        # diagonal tiles
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.result_type(
            data.dtype, x.dtype)),
        scratch_shapes=_dia_scratch(pltpu, win, x.dtype, db),
        interpret=interpret,
    )(xp, dpad)
    return out[:n]


# -- fused residual / smoother-step kernels ---------------------------------
#
# The V-cycle's hot chain at every DIA level is residual-shaped:
#   residual            r  = f − A x            (cycle + every Krylov loop)
#   scaled correction   x' = x + w ∘ (f − A x)  (Jacobi/SPAI-0 sweeps)
# Composed from dia_spmv + XLA elementwise, each costs an extra HBM
# round-trip of the SpMV output (write y, read y back) plus one kernel
# boundary, because XLA cannot fuse across a pallas_call. These kernels fold
# the elementwise tail into the same single-pass-over-x structure as
# dia_spmv: identical DMA window, identical static slices, only the
# accumulator init (f tile) and the output expression differ — no new
# Mosaic ops, so anywhere dia_spmv legalizes these do too.


@functools.partial(_watched_jit, name="ops.dia_fused",
                   static_argnames=("offsets", "mode", "tile", "interpret",
                                    "db"))
def _dia_fused(offsets, data, f, x, w, mode, tile=None, interpret=False,
               db=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    db = _DIA_DB if db is None else bool(db)
    n = data.shape[1]
    ndiag = len(offsets)
    tile = _resolve_tile(offsets, tile, x.dtype.itemsize, ndiag)
    base, win, n_pad, xp, dpad = _dia_window(offsets, data, x, tile,
                                             interpret)
    fp = jnp.pad(f, (0, n_pad - n))
    out_dtype = jnp.result_type(data.dtype, x.dtype, f.dtype)
    vecs = [fp]
    if mode == "correction":
        out_dtype = jnp.result_type(out_dtype, w.dtype)
        vecs.append(jnp.pad(w, (0, n_pad - n)))

    def kernel(x_hbm, d_ref, f_ref, *rest):
        (*w_refs, o_ref, scratch, sem) = rest
        i = pl.program_id(0)
        row = _dia_dma(pl, pltpu, x_hbm, scratch, sem, i, tile, win,
                       n_pad // tile)
        acc = f_ref[:].astype(out_dtype)
        for k, d in enumerate(offsets):
            acc = acc - d_ref[k, :] * row[pl.ds(base + d, tile)]
        if mode == "residual":
            o_ref[:] = acc
        else:                       # x tile lives in the window already
            xt = row[pl.ds(base, tile)].astype(out_dtype)
            o_ref[:] = xt + w_refs[0][:] * acc

    grid = (n_pad // tile,)
    vec_spec = pl.BlockSpec((tile,), lambda i: (i,))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),               # x stays in HBM
            pl.BlockSpec((ndiag, tile), lambda i: (np.int32(0), i)),
        ] + [vec_spec] * len(vecs),
        out_specs=vec_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad,), out_dtype),
        scratch_shapes=_dia_scratch(pltpu, win, x.dtype, db),
        interpret=interpret,
    )(xp, dpad, *vecs)
    return out[:n]


@functools.partial(_watched_jit, name="ops.dia_spmv_dots",
                   static_argnames=("offsets", "tile", "interpret",
                                    "db"))
def dia_spmv_dots(offsets, data, x, w=None, tile=None,
                  interpret: bool = False, db=None):
    """(y, <y, y>, <y, x>, <y, w>) in one pass, y = A x (w optional).

    The Krylov hot pairs: CG needs <Ap, p>; BiCGStab needs <rhat, v>
    with v = A z and, on the second stage, <t, t> and <t, s> with
    t = A shat. Composed, each dot re-reads its vectors from HBM after
    the spmv kernel; fused, per-tile partials reduce in-register and
    accumulate into SMEM scalars across the (sequential) grid steps.
    Square real operators only (the caller gates)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    db = _DIA_DB if db is None else bool(db)
    n = data.shape[1]
    if x.shape[0] != n:
        raise ValueError("dia_spmv_dots needs a square operator")
    ndiag = len(offsets)
    tile = _resolve_tile(offsets, tile, x.dtype.itemsize, ndiag)
    base, win, n_pad, xp, dpad = _dia_window(offsets, data, x, tile,
                                             interpret)
    out_dtype = jnp.result_type(data.dtype, x.dtype)
    acc_dtype = jnp.float32 if jnp.dtype(out_dtype).itemsize <= 4 \
        else jnp.float64
    has_w = w is not None
    wvecs = [jnp.pad(w, (0, n_pad - n))] if has_w else []
    vec_spec = pl.BlockSpec((tile,), lambda i: (i,))

    def kernel(x_hbm, d_ref, *rest):
        (*w_refs, o_ref, dots_ref, scratch, sem) = rest
        i = pl.program_id(0)
        row = _dia_dma(pl, pltpu, x_hbm, scratch, sem, i, tile, win,
                       n_pad // tile)
        acc = jnp.zeros((tile,), dtype=out_dtype)
        for k, d in enumerate(offsets):
            acc = acc + d_ref[k, :] * row[pl.ds(base + d, tile)]
        o_ref[:] = acc
        # padding rows contribute zero (dpad is zero there), so the
        # partials over the full tile equal the true dots
        ya = acc.astype(acc_dtype)
        p_yy = jnp.sum(ya * ya)
        p_yx = jnp.sum(ya * row[pl.ds(base, tile)].astype(acc_dtype))

        @pl.when(i == 0)
        def _init():
            for j in range(2 + has_w):
                dots_ref[0, j] = jnp.zeros((), acc_dtype)

        dots_ref[0, 0] += p_yy
        dots_ref[0, 1] += p_yx
        if has_w:
            dots_ref[0, 2] += jnp.sum(ya * w_refs[0][:].astype(acc_dtype))

    grid = (n_pad // tile,)
    y, dots = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((ndiag, tile), lambda i: (np.int32(0), i)),
        ] + [vec_spec] * len(wvecs),
        out_specs=(
            vec_spec,
            # explicit i32 index map: the default map's Python-0 block
            # indices trace as i64 under jax_enable_x64 and Mosaic fails
            # to legalize the i64 func.return (first seen on-chip r5)
            pl.BlockSpec((1, 2 + has_w),
                         lambda i: (np.int32(0), np.int32(0)),
                         memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_pad,), out_dtype),
            jax.ShapeDtypeStruct((1, 2 + has_w), acc_dtype),
        ),
        scratch_shapes=_dia_scratch(pltpu, win, x.dtype, db),
        interpret=interpret,
    )(xp, dpad, *wvecs)
    yy = dots[0, 0].astype(out_dtype)
    yx = dots[0, 1].astype(out_dtype)
    yw = dots[0, 2].astype(out_dtype) if has_w else None
    return y[:n], yy, yx, yw


def dia_spmv_dot(offsets, data, x, tile=None,
                 interpret: bool = False, db=None):
    """(y, <y, x>) — the CG pair; see dia_spmv_dots."""
    y, _, yx, _ = dia_spmv_dots(offsets, data, x, None, tile, interpret,
                                db)
    return y, yx


def dia_residual(offsets, data, f, x, tile=None,
                 interpret: bool = False, db=None):
    """r = f − A x in one pass (A in DIA storage, square or rectangular)."""
    with _tel_phase("pallas/dia_residual"):
        return _dia_fused(offsets, data, f, x, None, "residual", tile,
                          interpret, db)


@functools.partial(_watched_jit, name="ops.dia_residual_dot",
                   static_argnames=("offsets", "tile", "interpret",
                                    "db"))
def dia_residual_dot(offsets, data, f, x, tile=None,
                     interpret: bool = False, db=None):
    """(r, <r, r>) with r = f − A x in ONE pass — the residual and its
    norm reduction of the Krylov outer loop (Richardson's whole body,
    every solver's init) without re-reading r from HBM. Same window
    geometry as dia_residual; the per-tile partial reduces in-register
    and accumulates into an SMEM scalar across the sequential grid
    steps, like dia_spmv_dots. Square operators only (the caller
    gates)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    db = _DIA_DB if db is None else bool(db)
    n = data.shape[1]
    if x.shape[0] != n:
        raise ValueError("dia_residual_dot needs a square operator")
    ndiag = len(offsets)
    tile = _resolve_tile(offsets, tile, x.dtype.itemsize, ndiag)
    base, win, n_pad, xp, dpad = _dia_window(offsets, data, x, tile,
                                             interpret)
    fp = jnp.pad(f, (0, n_pad - n))
    out_dtype = jnp.result_type(data.dtype, x.dtype, f.dtype)
    acc_dtype = jnp.float32 if jnp.dtype(out_dtype).itemsize <= 4 \
        else jnp.float64
    vec_spec = pl.BlockSpec((tile,), lambda i: (i,))

    def kernel(x_hbm, d_ref, f_ref, o_ref, dots_ref, scratch, sem):
        i = pl.program_id(0)
        row = _dia_dma(pl, pltpu, x_hbm, scratch, sem, i, tile, win,
                       n_pad // tile)
        acc = f_ref[:].astype(out_dtype)
        for k, d in enumerate(offsets):
            acc = acc - d_ref[k, :] * row[pl.ds(base + d, tile)]
        o_ref[:] = acc
        ra = acc.astype(acc_dtype)

        @pl.when(i == 0)
        def _init():
            dots_ref[0, 0] = jnp.zeros((), acc_dtype)

        dots_ref[0, 0] += jnp.sum(ra * ra)

    with _tel_phase("pallas/dia_residual_dot"):
        r, dots = pl.pallas_call(
            kernel,
            grid=(n_pad // tile,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((ndiag, tile),
                             lambda i: (np.int32(0), i)),
                vec_spec,
            ],
            out_specs=(
                vec_spec,
                pl.BlockSpec((1, 1),
                             lambda i: (np.int32(0), np.int32(0)),
                             memory_space=pltpu.SMEM),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((n_pad,), out_dtype),
                jax.ShapeDtypeStruct((1, 1), acc_dtype),
            ),
            scratch_shapes=_dia_scratch(pltpu, win, x.dtype, db),
            interpret=interpret,
        )(xp, dpad, fp)
    return r[:n], dots[0, 0].astype(out_dtype)


def dia_scaled_correction(offsets, data, w, f, x, tile=None,
                          interpret: bool = False, db=None):
    """x + w ∘ (f − A x) in one pass — a damped-Jacobi/SPAI-0 sweep."""
    with _tel_phase("pallas/dia_scaled_correction"):
        return _dia_fused(offsets, data, f, x, w, "correction", tile,
                          interpret, db)
