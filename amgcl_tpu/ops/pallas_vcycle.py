"""Fused V-cycle down-sweep kernel: rc = Tᵀ (I − Mᵀ) (f − A u) in ONE pass.

The down-sweep tail at a grid-aligned stencil level chains three
fine-grid traversals (residual, smoothed-restriction filter, tentative
reduction), each separated by an HBM round-trip of an n-sized vector
because XLA cannot fuse across pallas_call boundaries:

    r  = f − A u            Pallas kernel       write n, read n
    t  = r − Mᵀ r           Pallas kernel       write n, read n
    rc = Tᵀ t               XLA reshape/reduce  write n/8

This kernel folds the whole chain into one pass: per coarse z-plane it
DMAs a fine 2-plane window (plus stencil halo) of f, u and both
diagonal sets, computes r and t entirely in VMEM, and reduces the
2×2×2 aggregates with a z-pair add followed by two small 0/1 matmuls
(S_y · t₂ · S_x — the pairwise sums ride the MXU, avoiding stride-2
lane slices that Mosaic may not legalize). Only the (c2, c1, c0)
coarse result ever returns to HBM.

Every op class here is already exercised by `ops/pallas_spmv.py` on
real hardware (1-D aligned DMA windows, static VMEM slices, FMA) plus
`jnp.dot` — but the composition is new and the chip is currently
unreachable, so the builder PROBE-COMPILES on first use (the
`ops/unstructured.py` pattern) and silently falls back to the composed
path when Mosaic declines.

Eligibility (v1, deliberately conservative): scalar DIA level operator
and Mt, grid-aligned tentative with blocks (2,2,2), f0 % 128 == 0
(keeps the (2s,) → (f1, f0) VMEM reshape layout-preserving),
f1 % 8 == 0, ≤32-bit dtype, and a VMEM window estimate under the cap.
At the 128³ Poisson headline this covers level 0 — ~85% of cycle
bytes; coarser levels keep the composed fused-residual path.

Reference context: the reference's cycle does the same three ops as
separate backend calls (amgcl/amg.hpp:514-553 + the spmv/residual
primitives of backend/interface.hpp) — batching them is impossible on
its vendor backends; on TPU it is the natural continuation of kernel
fusion.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from amgcl_tpu.telemetry.compile_watch import watched_jit as _watched_jit
from jax.tree_util import register_pytree_node_class

from amgcl_tpu.ops.pallas_spmv import probe_report
from amgcl_tpu.telemetry.tracing import phase as _tel_phase


_VMEM_CAP_BYTES = 12 << 20
_PROBE_OK = {}
# geometries whose on-device value check already PASSED (resp. FAILED)
# this process: a miscompute is a property of the compiled kernel
# (geometry + dtype), not of the operator data, so rebuilds skip the
# two composed-path executions + fetch (~2.4 s of the r5 warm 128³
# setup profile). Failures are only cached for the optional zero mode
# (a failing base kernel returns None and costs nothing to re-reach).
_VALUE_OK: set = set()
_VALUE_BAD: set = set()


def vcycle_fusion_enabled() -> bool:
    """AMGCL_TPU_FUSED_VCYCLE=0 disables ONLY this tier (the whole-leg
    sweep kernels), leaving the tier-1 spmv/residual kernels active — the
    A/B knob for isolating the fusion's effect on the chip
    (AMGCL_TPU_PALLAS=0 kills all Pallas paths at once)."""
    return os.environ.get("AMGCL_TPU_FUSED_VCYCLE", "1") != "0"


def _sslice(v, a, b):
    """Static slice of an in-register VALUE: Mosaic's TC lowering has no
    dynamic_slice primitive for values (first real-v5e decline log, r5),
    but every slice in these kernels has a Python-int start — lax.slice
    legalizes. Refs are unaffected (pl.ds loads were always fine)."""
    return jax.lax.slice(v, (int(a),), (int(a) + int(b),))


def _round_up(v, m):
    return -(-int(v) // int(m)) * int(m)


def down_geometry(offs_a, offs_m, dims):
    """(H, W, vmem_bytes_per_f32) for the down kernel's frame — the ONE
    source of the halo/window arithmetic, shared by every builder
    (single-chip and distributed slab)."""
    _, f1, f0 = dims
    s = f1 * f0
    hA = max(max(offs_a), -min(offs_a), 0)
    hM = max(max(offs_m), -min(offs_m), 0)
    H = _round_up(hA + hM, 512)
    W = 2 * s + 2 * H
    vmem = (len(offs_a) + len(offs_m) + 2) * W + 3 * s
    return H, W, vmem


def up_geometry(offs_a, offs_m, dims):
    """(hp, F, vmem_bytes_per_f32) for the up kernel's frame."""
    _, f1, f0 = dims
    s = f1 * f0
    hA = max(max(offs_a), -min(offs_a), 0)
    hM = max(max(offs_m), -min(offs_m), 0)
    hp = max(1, -(-(hA + hM) // (2 * s)))
    F = (2 * hp + 1) * 2 * s
    vmem = (len(offs_m) + 2) * F + (len(offs_a) + 4) * 2 * s
    return hp, F, vmem


def _pack_shape(f1, f0, c1, c0):
    """Lane-packing factor and the packed view of a plane.

    For f0 < 128 (coarser levels), k = 128 // f0 consecutive fine y-rows
    share one 128-lane row; a fine plane (f1, f0) is viewed flat-
    preserving as (f1//k, 128) and the coarse plane (c1, c0) as
    (f1//k, (k//2)·c0) — each packed row then holds complete y-pairs,
    so the whole 2-D pair reduction (or expansion) is ONE matmul with a
    0/1 operator instead of the two k=1 matmuls. Returns
    (k, fine_view, coarse_view)."""
    k = 128 // f0
    if k <= 1:
        return 1, (f1, f0), (c1, c0)
    return k, (f1 // k, 128), (f1 // k, (k // 2) * c0)


def _packed_reduce(f0, k, c0, dtype):
    """(128, (k//2)·c0) 0/1 operator: packed fine row -> packed coarse
    row, summing the 2x2 (y, x) pairs that live inside one packed row."""
    m = np.zeros((128, (k // 2) * c0), np.float32)
    j = np.arange(128)
    m[j, (j // f0 // 2) * c0 + (j % f0) // 2] = 1.0
    return jnp.asarray(m, dtype=dtype)


@functools.partial(_watched_jit, name="ops.fused_down_sweep",
                   static_argnames=(
    "offs_a", "offs_m", "dims", "coarse", "H", "zero_guess", "framed",
    "interpret"))
def fused_down_sweep(a_flat, mt_flat, sy, sx, f, u,
                     offs_a, offs_m, dims, coarse, H,
                     zero_guess: bool = False, framed: bool = False,
                     interpret: bool = False):
    """(c2, c1, c0) coarse rhs from fine f, u — see module docstring.

    a_flat / mt_flat: the level's DIA data rows, each zero-padded into a
    length-L aligned frame and flattened (built once at setup by
    ``build_fused_down``). sy (c1, f1) / sx (f0, c0): 0/1 pairwise-sum
    operators. H: halo frame (multiple of 512).

    ``zero_guess``: the npre=1 cycle entry — ``u`` is then the
    smoother's SCALE vector w, the pre-smoothed iterate u = w ∘ f is
    formed in VMEM, and the kernel returns ``(rc3, u)`` so the whole
    down-sweep is one pass with no separate smoothing launch.

    ``framed``: distributed-slab mode — f and u arrive ALREADY in the
    length-L aligned frame (halo-extended by the caller with real
    neighbor-slab values instead of the single-chip zero pad; requires
    an even plane count so L = n + 2H)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f2, f1, f0 = dims
    c2, c1, c0 = coarse
    s = f1 * f0
    n = f2 * s
    n2 = 2 * c2 * s                   # fine rows rounded up to even planes
    L = n2 + 2 * H
    hA = max(max(offs_a), -min(offs_a), 0)
    Hr = H - hA                       # halo left for the Mᵀ stage
    W = 2 * s + 2 * H                 # DMA window per step
    Wr = 2 * s + 2 * Hr               # extent on which r is valid
    nA = len(offs_a)
    nM = len(offs_m)
    dt = f.dtype
    _, fv, cv = _pack_shape(f1, f0, c1, c0)
    pc1, pc0 = cv
    if sy.shape != (pc1, fv[0]) or sx.shape != (fv[1], pc0):
        raise ValueError("reduction operator shapes %s/%s do not match "
                         "the packed plane views %s/%s"
                         % (sy.shape, sx.shape, (pc1, fv[0]), (fv[1], pc0)))

    # place the cycle vectors into the kernel's aligned frame
    if framed:
        if n2 != n or f.shape[0] != L or u.shape[0] != L:
            raise ValueError("framed mode needs an even plane count and "
                             "pre-framed length-L vectors")
        fp, up = f, u
    else:
        fp = jnp.zeros(L, dt).at[H:H + n].set(f)
        up = jnp.zeros(L, dt).at[H:H + n].set(u)

    def kernel(af_hbm, mf_hbm, fp_hbm, up_hbm, sy_ref, sx_ref, *rest):
        # per-diagonal 1-D window scratches (sa/sm lists): Mosaic rejects
        # DMA into a row view of a 2-D VMEM scratch — memref slices along
        # the sublane dim must be 8-aligned (r5 on-chip verification
        # error); separate (W,) buffers are the dia_spmv-proven shape
        if zero_guess:
            o_ref, o_u, *scr = rest
        else:
            o_ref, *scr = rest
            o_u = None
        sa = scr[:nA]
        sm = scr[nA:nA + nM]
        sf, su, sems = scr[nA + nM:]
        c = pl.program_id(0)
        start = c * (2 * s)
        cps = []
        for k in range(nA):
            cps.append(pltpu.make_async_copy(
                af_hbm.at[pl.ds(k * L + start, W)], sa[k], sems.at[np.int32(k)]))
        for k in range(nM):
            cps.append(pltpu.make_async_copy(
                mf_hbm.at[pl.ds(k * L + start, W)], sm[k],
                sems.at[np.int32(nA + k)]))
        cps.append(pltpu.make_async_copy(
            fp_hbm.at[pl.ds(start, W)], sf, sems.at[np.int32(nA + nM)]))
        cps.append(pltpu.make_async_copy(
            up_hbm.at[pl.ds(start, W)], su, sems.at[np.int32(nA + nM + 1)]))
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

        if zero_guess:
            # su holds the scale frame: pre-smooth u = w ∘ f in VMEM
            uext = su[:] * sf[:]
            o_u[:] = _sslice(uext, H, 2 * s)
            uslice = lambda a, b: _sslice(uext, a, b)
        else:
            uslice = lambda a, b: su[pl.ds(a, b)]

        # r = f − A u on the Wr frame (row j of the frame is global fine
        # row c·2s − Hr + j; u reads stay inside the W window by hA)
        acc = jnp.zeros((Wr,), dt)
        for k, d in enumerate(offs_a):
            acc = acc + sa[k][pl.ds(hA, Wr)] * uslice(hA + d, Wr)
        rext = sf[pl.ds(hA, Wr)] - acc

        # t = r − Mᵀ r on the 2-plane tile (tile row i ↔ frame Hr + i)
        accm = jnp.zeros((2 * s,), dt)
        for k, d in enumerate(offs_m):
            accm = accm + sm[k][pl.ds(H, 2 * s)] \
                * _sslice(rext, Hr + d, 2 * s)
        t = _sslice(rext, Hr, 2 * s) - accm

        # Tᵀ for 2×2×2 blocks: z-pair add, then MXU pairwise sums on the
        # lane-packed plane view (one matmul pair; for f0 < 128 the left
        # operator is I over packed rows and the right one folds both
        # the y- and x-pairs — see _pack_shape)
        t2 = (_sslice(t, 0, s) + _sslice(t, s, s)).reshape(fv)
        # precision=HIGHEST: inside a Pallas kernel an f32 dot lowers to a
        # SINGLE bf16 MXU pass by default (no XLA precision pass) — the r5
        # on-chip value check caught ~3e-3 relative error from exactly
        # this; the 0/1 pair-sum operators need f32-exact accumulation
        # operands go to f32 first: Mosaic refuses a bf16 lhs for an
        # f32-accumulating HIGHEST dot (as in the up kernel)
        red = jnp.dot(sy_ref[:].astype(jnp.float32),
                      t2.astype(jnp.float32),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
        out = jnp.dot(red, sx_ref[:].astype(jnp.float32),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
        o_ref[0] = out.astype(dt)

    rc_spec = pl.BlockSpec(
        (1, pc1, pc0), lambda c: (c, np.int32(0), np.int32(0)))
    rc_shape = jax.ShapeDtypeStruct((c2, pc1, pc0), dt)
    if zero_guess:
        out_specs = (rc_spec, pl.BlockSpec((2 * s,), lambda c: (c,)))
        out_shape = (rc_shape, jax.ShapeDtypeStruct((n2,), dt))
    else:
        out_specs = rc_spec
        out_shape = rc_shape
    out = pl.pallas_call(
        kernel,
        grid=(c2,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # a_flat
            pl.BlockSpec(memory_space=pl.ANY),          # mt_flat
            pl.BlockSpec(memory_space=pl.ANY),          # fp
            pl.BlockSpec(memory_space=pl.ANY),          # up (u or scale)
            pl.BlockSpec((pc1, fv[0]),
                         lambda c: (np.int32(0), np.int32(0))),
            pl.BlockSpec((fv[1], pc0),
                         lambda c: (np.int32(0), np.int32(0))),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=(
            [pltpu.VMEM((W,), dt) for _ in range(nA + nM)]
            + [pltpu.VMEM((W,), dt),
               pltpu.VMEM((W,), dt),
               pltpu.SemaphoreType.DMA((nA + nM + 2,))]
        ),
        interpret=interpret,
    )(a_flat, mt_flat, fp, up, sy, sx)
    return out


@register_pytree_node_class
class FusedDownSweep:
    """Device handle attached to a hierarchy Level; ``__call__(f, u)``
    returns the restricted filtered residual as a flat coarse vector.
    ``zero(f)`` (available when the level smoother is a scalar scaled-
    residual smoother — ``w`` is set) additionally forms the npre=1
    pre-smoothed iterate in the same pass and returns ``(u, fc)``."""

    def __init__(self, a_flat, mt_flat, sy, sx, w, offs_a, offs_m,
                 dims, coarse, H, interpret):
        self.a_flat = a_flat
        self.mt_flat = mt_flat
        self.sy = sy
        self.sx = sx
        self.w = w                    # smoother scale, or None
        self.offs_a = tuple(int(o) for o in offs_a)
        self.offs_m = tuple(int(o) for o in offs_m)
        self.dims = tuple(int(d) for d in dims)
        self.coarse = tuple(int(c) for c in coarse)
        self.H = int(H)
        self.interpret = bool(interpret)

    def tree_flatten(self):
        return ((self.a_flat, self.mt_flat, self.sy, self.sx, self.w),
                (self.offs_a, self.offs_m, self.dims, self.coarse,
                 self.H, self.interpret))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def __call__(self, f, u):
        with _tel_phase("pallas/fused_down"):
            rc = fused_down_sweep(
                self.a_flat, self.mt_flat, self.sy, self.sx, f, u,
                self.offs_a, self.offs_m, self.dims, self.coarse, self.H,
                zero_guess=False, interpret=self.interpret)
        return rc.reshape(-1)

    def zero(self, f):
        """(u, fc) from rhs alone — the whole npre=1 down-sweep."""
        n = int(np.prod(self.dims))
        with _tel_phase("pallas/fused_down_zero"):
            rc, u = fused_down_sweep(
                self.a_flat, self.mt_flat, self.sy, self.sx, f, self.w,
                self.offs_a, self.offs_m, self.dims, self.coarse, self.H,
                zero_guess=True, interpret=self.interpret)
        return u[:n], rc.reshape(-1)

    def bytes(self):
        return sum(a.size * a.dtype.itemsize
                   for a in (self.a_flat, self.mt_flat, self.sy, self.sx))


def _pair_sum(rows, cols, dtype):
    """(rows, cols) 0/1 matrix summing index pairs: out[i] = in[2i]+in[2i+1]."""
    m = np.zeros((rows, cols), np.float32)
    m[np.arange(cols) // 2, np.arange(cols)] = 1.0
    return jnp.asarray(m, dtype=dtype)


def _values_agree(got, want, dt):
    """One-shot build-time numeric check of a fused kernel against the
    composed path ON THE DEVICE. The probe-compile above catches Mosaic
    legalization failures; this catches the silent-miscompute class that
    interpret-mode CI cannot (interpret is not Mosaic). Tolerances are
    format-scaled."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return False
    tol = 0.05 if jnp.dtype(dt) == jnp.bfloat16 else 2e-3
    denom = np.linalg.norm(want) + 1e-30
    return np.linalg.norm(got - want) / denom < tol


@functools.partial(_watched_jit, name="ops.fused_up_sweep",
                   static_argnames=(
    "offs_a", "offs_m", "dims", "coarse", "halo_planes", "framed",
    "interpret"))
def fused_up_sweep(a_data, m_flat, syt, sxt, rc3p, f, w, u,
                   offs_a, offs_m, dims, coarse, halo_planes: int = 1,
                   framed: bool = False, interpret: bool = False):
    """u'' = u' + w ∘ (f − A u') with u' = u + (I − M) T uc, in ONE pass.

    The up-sweep mirror of :func:`fused_down_sweep`: per coarse z-plane
    the kernel expands the coarse plane plus ``halo_planes`` (= hp)
    neighbors each side — the halo the A/M products need — through the
    transposed pair-sum matmuls, forms u' = u + T uc − M (T uc) on a
    (2hp+1)·2-plane frame in VMEM, and applies the first post-smoothing
    sweep — prolongation, correction and smoother in one fine-grid
    traversal, with only u'' returning to HBM.

    a_data: the level's (nA, n) DIA data, read per-tile via BlockSpec.
    m_flat: M's diagonals in a ±hp·2s zero frame, flattened. rc3p: the
    coarse vector in its packed plane view with hp zero planes each
    side. Eligibility (enforced by ``build_fused_up``):
    hA + hM ≤ hp·2s and f2 even (no ghost fine plane)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f2, f1, f0 = dims
    c2, c1, c0 = coarse
    hp = int(halo_planes)
    s = f1 * f0
    n = f2 * s
    F = (2 * hp + 1) * 2 * s          # VMEM frame length
    Lm = n + 2 * hp * 2 * s
    hA = max(max(offs_a), -min(offs_a), 0)
    nA = len(offs_a)
    nM = len(offs_m)
    dt = f.dtype
    _, fv, cv = _pack_shape(f1, f0, c1, c0)
    pc1, pc0 = cv
    if syt.shape != (fv[0], pc1) or sxt.shape != (pc0, fv[1]):
        raise ValueError("expansion operator shapes %s/%s do not match "
                         "the packed plane views %s/%s"
                         % (syt.shape, sxt.shape, (fv[0], pc1),
                            (pc0, fv[1])))
    tile0 = hp * 2 * s                # tile offset inside the frame
    seg0 = tile0 - hA                 # u' segment start (width 2s + 2hA)
    E = 2 * s + 2 * hA

    def kernel(*args):
        (mf_hbm, up_hbm, a_ref, f_ref, w_ref) = args[:5]
        planes = args[5:5 + 2 * hp + 1]
        # sm: per-diagonal 1-D frame scratches (Mosaic rejects DMA into a
        # row view of 2-D VMEM — sublane slices must be 8-aligned)
        (syt_ref, sxt_ref, o_ref, *scr) = args[5 + 2 * hp + 1:]
        sm = scr[:nM]
        su, tuc, sems = scr[nM:]
        c = pl.program_id(0)
        start = c * (2 * s)
        cps = [pltpu.make_async_copy(
            up_hbm.at[pl.ds(start, F)], su, sems.at[np.int32(0)])]
        for k in range(nM):
            cps.append(pltpu.make_async_copy(
                mf_hbm.at[pl.ds(k * Lm + start, F)], sm[k],
                sems.at[np.int32(1 + k)]))
        for cp in cps:
            cp.start()
        # T uc on the frame while the DMAs fly: MXU pair expansion of
        # each coarse plane, written to its two fine planes
        for p, ref in enumerate(planes):
            plane = ref[0].astype(jnp.float32)
            # precision=HIGHEST: see the down kernel — default in-kernel
            # f32 dots are one bf16 MXU pass
            f2d = jnp.dot(syt_ref[:].astype(jnp.float32),
                          jnp.dot(plane, sxt_ref[:].astype(jnp.float32),
                                  preferred_element_type=jnp.float32,
                                  precision=jax.lax.Precision.HIGHEST),
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
            flat = f2d.reshape(s).astype(dt)
            tuc[pl.ds(2 * p * s, s)] = flat
            tuc[pl.ds((2 * p + 1) * s, s)] = flat
        for cp in cps:
            cp.wait()

        # u' = u + T uc − M (T uc) on frame [seg0, seg0 + E) (global
        # rows [2cs − hA, 2cs + 2s + hA); zero-frame edges match global
        # zero-fill)
        accm = jnp.zeros((E,), dt)
        for k, d in enumerate(offs_m):
            accm = accm + sm[k][pl.ds(seg0, E)] * tuc[pl.ds(seg0 + d, E)]
        upr = su[pl.ds(seg0, E)] + tuc[pl.ds(seg0, E)] - accm

        # first post-smooth sweep on the tile (tile i ↔ seg hA + i)
        acc = jnp.zeros((2 * s,), dt)
        for k, d in enumerate(offs_a):
            acc = acc + a_ref[k, :] * _sslice(upr, hA + d, 2 * s)
        o_ref[:] = _sslice(upr, hA, 2 * s) \
            + w_ref[:] * (f_ref[:] - acc)

    if m_flat.ndim != 1:
        raise ValueError("m_flat must be the pre-padded flat frame "
                         "built by build_fused_up")
    if framed:
        # distributed-slab mode: u arrives halo-extended by the caller
        # (real neighbor values); rc3p likewise carries hp neighbor
        # coarse planes each side
        if u.shape[0] != n + 2 * tile0:
            raise ValueError("framed mode needs a pre-framed u")
        up = u
    else:
        up = jnp.zeros(n + 2 * hp * 2 * s, dt).at[
            tile0:tile0 + n].set(u)
    vec = pl.BlockSpec((2 * s,), lambda c: (c,))
    plane = lambda off: pl.BlockSpec(
        (1, pc1, pc0),
        lambda c, _o=off: (c + _o, np.int32(0), np.int32(0)))
    out = pl.pallas_call(
        kernel,
        grid=(c2,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),              # m flat frame
            pl.BlockSpec(memory_space=pl.ANY),              # u padded
            pl.BlockSpec((nA, 2 * s), lambda c: (np.int32(0), c)),
            vec, vec,                                       # f, w
        ] + [plane(o) for o in range(2 * hp + 1)] + [      # rc planes
            pl.BlockSpec((fv[0], pc1),
                         lambda c: (np.int32(0), np.int32(0))),
            pl.BlockSpec((pc0, fv[1]),
                         lambda c: (np.int32(0), np.int32(0))),
        ],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((n,), dt),
        scratch_shapes=(
            [pltpu.VMEM((F,), dt) for _ in range(nM)]
            + [pltpu.VMEM((F,), dt),
               pltpu.VMEM((F,), dt),
               pltpu.SemaphoreType.DMA((nM + 1,))]
        ),
        interpret=interpret,
    )(m_flat, up, a_data, f, w, *([rc3p] * (2 * hp + 1)), syt, sxt)
    return out


@register_pytree_node_class
class FusedUpSweep:
    """Device handle for the fused prolong+correct+post-smooth pass."""

    def __init__(self, a_data, m_flat, syt, sxt, w,
                 offs_a, offs_m, dims, coarse, halo_planes, interpret):
        self.a_data = a_data
        self.m_flat = m_flat      # pre-padded frame, flattened
        self.syt = syt
        self.sxt = sxt
        self.w = w
        self.halo_planes = int(halo_planes)
        self.offs_a = tuple(int(o) for o in offs_a)
        self.offs_m = tuple(int(o) for o in offs_m)
        self.dims = tuple(int(d) for d in dims)
        self.coarse = tuple(int(c) for c in coarse)
        self.interpret = bool(interpret)

    def tree_flatten(self):
        return ((self.a_data, self.m_flat, self.syt, self.sxt, self.w),
                (self.offs_a, self.offs_m, self.dims, self.coarse,
                 self.halo_planes, self.interpret))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def __call__(self, f, u, uc):
        c2 = self.coarse[0]
        hp = self.halo_planes
        _, _, cv = _pack_shape(self.dims[1], self.dims[2],
                               self.coarse[1], self.coarse[2])
        rc3p = jnp.pad(uc.reshape(c2, cv[0], cv[1]),
                       ((hp, hp), (0, 0), (0, 0)))
        with _tel_phase("pallas/fused_up"):
            return fused_up_sweep(
                self.a_data, self.m_flat, self.syt, self.sxt, rc3p,
                f, self.w, u, self.offs_a, self.offs_m, self.dims,
                self.coarse, halo_planes=hp, interpret=self.interpret)

    def bytes(self):
        return sum(a.size * a.dtype.itemsize
                   for a in (self.m_flat, self.syt, self.sxt, self.w))


def build_fused_up(A_dev, P_dev, relax):
    """FusedUpSweep for an eligible (A, P, smoother) triple, else None."""
    from amgcl_tpu.ops.device import DiaMatrix
    from amgcl_tpu.ops.structured import ImplicitSmoothedP, GridTentative
    from amgcl_tpu.ops.pallas_spmv import pallas_mode
    from amgcl_tpu.relaxation.base import ScaledResidualSmoother

    if not vcycle_fusion_enabled():
        return None
    if not isinstance(A_dev, DiaMatrix) \
            or not isinstance(P_dev, ImplicitSmoothedP) \
            or not isinstance(P_dev.T, GridTentative) \
            or not isinstance(P_dev.M, DiaMatrix) \
            or not isinstance(relax, ScaledResidualSmoother) \
            or relax.scale.ndim != 1:
        return None
    T = P_dev.T
    if T.block != (2, 2, 2):
        return None
    f2, f1, f0 = T.fine
    k = 128 // f0 if f0 and 128 % f0 == 0 else 0
    if (not k) or f0 % 2 or f1 % 2 or (k > 1 and f1 % k) \
            or (f1 * f0) % 512 or f2 % 2 or f2 < 2:
        return None
    dt = jnp.dtype(A_dev.dtype)
    if dt != jnp.dtype(P_dev.M.dtype) or dt.itemsize > 4 \
            or jnp.issubdtype(dt, jnp.complexfloating) \
            or jnp.dtype(relax.scale.dtype) != dt:
        return None
    interpret = pallas_mode(dt)
    if interpret is None:
        return None
    offs_a, offs_m = A_dev.offsets, P_dev.M.offsets
    if not offs_a or not offs_m:
        return None
    s = f1 * f0
    # the COMBINED A+M halo sets how many coarse neighbor planes the
    # frame expands (hA <= hp*2s follows from the ceil)
    hp, _, vmem = up_geometry(offs_a, offs_m, T.fine)
    if hp > 2 or vmem * dt.itemsize > _VMEM_CAP_BYTES:
        return None
    # real-hardware window-redundancy gate (r5 on-chip A/B; interpret-
    # mode CI still exercises hp = 2): at hp = 2 the frame is 5 planes
    # per useful pair and the 128^3 level-1 fused up measured a wash vs
    # the composed path (273 us vs 271 us) — not worth the VMEM
    if hp > 1 and not interpret:
        return None
    n = A_dev.shape[0]
    nA, nM = len(offs_a), len(offs_m)
    c2, c1, c0 = T.coarse
    Lm = n + 2 * hp * 2 * s
    m_flat = jnp.zeros((nM, Lm), dt).at[
        :, hp * 2 * s:hp * 2 * s + n].set(P_dev.M.data).reshape(-1)
    _, fvw, cvw = _pack_shape(f1, f0, c1, c0)
    if k == 1:
        syt = _pair_sum(c1, f1, dt).T
        sxt = _pair_sum(c0, f0, dt)
    else:
        syt = jnp.eye(fvw[0], dtype=dt)
        sxt = _packed_reduce(f0, k, c0, dt).T

    if not interpret:
        key = ("up", tuple(offs_a), tuple(offs_m), T.fine, T.coarse,
               hp, dt.name)
        if key not in _PROBE_OK:
            try:
                av = jax.ShapeDtypeStruct((nA, n), dt)
                mv = jax.ShapeDtypeStruct((nM * Lm,), dt)
                sytv = jax.ShapeDtypeStruct((fvw[0], cvw[0]), dt)
                sxtv = jax.ShapeDtypeStruct((cvw[1], fvw[1]), dt)
                rv = jax.ShapeDtypeStruct((c2 + 2 * hp, cvw[0], cvw[1]),
                                          dt)
                fv = jax.ShapeDtypeStruct((n,), dt)
                jax.jit(functools.partial(
                    fused_up_sweep, offs_a=tuple(offs_a),
                    offs_m=tuple(offs_m), dims=T.fine, coarse=T.coarse,
                    halo_planes=hp)).lower(
                        av, mv, sytv, sxtv, rv, fv, fv, fv).compile()
                _PROBE_OK[key] = True
            except Exception as e:
                probe_report("fused_up_sweep%r" % (key,), e)
                _PROBE_OK[key] = False
        if not _PROBE_OK[key]:
            return None

    handle = FusedUpSweep(A_dev.data, m_flat, syt, sxt, relax.scale,
                          offs_a, offs_m, T.fine, T.coarse, hp, interpret)
    if not interpret:
        vkey = ("up", tuple(offs_a), tuple(offs_m), T.fine, T.coarse,
                hp, dt.name)
        if vkey not in _VALUE_OK:
            from amgcl_tpu.ops import device as _dev
            rng = np.random.RandomState(19)
            fv = jnp.asarray(rng.rand(n), dt)
            uv = jnp.asarray(rng.rand(n), dt)
            ucv = jnp.asarray(rng.rand(T.shape[1]), dt)
            want = relax.apply_post(A_dev, fv, uv + P_dev.mv(ucv))
            if not _values_agree(handle(fv, uv, ucv), want, dt):
                probe_report("fused_up_sweep", note="on-device value "
                             "check mismatch vs composed path (n=%d)" % n)
                return None
            _VALUE_OK.add(vkey)
    return handle


def build_fused_down(A_dev, R_dev, relax=None):
    """FusedDownSweep for an eligible (A, R) pair, else None.

    ``relax``: the level's smoother state; a scalar ScaledResidualSmoother
    additionally enables the zero-guess mode (pre-smooth + residual +
    restrict in one kernel). Eligibility and the probe-compile are both
    decided here, eagerly — inside the outer solve jit a Mosaic
    legalization failure would only surface at the OUTER compile, too
    late to fall back."""
    from amgcl_tpu.ops.device import DiaMatrix
    from amgcl_tpu.ops.structured import ImplicitSmoothedR, GridTentative
    from amgcl_tpu.ops.pallas_spmv import pallas_mode
    from amgcl_tpu.relaxation.base import ScaledResidualSmoother

    if not vcycle_fusion_enabled():
        return None
    if not isinstance(A_dev, DiaMatrix) \
            or not isinstance(R_dev, ImplicitSmoothedR) \
            or not isinstance(R_dev.T, GridTentative) \
            or not isinstance(R_dev.Mt, DiaMatrix):
        return None
    T = R_dev.T
    if T.block != (2, 2, 2):
        return None
    f2, f1, f0 = T.fine
    # odd f2 IS supported (the last coarse plane reduces over a zero
    # ghost plane, matching GridTentative.rmv's pad); f0 < 128 levels
    # pack k = 128//f0 y-rows per lane row (_pack_shape)
    k = 128 // f0 if f0 and 128 % f0 == 0 else 0
    if (not k) or f0 % 2 or f1 % 2 or (k > 1 and f1 % k) \
            or (f1 * f0) % 512 or f2 < 2:
        return None
    dt = jnp.dtype(A_dev.dtype)
    if dt != jnp.dtype(R_dev.Mt.dtype) or dt.itemsize > 4 \
            or jnp.issubdtype(dt, jnp.complexfloating):
        return None
    interpret = pallas_mode(dt)
    if interpret is None:
        return None
    offs_a, offs_m = A_dev.offsets, R_dev.Mt.offsets
    if not offs_a or not offs_m:
        return None
    s = f1 * f0
    H, _, vmem = down_geometry(offs_a, offs_m, T.fine)
    if vmem * dt.itemsize > _VMEM_CAP_BYTES:
        return None
    # real-hardware window-redundancy gate (r5 on-chip A/B; interpret-
    # mode CI still exercises the larger-halo geometry): each grid step
    # DMAs W = 2s + 2H per operand, so H > 2s re-reads the halo more
    # than twice per useful row — the 128^3 level-1 fused down measured
    # 501 us vs 237 us composed (H = 4s) while level 0 won 569 us vs
    # 2.5 ms (H = 2s). Coarser SA levels keep the composed fused-
    # residual path on hardware.
    if H > 2 * s and not interpret:
        return None
    c2, c1, c0 = T.coarse
    n = A_dev.shape[0]
    L = 2 * c2 * s + 2 * H

    w = None
    if isinstance(relax, ScaledResidualSmoother) and relax.scale.ndim == 1 \
            and jnp.dtype(relax.scale.dtype) == dt:
        w = relax.scale

    if not interpret:
        for zg in ((False, True) if w is not None else (False,)):
            key = (tuple(offs_a), tuple(offs_m), T.fine, T.coarse, H,
                   dt.name, zg)
            if key not in _PROBE_OK:
                try:
                    _, fvw, cvw = _pack_shape(f1, f0, c1, c0)
                    av = jax.ShapeDtypeStruct((len(offs_a) * L,), dt)
                    mv = jax.ShapeDtypeStruct((len(offs_m) * L,), dt)
                    syv = jax.ShapeDtypeStruct((cvw[0], fvw[0]), dt)
                    sxv = jax.ShapeDtypeStruct((fvw[1], cvw[1]), dt)
                    fvec = jax.ShapeDtypeStruct((n,), dt)
                    jax.jit(functools.partial(
                        fused_down_sweep, offs_a=tuple(offs_a),
                        offs_m=tuple(offs_m), dims=T.fine,
                        coarse=T.coarse, H=H, zero_guess=zg)).lower(
                            av, mv, syv, sxv, fvec, fvec).compile()
                    _PROBE_OK[key] = True
                except Exception as e:
                    probe_report("fused_down_sweep%r" % (key,), e)
                    _PROBE_OK[key] = False
            if not _PROBE_OK[key]:
                if zg:
                    w = None      # base kernel fine, zero-guess declined
                else:
                    return None

    def _flat(M):
        nd = len(M.offsets)
        padded = jnp.zeros((nd, L), dt).at[:, H:H + n].set(M.data)
        return padded.reshape(-1)

    if k == 1:
        red_a = _pair_sum(c1, f1, dt)
        red_b = _pair_sum(c0, f0, dt).T
    else:
        red_a = jnp.eye(f1 // k, dtype=dt)
        red_b = _packed_reduce(f0, k, c0, dt)
    handle = FusedDownSweep(
        _flat(A_dev), _flat(R_dev.Mt), red_a, red_b, w,
        offs_a, offs_m, T.fine, T.coarse, H, interpret)
    if not interpret:
        # real-hardware value checks vs the (round-2-proven) composed
        # path, once per geometry per process; base and zero-mode carry
        # SEPARATE verdicts so a failing zero mode neither re-runs the
        # passing base check on every rebuild nor gets retried forever
        vkey = ("down", tuple(offs_a), tuple(offs_m), T.fine, T.coarse,
                H, dt.name)
        zkey = vkey + ("zero",)
        from amgcl_tpu.ops import device as _dev
        rng = np.random.RandomState(17)
        fv = jnp.asarray(rng.rand(n), dt)
        if vkey not in _VALUE_OK:
            uv = jnp.asarray(rng.rand(n), dt)
            want = R_dev.mv(_dev.residual(fv, A_dev, uv))
            if not _values_agree(handle(fv, uv), want, dt):
                probe_report("fused_down_sweep", note="on-device value "
                             "check mismatch vs composed path (n=%d)" % n)
                return None
            _VALUE_OK.add(vkey)
        if w is not None:
            if zkey in _VALUE_BAD:
                handle.w = None
            elif zkey not in _VALUE_OK:
                uz, fz = handle.zero(fv)
                uw = w * fv
                if (_values_agree(uz, uw, dt) and _values_agree(
                        fz, R_dev.mv(_dev.residual(fv, A_dev, uw)), dt)):
                    _VALUE_OK.add(zkey)
                else:
                    probe_report("fused_down_sweep.zero", note="on-device"
                                 " value check mismatch (n=%d)" % n)
                    _VALUE_BAD.add(zkey)
                    handle.w = None  # base kernel fine, zero declined
    return handle
