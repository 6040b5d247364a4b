"""Real-size compiles of the main-path Pallas kernels for a TPU v5e that
is described but not attached (``jax.experimental.topologies``).

Nothing runs: each test lowers one kernel at the shapes the chip sees and
compiles it with the TPU compiler, which refuses what the chip would
refuse (unaligned blocks, scoped-VMEM overflow, ops Mosaic cannot lower).
Interpret-mode tests cannot catch those. Geometries:

* Poisson 128^3 level 0: 7 diagonals, 2,097,152 rows, float32;
* its level 1 in bf16: 33 diagonals on the 64^3 grid;
* the 85,623-row FE operator: in identity order, row tiles of 1024 rows
  with an 86,016-column window; in the RCM order the executed reorder
  gives it on TPU, a 13,312-column window and 84 tiles of 48 entry vregs
  (8 slots x 128 rows each) for the windowed-ELL lane-gather kernel.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""

import functools

import pytest

import jax
import jax.numpy as jnp

from amgcl_tpu.ops import densewin as dw
from amgcl_tpu.ops import fused_vec as fv
from amgcl_tpu.ops import pallas_spmv as ps
from amgcl_tpu.ops import pallas_vcycle as pv
from amgcl_tpu.ops import unstructured as us

N = 128
ROWS = N ** 3
#: 7-point stencil and the 33-point level-1 stencil of SA on it (the
#: 27-point box plus the six distance-2 face neighbours), as (dz, dy, dx)
STENCIL7 = [(0, 0, 0), (0, 0, 1), (0, 0, -1), (0, 1, 0), (0, -1, 0),
            (1, 0, 0), (-1, 0, 0)]
STENCIL33 = [(z, y, x) for z in (-1, 0, 1) for y in (-1, 0, 1)
             for x in (-1, 0, 1)] + [(2, 0, 0), (-2, 0, 0), (0, 2, 0),
                                     (0, -2, 0), (0, 0, 2), (0, 0, -2)]
#: the FE operator's windowed geometry (tile_windows at tile 1024), in
#: identity and in RCM order
FE_WIN = 86016
FE_ROWS, FE_RCM_WIN, FE_KV = 85623, 13312, 6


def offsets(stencil, g):
    return tuple(sorted(z * g * g + y * g + x for z, y, x in stencil))


OFFS0 = offsets(STENCIL7, N)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """ShapeDtypeStruct factory on one described v5e chip, with the
    persistent compilation cache off for the module."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one)
    yield spec
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_dia_spmv_level0(chip):
    compile_kernel(functools.partial(ps.dia_spmv, OFFS0),
                   chip((7, ROWS)), chip((ROWS,)))


@pytest.mark.parametrize("zero_guess", [False, True])
def test_fused_down_sweep_level0(chip, zero_guess):
    dims, coarse = (N,) * 3, (N // 2,) * 3
    H, _, _ = pv.down_geometry(OFFS0, OFFS0, dims)
    L = 2 * (N // 2) * N * N + 2 * H
    _, fine_v, coarse_v = pv._pack_shape(N, N, N // 2, N // 2)
    compile_kernel(
        functools.partial(pv.fused_down_sweep, offs_a=OFFS0, offs_m=OFFS0,
                          dims=dims, coarse=coarse, H=H,
                          zero_guess=zero_guess),
        chip((7 * L,)), chip((7 * L,)), chip((coarse_v[0], fine_v[0])),
        chip((fine_v[1], coarse_v[1])), chip((ROWS,)), chip((ROWS,)))


def test_fused_up_sweep_level0(chip):
    dims, coarse = (N,) * 3, (N // 2,) * 3
    hp, _, _ = pv.up_geometry(OFFS0, OFFS0, dims)
    Lm = ROWS + 2 * hp * 2 * N * N
    _, fine_v, coarse_v = pv._pack_shape(N, N, N // 2, N // 2)
    compile_kernel(
        functools.partial(pv.fused_up_sweep, offs_a=OFFS0, offs_m=OFFS0,
                          dims=dims, coarse=coarse, halo_planes=hp),
        chip((7, ROWS)), chip((7 * Lm,)), chip((fine_v[0], coarse_v[0])),
        chip((coarse_v[1], fine_v[1])),
        chip((N // 2 + 2 * hp, coarse_v[0], coarse_v[1])),
        chip((ROWS,)), chip((ROWS,)), chip((ROWS,)))


def test_fused_vec_xr_pass(chip):
    compile_kernel(lambda a, vs: fv._fused_pass("xr", a, vs),
                   (chip(()),), tuple(chip((ROWS,)) for _ in range(4)))


@pytest.mark.parametrize("kernel", ["spmv", "correction"])
def test_dia_bf16_33_diagonals(chip, kernel):
    offs = offsets(STENCIL33, N // 2)
    n = (N // 2) ** 3
    assert len(offs) == 33
    d, v = chip((33, n), jnp.bfloat16), chip((n,), jnp.bfloat16)
    if kernel == "spmv":
        compile_kernel(functools.partial(ps.dia_spmv, offs), d, v)
    else:
        compile_kernel(lambda d, f, x, w: ps._dia_fused(
            offs, d, f, x, w, "correction"), d, v, v, v)


def test_dense_window_fe_coarse(chip):
    # dense-window blocks of 64 rows over an 11,264-column window (the
    # FE operator's RCM window; the format is budget-gated on real size)
    tiles, win = 1338, 11264
    n = tiles * 64
    x = chip((n,))
    compile_kernel(functools.partial(dw.dense_window_fused,
                                     mode="correction", win=win, n_out=n),
                   chip((tiles,), jnp.int32), chip((tiles, 64, win)),
                   x, x, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_well_lane_gather_fe_level0(chip, dtype):
    # the windowed-ELL lane-gather kernel at the FE operator's RCM
    # geometry: 84 tiles x 48 entry vregs, each tile's x window DMA'd
    tiles = -(-FE_ROWS // 1024)
    vregs = tiles * 8 * FE_KV
    compile_kernel(functools.partial(us.well_spmv, n_out=FE_ROWS,
                                     win=FE_RCM_WIN, kv=FE_KV),
                   chip((2 * vregs,), jnp.int32), chip((tiles,), jnp.int32),
                   chip((vregs, 8, 128), jnp.int32),
                   chip((vregs, 8, 128), dtype), chip((FE_ROWS,)))


def test_window_gather_refused(chip):
    """A 1-D gather from a VMEM x-window does not lower on v5e at the FE
    operator's window ("Only 2D gather is supported"). The windowed-ELL
    kernel (ops/unstructured.well_spmv) takes the 2-D route instead: a
    lane gather within one (8, 128) vreg per x row. If a later JAX lowers
    the 1-D gather, this test fails and that simpler kernel becomes
    possible."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, c_ref, o_ref):
        o_ref[...] = jnp.take(x_ref[...], c_ref[...], axis=0)

    def gather(x, cols):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(cols.shape, x.dtype))(
                x, cols)

    with pytest.raises(Exception, match="Only 2D gather is supported"):
        jax.jit(gather).lower(chip((FE_WIN,)),
                              chip((1024,), jnp.int32)).compile()
