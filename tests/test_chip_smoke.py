"""chip_smoke.py off the chip: no TPU means a nonzero exit and no result
(for bench.py too), and its phases' host logic at small sizes on the
CPU — the float64 refinement loop and the four-device distributed phase
against its one-chip comparison."""

import os
import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


@pytest.mark.parametrize("argv", [["chip_smoke.py"],
                                  ["chip_smoke.py", "--chips", "4"],
                                  ["bench.py"]])
def test_no_tpu_exits_nonzero_without_a_result(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert got.returncode != 0
    assert got.stdout == ""
    assert "no TPU" in got.stderr


def test_refined_reaches_tolerance():
    """float32 solves of the scaled float64 residual, summed in float64,
    reach a true residual far below what one float32 solve gives."""
    from amgcl_tpu import AMGParams, make_solver
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.utils.sample_problem import poisson3d
    A, rhs = poisson3d(12)
    s = make_solver(A, AMGParams(dtype=jnp.float32),
                    CG(maxiter=100, tol=1e-6))
    x, rows = chip_smoke.refined(s, A.to_scipy(), rhs)
    assert rows[-1]["true_resid"] <= chip_smoke.REFINE_TOL
    assert len(rows) >= 2
    assert rows[0]["true_resid"] > rows[-1]["true_resid"]
    r = rhs - A.spmv(x)
    assert np.linalg.norm(r) / np.linalg.norm(rhs) \
        == pytest.approx(rows[-1]["true_resid"], rel=1e-6)


def test_distributed_phase_on_virtual_devices(capsys):
    """The --chips 4 phase end to end on four virtual CPU devices, with
    the per-device byte counts the CPU backend does not report stubbed."""

    class Dev:
        def __init__(self, d):
            self._d = d

        def memory_stats(self):
            return {"bytes_in_use": 1}

    fake = types.SimpleNamespace(
        devices=lambda: [Dev(d) for d in jax.devices()],
        block_until_ready=jax.block_until_ready)
    chip_smoke.distributed(fake, jnp, n=16, n_devices=4)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    phases = [ln.split('"phase": "')[1].split('"')[0] for ln in lines]
    assert phases == ["distributed", "distributed", "one_chip", "one_chip"]
    assert '"rel_diff_vs_distributed"' in lines[-1]
