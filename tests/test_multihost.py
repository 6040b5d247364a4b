"""REAL multi-process distributed execution: two controller processes,
Gloo CPU collectives, one global mesh — the jax.distributed rendition of
the reference's MPI scale-out (SURVEY.md §5.8). Worker scripts build
distributed solvers over the global mesh and solve the Poisson fixture;
the tests assert convergence AND iteration parity with a single-process
mesh of the same size (multi-controller must not change the math).

Both tests are ``@pytest.mark.serial``: they spawn controller
subprocesses that bind ports and race the Gloo init timeout, which is
known to fail under concurrent host load. The launcher now retries a
timed-out or init-crashed attempt on a fresh port (up to 3 attempts),
so load flakes self-heal; a failure that survives every attempt is a
real signal (the README re-run-alone protocol remains the final
arbiter: ``pytest tests/test_multihost.py -m serial``)."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# common per-worker bootstrap: env scrubbing, virtual devices, jax.distributed
_BOOT = r"""
import os, sys
pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=@NDEV@"
sys.path.insert(0, @REPO@)
from amgcl_tpu.parallel import multihost
multihost.initialize("127.0.0.1:" + port, nproc, pid)
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
assert jax.process_count() == nproc
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _scrub_env():
    return {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}


def _run_workers(body, nproc=2, devices_per_proc=2, timeout=420,
                 attempts=3):
    """Launch ``nproc`` workers running _BOOT + body; return their stdout
    and the parsed iters= values (body must print 'RESULT <pid> iters=N').

    Load-tolerant by construction (the README re-run-alone protocol,
    internalized): the Gloo init handshake and the port bind race the
    host load, so a timed-out or crashed attempt is retried up to
    ``attempts`` times on a FRESH port before the test fails — a real
    regression fails every attempt, a loaded host passes a later one."""
    src = (_BOOT.replace("@REPO@", repr(REPO))
           .replace("@NDEV@", str(devices_per_proc)) + body)
    last = None
    for attempt in range(attempts):
        port = str(_free_port())
        procs = [subprocess.Popen(
            [sys.executable, "-c", src, str(pid), str(nproc), port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_scrub_env()) for pid in range(nproc)]
        outs = []
        timed_out = False
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                for q in procs:       # reap so nothing leaks across
                    try:              # attempts
                        q.communicate(timeout=10)
                    except Exception:          # noqa: BLE001
                        pass
                timed_out = True
                break
            outs.append(out)
        if timed_out:
            last = "attempt %d timed out after %ss" % (attempt + 1,
                                                       timeout)
            continue
        bad = [pid for pid in range(nproc)
               if procs[pid].returncode != 0
               or "RESULT %d" % pid not in outs[pid]]
        if bad:
            last = outs[bad[0]][-3000:]
            if "Multiprocess computations aren't implemented" in last:
                # capability failure, not a regression: this jax build's
                # CPU backend cannot execute cross-process collectives
                # at all — no retry (or code change) can make the test
                # meaningful here, so say so instead of failing
                pytest.skip("jax CPU backend lacks multiprocess "
                            "collective support in this environment")
            continue
        iters = sorted(int(o.split("iters=")[1].split()[0])
                       for o in outs)
        return outs, iters
    pytest.fail("multi-process run failed after %d attempt(s): %s"
                % (attempts, last))


def _single_process_iters(body, n_devices, timeout=420):
    """Run ``body`` on one process with an ``n_devices`` virtual mesh;
    body must print 'ITERS <n>'."""
    src = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=@NDEV@"
sys.path.insert(0, @REPO@)
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
""".replace("@REPO@", repr(REPO)).replace("@NDEV@", str(n_devices)) + body
    try:
        probe = subprocess.run([sys.executable, "-c", src],
                               capture_output=True, text=True,
                               env=_scrub_env(), timeout=timeout)
    except subprocess.TimeoutExpired:
        # one load-tolerant retry with a doubled budget (compiles on a
        # saturated host legitimately take longer); a second timeout is
        # a real failure
        probe = subprocess.run([sys.executable, "-c", src],
                               capture_output=True, text=True,
                               env=_scrub_env(), timeout=2 * timeout)
    assert probe.returncode == 0, probe.stdout + probe.stderr
    return int(probe.stdout.split("ITERS")[1].split()[0])


@pytest.mark.serial
def test_two_process_dist_amg():
    outs, iters = _run_workers(r"""
from amgcl_tpu.utils.sample_problem import poisson3d
from amgcl_tpu.parallel.dist_amg import DistAMGSolver
from amgcl_tpu.models.amg import AMGParams
from amgcl_tpu.solver.cg import CG

mesh = multihost.global_mesh()
assert mesh.devices.size == 2 * nproc
A, rhs = poisson3d(12)
s = DistAMGSolver(A, mesh, AMGParams(dtype=jnp.float64, coarse_enough=300),
                  CG(maxiter=100, tol=1e-8))
x, info = s(rhs)
r = np.linalg.norm(rhs - A.spmv(x)) / np.linalg.norm(rhs)
assert r < 1e-7, r
print("RESULT %d iters=%d resid=%.3e" % (pid, info.iters, r), flush=True)
""", nproc=2, devices_per_proc=2)
    assert iters[0] == iters[1]

    single = _single_process_iters(r"""
from amgcl_tpu.utils.sample_problem import poisson3d
from amgcl_tpu.parallel.mesh import make_mesh
from amgcl_tpu.parallel.dist_amg import DistAMGSolver
from amgcl_tpu.models.amg import AMGParams
from amgcl_tpu.solver.cg import CG
A, rhs = poisson3d(12)
s = DistAMGSolver(A, make_mesh(4), AMGParams(dtype=jnp.float64,
                                             coarse_enough=300),
                  CG(maxiter=100, tol=1e-8))
x, info = s(rhs)
print("ITERS", info.iters)
""", n_devices=4)
    assert iters[0] == single


@pytest.mark.serial
def test_two_process_strip_ingestion():
    """VERDICT r3 item 3: each controller holds only its row strips; the
    hierarchy is built with real cross-process exchanges (strip-parallel
    setup, parallel/dist_setup.py) and matches the single-process strip
    build's iterations."""
    outs, iters = _run_workers(r"""
from amgcl_tpu.utils.sample_problem import poisson3d
from amgcl_tpu.parallel.dist_setup import (StripAMGSolver, MultihostComm,
                                           split_strips)
from amgcl_tpu.models.amg import AMGParams
from amgcl_tpu.solver.cg import CG

mesh = multihost.global_mesh()
nd = mesh.devices.size
assert nd == 4 * nproc
A, rhs = poisson3d(12)
# strip ingestion: this process keeps ONLY its own shards' row strips
# (the full A exists here only to generate the fixture; the solver never
# sees it and non-owned slots are None)
comm = MultihostComm(mesh)
full_strips, nloc = split_strips(A, nd)
mine = set(comm.my_shards)
strips = [full_strips[s] if s in mine else None for s in range(nd)]
del full_strips
s = StripAMGSolver(strips, mesh,
                   AMGParams(dtype=jnp.float64, coarse_enough=200),
                   CG(maxiter=100, tol=1e-8), n=A.nrows,
                   replicate_below=400, comm=comm)
x, info = s(rhs)
r = np.linalg.norm(rhs - A.spmv(np.asarray(x))) / np.linalg.norm(rhs)
assert r < 1e-7, r
print("RESULT %d iters=%d resid=%.3e sizes=%s" % (pid, info.iters, r,
                                                  s.sizes), flush=True)
""", nproc=2, devices_per_proc=4)
    assert iters[0] == iters[1]

    single = _single_process_iters(r"""
from amgcl_tpu.utils.sample_problem import poisson3d
from amgcl_tpu.parallel.mesh import make_mesh
from amgcl_tpu.parallel.dist_setup import StripAMGSolver
from amgcl_tpu.models.amg import AMGParams
from amgcl_tpu.solver.cg import CG
A, rhs = poisson3d(12)
s = StripAMGSolver(A, make_mesh(8),
                   AMGParams(dtype=jnp.float64, coarse_enough=200),
                   CG(maxiter=100, tol=1e-8), replicate_below=400)
x, info = s(rhs)
print("ITERS", info.iters)
""", n_devices=8)
    assert iters[0] == single
