"""C API: build the shared library + C test program and run it end-to-end
(reference parity: lib/amgcl.cpp + examples/call_lib). Skipped when the
toolchain or Python embedding config is unavailable."""

import os
import shutil
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _embed_flags():
    cfg = subprocess.run(
        [sys.executable + "-config" if shutil.which(sys.executable + "-config")
         else "python3-config", "--includes", "--ldflags", "--embed"],
        capture_output=True, text=True)
    if cfg.returncode != 0:
        # derive from sysconfig (python3-config may be absent)
        inc = "-I" + sysconfig.get_path("include")
        libdir = sysconfig.get_config_var("LIBDIR")
        ver = sysconfig.get_config_var("LDVERSION")
        return [inc, "-L" + libdir, "-lpython" + ver]
    return cfg.stdout.split()


@pytest.fixture(scope="module")
def c_binary(tmp_path_factory):
    if shutil.which("g++") is None or shutil.which("gcc") is None:
        pytest.skip("no C toolchain")
    tmp = tmp_path_factory.mktemp("capi")
    exe = str(tmp / "test_c_api")
    flags = _embed_flags()
    cmd = (["g++", "-O1", "-std=c++17",
            os.path.join(REPO, "csrc", "c_api.cpp"),
            os.path.join(REPO, "csrc", "test_c_api.c"),
            "-o", exe] + flags + ["-lm"])
    got = subprocess.run(cmd, capture_output=True, text=True)
    if got.returncode != 0:
        pytest.skip("cannot build C test: %s" % got.stderr[-800:])
    return exe


def test_c_api_end_to_end(c_binary):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # keep the embedded interpreter on the CPU
    env["JAX_PLATFORMS"] = "cpu"
    got = subprocess.run([c_binary], capture_output=True, text=True,
                         env=env, timeout=600)
    assert got.returncode == 0, got.stdout + got.stderr
    assert "C API smoke test OK" in got.stdout


def test_capi_python_surface():
    """The marshalling layer itself (no embedding needed): create params,
    build a solver from raw addresses, solve, destroy."""
    import ctypes
    from amgcl_tpu import capi
    from amgcl_tpu.utils.sample_problem import poisson3d

    A, rhs = poisson3d(10)
    ptr32 = A.ptr.astype(np.int32)
    col32 = A.col.astype(np.int32)
    val = A.val.astype(np.float64)
    x = np.zeros(A.nrows)

    h = capi.params_create()
    capi.params_set(h, "solver.type", "cg")
    capi.params_set(h, "solver.tol", 1e-8)
    capi.params_set(h, "precond.dtype", "float64")
    s = capi.solver_create(
        A.nrows, ptr32.ctypes.data, col32.ctypes.data, val.ctypes.data, h)
    assert capi.handle_n(s) == A.nrows
    rhs64 = np.asarray(rhs, dtype=np.float64)
    iters, resid = capi.solver_solve(
        s, rhs64.ctypes.data, x.ctypes.data, A.nrows)
    assert resid < 1e-8 and iters > 0
    r = np.linalg.norm(rhs64 - A.spmv(x)) / np.linalg.norm(rhs64)
    assert r < 1e-7
    assert "make_solver" in capi.report(s)
    capi.handle_destroy(s)
    capi.handle_destroy(h)
