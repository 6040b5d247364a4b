"""Build and run the C API smoke program — the reference's
examples/call_lib workflow (lib/amgcl.h surface) for the TPU framework.

    python examples/call_c_api.py

Compiles csrc/c_api.cpp + csrc/test_c_api.c against the embedded-Python
config, runs the resulting binary (a plain C program that assembles a 2-D
Poisson system, configures CG+AMG through dotted params, solves, and
checks the true residual in C), and prints its output.
"""
import os
import shutil
import subprocess
import sys
import sysconfig
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def embed_flags():
    # prefer the RUNNING interpreter's config (sys.executable-config, then
    # sysconfig): a bare python3-config from PATH may belong to a
    # different Python and embed the wrong libpython
    cfg = shutil.which(sys.executable + "-config")
    if cfg:
        got = subprocess.run([cfg, "--includes", "--ldflags", "--embed"],
                             capture_output=True, text=True)
        if got.returncode == 0:
            return got.stdout.split()
    return ["-I" + sysconfig.get_path("include"),
            "-L" + sysconfig.get_config_var("LIBDIR"),
            "-lpython" + sysconfig.get_config_var("LDVERSION")]


def main():
    if shutil.which("g++") is None:
        raise SystemExit("needs g++")
    with tempfile.TemporaryDirectory() as tmp:
        exe = os.path.join(tmp, "call_c_api")
        cmd = (["g++", "-O1", "-std=c++17",
                os.path.join(REPO, "csrc", "c_api.cpp"),
                os.path.join(REPO, "csrc", "test_c_api.c"),
                "-o", exe] + embed_flags() + ["-lm"])
        subprocess.run(cmd, check=True)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        got = subprocess.run([exe], env=env, text=True,
                             capture_output=True, timeout=600)
        print(got.stdout, end="")
        if got.returncode != 0:
            raise SystemExit(got.stderr or "C program failed")


if __name__ == "__main__":
    main()
