"""Static analysis (ISSUE 6): the AST linter's rules on synthetic
fixtures, the jaxpr auditor's contracts over all nine Krylov solvers and
both distributed CG bodies, the negative-injection paths (an extra psum
and an f64->f32 downcast must each be caught), the compile-watch
entry-point drift check, and the repo's own clean bill against the
committed ANALYSIS_BASELINE.json."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
from jax import lax

from amgcl_tpu import analysis
from amgcl_tpu.analysis import jaxpr_audit as ja
from amgcl_tpu.analysis import lint
from amgcl_tpu.telemetry.ledger import (DIST_CG_COLLECTIVES,
                                        KRYLOV_FUSED_PASSES,
                                        KRYLOV_VEC_STREAMS_FUSED)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ===========================================================================
# linter: one fixture per rule
# ===========================================================================

def _lint_src(tmp_path, src, readme="| `AMGCL_TPU_DOCUMENTED` | x |\n"):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(src))
    rd = tmp_path / "README.md"
    rd.write_text(readme)
    return lint.run_lint(root=str(pkg), readme=str(rd))


def _rules(findings):
    return sorted({f["rule"] for f in findings})


def test_lint_bare_jit_call_and_decorator(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax

        @jax.jit
        def deco(x):
            return x

        def build(fn):
            return jax.jit(fn)
    """)
    hits = [f for f in fs if f["rule"] == "bare-jit"]
    assert {f["symbol"] for f in hits} == {"deco", "build"}


def test_lint_host_sync_and_np_in_loop_body(tmp_path):
    fs = _lint_src(tmp_path, """
        import numpy as np
        from jax import lax

        def solve(rhs):
            def body(st):
                x, it = st
                v = float(x)            # host sync on a tracer
                y = np.linalg.norm(x)   # numpy on a tracer
                z = x.item()            # host sync
                d = np.float32(0.5)     # allowlisted constant helper
                g = bool(self_like)     # not a self attr: flagged
                return (x + v + y + z + d + g, it + 1)

            def cond(st):
                return st[1] < 3

            return lax.while_loop(cond, body, (rhs, 0))
    """)
    assert _rules(fs) == ["host-sync-in-loop", "np-in-jit"]
    assert sum(f["rule"] == "host-sync-in-loop" for f in fs) == 3
    assert sum(f["rule"] == "np-in-jit" for f in fs) == 1
    assert all(f["symbol"] == "solve.body" for f in fs)


def test_lint_loop_hazard_ignores_trace_time_config(tmp_path):
    """float(self.tol) and np.dtype in a loop body are trace-time
    constants, not hazards."""
    fs = _lint_src(tmp_path, """
        import numpy as np
        from jax import lax

        class S:
            def solve(self, rhs):
                def body(st):
                    eps = float(self.tol)
                    dt = np.dtype(np.float32)
                    return st * eps

                def cond(st):
                    return True

                return lax.while_loop(cond, body, rhs)
    """)
    assert fs == []


def test_lint_mutable_default(tmp_path):
    fs = _lint_src(tmp_path, """
        def f(x, cache={}, names=[], opts=dict()):
            return x
    """)
    assert _rules(fs) == ["mutable-default"]
    assert len(fs) == 3


def test_lint_pallas_interpret_seam(tmp_path):
    fs = _lint_src(tmp_path, """
        from jax.experimental import pallas as pl

        def good(kernel, interpret):
            return pl.pallas_call(kernel, interpret=interpret)

        def bad(kernel):
            return pl.pallas_call(kernel)
    """)
    assert _rules(fs) == ["pallas-no-interpret"]
    assert [f["symbol"] for f in fs] == ["bad"]


def test_lint_undocumented_knob(tmp_path):
    fs = _lint_src(tmp_path, """
        import os
        A = os.environ.get("AMGCL_TPU_DOCUMENTED", "1")
        B = os.environ.get("AMGCL_TPU_MYSTERY_KNOB")
    """)
    assert _rules(fs) == ["undocumented-knob"]
    assert fs[0]["symbol"] == "AMGCL_TPU_MYSTERY_KNOB"


def test_lint_baseline_split():
    findings = [lint.finding("bare-jit", "a.py", 3, "f", "m"),
                lint.finding("bare-jit", "b.py", 9, "g", "m")]
    baseline = {"suppressions": [
        {"rule": "bare-jit", "file": "a.py", "symbol": "f",
         "reason": "probe"},
        {"rule": "bare-jit", "file": "gone.py", "symbol": "h",
         "reason": "stale"}]}
    split = lint.apply_baseline(findings, baseline)
    assert [f["file"] for f in split["new"]] == ["b.py"]
    assert [f["file"] for f in split["suppressed"]] == ["a.py"]
    assert [s["file"] for s in split["stale"]] == ["gone.py"]


def test_repo_lint_is_clean_against_committed_baseline():
    """The tree as committed has zero NEW findings and zero stale
    suppressions — the acceptance criterion `python -m amgcl_tpu.analysis
    runs clean against the committed baseline`, lint half. The
    baseline is SHARED with the concurrency analyzer (ISSUE 15), so
    the stale check runs over the union of both findings streams."""
    from amgcl_tpu.analysis import run_concurrency
    split = lint.apply_baseline(lint.run_lint() + run_concurrency(),
                                analysis.load_baseline())
    assert split["new"] == [], lint.format_findings(split["new"])
    assert split["stale"] == [], split["stale"]


def test_lint_blocking_call_under_lock(tmp_path):
    """Rule 9: the cheap lexical blocking-under-lock check for modules
    outside the declared concurrent set."""
    fs = _lint_src(tmp_path, """
        import queue
        import threading
        import time

        _LOCK = threading.Lock()
        work_queue = queue.Queue()

        def bad_sleep():
            with _LOCK:
                time.sleep(0.1)

        def bad_get(self):
            with self._state_lock:
                return self.queue.get()

        def good(self):
            with self._state_lock:
                v = self.queue.get_nowait()
            time.sleep(0.1)
            return v

        def good_wait(cond):
            with cond._lock:
                cond.wait(timeout=1.0)
    """)
    hits = [f for f in fs if f["rule"] == "blocking-call-under-lock"]
    assert {f["symbol"] for f in hits} == {"bad_sleep", "bad_get"}, fs


# ===========================================================================
# jaxpr auditor: solver contracts
# ===========================================================================

@pytest.mark.parametrize("name", sorted(KRYLOV_FUSED_PASSES))
def test_audit_solver_contracts(name):
    """Every Krylov solver's iteration body satisfies its declared
    fused-engagement contract with the tier on AND off."""
    for fused in (True, False):
        rec = ja.audit_solver(name, fused=fused)
        findings = ja.check_solver(rec)
        errors = [f for f in findings if f["severity"] == "error"]
        assert not errors, (rec, errors)
        if fused:
            assert rec["fused_passes"] == KRYLOV_FUSED_PASSES[name][0]
        else:
            assert rec["fused_passes"] == 0


def test_audit_cg_streams_match_fused_model():
    """The acceptance pin: fused CG's statically recounted per-iteration
    vector streams equal KRYLOV_VEC_STREAMS_FUSED['CG'] exactly."""
    rec = ja.audit_solver("CG", fused=True)
    assert rec["streams"] == KRYLOV_VEC_STREAMS_FUSED["CG"] == 11
    assert rec["fused_passes"] == 1
    assert rec["collectives"]["psum"] == 0
    assert rec["host_callbacks"] == []
    assert rec["casts"] == []


def test_audit_bicgstab_streams_match_fused_model():
    rec_on = ja.audit_solver("BiCGStab", fused=True)
    rec_off = ja.audit_solver("BiCGStab", fused=False)
    assert rec_on["streams"] == KRYLOV_VEC_STREAMS_FUSED["BiCGStab"] == 15
    # the composed body pays more vector traffic than the fused one
    assert rec_off["streams"] > rec_on["streams"]


def test_audit_detects_dead_fused_path():
    """AMGCL_TPU_FUSED_VEC on but kernels not engaged (Pallas gated off,
    no interpret seam) — exactly the silently-dead-fused-path scenario:
    the audit must fail the fusion contract."""
    with ja._env(AMGCL_TPU_FUSED_VEC="1", AMGCL_TPU_PALLAS="0",
                 AMGCL_TPU_PALLAS_INTERPRET=None):
        import jax as _jax
        Ad, rhs, dinv = ja._probe_problem()
        from amgcl_tpu.solver.cg import CG
        jx = _jax.make_jaxpr(
            lambda b: CG(maxiter=10).solve(Ad, ja._audit_precond(dinv),
                                           b))(rhs)
    body = ja.find_while_bodies(jx.jaxpr)[0]
    vs = ja.vector_streams(body, int(rhs.shape[0]))
    rec = {"entry": "solver.CG", "fused_env": True,
           "streams": vs["streams"], "fused_passes": vs["fused_passes"],
           "collectives": ja.collective_census(body),
           "casts": [], "host_callbacks": []}
    errors = [f for f in ja.check_solver(rec)
              if f["severity"] == "error"]
    assert vs["fused_passes"] == 0
    assert errors and any("not engaged" in f["message"] for f in errors)


def test_audit_detects_injected_downcast():
    """Negative injection: a preconditioner that round-trips the
    residual through f64 plants a vector f64->f32 downcast in the
    iteration body; the dtype pass must catch it."""
    _, _, dinv = ja._probe_problem()

    def audit_precond(r):
        return (dinv * r.astype(jnp.float64)).astype(jnp.float32)

    rec = ja.audit_solver("CG", fused=True,
                          precond=jax.jit(audit_precond))
    kinds = {c["kind"] for c in rec["casts"]}
    assert "downcast" in kinds, rec["casts"]
    errors = [f for f in ja.check_solver(rec)
              if f["severity"] == "error" and f["pass"] == "dtype"]
    assert errors, rec["casts"]


def test_audit_detects_host_callback_in_loop():
    """CG(verbose=True) debug-prints inside the loop — the host-sync
    pass must flag it (and quiet CG stays clean, asserted above)."""
    from amgcl_tpu.solver.cg import CG
    rec = ja.audit_solver("CG", fused=True,
                          solver=CG(maxiter=10, verbose=True))
    assert rec["host_callbacks"], "debug callback not detected"
    errors = [f for f in ja.check_solver(rec)
              if f["severity"] == "error" and f["pass"] == "host-sync"]
    assert errors


# ===========================================================================
# jaxpr auditor: distributed collective census
# ===========================================================================

def test_audit_dist_cg_collective_census():
    """Classical dist CG: exactly 3 scalar psums + one fwd/bwd halo
    ppermute pair per iteration, as DIST_CG_COLLECTIVES declares."""
    rec = ja.audit_dist_cg(pipelined=False)
    assert "skipped" not in rec, rec
    assert rec["collectives"]["psum"] == 3
    assert max(rec["collectives"]["psum_elems"]) == 1
    assert rec["collectives"]["ppermute"] == 2
    assert [f for f in ja.check_dist(rec)
            if f["severity"] == "error"] == []


def test_audit_dist_cg_pipelined_single_stacked_psum():
    """The acceptance pin: dist_cg_pipelined issues exactly ONE psum per
    iteration and it carries the stacked 3-vector."""
    rec = ja.audit_dist_cg(pipelined=True)
    assert "skipped" not in rec, rec
    assert rec["collectives"]["psum"] == 1
    assert rec["collectives"]["psum_elems"] == [3]
    assert [f for f in ja.check_dist(rec)
            if f["severity"] == "error"] == []


def test_audit_detects_extra_psum():
    """Negative injection: a pipelined-CG-shaped body with a second
    psum (the regression the contract exists for) must fail the
    census."""
    from jax import shard_map
    from amgcl_tpu.parallel.mesh import make_mesh, ROWS_AXIS
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(len(jax.devices()))
    nd = int(mesh.shape[ROWS_AXIS])
    n = 64 * nd

    def body_shard(f):
        def cond(st):
            return st[1] < 10

        def body(st):
            x, it = st
            g = lax.psum(jnp.stack([jnp.vdot(x, x), jnp.vdot(x, f),
                                    jnp.vdot(f, f)]), ROWS_AXIS)
            extra = lax.psum(jnp.vdot(x, x), ROWS_AXIS)   # the bug
            return (x * (g[0] + extra), it + 1)

        return lax.while_loop(cond, body, (f, 0))[0]

    fn = shard_map(body_shard, mesh=mesh, in_specs=(P(ROWS_AXIS),),
                   out_specs=P(ROWS_AXIS), check_vma=False)
    jx = jax.make_jaxpr(fn)(jnp.ones(n))
    census = ja.collective_census(ja.find_while_bodies(jx.jaxpr)[0])
    assert census["psum"] == 2
    rec = {"entry": "parallel.dist_cg_pipelined", "devices": nd,
           "halo_width": 0, "collectives": census, "host_callbacks": []}
    errors = [f for f in ja.check_dist(rec) if f["severity"] == "error"]
    assert errors and any("psum" in f["message"] for f in errors)


# ===========================================================================
# make_solver program audit + entry-point drift
# ===========================================================================

def test_audit_make_solver_uniform_and_mixed():
    uni = ja.audit_make_solver(mixed=False)
    assert uni["downcasts"] == 0 and uni["upcasts"] == 0
    assert uni["host_callbacks"] == []
    mixed = ja.audit_make_solver(mixed=True)
    assert "skipped" not in mixed, mixed
    # the declared mixing seam: exactly one down + one up per apply
    assert mixed["downcasts"] == 1 and mixed["upcasts"] == 1
    for rec in (uni, mixed):
        errors = [f for f in ja.check_make_solver(rec)
                  if f["severity"] == "error"]
        assert errors == [], errors
    # donation groundwork (ROADMAP 1): contract says none today, and
    # the audit keeps the reminder finding alive
    assert uni["donation"]["donated_args"] == 0
    infos = [f for f in ja.check_make_solver(uni)
             if f["pass"] == "donation"]
    assert infos and infos[0]["severity"] == "info"


def test_watched_entry_points_match_declared():
    """ISSUE 6 small fix: compile_watch.DECLARED_ENTRY_POINTS is exactly
    the set of watched_jit(name=...) registrations in the source — the
    PR-4 docstring list can no longer drift from reality."""
    assert ja.check_entry_points() == []
    found = lint.watched_entry_points()
    assert "<dynamic>" not in found, (
        "watched_jit call sites must pass a static name= so the "
        "entry-point contract stays auditable: %r" % found["<dynamic>"])


def test_dist_comm_model_priced_from_contract():
    """dist_solver prices its SolveReport comm model from
    DIST_CG_COLLECTIVES — one declaration for model and audit."""
    assert DIST_CG_COLLECTIVES["dist_cg_pipelined"]["psums"] == 1
    assert DIST_CG_COLLECTIVES["dist_cg_pipelined"]["elems_per_psum"] == 3
    assert DIST_CG_COLLECTIVES["dist_cg"]["psums"] == 3
    import inspect
    from amgcl_tpu.parallel import dist_solver
    src = inspect.getsource(dist_solver.dist_cg)
    assert "DIST_CG_COLLECTIVES" in src


# ===========================================================================
# the gate itself
# ===========================================================================

def test_run_all_lint_only_ok():
    rec = analysis.run_all(with_audit=False)
    assert rec["ok"], rec["lint"]["new"]


def test_full_audit_ok():
    """run_audit end to end on the 8-virtual-device mesh: zero errors
    (infos — the donation reminder — are allowed)."""
    res = ja.run_audit()
    assert res["ok"], ja.format_report(res)
    assert res["errors"] == 0
    entries = {r["entry"] for r in res["records"]}
    assert "parallel.dist_cg_pipelined" in entries
    assert "make_solver._solve_fn" in entries


def test_analysis_cli_lint_only(tmp_path):
    """`python -m amgcl_tpu.analysis --no-audit` exits 0 against the
    committed baseline and FAILs (exit 1) against an empty one."""
    r = subprocess.run(
        [sys.executable, "-m", "amgcl_tpu.analysis", "--no-audit"],
        capture_output=True, text=True, timeout=300, cwd=_REPO,
        env=dict(os.environ))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ANALYSIS OK" in r.stdout
    empty = tmp_path / "empty_baseline.json"
    empty.write_text(json.dumps({"version": 1, "suppressions": []}))
    r2 = subprocess.run(
        [sys.executable, "-m", "amgcl_tpu.analysis", "--no-audit",
         "--json", "--baseline", str(empty)],
        capture_output=True, text=True, timeout=300, cwd=_REPO,
        env=dict(os.environ))
    assert r2.returncode == 1
    rec = json.loads(r2.stdout.strip().splitlines()[-1])
    assert not rec["ok"] and len(rec["lint"]["new"]) > 0
