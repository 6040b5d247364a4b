"""Chaos matrix — every injected fault scenario must recover or fail
cleanly, under a deadline.

``python -m amgcl_tpu.faults --selftest`` (and ``bench.py --check``
behind ``AMGCL_TPU_GATE_RECOVERY``) runs the scenarios below
sequentially, each inside a watchdog thread with its own deadline and a
global budget (``AMGCL_TPU_CHAOS_TIMEOUT``, default 900 s). A scenario
passes when its injected fault either

* **recovers** — the solve converges and matches the un-faulted
  baseline within tolerance (solution parity), or the serving surface
  absorbs the fault (futures resolve, worker restarts, retries land); or
* **fails cleanly** — the typed error hierarchy (``amgcl_tpu.faults``)
  reaches the caller and a flight bundle is written when a dump dir is
  configured.

A hang (scenario thread still alive at its deadline) fails the matrix
outright — that is the one outcome the recovery layer exists to make
impossible. Scenario order and every injected trigger are
deterministic for a fixed plan/seed (inject.py's seeded PRNG), so the
recorded ladder trails are reproducible run to run.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time
import traceback
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from amgcl_tpu.faults import (AdmissionError, DeviceLostError,
                              LoadShedError, PoisonRequestError,
                              RecoveryExhausted, WorkerDiedError)
from amgcl_tpu.faults import inject, recovery

#: per-scenario deadline ceiling (seconds); the global budget
#: (AMGCL_TPU_CHAOS_TIMEOUT) is divided over what remains
SCENARIO_DEADLINE_S = 240.0

#: parity tolerance on the recovered solution vs the un-faulted
#: baseline (relative 2-norm; both solves converge to the same
#: residual target, so this bounds the *path* difference only)
PARITY_RTOL = 1e-3

_N = 8          # poisson3d edge — small enough for CPU CI


@contextmanager
def _env(**kw):
    """Scenario-scoped env: set (or remove, value None) the given
    knobs, reset the injector so the new plan re-parses with fresh
    counters, restore on exit."""
    saved = {k: os.environ.get(k) for k in kw}
    for k, v in kw.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    inject._reset_for_tests()
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        inject._reset_for_tests()


def _plan(*rules) -> str:
    return json.dumps(list(rules) if len(rules) != 1 else rules[0])


def _problem():
    from amgcl_tpu.utils.sample_problem import poisson3d
    A, rhs = poisson3d(_N)
    return A, rhs.astype(np.float32)


def _bundle(A, recovery_on=True, maxiter=100, tol=1e-6):
    import jax.numpy as jnp
    from amgcl_tpu.models.amg import AMGParams
    from amgcl_tpu.models.make_solver import make_solver
    from amgcl_tpu.solver.cg import CG
    return make_solver(A, AMGParams(dtype=jnp.float32,
                                    coarse_enough=200),
                       CG(maxiter=maxiter, tol=tol),
                       recovery=recovery_on)


_baseline_cache: Dict[str, Any] = {}


def _baseline() -> Tuple[Any, np.ndarray, np.ndarray, float]:
    """(A, rhs, x_ref, resid_ref) of the un-faulted solve — computed
    once, the parity anchor for every recovering scenario."""
    if not _baseline_cache:
        with _env(AMGCL_TPU_FAULT_PLAN=None):
            A, rhs = _problem()
            x, rep = _bundle(A, recovery_on=False)(rhs)
            _baseline_cache.update(A=A, rhs=rhs,
                                   x=np.asarray(x, np.float64),
                                   resid=float(rep.resid))
    c = _baseline_cache
    return c["A"], c["rhs"], c["x"], c["resid"]


def _assert_parity(x, detail: Dict[str, Any]) -> None:
    _, _, x_ref, _ = _baseline()
    num = float(np.linalg.norm(np.asarray(x, np.float64) - x_ref))
    den = float(np.linalg.norm(x_ref)) or 1.0
    detail["parity_rel"] = round(num / den, 8)
    assert num / den <= PARITY_RTOL, \
        "solution parity %.2e > %.0e" % (num / den, PARITY_RTOL)


# ---------------------------------------------------------------------------
# scenarios — each returns (outcome, detail) or raises AssertionError
# ---------------------------------------------------------------------------

def _numeric(site: str, expect_flag: str):
    A, rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=_plan(
            {"site": site, "at": 2, "count": 1})):
        b = _bundle(A)
        x, rep = b(rhs)
        rec = rep.recovery or {}
        assert rec.get("recovered"), rec
        first = (rec.get("attempts") or [{}])[0]
        assert any(expect_flag in f for f in first.get("flags", [])), \
            first
        assert float(rep.resid) <= 1e-6, rep.resid
        detail = {"ladder": [a["rung"] for a in rec["attempts"]],
                  "faults": inject.injected_total()}
        _assert_parity(x, detail)
        assert detail["faults"] >= 1
    return "recovered", detail


def s_numeric_nan():
    return _numeric("numeric.nan", "nan")


def s_numeric_inf():
    return _numeric("numeric.inf", "nan")     # Inf trips the NAN guard


def s_numeric_breakdown():
    return _numeric("numeric.breakdown", "breakdown")


def s_numeric_exhausted(workdir: str):
    """An unlimited numeric fault defeats every rung — the ladder must
    exhaust with the typed error + attempt trail + a flight bundle."""
    A, rhs, _x, _r = _baseline()
    fdir = os.path.join(workdir, "exhausted")
    with _env(AMGCL_TPU_FAULT_PLAN=_plan(
            {"site": "numeric.nan", "at": 1, "count": -1}),
            AMGCL_TPU_FLIGHT_DIR=fdir,
            AMGCL_TPU_FLIGHT_MAX_DUMPS="0"):
        b = _bundle(A)
        try:
            b(rhs)
        except RecoveryExhausted as e:
            assert len(e.attempts) >= 2, e.attempts
            bundles = [d for d in os.listdir(fdir)
                       if "recovery_exhausted" in d] \
                if os.path.isdir(fdir) else []
            assert bundles, "no recovery_exhausted flight bundle"
            return "clean_fail", {
                "ladder": [a["rung"] for a in e.attempts],
                "bundle": bundles[0]}
        raise AssertionError("expected RecoveryExhausted")


def s_device_loss_checkpoint():
    """Device loss mid-solve with checkpoints on: the solve resumes
    from the newest host snapshot and still converges to parity."""
    A, rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=_plan(
            {"site": "device.loss", "count": 1, "after": 1,
             "target": "solve"}),
            AMGCL_TPU_CKPT_EVERY="4"):
        b = _bundle(A)
        x, rep = b(rhs)
        ck = (rep.extra or {}).get("checkpoints") or {}
        assert ck.get("resumes", 0) >= 1, ck
        assert float(rep.resid) <= 1e-6, rep.resid
        detail = {"checkpoints": ck,
                  "faults": inject.injected_total()}
        _assert_parity(x, detail)
    return "recovered", detail


def s_farm_admission_retry():
    """Injected HBM admission failure at farm register: the admission
    loop evicts/backs off and retries — registration succeeds."""
    from amgcl_tpu.serve.farm import SolverFarm
    A, rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=None, AMGCL_TPU_RETRY_MAX="2"):
        farm = SolverFarm(max_bytes=0, metrics_port=-1)
        try:
            farm.register("anchor", A)
            with _env(AMGCL_TPU_FAULT_PLAN=_plan(
                    {"site": "alloc.farm", "count": 1})):
                out = farm.register("tenant-b", _shifted(A))
                assert out["outcome"] in ("miss", "rebuild"), out
            x, rep = farm.solve("tenant-b", rhs, timeout_s=60)
            assert float(rep.resid) <= 1e-6
            detail = {"outcome": out["outcome"],
                      "pool_used": farm.pool.used}
        finally:
            farm.close()
    return "recovered", detail


def s_farm_admission_exhausted():
    """Admission failing persistently with nothing evictable must end
    in the typed AdmissionError after the backoff retries — never a
    hang, never a silent partial registration."""
    from amgcl_tpu.serve.farm import SolverFarm
    A, _rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=_plan(
            {"site": "alloc.farm", "count": -1}),
            AMGCL_TPU_RETRY_MAX="1",
            AMGCL_TPU_RETRY_BACKOFF_MS="10"):
        farm = SolverFarm(max_bytes=0, metrics_port=-1)
        try:
            try:
                farm.register("t0", A)
            except AdmissionError as e:
                assert "FARM_MAX_BYTES" in str(e)
                return "clean_fail", {"error": type(e).__name__}
            raise AssertionError("expected AdmissionError")
        finally:
            farm.close()


def s_serve_worker_death():
    """Worker-thread death: every in-flight and queued future FAILS
    (typed — never strands), the supervisor restarts the worker, and
    the next submit succeeds."""
    from amgcl_tpu.serve.service import SolverService
    A, rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=_plan(
            {"site": "serve.worker", "count": 1, "target": "serve"})):
        svc = SolverService(_bundle(A, recovery_on=False), batch=2,
                            flush_ms=20, metrics_port=-1)
        try:
            futs = [svc.submit(rhs) for _ in range(3)]
            failed = 0
            for f in futs:
                try:
                    f.result(timeout=90)
                except WorkerDiedError:
                    failed += 1
            assert failed >= 1, "injected worker death never surfaced"
            x, rep = svc.submit(rhs).result(timeout=90)
            assert float(rep.resid) <= 1e-6
            st = svc.stats().get("recovery") or {}
            assert st.get("worker_deaths", 0) == 1, st
            detail = {"failed_futures": failed, "stats": st}
        finally:
            svc.close()
    return "recovered", detail


def s_serve_timeout_storm():
    """An injected timeout storm: the affected requests fail with the
    stdlib TimeoutError (typed), the rest of the traffic is served."""
    from amgcl_tpu.serve.service import SolverService
    A, rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=_plan(
            {"site": "serve.timeout", "count": 2})):
        svc = SolverService(_bundle(A, recovery_on=False), batch=4,
                            flush_ms=20, metrics_port=-1)
        try:
            futs = [svc.submit(rhs) for _ in range(4)]
            timed_out = served = 0
            for f in futs:
                try:
                    f.result(timeout=90)
                    served += 1
                except TimeoutError:
                    timed_out += 1
            assert timed_out == 2, (timed_out, served)
            assert served == 2
        finally:
            svc.close()
    return "clean_fail", {"timed_out": timed_out, "served": served}


def s_serve_poison_bisect():
    """A poison request that fails every batch containing it: bisection
    isolates it (typed failure), its batch-mates all succeed."""
    from amgcl_tpu.serve.service import SolverService
    A, rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=_plan(
            {"site": "serve.poison", "rid": 2, "count": -1}),
            AMGCL_TPU_RETRY_MAX="1",
            AMGCL_TPU_RETRY_BACKOFF_MS="10"):
        svc = SolverService(_bundle(A, recovery_on=False), batch=4,
                            flush_ms=60, metrics_port=-1)
        try:
            futs = [svc.submit(rhs) for _ in range(4)]
            outcomes = []
            for i, f in enumerate(futs, 1):
                try:
                    _x2, rep = f.result(timeout=120)
                    assert float(rep.resid) <= 1e-6
                    outcomes.append("ok")
                except PoisonRequestError:
                    outcomes.append("poison")
            assert outcomes.count("poison") == 1 \
                and outcomes[1] == "poison", outcomes
            assert outcomes.count("ok") == 3, outcomes
        finally:
            svc.close()
    return "recovered", {"outcomes": outcomes}


def s_serve_device_loss_retry():
    """A one-off device loss at the serve dispatch seam: the request is
    retried with backoff and lands on the second attempt."""
    from amgcl_tpu.serve.service import SolverService
    A, rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=_plan(
            {"site": "device.loss", "count": 1, "target": "serve"}),
            AMGCL_TPU_RETRY_MAX="2",
            AMGCL_TPU_RETRY_BACKOFF_MS="10"):
        svc = SolverService(_bundle(A, recovery_on=False), batch=2,
                            flush_ms=20, metrics_port=-1)
        try:
            x, rep = svc.submit(rhs).result(timeout=120)
            assert float(rep.resid) <= 1e-6
            st = svc.stats().get("recovery") or {}
            assert st.get("retries", 0) >= 1, st
            detail = {"stats": st}
            _assert_parity(x, detail)
        finally:
            svc.close()
    return "recovered", detail


def s_farm_load_shed():
    """Sustained SLO breach: the tenant sheds load with the typed
    reject instead of queueing requests it cannot serve in time."""
    from amgcl_tpu.serve.farm import SolverFarm
    A, rhs, _x, _r = _baseline()
    with _env(AMGCL_TPU_FAULT_PLAN=None, AMGCL_TPU_SHED_BREACHES="1"):
        farm = SolverFarm(max_bytes=0, metrics_port=-1)
        try:
            farm.register("hot", A, slo={"p99_ms": 1e-3},
                          slo_window=4)
            farm.solve("hot", rhs, timeout_s=60)   # trips p99
            deadline = time.monotonic() + 60
            shed = False
            while time.monotonic() < deadline:
                try:
                    farm.solve("hot", rhs, timeout_s=60)
                except LoadShedError:
                    shed = True
                    break
            assert shed, "tenant never shed load under a breached SLO"
        finally:
            farm.close()
    return "clean_fail", {"shed": True}


def _shifted(A):
    """Same sparsity, different values — a distinct farm operator."""
    from amgcl_tpu.ops.csr import CSR
    return CSR(A.ptr, A.col, np.asarray(A.val) * 1.5, A.ncols)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

SCENARIOS: List[Tuple[str, Callable]] = [
    ("numeric_nan", s_numeric_nan),
    ("numeric_inf", s_numeric_inf),
    ("numeric_breakdown", s_numeric_breakdown),
    ("numeric_exhausted", s_numeric_exhausted),
    ("device_loss_checkpoint", s_device_loss_checkpoint),
    ("farm_admission_retry", s_farm_admission_retry),
    ("farm_admission_exhausted", s_farm_admission_exhausted),
    ("serve_worker_death", s_serve_worker_death),
    ("serve_timeout_storm", s_serve_timeout_storm),
    ("serve_poison_bisect", s_serve_poison_bisect),
    ("serve_device_loss_retry", s_serve_device_loss_retry),
    ("farm_load_shed", s_farm_load_shed),
]


def run_chaos(names: Optional[List[str]] = None,
              workdir: Optional[str] = None,
              budget_s: Optional[float] = None) -> Dict[str, Any]:
    """Run the chaos matrix; returns the machine-readable verdict the
    ``--check`` gate consumes: {ok, scenarios: [...], recovered,
    clean_fail, failures, hangs, faults_injected}."""
    try:
        budget = budget_s if budget_s is not None else float(
            os.environ.get("AMGCL_TPU_CHAOS_TIMEOUT", "900"))
    except ValueError:
        budget = 900.0
    workdir = workdir or tempfile.mkdtemp(prefix="amgcl-chaos-")
    rows: List[Dict[str, Any]] = []
    t_start = time.monotonic()
    picked = [(n, fn) for n, fn in SCENARIOS
              if names is None or n in names]
    for name, fn in picked:
        left = budget - (time.monotonic() - t_start)
        if left <= 5:
            rows.append({"name": name, "ok": False,
                         "outcome": "not_run",
                         "error": "global chaos deadline exhausted"})
            continue
        deadline = min(left, SCENARIO_DEADLINE_S)
        box: Dict[str, Any] = {}

        def work(fn=fn, box=box):
            try:
                kw = {"workdir": workdir} \
                    if "workdir" in fn.__code__.co_varnames[
                        :fn.__code__.co_argcount] else {}
                box["result"] = fn(**kw)
            except BaseException as e:      # noqa: BLE001 — verdict row
                box["error"] = e
                box["tb"] = traceback.format_exc()

        t0 = time.monotonic()
        th = threading.Thread(target=work, daemon=True,
                              name="chaos-" + name)
        th.start()
        th.join(deadline)
        row: Dict[str, Any] = {"name": name,
                               "wall_s": round(time.monotonic() - t0, 2)}
        if th.is_alive():
            # THE failure mode this harness exists to catch: the
            # scenario neither recovered nor failed cleanly — it hung
            row.update(ok=False, outcome="hang",
                       error="scenario exceeded its %.0fs deadline"
                       % deadline)
            rows.append(row)
            # the hung daemon thread holds unknown state (env, locks) —
            # stop the matrix rather than trust later scenarios
            rows.extend({"name": n2, "ok": False, "outcome": "not_run",
                         "error": "aborted after a hang"}
                        for n2, _ in picked[len(rows):])
            break
        if "error" in box:
            row.update(ok=False, outcome="error",
                       error=repr(box["error"])[:300],
                       traceback=box.get("tb", "")[-2000:])
        else:
            outcome, detail = box["result"]
            row.update(ok=True, outcome=outcome)
            if detail:
                row["detail"] = detail
        rows.append(row)
    out = {
        "ok": bool(rows) and all(r["ok"] for r in rows),
        "scenarios": rows,
        "total": len(rows),
        "recovered": sum(1 for r in rows
                         if r.get("outcome") == "recovered"),
        "clean_fail": sum(1 for r in rows
                          if r.get("outcome") == "clean_fail"),
        "hangs": sum(1 for r in rows if r.get("outcome") == "hang"),
        "failures": [r["name"] for r in rows if not r["ok"]],
        "wall_s": round(time.monotonic() - t_start, 2),
        "workdir": workdir,
    }
    try:
        from amgcl_tpu.analysis import lockwitness as _lockwitness
        if _lockwitness.enabled():
            # runtime validation of the static concurrency analyzer:
            # every lock-order edge the scenarios actually took must
            # be in the static graph (witnessed ⊆ static), and the
            # starvation watchdog must not have tripped — a witness
            # failure fails the matrix like a hang would
            out["lock_witness"] = _lockwitness.validate(emit=True)
            out["ok"] = out["ok"] and out["lock_witness"]["ok"]
    except Exception as e:               # noqa: BLE001 — verdict row
        out["lock_witness"] = {"ok": False, "error": repr(e)[:200]}
        out["ok"] = False
    return out


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m amgcl_tpu.faults --selftest [names...]`` — one JSON
    line on stdout, exit 0 when the matrix is green (the flight.py
    ``--selftest`` convention the --check subprocess expects)."""
    args = list(argv if argv is not None else sys.argv[1:])
    names = None
    if "--selftest" in args:
        args.remove("--selftest")
    rest = [a for a in args if not a.startswith("-")]
    if rest:
        names = rest
    result = run_chaos(names=names)
    from amgcl_tpu.telemetry import sink as _sink
    print(json.dumps(_sink._clean(result), default=_sink._jsonable))
    return 0 if result.get("ok") else 1
