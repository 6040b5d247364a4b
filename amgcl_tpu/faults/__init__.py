"""Fault-tolerance layer: deterministic fault injection + recovery.

Two halves (ISSUE 13):

* :mod:`amgcl_tpu.faults.inject` — a seeded, plan-driven fault injector
  (``AMGCL_TPU_FAULT_PLAN`` JSON) with hook points at the seams that
  already exist: numeric faults at the HistoryMixin guard seam,
  allocation faults at the ledger charge seam, device faults at the
  solve/serve dispatch seams, serve faults (worker death, queue
  saturation, timeout storms, poison requests) in the service worker.
* :mod:`amgcl_tpu.faults.recovery` — the bounded recovery policy ladder
  consumed by ``models/make_solver.py`` (re-run from last-good iterate →
  f64 precision escalation → solver switch cg→bicgstab→gmres → smoother
  fallback, with host-side Krylov-iterate checkpoints behind
  ``AMGCL_TPU_CKPT_EVERY``), plus the serve-level retry/bisection and
  the farm admission/shedding policies implemented in
  ``serve/service.py`` / ``serve/farm.py``.

``python -m amgcl_tpu.faults --selftest`` runs the chaos matrix
(:mod:`amgcl_tpu.faults.chaos`): every injected scenario must either
*recover* (converged, parity with the un-faulted solve) or *fail
cleanly* (typed error + flight bundle) under a global deadline.

The typed error hierarchy below is the "fails cleanly" contract: every
fault path that gives up raises one of these (all ``RuntimeError``
subclasses, so existing broad handlers keep working).
"""

from __future__ import annotations


class FaultError(RuntimeError):
    """Base of the typed fault/recovery error hierarchy."""


class DeviceLostError(FaultError):
    """The device executing a solve was lost or preempted (real or
    injected via the ``device.loss`` site). Recoverable: the ladder
    resumes from the last host-side checkpoint, the serve layer
    retries with backoff."""


class WorkerDiedError(FaultError):
    """A serve/farm dispatch thread died on an unexpected exception.
    Every pending and queued future is failed with this (never
    stranded); the supervisor restarts the worker."""


class PoisonRequestError(FaultError):
    """A request isolated by batch bisection as the one that keeps
    failing its batch (``serve.poison`` injection, or any
    deterministically-fatal rhs)."""


class LoadShedError(FaultError):
    """Typed reject: the tenant is shedding load under a sustained SLO
    breach (``AMGCL_TPU_SHED_BREACHES``). Retry later or against
    another replica."""


class AllocationError(FaultError):
    """Device memory allocation failed — a real backend
    ``RESOURCE_EXHAUSTED`` caught at a solve/serve/farm seam (see
    :func:`is_resource_exhausted`), or an injected ``alloc.*`` refusal.
    Admission-class, NOT a worker death: the farm's recovery response
    is evict-and-retry, and every raise site first trips the memwatch
    OOM forensics (flight bundle with the memory timeline and
    top-owner table). The message carries the pool/budget state known
    at the seam."""


class AdmissionError(AllocationError):
    """HBM admission failed after eviction attempts and backoff — the
    farm budget cannot fit the operator. The message names
    AMGCL_TPU_FARM_MAX_BYTES (the existing test contract). A subclass
    of :class:`AllocationError`: the ``alloc.farm`` injection and the
    modeled budget path share the typed hierarchy with real OOMs."""


class RecoveryExhausted(FaultError):
    """The recovery ladder ran out of rungs without a healthy solve.
    Carries the attempt trail (``.attempts``) and the last report
    (``.report``)."""

    def __init__(self, message, attempts=None, report=None):
        super().__init__(message)
        self.attempts = attempts or []
        self.report = report


def is_resource_exhausted(exc) -> bool:
    """Conservatively classify a backend exception as a device
    allocation failure: XLA surfaces OOM as ``XlaRuntimeError`` (or a
    jaxlib status error) whose message leads with RESOURCE_EXHAUSTED /
    an out-of-memory phrase. String-match on purpose — the exception
    TYPES are private to jaxlib and have moved across releases, the
    status words are the stable API. Never raises."""
    if exc is None or isinstance(exc, FaultError):
        return False
    try:
        msg = str(exc)
    except Exception:
        return False
    name = type(exc).__name__
    if "RESOURCE_EXHAUSTED" in msg or "RESOURCE_EXHAUSTED" in name:
        return True
    low = msg.lower()
    return ("xlaruntimeerror" in name.lower()
            or "status" in name.lower()) and (
        "out of memory" in low or "oom" in low
        or "failed to allocate" in low)


__all__ = [
    "FaultError", "DeviceLostError", "WorkerDiedError",
    "PoisonRequestError", "LoadShedError", "AllocationError",
    "AdmissionError", "RecoveryExhausted", "is_resource_exhausted",
]
