"""Telemetry — the uniform observability layer every part of the stack
reports through (the reference's profiler tree + per-level printouts +
per-iteration residual logging, amgcl/profiler.hpp / amg.hpp:560-598 /
cg.hpp:199, reworked as structured data instead of text).

Five pieces:

* :mod:`report`  — :class:`SolveReport`, the structured convergence record
  returned by every solver bundle (iters, final relative residual,
  per-iteration history, convergence rate, wall time, hierarchy stats).
* :mod:`history` — :class:`HistoryMixin`, per-iteration residual capture
  *inside* the ``lax.while_loop`` (no per-iteration host syncs), shared by
  all Krylov solvers.
* :mod:`tracing` — ``phase(name)`` named scopes so ``jax.profiler`` traces
  of the V-cycle read like the reference's profiler tree.
* :mod:`sink`    — JSONL metrics sink with a process-global default that
  bench.py, cli.py and the distributed solvers all emit through.
  Deliberately stdlib-only so the bench supervisor can load it without
  importing jax.
* :mod:`health`  — the numerics leg: in-loop guard detection (NaN,
  Krylov breakdowns, stagnation, divergence — a compact bitmask carried
  through every solver's ``lax.while_loop``, decoded into
  ``SolveReport.health``), per-level convergence probes
  (``AMG.probe_convergence()``) and the convergence doctor
  (:func:`diagnose`, ``cli.py --doctor``).

plus the efficiency leg (PR 4):

* :mod:`roofline` — measured per-stage times x the ledger's FLOP/byte
  models -> achieved GB/s / GFLOP/s vs device peaks, compute-/memory-
  bound classification, ranked bottlenecks (``AMG.roofline()``,
  ``cli.py --roofline``).
* :mod:`compile_watch` — process-global trace/compile/retrace observer
  over our jitted entry points (``SolveReport.compile``).
* :mod:`metrics` — stdlib-only percentile rollups of sink events and
  bench history, Prometheus-text export (``bench.py --trend``).
"""

from amgcl_tpu.telemetry.report import SolveReport
from amgcl_tpu.telemetry.history import HistoryMixin
from amgcl_tpu.telemetry.tracing import (phase, annotate, setup_scope,
                                         span, RequestSpans)
from amgcl_tpu.telemetry.sink import (JsonlSink, NullSink, emit,
                                      get_default_sink, set_default_sink)
from amgcl_tpu.telemetry.health import (HealthState, decode as decode_health,
                                        diagnose, format_findings,
                                        probe_hierarchy, serve_findings,
                                        two_grid_factor)
from amgcl_tpu.telemetry.ledger import (DeviceMemoryBudget,
                                        dense_window_budget,
                                        hierarchy_ledger, summarize_ledger,
                                        format_ledger, mv_cost,
                                        cycle_cost_model,
                                        krylov_iteration_model, comm_model,
                                        allreduce_model, krylov_comm_model,
                                        xla_cost_analysis)
# NOTE: the bare function names stay unshadowed — ``telemetry.roofline``
# / ``telemetry.compile_watch`` must keep naming the MODULES
from amgcl_tpu.telemetry.roofline import (device_peaks, measure_stages,
                                          format_roofline,
                                          solve_roofline, counter_map,
                                          xla_stage_check)
from amgcl_tpu.telemetry.compile_watch import (watched_jit,
                                               compile_snapshot,
                                               global_watch)
from amgcl_tpu.telemetry import metrics
# live registry + scrape endpoint (serve observability) — module-named
# like ``metrics``; the classes ride along for direct construction
from amgcl_tpu.telemetry import live
from amgcl_tpu.telemetry.live import LiveRegistry, MetricsServer
# forensics leg (PR 12): flight recorder + replay bundles, and the
# stdlib-only structured report diff (cross-run regression attribution)
from amgcl_tpu.telemetry import diff
from amgcl_tpu.telemetry import flight
# structure leg (PR 14): the operator X-ray — per-level structural
# analytics, the to_device('auto') format-decision ledger, and the
# predict-only reorder-gain advisor (host-side, never imports jax)
from amgcl_tpu.telemetry import structure
# memory observatory (PR 18): measured device-memory truth — sampling
# timeline, weakref ownership attribution, measured-vs-ledger joins,
# leak gate and OOM forensics (stdlib at module level, jax lazy)
from amgcl_tpu.telemetry import memwatch

__all__ = ["SolveReport", "HistoryMixin", "phase", "annotate",
           "setup_scope", "span", "RequestSpans", "JsonlSink", "NullSink",
           "emit",
           "get_default_sink", "set_default_sink", "DeviceMemoryBudget",
           "dense_window_budget", "hierarchy_ledger", "summarize_ledger",
           "format_ledger", "mv_cost", "cycle_cost_model",
           "krylov_iteration_model", "comm_model", "allreduce_model",
           "krylov_comm_model", "xla_cost_analysis", "HealthState",
           "decode_health", "diagnose", "format_findings",
           "probe_hierarchy", "serve_findings", "two_grid_factor",
           "device_peaks",
           "measure_stages", "format_roofline",
           "solve_roofline", "counter_map", "xla_stage_check",
           "watched_jit", "compile_snapshot", "global_watch", "metrics",
           "live", "LiveRegistry", "MetricsServer", "diff", "flight",
           "structure", "memwatch"]
