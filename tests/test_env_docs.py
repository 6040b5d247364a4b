"""Env-knob documentation lint, asserted through the ONE implementation
(ISSUE 6 satellite): ``analysis.lint``'s ``undocumented-knob`` rule owns
the scan — every ``AMGCL_TPU_*`` variable referenced under ``amgcl_tpu/``
must have a row in README's environment-variable table. A knob nobody
can discover is a knob that does not exist."""

from amgcl_tpu.analysis import lint


def test_every_env_var_documented():
    refs = lint.referenced_env_vars()
    assert refs, "lint is broken: no AMGCL_TPU_* references found"
    missing = lint.undocumented_knobs()
    assert not missing, (
        "env vars referenced under amgcl_tpu/ but missing from README's "
        "environment-variable table: %s" % ", ".join(missing))


def test_rule_rides_run_lint():
    """The same check fires as an `undocumented-knob` finding through
    run_lint, so `python -m amgcl_tpu.analysis` and this test can never
    disagree about what counts as documented."""
    findings = lint.run_lint(rules=["undocumented-knob"])
    assert [f["symbol"] for f in findings] == lint.undocumented_knobs()


def test_table_covers_new_knobs():
    """Knobs recent PRs added are in the table (guards against the table
    regressing while the lint above is green only by accident)."""
    documented = lint.documented_env_vars()
    for var in ("AMGCL_TPU_TELEMETRY_MAX_BYTES", "AMGCL_TPU_PEAK_GBPS",
                "AMGCL_TPU_PEAK_FLOPS", "AMGCL_TPU_COMPILE_WATCH",
                "AMGCL_TPU_ROOFLINE_REPS", "AMGCL_TPU_FUSED_VEC",
                "AMGCL_TPU_PIPELINED_CG", "AMGCL_TPU_ANALYSIS_IN_CHECK",
                "AMGCL_TPU_ANALYSIS_TIMEOUT",
                "AMGCL_TPU_SERVE_METRICS_PORT", "AMGCL_TPU_SLO_P99_MS",
                "AMGCL_TPU_SLO_TIMEOUT_RATE",
                "AMGCL_TPU_SLO_UNHEALTHY_RATE", "AMGCL_TPU_SLO_WINDOW",
                "AMGCL_TPU_COMM_REPS", "AMGCL_TPU_PEAK_ICI_GBPS",
                "AMGCL_TPU_SCALING_N", "AMGCL_TPU_SCALING_DEVICES",
                "AMGCL_TPU_SCALING_SOLVERS",
                "AMGCL_TPU_GATE_MULTICHIP",
                "AMGCL_TPU_GATE_COMM_FRAC",
                "AMGCL_TPU_FARM_MAX_BYTES", "AMGCL_TPU_FARM_QUEUE_MAX",
                "AMGCL_TPU_FARM_METRICS_PORT", "AMGCL_TPU_GATE_FARM",
                "AMGCL_TPU_FLIGHT", "AMGCL_TPU_FLIGHT_DIR",
                "AMGCL_TPU_FLIGHT_MAX_DUMPS", "AMGCL_TPU_XRAY",
                "AMGCL_TPU_XRAY_VARIANTS",
                "AMGCL_TPU_XRAY_MAX_ADVISE_NNZ",
                "AMGCL_TPU_STORM_SEED", "AMGCL_TPU_STORM_N",
                "AMGCL_TPU_STORM_DURATION_S", "AMGCL_TPU_STORM_DRAIN_S",
                "AMGCL_TPU_STORM_SLO_MS", "AMGCL_TPU_STORM_RATES",
                "AMGCL_TPU_STORM_FAULT_PLAN", "AMGCL_TPU_STORM_TRACE",
                "AMGCL_TPU_STORM_IN_CHECK", "AMGCL_TPU_STORM_TIMEOUT",
                "AMGCL_TPU_GATE_STORM", "AMGCL_TPU_GATE_STORM_P99",
                "AMGCL_TPU_GATE_STORM_CANDIDATE",
                "AMGCL_TPU_MEMWATCH", "AMGCL_TPU_MEMWATCH_INTERVAL_MS",
                "AMGCL_TPU_MEMWATCH_TIMELINE", "AMGCL_TPU_MEMWATCH_TOL",
                "AMGCL_TPU_MEMWATCH_CENSUS_MS",
                "AMGCL_TPU_MEMWATCH_IN_CHECK",
                "AMGCL_TPU_MEMWATCH_LEAK_BYTES",
                "AMGCL_TPU_MEMWATCH_TIMEOUT",
                "AMGCL_TPU_GATE_MEMDRIFT", "AMGCL_TPU_FARM_HEADROOM",
                "AMGCL_TPU_REORDER", "AMGCL_TPU_GATE_XRAY"):
        assert var in documented, var
