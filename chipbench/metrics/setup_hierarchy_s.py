"""setup_hierarchy_s. Layer: setup (``models/amg.py``, ``coarsening/``,
``ops/stencil_device.py``, ``ops/device.to_device``). Moves: setup_s.

Seconds of the run's hierarchy build: the first ``setup/hierarchy`` span
of the program's span recorder (``amgcl_tpu.telemetry.tracing
.RECORDER``, read in the process that ran the cell), which covers
``AMG._build`` on either set-up path. Nothing to read where the program
has no recorder."""


def read(rec):
    from amgcl_tpu.telemetry import tracing
    recorder = getattr(tracing, "RECORDER", None)
    tot = recorder.totals().get("setup/hierarchy") if recorder else None
    return tot["first_s"] if tot else None
