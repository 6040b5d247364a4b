"""setup_compile_s. Layer: setup (``models/amg.py``, ``coarsening/``,
``ops/stencil_device.py``, ``ops/device.to_device``). Moves: setup_s.

Seconds of XLA backend compilation during set-up, which for a program
found in the persistent compilation cache is the time to load it, from
the benchmark's own listener on JAX's compile events
(``chipbench/compiles.py``)."""


def read(rec):
    return rec["compiles"]["setup"]["compile_s"]
