"""Test configuration: run on a virtual 8-device CPU mesh with x64 enabled.

Mirrors the survey's test-strategy note (SURVEY.md §4): distributed behavior
is validated on `xla_force_host_platform_device_count=8` virtual devices so
multi-chip code paths are exercised in CI without TPU pod hardware. Real-TPU
benchmarking lives in bench.py, not in the test suite.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_enable_x64", True)
