"""Chip benchmark of amgcl_tpu: the harness behind ``chipbench/run.py``."""
