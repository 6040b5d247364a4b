"""kernels_roofline. Layer: kernels (``ops/pallas_spmv.py``,
``ops/pallas_vcycle.py``, ``ops/fused_vec.py``, ``ops/unstructured.py``,
``ops/densewin.py``). Moves: solve_ms.

The least time the window's solves need at the chip's published peaks
(``chipbench/work.py`` counts the least bytes and operations from the
hierarchy's nonzero values, the cycle parameters and each solve's
iterations; ``chipbench/peaks.py`` holds the peaks), as a share of the
device's busy time in the traced window, in percent. Nothing to read
without a trace with device time."""


def read(rec):
    tr, w = rec.get("trace") or {}, rec.get("work")
    if not w or not tr.get("busy_s"):
        return None
    return 100.0 * w["least_s"] / tr["busy_s"]
