"""solve_p95_ms (end to end, host clock): the 95th percentile of the
wall time of every solve in the window, in milliseconds (linear
interpolation between order statistics)."""

import numpy as np


def read(rec):
    return float(np.percentile(np.asarray(rec["solve_s"]) * 1e3, 95))
