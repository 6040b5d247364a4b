"""The comparison that decides ``correct``.

The plain reference is the configuration's operator from the frozen
generator, in float64 on the host: for each sampled solve of the window
it evaluates the true relative residual ``||b - A x|| / ||b||`` of the
solution the timed call returned, against the right-hand side it was
given. The number compared is the worst of them; its limit is the
tolerance the configuration states.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np


def true_residual(A, b, x) -> float:
    """``||b - A x|| / ||b||`` in float64 (NaN for a non-finite x)."""
    b = np.asarray(b, np.float64)
    x = np.asarray(x, np.float64)
    if x.shape != b.shape or not np.all(np.isfinite(x)):
        return float("nan")
    return float(np.linalg.norm(b - A @ x) / np.linalg.norm(b))


def compare(A, pairs: Iterable[Tuple[object, object]], limit: float
            ) -> Dict[str, Dict[str, float]]:
    """``{"worst_true_resid": {"value", "limit"}, "checked": {...}}`` for
    the (b, x) pairs; ``passed(...)`` of it decides ``correct``. A
    non-finite solution reads as ``None``, which fails."""
    res = [true_residual(A, b, x) for b, x in pairs]
    worst = None if not res or any(np.isnan(res)) else max(res)
    return {"worst_true_resid": {"value": worst, "limit": float(limit)},
            "checked": {"value": len(res), "limit": 1}}


def passed(result: Dict[str, Dict[str, float]]) -> bool:
    w, c = result["worst_true_resid"], result["checked"]
    return (w["value"] is not None and w["value"] <= w["limit"]
            and c["value"] >= c["limit"])
