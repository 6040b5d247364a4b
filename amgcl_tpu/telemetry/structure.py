"""Operator X-ray — structure analytics, format-candidate costing, and
the reorder-gain advisor (ROADMAP item 2's measurement harness).

``to_device('auto')`` picks a device format per hierarchy level from a
handful of structural facts (diagonal count, window span, row-length
spread) and, until this module, recorded none of them: the ~31×
unstructured gap (poisson3Db-class operators) was invisible because
nothing measured *why* a windowed-ELL/DIA packing wastes bandwidth on a
given sparsity pattern or what a bandwidth-reducing reordering would
buy. This module is the per-level structural microscope:

* :func:`structure_metrics` — bandwidth profile and envelope,
  per-diagonal occupancy histogram and DIA fill ratio, ELL row-length
  distribution and padding waste, dense-window span/fill plus a density
  curve at TPU lane/sublane tile granularity, and a blake2b structure
  fingerprint byte-identical to the serve/registry scheme
  (:func:`fingerprint` — pinned by a parity test).
* :func:`candidate_table` — predicted ``{flops, bytes}`` per SpMV for
  every device format the level COULD take, priced from the host CSR
  with the PR-2 ledger byte models (``telemetry.ledger.mv_cost`` of the
  hypothetical packed matrix) — no conversion, no device work. Each
  candidate carries an eligibility verdict with the decline reason, and
  the dense-window candidate distinguishes "budget" (starved by earlier
  levels' draws on the shared pool) from "window" (no banded locality
  at any budget) — the satellite fix that makes budget-starved picks
  visible in the X-ray table.
* the **format-decision ledger** — ``ops/device.to_device('auto')``
  fills a decision record (this table + the winner + the margin + a
  ``reason`` in {"cost", "budget", "forced"}) and attaches it to the
  converted matrix; ``models/amg.py`` collects the records per level so
  the hierarchy carries its own decision history instead of deciding
  silently.
* :func:`advise` — the **reorder-gain advisor**: compute an RCM (and
  variant) permutation host-side, re-evaluate the structural metrics
  and the candidate table under the permutation WITHOUT building
  anything on device, and report the predicted densification (window
  fill, DIA ndiags, ELL padding) and predicted SpMV-byte gain.
  Predict-only by contract: the advisor never converts, never compiles,
  never touches the device (``STRUCTURE_CONTRACTS`` +
  ``analysis/jaxpr_audit.audit_structure`` enforce it).
* :func:`hierarchy_xray` / :func:`structure_findings` /
  :func:`format_xray` — the per-level report ``AMG.structure_report()``
  returns, ``cli.py --xray`` prints, the ``structure`` JSONL event
  carries, and ``telemetry.diagnose(structure=)`` folds into the
  doctor — including the predicted-vs-achieved cross-check against
  measured roofline rows, ranked by time share.

IMPORTANT: this module is host-side analytics ONLY — stdlib + numpy
(+ scipy inside the advisor), never jax and never ``amgcl_tpu.ops``
(those import jax at module scope). ``analysis/jaxpr_audit.
audit_structure`` statically scans this file for violations and asserts
a compile-watch delta of zero across a full ``structure_report`` run.
The window/tiling constants therefore MIRROR ``ops/unstructured.py``
(_TILE/_WIN_ALIGN/_ELL_PAD) instead of importing them; a parity test
pins :func:`tile_windows_host` against ``ops.unstructured.tile_windows``.
"""

from __future__ import annotations

import hashlib
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# mirrored from ops/unstructured.py (_TILE, _WIN_ALIGN), ops/densewin.py
# (_DWIN_TILE) and ops/device.py (_ELL_PAD) — kept equal by
# tests/test_structure.py so the X-ray prices exactly the windows the
# conversions would build
_TILE = 1024
_WIN_ALIGN = 1024
_DWIN_TILE = 64
_ELL_PAD = 4

#: TPU register-tile granularity for the density curve: a (sublane,
#: lane) = (8, 128) f32 tile is the unit the VPU/MXU actually moves —
#: window bytes whose (8, 128) granule holds no nonzero are pure waste
SUBLANE = 8
LANE = 128

#: density-curve granularities: element, the TPU (8, 128) register
#: tile, and a DMA-ish (64, 1024) super-tile
DENSITY_GRANULES: Tuple[Tuple[int, int], ...] = (
    (1, 1), (SUBLANE, LANE), (64, 1024))

#: candidate formats the X-ray prices, in to_device's auto preference
#: order; "ell" is the unconditional fallback
CANDIDATE_FORMATS = ("dense", "dia", "dwin", "well", "ell")

#: advisor gain below which a reorder is not worth reporting
GAIN_FLOOR = 1.15

#: TPU prices (``candidate_table(on_tpu=True)``): time that is not HBM
#: streaming, turned into bytes at the v5e's HBM rate (Google Cloud,
#: "TPU v5e": 819 GB/s) so it ranks beside the stored bytes. One scan
#: step of the windowed-ELL kernel (ops/unstructured.py: broadcast one x
#: row, lane gather, compare and select on one (8, 128) entry vreg; the
#: 85,623-row FE operator in Cuthill-McKee order on a v5e, 0.93 ms for
#: 4,032 entry vregs x 27.75 rows), and one element of XLA's gather (the
#: take-ELL SpMV of that operator, 27.4 ms for 85,623 x 44 slots).
TPU_HBM_BYTES_PER_S = 819e9
WELL_SCAN_STEP_S = 0.93e-3 / (4032 * 27.75)
XLA_GATHER_ELEM_S = 27.4e-3 / (85623 * 44)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def advisor_variants() -> Tuple[str, ...]:
    """Advisor permutation variants (``AMGCL_TPU_XRAY_VARIANTS``,
    comma-separated, default ``rcm,cm``): ``rcm`` is scipy's reverse
    Cuthill-McKee, ``cm`` the un-reversed ordering (rcm flipped)."""
    raw = os.environ.get("AMGCL_TPU_XRAY_VARIANTS", "rcm,cm")
    out = tuple(v.strip() for v in raw.split(",")
                if v.strip() in ("rcm", "cm"))
    return out or ("rcm",)


def max_advise_nnz() -> int:
    """Advisor size ceiling for ``advise="auto"`` levels
    (``AMGCL_TPU_XRAY_MAX_ADVISE_NNZ``, default 3M nonzeros): RCM plus
    a symmetric permutation is O(nnz log nnz) host work per level — the
    bench worker's always-on summary must not stall on a 14M-nnz fine
    level. ``advise=True`` ignores the ceiling."""
    return _env_int("AMGCL_TPU_XRAY_MAX_ADVISE_NNZ", 3_000_000)


# ---------------------------------------------------------------------------
# fingerprint (the serve/registry scheme, byte-identical)
# ---------------------------------------------------------------------------

def fingerprint(A) -> str:
    """Hex digest of the sparsity PATTERN — the exact
    ``serve.registry.sparsity_fingerprint`` scheme (shape, block size,
    ``ptr``/``col``; values excluded), reimplemented here so the X-ray
    stays importable without jax (serve's package init pulls it in).
    Shares the ``_sparsity_fp`` cache attribute, so whichever side
    hashes first serves the other; a parity test pins the two digests
    equal."""
    cached = getattr(A, "_sparsity_fp", None)
    if cached is not None:
        return cached
    h = hashlib.blake2b(digest_size=16)
    br, bc = getattr(A, "block_size", (1, 1))
    h.update(np.asarray([A.nrows, A.ncols, A.nnz, br, bc],
                        np.int64).tobytes())
    h.update(np.ascontiguousarray(A.ptr).tobytes())
    h.update(np.ascontiguousarray(A.col).tobytes())
    fp = h.hexdigest()
    try:
        A._sparsity_fp = fp
    except AttributeError:
        pass
    return fp


# ---------------------------------------------------------------------------
# window tiling (host mirror of ops.unstructured.tile_windows)
# ---------------------------------------------------------------------------

def _row_min_max(A):
    """Per-row min/max column, O(n) against the canonical sorted-CSR
    convention (``CSR.from_scipy``/``sort_rows`` sort indices; every
    builder in this repo emits sorted rows): the first entry of a row
    is its min column, the last its max. Empty rows report (m, -1)."""
    n, m = A.shape
    row_min = np.full(n, m, dtype=np.int64)
    row_max = np.full(n, -1, dtype=np.int64)
    nz = np.flatnonzero(np.diff(A.ptr))
    if len(nz):
        col = A.col
        row_min[nz] = col[A.ptr[nz]]
        row_max[nz] = col[A.ptr[nz + 1] - 1]
    return row_min, row_max


def tile_windows_host(A, tile: int = _TILE):
    """(n_tiles, rows, tiles, starts, win) — the same aligned per-tile
    column windows ``ops.unstructured.tile_windows`` computes for the
    windowed-ELL / dense-window conversions (starts floored to
    ``_WIN_ALIGN``, ``win`` the alignment-rounded max span, empty tiles
    pointing past the matrix), duplicated here because that module
    imports jax at module scope — but O(n) instead of the packer's
    O(nnz) ``ufunc.at`` (the X-ray runs on every ``to_device('auto')``,
    so it must stay cheaper than the conversion it annotates).
    tests/test_structure.py pins the two implementations equal."""
    n, m = A.shape
    n_tiles = -(-n // tile)
    rows = A.expanded_rows()
    tiles = rows // tile
    row_min, row_max = _row_min_max(A)
    pad = n_tiles * tile - n
    grid_min = np.pad(row_min, (0, pad), constant_values=m) \
        .reshape(n_tiles, tile)
    grid_max = np.pad(row_max, (0, pad), constant_values=-1) \
        .reshape(n_tiles, tile)
    starts = grid_min.min(axis=1)
    ends = grid_max.max(axis=1) + 1
    empty = ends <= starts
    starts[empty] = m
    ends[empty] = m + 1
    starts = (starts // _WIN_ALIGN) * _WIN_ALIGN
    span = ends - starts
    win = int(span.max()) if n_tiles else 1
    win = -(-win // _WIN_ALIGN) * _WIN_ALIGN
    return n_tiles, rows, tiles, starts, win


# ---------------------------------------------------------------------------
# entry vregs of the windowed-ELL kernel (layout shared with the packer)
# ---------------------------------------------------------------------------

def vreg_slots(A) -> Tuple[int, np.ndarray]:
    """(kv, flat): the entry-vreg layout of scalar windowed ELL. Each
    group of 128 consecutive rows stores its ELL slots as ``kv`` vregs
    of (8 slots, 128 lanes), lane = row within the group, slot k of a row
    its k-th entry (its k-th smallest column in a sorted CSR);
    ``flat[e]`` is nonzero e's position in the (n_vregs, 8, 128) array.
    ``ops/unstructured.csr_to_windowed_ell`` packs with it."""
    rows = A.expanded_rows()
    k = int(np.diff(A.ptr).max()) if A.nnz else 0
    kv = max(1, -(-k // SUBLANE))
    slot = np.arange(A.nnz, dtype=np.int64) - A.ptr[rows]
    flat = ((rows // LANE) * kv + slot // SUBLANE) * (SUBLANE * LANE) \
        + (slot % SUBLANE) * LANE + rows % LANE
    return kv, flat


def vreg_scan(A, flat: np.ndarray, n_vregs: int, starts: np.ndarray,
              tile: int = _TILE) -> Tuple[np.ndarray, np.ndarray]:
    """(lo, hi): each entry vreg's scan bounds, the window-local x rows
    (128 columns each) its real entries fall in, as ``[lo, hi)``; an
    empty vreg gets (0, 0). ``starts`` are the tile windows'
    starts (:func:`tile_windows_host`)."""
    xrow = ((A.col - starts[A.expanded_rows() // tile]) // LANE) \
        .astype(np.int32)
    grid = np.full(n_vregs * SUBLANE * LANE, np.iinfo(np.int32).max,
                   np.int32)
    grid[flat] = xrow
    lo = grid.reshape(n_vregs, -1).min(axis=1)
    grid.fill(-1)
    grid[flat] = xrow
    hi = grid.reshape(n_vregs, -1).max(axis=1) + 1
    lo[hi == 0] = 0
    return lo, hi


def well_scan(A, tile: int = _TILE) -> Dict[str, Any]:
    """What the windowed-ELL kernel scans on ``A``: ``entry_vregs``
    (stored (8, 128) entry vregs, empty ones included), ``steps`` (x rows
    scanned, summed over them) and ``scan_xrows_mean`` (their ratio).
    Cached on the matrix beside :func:`fast_facts`."""
    cached = getattr(A, "_xray_scan", None)
    if cached is not None and cached.get("tile") == tile:
        return cached
    n_tiles, _, _, starts, _ = tile_windows_host(A, tile)
    kv, flat = vreg_slots(A)
    n_vregs = n_tiles * (tile // LANE) * kv
    lo, hi = vreg_scan(A, flat, n_vregs, starts, tile)
    steps = int((hi - lo).sum())
    out = {"tile": tile, "entry_vregs": int(n_vregs), "steps": steps,
           "scan_xrows_mean": round(steps / max(n_vregs, 1), 4)}
    try:
        A._xray_scan = out
    except AttributeError:
        pass
    return out


def fast_facts(A, tile: int = _TILE, itemsize: int = 4
               ) -> Dict[str, Any]:
    """The cheap structural facts the candidate table prices from —
    O(nnz) bincount for the diagonal census (reusing the
    ``_dia_offsets_cache`` the device conversion leaves behind when
    present), O(n) row-length and window spans. Cached on the matrix
    (``_xray_facts``) so the decision ledger in ``to_device`` and a
    later full X-ray share one pass. The full
    :func:`structure_metrics` builds on these and adds the occupancy
    histogram, bandwidth profile and density curve."""
    cached = getattr(A, "_xray_facts", None)
    if cached is not None and cached.get("itemsize") == itemsize \
            and cached.get("tile") == tile:
        return cached
    n, m = A.shape
    nnz = A.nnz
    facts: Dict[str, Any] = {"itemsize": itemsize, "tile": tile,
                             "rows": int(n), "cols": int(m),
                             "nnz": int(nnz)}
    if n == 0 or nnz == 0:
        facts.update({"ndiags": 0, "dia_fill": 0.0, "k": 0,
                      "k_padded": _ELL_PAD, "tiles": 0, "win": 1,
                      "win_bytes": 0, "dwin_tiles": 0, "dwin_win": 1,
                      "dwin_bytes": 0})
        return facts
    off = getattr(A, "_dia_offsets_cache", None)
    if off is None:
        d = A.col.astype(np.int64) - A.expanded_rows()
        base = n - 1
        hits = np.bincount(d + base, minlength=base + m)
        off = np.flatnonzero(hits) - base
        # keep the occupancy counts for structure_metrics (underscore
        # keys: host-side cache only, never emitted) — the full X-ray
        # must not redo this O(nnz + n + m) census
        facts["_occ_off"] = off
        facts["_occ_cnt"] = hits[off + base]
        try:
            A._dia_offsets_cache = off
        except AttributeError:
            pass
    facts["ndiags"] = int(len(off))
    facts["dia_fill"] = round(len(off) * n / max(nnz, 1), 4)
    rnnz = np.diff(A.ptr)
    k_raw = int(rnnz.max())
    facts["k"] = k_raw
    facts["k_padded"] = max(_ELL_PAD, -(-k_raw // _ELL_PAD) * _ELL_PAD)
    n_tiles, _, _, _, win = tile_windows_host(A, tile)
    facts["tiles"] = int(n_tiles)
    facts["win"] = int(win)
    facts["win_bytes"] = int(n_tiles * tile * win * itemsize)
    # the dense-window packer tiles 64 rows at a time (ops/densewin.py
    # _TILE) — its storage footprint must be priced on ITS geometry,
    # not the windowed-ELL 1024-row tiling
    dw_tiles, _, _, _, dw_win = tile_windows_host(A, _DWIN_TILE)
    facts["dwin_tiles"] = int(dw_tiles)
    facts["dwin_win"] = int(dw_win)
    facts["dwin_bytes"] = int(dw_tiles * _DWIN_TILE * dw_win * itemsize)
    try:
        A._xray_facts = facts
    except AttributeError:
        pass
    return facts


# ---------------------------------------------------------------------------
# structural metrics
# ---------------------------------------------------------------------------

def _percentile(vals: np.ndarray, p: float) -> float:
    return float(np.percentile(vals, p)) if len(vals) else 0.0


def structure_metrics(A, tile: int = _TILE, itemsize: int = 4,
                      granules: Sequence[Tuple[int, int]] =
                      DENSITY_GRANULES) -> Dict[str, Any]:
    """Structural analytics of one host CSR (block units for BCSR —
    ``block`` records the value-block dims): bandwidth profile,
    per-diagonal occupancy, ELL row-length distribution and padding
    waste, dense-window span/fill and the tile-granularity density
    curve. Pure numpy over ``ptr``/``col`` — O(nnz log nnz) worst case,
    no values touched, nothing built."""
    n, m = A.shape
    nnz = A.nnz
    br, bc = getattr(A, "block_size", (1, 1))
    out: Dict[str, Any] = {
        "rows": int(n), "cols": int(m), "nnz": int(nnz),
        "block": [int(br), int(bc)], "fingerprint": fingerprint(A)}
    if n == 0 or nnz == 0:
        # full shape with zeroed sub-blocks: every consumer (format_xray,
        # the hierarchy_stats fold, xray_summary) indexes these keys
        # unconditionally — an empty level must not change the schema
        out.update({
            "empty": True,
            "bandwidth": {"max": 0, "mean": 0.0, "p90": 0,
                          "envelope": 0},
            "diagonals": {"ndiags": 0, "fill": 0.0,
                          "occupancy_top": [], "occupancy_p50": 0},
            "ell": {"k": 0, "k_padded": _ELL_PAD,
                    "row_nnz": {"min": 0, "mean": 0.0, "p50": 0,
                                "max": 0},
                    "pad_frac": 0.0, "lane_pad_frac": 0.0},
            "window": {"tiles": 0, "tile": int(tile), "win": 1,
                       "fill": 0.0, "bytes": 0, "density_curve": []},
        })
        return out
    facts = fast_facts(A, tile=tile, itemsize=itemsize)
    rows = A.expanded_rows()
    col = A.col.astype(np.int64)
    d = col - rows

    # bandwidth profile + envelope (the classic reordering objectives:
    # what RCM minimizes, what the window span pays for)
    row_min, row_max = _row_min_max(A)
    has = row_max >= 0
    half_bw = np.zeros(n, dtype=np.int64)
    span = np.zeros(n, dtype=np.int64)
    ridx = np.arange(n, dtype=np.int64)
    half_bw[has] = np.maximum(np.abs(row_max[has] - ridx[has]),
                              np.abs(ridx[has] - row_min[has]))
    span[has] = row_max[has] - row_min[has] + 1
    out["bandwidth"] = {
        "max": int(half_bw.max()),
        "mean": round(float(half_bw.mean()), 2),
        "p90": int(_percentile(half_bw, 90)),
        "envelope": int(span.sum()),
    }

    # per-diagonal occupancy (the DIA story): distinct diagonals, fill
    # ratio stored/nnz, and the top occupied diagonals — reusing the
    # census fast_facts cached when it ran the bincount itself (the
    # native-offsets path caches offsets only, so counts re-derive)
    occ_off = facts.get("_occ_off")
    occ_cnt = facts.get("_occ_cnt")
    if occ_cnt is None:
        base = n - 1
        hits = np.bincount(d + base, minlength=base + m)
        occ_off = np.flatnonzero(hits) - base
        occ_cnt = hits[occ_off + base]
    order = np.argsort(-occ_cnt, kind="stable")[:8]
    out["diagonals"] = {
        "ndiags": facts["ndiags"],
        "fill": facts["dia_fill"],
        "occupancy_top": [[int(occ_off[k]), int(occ_cnt[k]),
                           round(float(occ_cnt[k]) / nnz, 4)]
                          for k in order],
        "occupancy_p50": int(_percentile(occ_cnt, 50)),
    }

    # ELL row-length distribution + padding waste: pad_frac is the
    # row-length-variance waste (vs the raw max K), lane_pad_frac what
    # the packed (lane-padded) format actually stores
    rnnz = np.diff(A.ptr)
    k_raw, k_pad = facts["k"], facts["k_padded"]
    out["ell"] = {
        "k": k_raw, "k_padded": k_pad,
        "row_nnz": {"min": int(rnnz.min()),
                    "mean": round(float(rnnz.mean()), 2),
                    "p50": int(_percentile(rnnz, 50)),
                    "max": k_raw},
        "pad_frac": round(1.0 - nnz / (n * max(k_raw, 1)), 4),
        "lane_pad_frac": round(1.0 - nnz / (n * k_pad), 4),
    }

    # dense-window span/fill + the density curve at TPU tile
    # granularity: fraction of (sublane x lane) granules of the
    # (tile, win) band that hold at least one nonzero, and the fill
    # inside occupied granules — the two numbers that say whether the
    # window trade (HBM capacity for streaming) pays on this pattern
    n_tiles, _, tiles, starts, win = tile_windows_host(A, tile)
    local = col - starts[tiles]
    r_in_tile = rows - tiles * tile
    curve: List[Dict[str, Any]] = []
    for gr, gc in granules:
        key = (tiles * (-(-tile // gr)) + r_in_tile // gr) \
            * (-(-win // gc)) + local // gc
        occupied = int(len(np.unique(key)))
        total = n_tiles * (-(-tile // gr)) * (-(-win // gc))
        row_curve = {
            "granule": "%dx%d" % (gr, gc),
            "occupied_frac": round(occupied / max(total, 1), 6),
        }
        if (gr, gc) != (1, 1):
            row_curve["fill_in_occupied"] = round(
                nnz / max(occupied * gr * gc, 1), 6)
        curve.append(row_curve)
    out["window"] = {
        "tiles": int(n_tiles), "tile": int(tile), "win": int(win),
        "fill": round(nnz / max(n_tiles * tile * win, 1), 6),
        "bytes": int(n_tiles * tile * win * itemsize),
        "density_curve": curve,
    }
    return out


# ---------------------------------------------------------------------------
# candidate cost table (the PR-2 ledger byte models, predicted)
# ---------------------------------------------------------------------------

def candidate_table(A, itemsize: int = 4, on_tpu: bool = False,
                    dense_cutoff: int = 2048,
                    max_diags: Optional[int] = None,
                    max_fill: Optional[float] = None,
                    well_max_win_bytes: int = 4 << 20,
                    budget_remaining: Optional[int] = None,
                    budget_total: Optional[int] = None,
                    tile: int = _TILE) -> List[Dict[str, Any]]:
    """Predicted per-SpMV ``{flops, bytes}`` for every candidate device
    format of ``A``, priced from the host CSR exactly like
    ``ledger.mv_cost`` would price the packed matrix (stored operator
    streamed once + x read + y written — the roofline floor). Mirrors
    ``ops/device.to_device``'s auto eligibility rules (same thresholds,
    passed in by the caller when it resolved them differently); nothing
    is converted or compiled.

    The dense-window candidate's decline reason distinguishes
    ``"budget"`` (its bytes fit ``budget_total`` but not what earlier
    conversions left in ``budget_remaining`` — a budget-STARVED pick)
    from ``"window"`` (the aligned span is too wide for any budget — a
    structural decline a reorder might fix).

    Each row's ``price`` is what auto selection and the advisor rank by:
    its predicted bytes, and with ``on_tpu`` also the time the format
    spends beyond streaming them, as bytes at HBM rate — the windowed-
    ELL kernel's scan (``entry_vregs`` x ``scan_xrows_mean`` steps) for
    a scalar operator of <= 32-bit values, XLA's gather per stored slot
    for ELL and for windowed ELL the kernel does not take. Off TPU the
    price is the bytes."""
    n, m = A.shape
    nnz = max(A.nnz, 1)
    br, bc = getattr(A, "block_size", (1, 1))
    is_block = (br, bc) != (1, 1)
    vec = (n * br + m * bc) * itemsize
    if max_diags is None:
        max_diags = 512 if on_tpu else 40
    if max_fill is None:
        max_fill = 16.0 if on_tpu else 1.5
    facts = fast_facts(A, tile=tile, itemsize=itemsize)
    rows: List[Dict[str, Any]] = []

    def cand(fmt, eligible, why, flops, stored, extra_s=0.0, **info):
        rows.append({
            "format": fmt, "eligible": bool(eligible),
            **({"why": why} if why else {}),
            "predicted": {"flops": int(flops),
                          "bytes": int(stored + vec)},
            "stored_bytes": int(stored),
            "price": int(stored + vec + extra_s * TPU_HBM_BYTES_PER_S),
            **info})

    # dense (MXU matmul; small coarse levels)
    dense_ok = (not is_block and max(n, m) <= dense_cutoff
                and nnz > 0.02 * n * m)
    cand("dense", dense_ok,
         None if dense_ok else (
             "block values" if is_block else
             "%d > dense cutoff %d" % (max(n, m), dense_cutoff)
             if max(n, m) > dense_cutoff else
             "density below the 2% dense floor"),
         2 * n * m, n * m * itemsize)

    # dia (zero-gather shifted multiply-adds)
    nd = facts["ndiags"]
    fill = facts["dia_fill"] if nd else float("inf")
    dia_stored = nd * n * itemsize
    dia_ok = (not is_block and nd and nd <= max_diags
              and fill <= max_fill and dia_stored < 2 << 30)
    cand("dia", dia_ok,
         None if dia_ok else (
             "block values" if is_block else
             "%d diagonals > max_diags %d" % (nd, max_diags)
             if nd > max_diags else
             "fill %.3g > max_fill %.3g" % (fill, max_fill)
             if fill > max_fill else "data over the 2 GB guard"),
         2 * nd * n, dia_stored)

    # dwin (gather-free dense windows; TPU auto path, square scalar) —
    # priced on the dense-window packer's own 64-row tiling
    need = facts["dwin_bytes"]
    cap_total = budget_total
    if cap_total is None:
        cap_total = _env_int("AMGCL_TPU_DWIN_MAX_BYTES", 6 << 30)
    cap_now = cap_total if budget_remaining is None \
        else min(cap_total, budget_remaining)
    vmem_ok = (2 * _DWIN_TILE + 4) * facts["dwin_win"] * itemsize \
        <= 10 << 20
    dwin_why = None
    if is_block:
        dwin_why = "block values"
    elif n != m:
        dwin_why = "rectangular"
    elif need > cap_total:
        dwin_why = "window"        # too wide for ANY budget: structural
    elif need > cap_now:
        dwin_why = "budget"        # starved by earlier levels' draws
    elif not vmem_ok:
        dwin_why = "vmem"
    elif not on_tpu:
        dwin_why = "auto picks dense windows on TPU only"
    cand("dwin", dwin_why is None, dwin_why,
         2 * facts["dwin_tiles"] * _DWIN_TILE * facts["dwin_win"],
         need)

    # well (windowed ELL: per-tile x windows, (8, 128) entry vregs; on
    # TPU the lane-gather kernel for scalar <= 32-bit values, else XLA's
    # gather)
    k_pad = max(4, facts["k_padded"])
    k_well = max(SUBLANE, -(-facts["k"] // SUBLANE) * SUBLANE)
    win = facts["win"]
    well_ok = win * bc * 4 <= well_max_win_bytes
    n_tiles = facts["tiles"]
    slots = n_tiles * tile * k_well
    well_stored = n_tiles * 4 + slots * (4 + itemsize * br * bc)
    info: Dict[str, Any] = {}
    extra_s = 0.0
    if on_tpu:
        if is_block or itemsize > 4 or tile % LANE:
            info["kernel"] = "xla"
            extra_s = slots * XLA_GATHER_ELEM_S
        else:
            scan = well_scan(A, tile)
            info = {"kernel": "pallas",
                    "entry_vregs": scan["entry_vregs"],
                    "scan_xrows_mean": scan["scan_xrows_mean"]}
            extra_s = scan["steps"] * WELL_SCAN_STEP_S
    cand("well", well_ok,
         None if well_ok else
         "window %d col x 4 B > %d B VMEM budget"
         % (win * bc, well_max_win_bytes),
         2 * slots * br * bc, well_stored, extra_s, **info)

    # ell (global gather — the unconditional fallback)
    k_ell = max(_ELL_PAD, k_pad)
    cand("ell", True, None,
         2 * n * k_ell * br * bc,
         n * k_ell * (4 + itemsize * br * bc),
         n * k_ell * XLA_GATHER_ELEM_S if on_tpu else 0.0)
    return rows


def price(c: Dict[str, Any]) -> int:
    """A candidate row's price (:func:`candidate_table`); rows recorded
    before prices existed rank by their predicted bytes."""
    return c.get("price", c["predicted"]["bytes"])


def best_candidate(candidates: List[Dict[str, Any]],
                   eligible_only: bool = True
                   ) -> Optional[Dict[str, Any]]:
    """Price argmin over the table (eligible rows only by default)."""
    rows = [c for c in candidates if c["eligible"]] if eligible_only \
        else list(candidates)
    return min(rows, key=price) if rows else None


def decision_record(candidates: List[Dict[str, Any]], winner_fmt: str,
                    forced: bool = False,
                    built_bytes: Optional[int] = None
                    ) -> Dict[str, Any]:
    """The format-decision ledger entry ``to_device`` attaches to the
    converted matrix: the candidate table, the winner, the margin
    (best other candidate's price / winner's — > 1 means the winner
    also priced cheapest), and the ``reason``:

    * ``"forced"`` — the caller named the format;
    * ``"budget"`` — a candidate the auto policy PREFERS to the winner
      (earlier in :data:`CANDIDATE_FORMATS`, to_device's preference
      order — dense-window buys gather-freedom, not fewer stored
      bytes, so byte ranking alone would never flag it) or one
      predicted cheaper lost solely on the shared HBM budget: the
      budget changed the outcome (the budget-starved pick the
      satellite fix makes distinguishable);
    * ``"cost"``   — everything else: the winner won on the cost/
      eligibility rules.
    """
    win = next((c for c in candidates if c["format"] == winner_fmt),
               None)
    reason = "forced" if forced else "cost"
    if not forced and win is not None:
        order = {f: i for i, f in enumerate(CANDIDATE_FORMATS)}
        wi = order.get(winner_fmt, len(CANDIDATE_FORMATS))
        wb = price(win)
        for c in candidates:
            if c is win or c.get("why") != "budget":
                continue
            if order.get(c["format"], 99) < wi or price(c) < wb:
                reason = "budget"
                break
    margin = None
    if win is not None:
        others = [price(c) for c in candidates
                  if c is not win and c["eligible"]]
        if others and price(win):
            margin = round(min(others) / price(win), 4)
    out: Dict[str, Any] = {"fmt": winner_fmt, "reason": reason,
                           "candidates": candidates, "margin": margin}
    if win is not None:
        out["predicted"] = dict(win["predicted"])
        out["stored_bytes"] = int(win["stored_bytes"])
    if built_bytes is not None:
        out["built_bytes"] = int(built_bytes)
    return out


# ---------------------------------------------------------------------------
# reorder-gain advisor (predict-only)
# ---------------------------------------------------------------------------

def _rcm_perm(A) -> np.ndarray:
    """Reverse Cuthill-McKee permutation of the symmetrized pattern —
    the same scipy routine ``utils.adapters.cuthill_mckee`` wraps (that
    module is host-only too, but imports the CSR class tree; the X-ray
    works from raw ptr/col)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import reverse_cuthill_mckee
    mat = sp.csr_matrix(
        (np.ones(A.nnz, np.int8), A.col, A.ptr), shape=A.shape)
    return np.asarray(reverse_cuthill_mckee(mat, symmetric_mode=True))


def permute_pattern(A, perm: np.ndarray):
    """B = P A Pᵀ of the PATTERN (values dropped — the advisor never
    needs them), returned as a lightweight CSR-shaped host object."""
    import scipy.sparse as sp
    mat = sp.csr_matrix(
        (np.ones(A.nnz, np.float32), A.col, A.ptr), shape=A.shape)
    mat = mat[perm][:, perm].tocsr()
    mat.sort_indices()

    class _Pattern:
        pass

    B = _Pattern()
    B.ptr = mat.indptr.astype(np.int64)
    B.col = mat.indices.astype(np.int32)
    B.shape = mat.shape
    B.nrows = mat.shape[0]
    B.ncols = mat.shape[1]
    B.nnz = int(mat.nnz)
    B.block_size = getattr(A, "block_size", (1, 1))

    def _rows():
        # cached like CSR.expanded_rows — metrics + candidate pricing
        # call this several times per variant, and the O(nnz) repeat
        # must not multiply on exactly the large levels the advisor
        # ceiling keeps cheap
        r = getattr(B, "_rows_cache", None)
        if r is None:
            r = np.repeat(np.arange(B.nrows), np.diff(B.ptr))
            B._rows_cache = r
        return r

    B.expanded_rows = _rows
    return B


def advise(A, metrics: Optional[Dict[str, Any]] = None,
           variants: Optional[Sequence[str]] = None,
           itemsize: int = 4, on_tpu: bool = False,
           tile: int = _TILE,
           dense_cutoff: int = 2048) -> Dict[str, Any]:
    """The reorder-gain advisor for ONE operator: for each permutation
    variant, re-evaluate the structural metrics and the candidate cost
    table under the permutation — host-side, predict-only — and report
    the predicted densification and SpMV gain vs the identity ordering.
    ``gain`` is the best eligible price (identity) / the best eligible
    price (permuted, :func:`candidate_table`): the factor the format
    layer is predicted to win back if ``to_device`` saw the reordered
    operator (``cli --reorder`` / ``utils.adapters.Reordered``)."""
    met_id = metrics if metrics is not None else structure_metrics(
        A, tile=tile, itemsize=itemsize)
    cand_id = candidate_table(A, itemsize=itemsize, on_tpu=on_tpu,
                              dense_cutoff=dense_cutoff, tile=tile)
    best_id = best_candidate(cand_id)
    out: Dict[str, Any] = {
        "identity": {"best": best_id["format"] if best_id else None,
                     "bytes": best_id["predicted"]["bytes"]
                     if best_id else None,
                     "price": price(best_id) if best_id else None},
        "variants": []}
    if A.nnz == 0 or A.nrows == 0:
        return out
    try:
        rcm = _rcm_perm(A)
    except Exception as e:      # scipy missing / disconnected pattern:
        out["error"] = repr(e)[:200]   # the advisor degrades to silence
        return out
    perms = {"rcm": rcm, "cm": rcm[::-1]}
    best_row = None
    for name in (variants if variants is not None
                 else advisor_variants()):
        perm = perms.get(name)
        if perm is None:
            continue
        B = permute_pattern(A, perm)
        met_p = structure_metrics(B, tile=tile, itemsize=itemsize)
        cand_p = candidate_table(B, itemsize=itemsize, on_tpu=on_tpu,
                                 dense_cutoff=dense_cutoff, tile=tile)
        best_p = best_candidate(cand_p)
        gain = None
        if best_id and best_p and price(best_p):
            gain = round(price(best_id) / price(best_p), 4)
        # mechanism-matched gains: the price of each format under
        # identity / under the permutation, eligibility ignored — the
        # number ``bench --xray`` validates measured (same format both
        # sides, so time tracks the price on any platform)
        by_id = {c["format"]: price(c) for c in cand_id}
        per_format = {
            c["format"]: round(by_id[c["format"]] / price(c), 4)
            for c in cand_p if price(c) and by_id.get(c["format"])}
        row = {
            "variant": name,
            "best": best_p["format"] if best_p else None,
            "bytes": best_p["predicted"]["bytes"] if best_p else None,
            "price": price(best_p) if best_p else None,
            "gain": gain,
            "per_format": per_format,
            "densify": {
                "ndiags": [met_id["diagonals"]["ndiags"],
                           met_p["diagonals"]["ndiags"]],
                "window_fill": [met_id["window"]["fill"],
                                met_p["window"]["fill"]],
                "window_win": [met_id["window"]["win"],
                               met_p["window"]["win"]],
                "ell_pad_frac": [met_id["ell"]["pad_frac"],
                                 met_p["ell"]["pad_frac"]],
                "bandwidth_max": [met_id["bandwidth"]["max"],
                                  met_p["bandwidth"]["max"]],
            },
            "candidates": cand_p,
        }
        out["variants"].append(row)
        # only a GAIN is a recommendation: a variant predicted to make
        # the structure worse (gain < 1, e.g. RCM on an already-banded
        # stencil) stays in the raw variants data but never becomes the
        # headline "best" the summary/gauges/print surface
        if gain is not None and gain > 1.0 and (
                best_row is None or gain > best_row["gain"]):
            best_row = row
    if best_row is not None:
        out["best"] = {"variant": best_row["variant"],
                       "gain": best_row["gain"],
                       "format": best_row["best"],
                       "per_format": best_row["per_format"],
                       "densify": best_row["densify"]}
    return out


# ---------------------------------------------------------------------------
# executed reorder (ISSUE 20): the advisor's prediction, turned into a plan
# ---------------------------------------------------------------------------

#: fingerprint-keyed plan cache: the permutation is a function of the
#: sparsity PATTERN only, so PR-9 ``rebuild()`` (same pattern, new
#: values) and farm re-registrations of the same system reuse the plan
#: for free instead of re-running scipy's RCM
_PERM_CACHE: Dict[Tuple[str, str, bool, int],
                  Optional[Dict[str, Any]]] = {}


def reorder_mode() -> str:
    """``AMGCL_TPU_REORDER``, normalized: ``auto`` (default — engage
    when the advisor predicts at least :data:`GAIN_FLOOR` gain in price),
    ``rcm``/``cm`` (force that variant regardless of predicted gain),
    or ``off``. Read per call so flight replay's env re-application and
    per-test monkeypatching see the live value."""
    raw = os.environ.get("AMGCL_TPU_REORDER", "auto").strip().lower()
    if raw in ("0", "off", "no", "false"):
        return "off"
    if raw in ("rcm", "cm"):
        return raw
    return "auto"


def reorder_plan(A, on_tpu: bool = False, mode: Optional[str] = None,
                 itemsize: int = 4) -> Optional[Dict[str, Any]]:
    """Decide whether to EXECUTE a reorder on ``A`` and, if so, return
    the plan — or ``None`` to keep the identity ordering.

    The plan dict carries everything the build/rebuild/solve seams
    need to make the permutation invisible:

    * ``perm``/``iperm`` — row/col permutation and its inverse
      (``A_perm = P A Pᵀ``; solve permutes rhs in by ``perm`` and
      un-permutes x out by ``iperm``),
    * ``val_perm`` — nnz-sized index array with
      ``A_perm.val = A.val[val_perm]``, so a same-pattern ``rebuild``
      re-permutes values without touching scipy again,
    * ``variant`` (``rcm``/``cm``), ``fingerprint`` (identity-pattern
      digest the plan is cached under), ``predicted_gain`` (advisor
      price ratio, ``None`` when forced), ``n``, and the ORIGINAL
      pattern refs ``ptr``/``col`` (so rebuild can recognize a caller
      handing back an original-order CSR).

    Scalar matrices only (``block_size == (1, 1)``) — the advisor does
    not price block permutations — and patterns above
    :func:`max_advise_nnz` are left alone, same ceiling as the X-ray."""
    md = reorder_mode() if mode is None else str(mode).strip().lower()
    if md in ("off", "0", "no", "false"):
        return None
    if getattr(A, "block_size", (1, 1)) != (1, 1):
        return None
    if A.nnz == 0 or A.nrows == 0 or A.nrows != A.ncols:
        return None
    if A.nnz > max_advise_nnz():
        return None
    fp = fingerprint(A)
    # the auto decision reads the prices, which differ on and off TPU
    key = (fp, md, bool(on_tpu), int(itemsize))
    if key in _PERM_CACHE:
        return _PERM_CACHE[key]
    plan: Optional[Dict[str, Any]] = None
    try:
        if md == "auto":
            # cheap pre-filter before the full advisor pass: an operator
            # that already packs into a handful of well-filled diagonals
            # (3D stencils: 7) is the structured regime the reorder
            # exists to RECOVER, not improve — RCM cannot beat the
            # identity there, and every AMG build would otherwise pay an
            # RCM + candidate-table pass at setup. O(nnz) unique() vs
            # the advisor's O(nnz log nnz + tables).
            offs = np.unique(
                np.repeat(np.arange(A.nrows, dtype=np.int64),
                          np.diff(A.ptr)) - A.col)
            if len(offs) <= 16 and \
                    len(offs) * A.nrows <= 1.5 * A.nnz:
                _PERM_CACHE[key] = None
                return None
            adv = advise(A, itemsize=itemsize, on_tpu=on_tpu)
            best = adv.get("best")
            if best is not None and best.get("gain") and \
                    best["gain"] >= GAIN_FLOOR:
                variant, gain = best["variant"], float(best["gain"])
            else:
                variant, gain = None, None
        else:
            variant, gain = md, None
        if variant is not None:
            rcm = _rcm_perm(A)
            perm = rcm if variant == "rcm" else rcm[::-1]
            perm = np.ascontiguousarray(perm, dtype=np.int64)
            iperm = np.empty_like(perm)
            iperm[perm] = np.arange(A.nrows, dtype=np.int64)
            # value map via a scipy pass whose "values" are positions:
            # row i of A_perm holds A.val[val_perm[ptr[i]:ptr[i+1]]]
            import scipy.sparse as sp
            # 1-based positions: position 0 as a stored value would be
            # indistinguishable from an explicit zero to scipy's pruning
            tag = sp.csr_matrix(
                (np.arange(1, A.nnz + 1, dtype=np.int64), A.col, A.ptr),
                shape=A.shape)
            tag = tag[perm][:, perm].tocsr()
            tag.sort_indices()
            plan = {"perm": perm, "iperm": iperm,
                    "val_perm": np.ascontiguousarray(tag.data) - 1,
                    "variant": variant, "fingerprint": fp,
                    "predicted_gain": gain, "n": int(A.nrows),
                    "ptr": A.ptr, "col": A.col}
    except Exception:
        plan = None          # scipy missing / degenerate pattern:
    _PERM_CACHE[key] = plan  # the executed reorder degrades to identity
    return plan


# ---------------------------------------------------------------------------
# the hierarchy X-ray
# ---------------------------------------------------------------------------

def _is_csr_like(A) -> bool:
    return (A is not None and hasattr(A, "ptr") and hasattr(A, "col")
            and hasattr(A, "nnz"))


def hierarchy_xray(host_levels, decisions: Optional[List] = None,
                   advise_mode: Any = "auto",
                   variants: Optional[Sequence[str]] = None,
                   itemsize: int = 4, on_tpu: bool = False,
                   tile: int = _TILE) -> Dict[str, Any]:
    """The operator X-ray over every hierarchy level: per-level
    structural metrics + the recorded format decision + (optionally)
    the reorder-gain advisor. ``host_levels`` is ``AMG.host_levels``
    (``(A, P, R)`` rows; non-CSR meta rows from device-built prefixes
    degrade to skipped entries); ``decisions`` the per-level decision
    records ``models/amg.py`` collected from ``to_device``.

    ``advise_mode``: True (every CSR level), False (none), or "auto"
    (levels up to :func:`max_advise_nnz` nonzeros — the always-on bench
    summary must stay cheap)."""
    levels: List[Dict[str, Any]] = []
    ceiling = max_advise_nnz()
    for i, row in enumerate(host_levels or []):
        Ai = row[0] if isinstance(row, (tuple, list)) and row else row
        if not _is_csr_like(Ai):
            levels.append({"level": i,
                           "skipped": "no host CSR (device-built or "
                           "filtered level)"})
            continue
        met = structure_metrics(Ai, tile=tile, itemsize=itemsize)
        lrow: Dict[str, Any] = {"level": i, "metrics": met}
        dec = decisions[i] if decisions is not None \
            and i < len(decisions) else None
        if dec is not None:
            lrow["decision"] = dec
        else:
            # no recorded decision (pre-xray build / device-built
            # level): the predicted table still renders the X-ray
            lrow["candidates"] = candidate_table(
                Ai, itemsize=itemsize, on_tpu=on_tpu, tile=tile)
        do_advise = bool(advise_mode) and met.get("nnz", 0) > 0
        if advise_mode == "auto" and met.get("nnz", 0) > ceiling:
            do_advise = False
            lrow["advisor"] = {"skipped": "nnz %d > advise ceiling %d "
                               "(AMGCL_TPU_XRAY_MAX_ADVISE_NNZ)"
                               % (met["nnz"], ceiling)}
        if do_advise:
            lrow["advisor"] = advise(Ai, metrics=met, variants=variants,
                                     itemsize=itemsize, on_tpu=on_tpu,
                                     tile=tile)
        levels.append(lrow)
    out = {"schema": 1, "levels": levels}
    out["summary"] = xray_summary(out)
    return out


def xray_summary(xray: Dict[str, Any]) -> Dict[str, Any]:
    """Compact roll-up of a hierarchy X-ray — what the bench worker
    embeds on every record, the live gauges publish, and the
    ``structure`` JSONL event's headline block. Finest-level waste
    numbers plus the best advisor gain across levels."""
    levels = xray.get("levels") or []
    rows = [r for r in levels if "metrics" in r]
    summary: Dict[str, Any] = {"n_levels": len(levels)}
    if not rows:
        return summary
    finest = rows[0]
    met = finest["metrics"]
    summary.update({
        "fingerprint": met.get("fingerprint"),
        "bandwidth_max": met.get("bandwidth", {}).get("max"),
        "ndiags": met.get("diagonals", {}).get("ndiags"),
        "dia_fill": met.get("diagonals", {}).get("fill"),
        "padding_waste_frac":
            met.get("ell", {}).get("lane_pad_frac"),
        "window_fill": met.get("window", {}).get("fill"),
    })
    fmts, reasons = [], []
    gain = None
    for r in levels:
        dec = r.get("decision")
        fmts.append((dec or {}).get("fmt", "-"))
        reasons.append((dec or {}).get("reason", "-"))
        g = ((r.get("advisor") or {}).get("best") or {}).get("gain")
        if g is not None and (gain is None or g > gain):
            gain = g
    summary["formats"] = "/".join(fmts)
    summary["reasons"] = "/".join(reasons)
    if gain is not None:
        summary["predicted_reorder_gain"] = gain
    return summary


# ---------------------------------------------------------------------------
# findings (the doctor fold) + the roofline cross-check
# ---------------------------------------------------------------------------

def _finding(severity, code, message, suggestion=None, **extra):
    out = {"severity": severity, "code": code, "message": message}
    if suggestion:
        out["suggestion"] = suggestion
    out.update(extra)
    return out


def decision_roofline_check(xray: Dict[str, Any],
                            roofline: Dict[str, Any]
                            ) -> List[Dict[str, Any]]:
    """Join the decision ledger's predicted per-SpMV bytes to the
    measured roofline rows: per level, the mean achieved GB/s over its
    operator-streaming stages vs the hierarchy median, ranked by time
    share — the predicted-vs-achieved divergence table. A level whose
    chosen format achieves far below the rest is where the auto
    decision (or its byte model) is wrong on this pattern."""
    stages = (roofline or {}).get("stages") or []
    if not stages:
        return []
    per_level: Dict[int, Dict[str, float]] = {}
    for r in stages:
        if r.get("gbps") is None:
            continue
        acc = per_level.setdefault(int(r["level"]),
                                   {"gbps": 0.0, "k": 0, "t": 0.0})
        acc["gbps"] += r["gbps"]
        acc["k"] += 1
        acc["t"] += r["t_s"] * r.get("visits", 1)
    if not per_level:
        return []
    total_t = sum(a["t"] for a in per_level.values()) or 1.0
    means = {lvl: a["gbps"] / a["k"] for lvl, a in per_level.items()}
    median = float(np.median(list(means.values())))
    dec_by_level = {r["level"]: r.get("decision")
                    for r in xray.get("levels") or []}
    rows = []
    for lvl, mean_gbps in means.items():
        dec = dec_by_level.get(lvl) or {}
        row = {"level": lvl, "format": dec.get("fmt"),
               "reason": dec.get("reason"),
               "achieved_gbps": round(mean_gbps, 3),
               "median_gbps": round(median, 3),
               "t_share": round(per_level[lvl]["t"] / total_t, 4),
               "predicted_bytes": (dec.get("predicted") or {}).get(
                   "bytes"),
               "built_bytes": dec.get("built_bytes")}
        row["deficit"] = round(1.0 - mean_gbps / median, 4) \
            if median > 0 else None
        rows.append(row)
    rows.sort(key=lambda r: -(max(r["deficit"] or 0.0, 0.0)
                              * r["t_share"]))
    return rows


def structure_findings(xray: Dict[str, Any],
                       roofline: Optional[Dict[str, Any]] = None
                       ) -> List[Dict[str, Any]]:
    """Doctor-shaped findings from a hierarchy X-ray: advisor gains,
    padding/fill waste, budget-starved decisions, predicted-vs-built
    ledger drift, and (with a measured roofline) the
    predicted-vs-achieved divergence per format, ranked. Pure dict
    crunching — never raises on missing pieces."""
    out: List[Dict[str, Any]] = []
    if not xray:
        return out
    for r in xray.get("levels") or []:
        lvl = r.get("level")
        dec = r.get("decision") or {}
        met = r.get("metrics") or {}
        best = (r.get("advisor") or {}).get("best") or {}
        gain = best.get("gain")
        if gain is not None and gain >= GAIN_FLOOR:
            dn = best.get("densify") or {}
            nd = dn.get("ndiags", [None, None])
            wf = dn.get("window_fill", [None, None])
            ep = dn.get("ell_pad_frac", [None, None])
            out.append(_finding(
                "warning" if (gain >= 1.5 and lvl == 0) else "info",
                "reorder_gain",
                "level %s: a %s reorder is predicted to cut the best "
                "format's SpMV bytes %.2fx (best format %s; ndiags "
                "%s -> %s, window fill %s -> %s, ELL padding "
                "%s -> %s)" % (
                    lvl, best.get("variant"), gain, best.get("format"),
                    nd[0], nd[1], wf[0], wf[1], ep[0], ep[1]),
                "apply the bandwidth-reducing reorder at setup "
                "(cli --reorder / utils.adapters.Reordered) — the "
                "hierarchy absorbs the permutation, the solve phase "
                "never pays it",
                level=lvl, predicted_gain=gain,
                variant=best.get("variant")))
        # mechanism-matched densification: the winning format's OWN
        # byte gain under the reorder (same packing both sides — the
        # number bench --xray validates measured, since same-format
        # time tracks bytes on any platform)
        fmt_gain = (best.get("per_format") or {}).get(
            best.get("format"))
        if fmt_gain is not None and fmt_gain >= GAIN_FLOOR:
            nd = (best.get("densify") or {}).get("ndiags",
                                                 [None, None])
            out.append(_finding(
                "info", "reorder_densification",
                "level %s: the %s packing itself densifies %.2fx "
                "under the %s ordering (predicted stored+streamed "
                "bytes per spmv, same format both sides; ndiags "
                "%s -> %s)" % (lvl, best.get("format"), fmt_gain,
                               best.get("variant"), nd[0], nd[1]),
                "bench --xray measures exactly this pair "
                "(identity-vs-reordered spmv per format) and joins "
                "predicted vs achieved",
                level=lvl, predicted_gain=fmt_gain,
                format=best.get("format"),
                variant=best.get("variant")))
        if dec.get("reason") == "budget":
            out.append(_finding(
                "warning", "budget_starved_format",
                "level %s: the predicted-cheapest format lost on the "
                "shared dense-window budget, not on cost — the level "
                "runs %s instead" % (lvl, dec.get("fmt")),
                "raise AMGCL_TPU_DWIN_MAX_BYTES (the hierarchy-wide "
                "pool) or reorder coarser levels off the dense-window "
                "format", level=lvl))
        pred = dec.get("stored_bytes")
        built = dec.get("built_bytes")
        if pred and built and not (0.75 <= built / pred <= 1.25):
            out.append(_finding(
                "info", "ledger_divergence",
                "level %s: the decision ledger predicted %d stored "
                "bytes for %s but the conversion built %d (%.2fx) — "
                "the candidate byte model drifted from the packer"
                % (lvl, pred, dec.get("fmt"), built, built / pred),
                level=lvl))
        ell = met.get("ell") or {}
        if lvl == 0 and (ell.get("lane_pad_frac") or 0) > 0.3 \
                and dec.get("fmt") in ("ell", "well"):
            out.append(_finding(
                "info", "ell_padding_waste",
                "finest level stores %.0f%% padding in its %s packing "
                "(row-length spread %s..%s)" % (
                    100 * ell["lane_pad_frac"], dec.get("fmt"),
                    ell.get("row_nnz", {}).get("min"),
                    ell.get("row_nnz", {}).get("max")),
                "a reorder or row binning that evens row lengths "
                "reclaims the padded bandwidth", level=lvl))
    rows = decision_roofline_check(xray, roofline) if roofline else []
    for row in rows:
        if (row.get("deficit") or 0) > 0.5 and row["t_share"] > 0.05:
            out.append(_finding(
                "warning", "format_underperforms",
                "level %d (%s, decided on %s) achieves %.3g GB/s vs "
                "the hierarchy median %.3g — %.0f%% below, carrying "
                "%.0f%% of the measured cycle time: the predicted "
                "cost and the achieved rate diverge on this pattern"
                % (row["level"], row.get("format"), row.get("reason"),
                   row["achieved_gbps"], row["median_gbps"],
                   100 * row["deficit"], 100 * row["t_share"]),
                "check the X-ray's advisor row for this level — a "
                "reorder that densifies windows usually closes "
                "exactly this gap", level=row["level"],
                t_share=row["t_share"]))
    sev = {"critical": 0, "warning": 1, "info": 2}
    out.sort(key=lambda f: (sev.get(f["severity"], 3),
                            -(f.get("t_share") or
                              f.get("predicted_gain") or 0)))
    return out


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _human_bytes(x) -> str:
    x = float(x or 0)
    for unit in ("B", "K", "M", "G"):
        if abs(x) < 1024 or unit == "G":
            return "%.2f %s" % (x, unit)
        x /= 1024.0


def format_xray(xray: Dict[str, Any]) -> str:
    """Human rendering of a hierarchy X-ray: the per-level structure
    table, the format-decision candidate ledger, and the advisor rows
    (``cli.py --xray``)."""
    lines = ["Operator X-ray:",
             "level    rows       nnz    bw_max  ndiags  dia_fill  "
             "ell_pad  win_fill  decision",
             "-" * 86]
    for r in xray.get("levels") or []:
        if "metrics" not in r:
            lines.append("%5s  %s" % (r.get("level"),
                                      r.get("skipped", "-")))
            continue
        met = r["metrics"]
        dec = r.get("decision") or {}
        dtxt = "-"
        if dec:
            dtxt = "%s (%s%s)" % (
                dec.get("fmt"), dec.get("reason"),
                ", margin %.2f" % dec["margin"]
                if dec.get("margin") is not None else "")
        lines.append("%5d %7d %9d %9d %7d %9.3f %8.3f %9.4f  %s" % (
            r["level"], met["rows"], met["nnz"],
            met["bandwidth"]["max"], met["diagonals"]["ndiags"],
            met["diagonals"]["fill"], met["ell"]["lane_pad_frac"],
            met["window"]["fill"], dtxt))
    lines.append("")
    lines.append("Format-decision ledger (predicted bytes per spmv):")
    for r in xray.get("levels") or []:
        cands = (r.get("decision") or {}).get("candidates") \
            or r.get("candidates")
        if not cands:
            continue
        dec = r.get("decision") or {}
        cells = []
        for c in cands:
            mark = "*" if c["format"] == dec.get("fmt") else \
                ("" if c["eligible"] else "x")
            cells.append("%s%s %s" % (mark, c["format"],
                                      _human_bytes(c["predicted"]
                                                   ["bytes"])))
        lines.append("  level %s: %s" % (r.get("level"),
                                         "  ".join(cells)))
        rejected = [c for c in cands if not c["eligible"]
                    and c.get("why")]
        if rejected:
            lines.append("          rejected: " + "; ".join(
                "%s (%s)" % (c["format"], c["why"]) for c in rejected))
    adv_lines = []
    for r in xray.get("levels") or []:
        best = (r.get("advisor") or {}).get("best")
        if best and best.get("gain") is not None:
            dn = best.get("densify") or {}
            adv_lines.append(
                "  level %s: %s -> predicted gain %.2fx (best format "
                "%s; ndiags %s->%s, window fill %.4g->%.4g)" % (
                    r.get("level"), best.get("variant"), best["gain"],
                    best.get("format"),
                    dn.get("ndiags", ["-", "-"])[0],
                    dn.get("ndiags", ["-", "-"])[1],
                    dn.get("window_fill", [0, 0])[0],
                    dn.get("window_fill", [0, 0])[1]))
    if adv_lines:
        lines.append("")
        lines.append("Reorder-gain advisor (predict-only):")
        lines += adv_lines
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# test / bench fixture: a banded operator under a random permutation
# ---------------------------------------------------------------------------

def banded_pattern(n: int, bw: int = 4):
    """(ptr, col, val) of an SPD-ish Toeplitz band of half-bandwidth
    ``bw`` — every in-range diagonal in [-bw, bw] fully occupied, so
    the structure is exactly ``2*bw + 1`` diagonals."""
    offs = np.arange(-bw, bw + 1)
    rows_l, cols_l, vals_l = [], [], []
    ridx = np.arange(n, dtype=np.int64)
    for off in offs:
        c = ridx + off
        ok = (c >= 0) & (c < n)
        rows_l.append(ridx[ok])
        cols_l.append(c[ok])
        vals_l.append(np.full(ok.sum(),
                              2.0 * bw + 1.0 if off == 0 else -0.5))
    rows = np.concatenate(rows_l)
    cols = np.concatenate(cols_l)
    vals = np.concatenate(vals_l)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    ptr = np.zeros(n + 1, np.int64)
    np.add.at(ptr, rows + 1, 1)
    ptr = np.cumsum(ptr)
    return ptr, cols.astype(np.int32), vals


def permuted_banded(n: int = 2048, bw: int = 4, seed: int = 0,
                    local: Optional[int] = None):
    """The advisor-validation fixture (tests + ``bench.py --xray``): a
    banded SPD matrix scrambled by a random symmetric permutation —
    RCM recovers the band, so the predicted densification (ndiags,
    window fill, ELL padding) is large and checkable. Returns
    ``(A_permuted, A_banded, perm)`` as ``ops.csr.CSR`` objects (the
    one place this module touches the CSR class — imported lazily;
    ops.csr is numpy-only).

    ``local`` shuffles within contiguous blocks of that size instead
    of globally: the bandwidth grows to ~2·local+bw instead of ~n, so
    the DIA packing stays BUILDABLE at identity (a few hundred
    diagonals, not thousands) while remaining badly wasteful — the
    bench microbenchmark uses this to measure the same format on both
    orderings (the mechanism-matched join)."""
    from amgcl_tpu.ops.csr import CSR
    import scipy.sparse as sp
    ptr, col, val = banded_pattern(n, bw)
    A0 = CSR(ptr, col, val, n)
    rng = np.random.RandomState(seed)
    if local:
        perm = np.arange(n)
        for s in range(0, n, int(local)):
            blk = perm[s:s + int(local)].copy()
            rng.shuffle(blk)
            perm[s:s + int(local)] = blk
    else:
        perm = rng.permutation(n)
    mat = sp.csr_matrix((A0.val, A0.col, A0.ptr), shape=(n, n))
    mat = mat[perm][:, perm].tocsr()
    mat.sort_indices()
    return CSR(mat.indptr, mat.indices, mat.data, n), A0, perm
