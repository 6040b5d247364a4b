"""The trace reduction: busy union, idle share, top operations and idle
time by host step, on a hand-made trace and on an excerpt recorded on a
v5e."""

import json
from pathlib import Path

import pytest

from chipbench import devtrace

S = 1e9  # nanoseconds per second

HAND = {
    "device": [[(0.1 * S, 0.3 * S, "level0/down_fused"),
                (0.2 * S, 0.4 * S, "fusion.7"),
                (0.6 * S, 0.7 * S, "level0/down_fused"),
                (1.1 * S, 1.2 * S, "after the window")]],
    "host": [(0.0, 1.0 * S, "chipbench/window"),
             (0.02 * S, 0.5 * S, "chipbench/solve_call"),
             (0.45 * S, 0.58 * S, "chipbench/report"),
             (0.58 * S, 0.95 * S, "chipbench/solve_call")],
}


def test_hand_trace():
    r = devtrace.reduce(HAND)
    assert r["window_s"] == pytest.approx(1.0)
    # [0.1, 0.4] and [0.6, 0.7]: overlapping operations count once
    assert r["busy_s"] == pytest.approx(0.4)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["device_ops"] == [["level0/down_fused", pytest.approx(0.3)],
                               ["fusion.7", pytest.approx(0.2)]]
    # gaps [0, 0.1] and [0.7, 1.0] fall in solve calls, [0.4, 0.6] in
    # the report step
    assert dict(r["idle_gaps"]) == {
        "chipbench/solve_call": pytest.approx(0.4),
        "chipbench/report": pytest.approx(0.2)}


def test_two_devices_average():
    two = dict(HAND, device=HAND["device"] * 2)
    r = devtrace.reduce(two)
    assert r["busy_s"] == pytest.approx(0.4)
    assert r["device_ops"][0][1] == pytest.approx(0.3)


def test_no_window_or_no_device_reads_nothing():
    assert devtrace.reduce({"device": HAND["device"], "host": []}) == {}
    assert devtrace.reduce({"device": [], "host": HAND["host"]}) == {}


def test_op_name_prefers_the_scope_path():
    assert devtrace.op_name("fusion.3", {"tf_op": "jit(f)/level1/restrict"}) \
        == "jit(f)/level1/restrict"
    assert devtrace.op_name("fusion.3", {"hlo_op": "fusion.3"}) == "fusion.3"


DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("excerpt", ["v5e_poisson128_excerpt.json",
                                     "v5e_fe85k_excerpt.json"])
def test_recorded_v5e_trace(excerpt):
    """On a v5e the operations nest (a ``while`` spans its body): the
    operations' own times add up to the busy union, and busy plus idle
    time by host step fills the window."""
    trace = json.loads((DATA / excerpt).read_text())
    r = devtrace.reduce(trace, top=10 ** 6)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(r["busy_s"])
    assert sum(s for _, s in r["idle_gaps"]) \
        == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert all(" " not in n for n, _ in r["device_ops"])
    top = [n for n, _ in devtrace.reduce(trace)["device_ops"]]
    assert len(top) == 10
    if "poisson" in excerpt:
        assert {"fused_down_sweep.14", "fused_up_sweep.14"} <= set(top)
