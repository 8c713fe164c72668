"""The reduction from trace to numbers, on a trace small enough to work by hand
and on a small trace recorded on the chip (tests/data/)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

MS = 1e6  # nanoseconds


def hand_trace():
    """Two steps of 100 ms.  The device runs 10-50 and 60-90 in step one (a
    loop op 60-90 encloses a kernel 65-85), 110-190 in step two."""
    ops = [
        ["fusion.1", 10 * MS, 40 * MS],
        ["while.2", 60 * MS, 30 * MS],
        ["tpuft_fa_fwd.3", 65 * MS, 20 * MS],
        ["fusion.1", 110 * MS, 50 * MS],
        ["jvp_tpuft_ce_lse_.1", 160 * MS, 30 * MS],
    ]
    host = [
        [tr.STEP, 0.0, 100 * MS, 1_000 * MS],  # monotonic clock runs 1000 ms ahead of the trace's
        ["next_batch", 0.0, 5 * MS, None],
        ["ft_step", 5 * MS, 55 * MS, None],
        ["wait_device", 60 * MS, 40 * MS, None],
        [tr.STEP, 100 * MS, 100 * MS, 1_100 * MS],
        ["next_batch", 100 * MS, 5 * MS, None],
        ["ft_step", 105 * MS, 55 * MS, None],
        ["wait_device", 160 * MS, 40 * MS, None],
    ]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_hand_worked_trace():
    # the program's spans on the monotonic clock: a quorum 1000-1008, an exchange 1045-1062
    spans = [("quorum", 1_000 * MS, 1_008 * MS), ("allreduce_d2h", 1_045 * MS, 1_062 * MS),
             ("commit_vote", 1_195 * MS, 1_199 * MS)]
    kernels = {"attn": lambda n: "tpuft_fa" in n, "ce": lambda n: "tpuft_ce" in n}
    out = tr.reduce(hand_trace(), spans, kernels)
    assert out["steps"] == 2 and out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.2)
    assert out["busy_s"] == pytest.approx(0.040 + 0.030 + 0.080)  # the enclosed kernel is not counted twice
    assert out["device_step_s"] == pytest.approx(0.075)
    assert out["clock_offset_ns"] == pytest.approx(-1_000 * MS)
    assert out["kernel_s_per_step"] == {"attn": pytest.approx(0.010), "ce": pytest.approx(0.015)}
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.090)
    assert ops["while.2"] == pytest.approx(0.010)  # 30 ms less the 20 ms it encloses
    # idle 0-10: the quorum span covers 0-8, `ft_step` the rest; 50-60: the exchange span;
    # 90-110: wait_device to 100, next_batch to 105, ft_step after; 190-200: the vote 195-199
    # inside wait_device.  A gap is cut where a span starts or ends.
    idle = dict(out["breakdown"]["idle_gaps"])
    assert idle == {"quorum": pytest.approx(0.008), "ft_step": pytest.approx(0.007),
                    "allreduce_d2h": pytest.approx(0.010), "wait_device": pytest.approx(0.016),
                    "next_batch": pytest.approx(0.005), "commit_vote": pytest.approx(0.004)}
    assert sum(idle.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    # the exchange span 45-62 is exposed only where the device idles inside it: 50-60
    assert out["exposed_exchange_s_per_step"] == [pytest.approx(0.010), pytest.approx(0.0)]


def test_nothing_to_read_gives_nothing():
    empty = {"devices": {}, "host": hand_trace()["host"]}
    assert tr.reduce(empty, [], {}) is None
    no_steps = {"devices": hand_trace()["devices"], "host": []}
    assert tr.reduce(no_steps, [], {}) is None


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [(0, 3), (5, 7)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.length(tr.clip([(0, 10)], 2, 5)) == 3


RECORDED = os.path.join(os.path.dirname(__file__), "data", "trace_v5e_internlm2_2steps.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace in this checkout")
def test_recorded_chip_trace():
    """Two traced steps of internlm2-1.8b.steady-1g recorded on a TPU v5 lite
    (PR 24), with the numbers the reduction gave there: it has to give them
    again, whatever is refactored."""
    with open(RECORDED, encoding="utf-8") as f:
        doc = json.load(f)
    kernels = {"attn": lambda n: "tpuft_fa" in n, "ce": lambda n: "tpuft_ce" in n}
    out = tr.reduce(doc["trace"], [tuple(s) for s in doc["spans"]], kernels)
    want = doc["expected"]
    assert out["steps"] == want["steps"]
    for key in ("window_s", "busy_s", "device_step_s"):
        assert out[key] == pytest.approx(want[key], rel=1e-9)
    for group, seconds in want["kernel_s_per_step"].items():
        assert out["kernel_s_per_step"][group] == pytest.approx(seconds, rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert [n for n, _ in out["breakdown"]["device_ops"][:3]] == [n for n, _ in want["device_ops"][:3]]
