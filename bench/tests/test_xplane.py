"""The reduction from a profiler trace to busy time, op time and idle
gaps: on hand-made intervals, and on a trace recorded on the chip."""
from pathlib import Path

import pytest

from bench import xplane

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_overlap():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2


def test_reduce_by_hand():
    # window [0, 100); ops busy [10, 30) and [50, 60) (two ops overlap)
    ops = [("kernel", 10, 20), ("fusion", 15, 30), ("kernel", 50, 60),
           ("late", 95, 120)]
    # the worker was inside execute over [0, 40) and [45, 100)
    execs = [(0, 40), (45, 100)]
    r = xplane.reduce_device(ops, execs, 0, 100)
    assert r["window_ns"] == 100
    assert r["busy_ns"] == 20 + 10 + 5
    assert r["op_ns"] == {"kernel": 20, "fusion": 15, "late": 5}
    # idle: [0,10) [30,50) [60,95): inside execute all but [40, 45)
    assert r["idle_ns"] == {xplane.IN_EXECUTE: 60, xplane.BETWEEN: 5}
    assert r["longest_gaps"][0] == (xplane.IN_EXECUTE, 35)
    assert sum(ns for _, ns in r["longest_gaps"]) == 65


def test_clock_offset_is_the_median_match():
    execs = [(1, 100, 150), (2, 300, 320), (3, 500, 520), (9, 7, 8)]
    host = {1: 1100, 2: 1300, 3: 1501}
    assert xplane.clock_offset(execs, host) == 1000
    assert xplane.clock_offset(execs, {}) is None


@pytest.fixture(scope="module")
def recorded():
    """A chat-q05 trace recorded on a TPU v5e (one worker, a 1 s window),
    pruned to the device's op line and the host's execute annotations."""
    return xplane.load(str(DATA / "chat-q05.xplane.pb"))


def test_recorded_trace_loads(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    ops = recorded["devices"]["/device:TPU:0"]
    names = {n for n, _, _ in ops}
    assert "%attend_logits.1 (tpu_custom_call)" in names
    steps = [s for s, _, _ in recorded["executes"]]
    assert steps == sorted(steps) and len(steps) > 100


def test_recorded_trace_reduces_like_a_brute_force_count(recorded):
    ops = recorded["devices"]["/device:TPU:0"]
    execs = [(s, e) for _, s, e in recorded["executes"]]
    w0, w1 = execs[50][0], execs[60][0]        # ten steps of the window
    r = xplane.reduce_device(ops, execs, w0, w1)
    # busy time on a 1 us grid
    us = (w1 - w0) // 1000
    busy = [False] * us
    for _, s, e in ops:
        for t in range(max(s, w0), min(e, w1), 1000):
            busy[(t - w0) // 1000] = True
    assert r["busy_ns"] / 1000 == pytest.approx(sum(busy), rel=0.02)
    idle = r["window_ns"] - r["busy_ns"]
    assert sum(r["idle_ns"].values()) == idle
    # the device idles inside execute: the backend's host work
    assert r["idle_ns"][xplane.IN_EXECUTE] > 0.9 * idle
    assert r["longest_gaps"][0][0] == xplane.IN_EXECUTE


def test_recorded_trace_clock_offset(recorded):
    host = {step: s + 123_456_789 for step, s, _ in recorded["executes"]}
    assert xplane.clock_offset(recorded["executes"], host) == 123_456_789
