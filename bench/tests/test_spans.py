"""The readers of the program's worker spans and admission stamps, on
hand-made runs: by hand, and silent (None) on a program that records
none of them."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, spans
from repro.profiling import SpanEvent

ROOT = Path(__file__).resolve().parents[2]
PHASES = ("kv_write", "page_gather", "upload", "device_wait", "sample")


def _run(events, results=()):
    """A run whose window is [10, 20) s."""
    requests = [{"in_window": True, "result": r} for r in results]
    return harness.Run(cell=None, setup_s=0.0, window=(10.0, 20.0),
                       t_end=20.0, requests=requests, workers={},
                       traces={}, spans=list(events), peak=None,
                       chips=1)


def _step(role, step, t0, dur, upload_bytes):
    """One worker step: ``execute`` and a span per phase inside it."""
    out = [(role, SpanEvent("execute", t0, dur, step, phase="decode"))]
    part, t = dur / 20, t0
    for i, site in enumerate(PHASES):       # 1, 2, .. 5 parts, in turn
        out.append((role, SpanEvent(
            site, t, (i + 1) * part, step, phase="decode",
            n=upload_bytes if site == "upload" else None)))
        t += (i + 1) * part
    return out


def test_phase_means_by_hand():
    ev = (_step("worker0", 1, 5.0, 1.0, 7e6)         # before the window
          + _step("worker0", 2, 11.0, 1.0, 2e6)
          + _step("worker0", 3, 12.0, 0.5, 4e6)
          + _step("worker1", 2, 11.0, 2.0, 6e6)
          + _step("worker0", 9, 25.0, 1.0, 9e6))     # after it
    run = _run(ev)
    # worker0: steps 2 and 3 (50 and 25 ms a part); worker1: step 2
    for i, site in enumerate(PHASES):
        w0 = (i + 1) * (50.0 + 25.0) / 2
        w1 = (i + 1) * 100.0
        assert spans.ms_per_step(run, site) == pytest.approx((w0 + w1) / 2)
    assert harness.metric_reader("h2d_mb_per_step.code")(run) == \
        pytest.approx(((2 + 4) / 2 + 6) / 2)
    assert harness.metric_reader("kv_write_ms_mean.code")(run) == \
        pytest.approx(spans.ms_per_step(run, "kv_write"))


def test_phase_spans_join_their_own_worker_and_step():
    # a step's phase span counts once, for its worker, even when another
    # worker has the same step id outside the window
    ev = (_step("worker0", 4, 11.0, 1.0, 1e6)
          + [("worker1", SpanEvent("execute", 30.0, 1.0, 4)),
             ("worker1", SpanEvent("sample", 30.0, 1.0, 4)),
             ("engine", SpanEvent("sample", 11.0, 5.0, 4))])
    run = _run(ev)
    assert spans.ms_per_step(run, "sample") == pytest.approx(250.0)


def test_a_program_without_the_spans_reads_none():
    # a parent tree: its worker step span is ``device``, no phase spans,
    # and its events have no ``n``
    old = SimpleNamespace(site="device", t0=11.0, dur=1.0, step=1,
                          req=None, instant=False, phase="decode")
    run = _run([("worker0", old)],
               results=[{"t_tokenize_done": 10.0, "t_first_token": 10.5,
                         "timed_out": False}])
    for name in ("kv_write_ms_mean.code", "page_gather_ms_mean.code",
                 "upload_ms_mean.code", "device_wait_ms_mean.code",
                 "sample_ms_mean.code", "h2d_mb_per_step.code",
                 "queue_wait_p95_ms"):
        assert harness.metric_reader(name)(run) is None, name


def test_queue_wait_by_hand():
    results = [{"t_tokenize_done": 10.0, "t_first_scheduled": 10.0 + w,
                "t_first_token": 11.0, "timed_out": False}
               for w in (0.01, 0.02, 0.03, 0.04, 0.05)]
    results.append({"t_tokenize_done": 10.0, "t_first_scheduled": 19.0,
                    "timed_out": True})               # not finished
    run = _run([], results)
    got = harness.metric_reader("queue_wait_p95_ms")(run)
    assert got == pytest.approx(48.0)                 # numpy's p95 of 10-50


def test_every_new_metric_has_a_reader_and_its_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = {m["name"]: m for m in bench["per_layer"]}
    for name in ("kv_write_ms_mean.code", "page_gather_ms_mean.code",
                 "upload_ms_mean.code", "device_wait_ms_mean.code",
                 "sample_ms_mean.code", "h2d_mb_per_step.code",
                 "queue_wait_p95_ms"):
        assert names[name]["workloads"] == ["code-q05"]
        assert callable(harness.metric_reader(name))
