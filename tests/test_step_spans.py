"""Spans inside a worker's step and the scheduler's admission stamp.

The paged JAX backend traces its phases (``kv_write``, ``page_gather``,
``upload``, ``device_wait``, ``sample``) inside each ``execute``, tagged
with the plan's step id; ``upload`` counts the bytes handed to the
device.  With no profiler the step records nothing and samples the same
tokens.  In a JAX worker the spans are also annotations on the device
profiler's clock.  Every finished request carries the time of its first
plan between its tokenization and its first token."""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp

from repro import profiling
from repro.backend import jax_backend
from repro.backend.jax_backend import JaxBackend
from repro.core.devmodel import DeviceModel
from repro.core.engine import EngineConfig, ServingSystem
from repro.profiling import ProfilingConfig
from repro.serving.scheduler import StepPlan

PHASES = ("kv_write", "page_gather", "upload", "device_wait", "sample")
BLOCK, NBLOCKS = 8, 16


def _plans():
    """Two prefills (one of two chunks), then a mixed step, then a
    decode step."""
    tables = {1: [0, 1, 2], 2: [3]}
    return [
        StepPlan(1, [(1, 0, 12), (2, 0, 5)], [], [],
                 block_tables=tables,
                 new_tokens={1: list(range(12)), 2: list(range(5))}),
        StepPlan(2, [(1, 12, 4)], [2], [], block_tables=tables,
                 new_tokens={1: list(range(12, 16)), 2: [7]}),
        StepPlan(3, [], [1, 2], [], block_tables=tables,
                 new_tokens={1: [3], 2: [9]}),
    ]


def _drive(prof=None):
    """Run the plans on a fresh backend; per step, its tokens and the
    host-clock interval of its ``execute`` call."""
    be = JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS, vocab=128)
    if prof is not None:
        profiling.install(prof)
    try:
        out = []
        for plan in _plans():
            t0 = time.perf_counter()
            res = be.execute(plan)
            out.append((plan, res.tokens, t0,
                        time.perf_counter()))
        return out
    finally:
        profiling.deactivate()


def test_each_step_has_one_span_per_phase_inside_its_execute():
    prof = profiling.Profiler(ProfilingConfig(trace=True), role="w")
    steps = _drive(prof)
    for plan, _, t0, t1 in steps:
        evs = [ev for ev in prof.events if ev.step == plan.step_id]
        assert sorted(ev.site for ev in evs) == sorted(PHASES)
        for ev in evs:
            assert t0 <= ev.t0 and ev.t0 + ev.dur <= t1, ev
            assert ev.phase == plan.phase
        # in the order the step runs them, none overlapping
        order = sorted(evs, key=lambda ev: ev.t0)
        assert [ev.site for ev in order] == list(PHASES)
        for a, b in zip(order, order[1:]):
            assert a.t0 + a.dur <= b.t0
    assert len(prof.events) == len(PHASES) * len(steps)


def test_upload_counts_the_bytes_handed_to_the_device(monkeypatch):
    sent = []

    def asarray(a, *args, **kw):
        sent.append((a.shape, a.nbytes))
        return jnp.asarray(a, *args, **kw)

    monkeypatch.setattr(jax_backend, "jnp",
                        SimpleNamespace(asarray=asarray))
    prof = profiling.Profiler(ProfilingConfig(trace=True), role="w")
    steps = _drive(prof)
    # float32 pool: every step hands over q, K pages, V pages, block
    # table, lengths; the first also the projection, kept from then on
    assert len(sent) == 1 + 5 * len(steps)
    per_step = [sent[:6]] + [sent[i:i + 5] for i in range(6, len(sent), 5)]
    ups = [ev for ev in prof.events if ev.site == "upload"]
    assert [ev.n for ev in ups] == [sum(n for _, n in s) for s in per_step]
    # the projection is E x vocab float32s, in the first step only
    proj = ((64, 128), 64 * 128 * 4)
    assert per_step[0][-1] == proj
    assert all(proj not in s for s in per_step[1:])
    assert all(ev.n < proj[1] for ev in ups[1:])
    assert all(ev.n is None for ev in prof.events if ev.site != "upload")


def test_no_profiler_records_nothing_and_samples_the_same_tokens():
    calls = []
    prof = profiling.Profiler(ProfilingConfig(trace=True), role="w")
    prof.annotate = lambda *a, **kw: calls.append(a) or _Null()
    traced = _drive(prof)
    n_events, n_calls = len(prof.events), len(calls)
    assert profiling.active() is None
    plain = _drive()
    assert [t for _, t, _, _ in plain] == [t for _, t, _, _ in traced]
    assert len(prof.events) == n_events and len(calls) == n_calls
    assert profiling.span("upload") is profiling.NO_SPAN


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def test_spans_open_annotations_with_their_step():
    """The ``annotate`` hook: each span opens an annotation of its own
    name, with ``step=``, around itself."""
    opened = []

    class Ann(_Null):
        def __init__(self, name, **kw):
            self.rec = [name, kw, time.perf_counter(), None]
            opened.append(self.rec)

        def __exit__(self, *exc):
            self.rec[3] = time.perf_counter()

    prof = profiling.Profiler(ProfilingConfig(trace=True), role="w")
    prof.annotate = Ann
    _drive(prof)
    assert len(opened) == len(prof.events)
    for (name, kw, a0, a1), ev in zip(opened, prof.events):
        assert name == ev.site and kw == {"step": ev.step}
        assert a0 <= ev.t0 and ev.t0 + ev.dur <= a1


def test_annotations_land_in_the_device_trace_inside_execute(tmp_path):
    """With the real hook and a profiler session, the phase annotations
    sit on the host plane inside the step's ``execute``, with its id."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    be = JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS, vocab=128)
    plans = _plans()
    be.execute(plans[0])                     # compile outside the trace
    prof = profiling.Profiler(ProfilingConfig(trace=True), role="w")
    prof.annotate = TraceAnnotation
    profiling.install(prof)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for plan in plans[1:]:
            with prof.span("execute", step=plan.step_id, phase=plan.phase):
                be.execute(plan)
    finally:
        jax.profiler.stop_trace()
        profiling.deactivate()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    got = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name in ("execute",) + PHASES:
                    step = dict(ev.stats).get("step")
                    got.setdefault(step, {})[ev.name] = (
                        int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
    assert set(got) == {p.step_id for p in plans[1:]}
    for step, names in got.items():
        assert set(names) == {"execute", *PHASES}
        s, e = names["execute"]
        for site in PHASES:
            assert s <= names[site][0] and names[site][1] <= e, site


def test_live_results_stamp_the_first_plan():
    """Emulated workers: every finished request was first scheduled
    after its tokenization and before its first token."""
    cfg = EngineConfig(
        tp_degree=1, pool_width=1, yield_every=64,
        device=DeviceModel(t_fixed=1e-4, t_prefill_tok=1e-7,
                           t_decode_seq=1e-5))
    sys_ = ServingSystem(cfg).start()
    try:
        for i in range(6):
            sys_.submit("the quick brown fox " * (2 + i), max_new_tokens=3)
        results = sys_.collect(6, timeout=60.0)
    finally:
        sys_.shutdown()
    assert len(results) == 6
    for rec in results.values():
        assert not rec["timed_out"]
        assert (rec["t_tokenize_done"] <= rec["t_first_scheduled"]
                <= rec["t_first_token"])


# Runs in a fresh interpreter: a worker forked from a process in which JAX
# has already started (as it has in a test process) can hang in JAX.
_TRACED_ENGINE = """
import json
from repro.configs import get_config
from repro.core.engine import EngineConfig, ServingSystem
from repro.profiling import ProfilingConfig, events_from_stats
from repro.serving.scheduler import SchedulerConfig

cfg = EngineConfig(
    tp_degree=1, pool_width=1, backend="jax", yield_every=64,
    model=get_config("qwen2-0.5b").scaled(d_model=64, n_heads=4,
                                          n_kv_heads=2, vocab_size=256),
    scheduler=SchedulerConfig(kv_capacity_tokens=512, block_size=8),
    profiling=ProfilingConfig(trace=True))
sys_ = ServingSystem(cfg).start()
try:
    for i in range(3):
        sys_.submit("the quick brown fox " * (2 + i), max_new_tokens=3)
    results = sys_.collect(3, timeout=120.0)
finally:
    stats = sys_.shutdown()
print(json.dumps({
    "results": list(results.values()),
    "spans": [[role, ev.site, ev.t0, ev.dur, ev.step, ev.n]
              for role, ev in events_from_stats(stats)]}))
"""


def test_live_jax_worker_phase_spans_lie_inside_its_execute():
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    proc = subprocess.run([sys.executable, "-c", _TRACED_ENGINE], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(out["results"]) == 3
    for rec in out["results"]:
        assert not rec["timed_out"]
        assert (rec["t_tokenize_done"] <= rec["t_first_scheduled"]
                <= rec["t_first_token"])
    execs = {step: (t0, t0 + dur)
             for role, site, t0, dur, step, _ in out["spans"]
             if role == "worker0" and site == "execute"}
    assert execs
    seen = {}
    for role, site, t0, dur, step, n in out["spans"]:
        if site not in PHASES:
            continue
        assert role == "worker0"
        s, e = execs[step]
        assert s <= t0 and t0 + dur <= e, (site, step)
        seen.setdefault(step, []).append(site)
        assert (n is not None and n > 0) == (site == "upload")
    assert set(seen) == set(execs)
    assert all(sorted(v) == sorted(PHASES) for v in seen.values())
