"""Speed-bump harness pins: the zero-overhead oracle and trace contract.

The profiling subsystem (repro.profiling, docs/profiling.md) is only
trustworthy if measuring changes nothing: an engine run with tracing
enabled and zero injected delay must be *bit-identical* to an
uninstrumented run — same completion order, same token streams — and a
DES run with a zero-delay profiler must land on exactly the same event
arithmetic as one with no profiler at all.  That oracle is pinned here
across every backend and the copy-stream / multi-step axes, alongside:

  * spec-grammar units (``parse_inject`` accepts, rejects, overrides);
  * trace well-formedness properties under preempt/swap/restore/abort
    churn (spans balanced and non-negative, completion-ordered per
    role, every recorded request id was actually admitted);
  * Chrome-trace export round-trip + critical-path-summary invariants
    (``0 <= exposed <= total`` per site, device spans are the cover
    set, never a summarized site);
  * the monotone-sensitivity regression: injecting delay at the
    scheduler site never *increases* DES throughput, and the
    amplification slope (makespan seconds lost per second injected —
    the cross-budget metric benchmarks/speed_bump.py fits) is at least
    as steep at 1 core as at 32 — the paper's thesis as a regression
    test.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                       # pragma: no cover
    from _hypothesis_fallback import given, settings, strategies as st

from repro import profiling
from repro.backend import EmulatedBackend
from repro.core.devmodel import DeviceModel
from repro.profiling import (SITES, Profiler, ProfilingConfig, SpanEvent,
                             critical_path_summary, events_from_stats,
                             export_chrome_trace, format_summary,
                             parse_inject)
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.sim.serving import (ServingModel, llama8b_tp4_params,
                               with_async_copies, with_multi_step)

BLOCK, NBLOCKS, NSWAP = 8, 64, 32
# trace-only span sites (no injection): the engine's barrier, the
# worker's step and the backends' phases inside it
TRACE_ONLY = ("barrier", "execute", "kv_write", "page_gather", "upload",
              "device_wait", "sample")

# ~1.5 requests resident under swap: preempt/swap/restore churn
# (mirrors the pressure configs of the conformance + copy-engine suites)
def pressure_cfg(copy_streams: int = 0, multi_step: int = 1,
                 **kw) -> SchedulerConfig:
    return SchedulerConfig(
        max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
        enable_prefix_cache=False, block_size=BLOCK,
        kv_capacity_tokens=9 * BLOCK, preemption_policy="swap",
        swap_capacity_tokens=NSWAP * BLOCK, copy_streams=copy_streams,
        max_steps_per_dispatch=multi_step, **kw)


def make(name: str, cfg: SchedulerConfig):
    from repro.backend.cpu_decode import CpuDecodeBackend
    from repro.backend.hybrid import HybridBackend
    from repro.backend.jax_backend import JaxBackend
    kw = dict(block_size=cfg.block_size, num_blocks=cfg.num_kv_blocks,
              num_swap_blocks=cfg.num_swap_blocks,
              copy_streams=cfg.copy_streams, vocab=128)
    if name == "emulated":
        return EmulatedBackend(DeviceModel(t_fixed=1e-5, t_prefill_tok=1e-8,
                                           t_decode_seq=1e-6,
                                           copy_streams=cfg.copy_streams))
    if name == "jax":
        return JaxBackend(**kw)
    if name == "cpu":
        return CpuDecodeBackend(**kw)
    if name == "hybrid":
        return HybridBackend(JaxBackend(**kw), CpuDecodeBackend(**kw),
                             t_handoff_block=1e-6,
                             copy_streams=cfg.copy_streams)
    raise AssertionError(name)


def _reqs(specs):
    out = []
    for i, (n, m) in enumerate(specs):
        r = Request(text="", max_new_tokens=m)
        base = (i + 1) << 10
        r.prompt_tokens = [3 + ((base + j) % 100) for j in range(n)]
        out.append(r)
    return out


def _drive(backend, cfg, reqs, max_steps=800):
    """Run to completion; (completion order by workload position, token
    counts, token streams) — the bit-identity triple."""
    sched = Scheduler(cfg)
    for r in reqs:
        sched.add_request(r)
    idx_of = {r.req_id: i for i, r in enumerate(reqs)}
    order, step = [], 0
    while sched.has_work and step < max_steps:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        res = backend.execute(plan)
        for req in sched.complete_step(plan, float(step), res):
            order.append(idx_of[req.req_id])
            if hasattr(backend, "release"):
                backend.release(req.req_id)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    counts = {idx_of[r.req_id]: len(r.generated) for r in reqs}
    tokens = {idx_of[r.req_id]: list(r.generated) for r in reqs}
    return order, counts, tokens


# -- spec grammar ------------------------------------------------------------


def test_parse_inject_grammar():
    assert parse_inject("") == {}
    assert parse_inject("scheduler=100") == \
        {"scheduler": pytest.approx(100e-6)}
    # the speed-bump exemplar's colon separator is accepted too
    assert parse_inject("dispatch:250") == \
        {"dispatch": pytest.approx(250e-6)}
    # '*' targets the whole catalogue; later entries override
    d = parse_inject("*=100,tokenize=0")
    assert set(d) == set(SITES)
    assert d["tokenize"] == 0.0
    assert d["scheduler"] == pytest.approx(100e-6)
    with pytest.raises(ValueError, match="unknown injection site"):
        parse_inject("schedular=100")          # typo must not fit a 0 slope
    with pytest.raises(ValueError, match="negative"):
        parse_inject("scheduler=-5")


def test_profiling_config_gate(monkeypatch):
    assert not ProfilingConfig().enabled
    assert ProfilingConfig(inject="*=0").enabled      # explicit zeros count
    assert ProfilingConfig(trace=True).enabled
    # an all-default config installs nothing: the fast path stays None
    monkeypatch.delenv(profiling.ENV_INJECT, raising=False)
    monkeypatch.delenv(profiling.ENV_TRACE, raising=False)
    assert profiling.activate(ProfilingConfig()) is None
    assert profiling.active() is None
    # the env spec reaches entry points that never touch the config
    monkeypatch.setenv(profiling.ENV_INJECT, "scheduler=42")
    prof = profiling.activate(ProfilingConfig(), role="envtest")
    try:
        assert prof is not None
        assert prof.delays["scheduler"] == pytest.approx(42e-6)
    finally:
        profiling.deactivate()
    assert profiling.active() is None


# -- zero-overhead oracle (live scheduler + backend path) --------------------


@pytest.mark.parametrize("name", ("emulated", "jax", "cpu", "hybrid"))
def test_oracle_traced_run_bit_identical(name):
    """Tracing on, delays zero: the instrumented run's completion order,
    token counts, and token streams equal the uninstrumented run's —
    across copy_streams {0, 2} x multi-step {1, 4} on every backend.
    Measurement must not perturb the thing measured."""
    specs = [(40, 8), (37, 8)]
    for streams in (0, 2):
        for k in (1, 4):
            cfg = pressure_cfg(copy_streams=streams, multi_step=k)
            base = _drive(make(name, cfg), cfg, _reqs(specs))
            prof = profiling.activate(
                ProfilingConfig(inject="*=0", trace=True), role="oracle")
            try:
                traced = _drive(make(name, cfg), cfg, _reqs(specs))
            finally:
                profiling.deactivate()
            assert traced == base, (name, streams, k)
            # the oracle is only meaningful if instrumentation really ran
            assert any(ev.site == "block_alloc" for ev in prof.events), \
                (name, streams, k)
            if streams > 0:
                assert any(ev.site == "copy_submit" for ev in prof.events)
            assert prof.charged == 0.0


# -- zero-overhead oracle (DES) ----------------------------------------------


def _des_run(params, n_req=5):
    model = ServingModel(params)
    for i in range(n_req):
        model.add_request(0.05 * i, 600, max_new_tokens=24, stream=i)
    res = model.run(horizon=120.0)
    sig = [(r.t_arrival, r.t_first_token, r.t_done, len(r.generated))
           for r in res.requests]
    assert all(r.t_done for r in res.requests)
    return res, sig


@pytest.mark.parametrize("variant", ("plain", "copies", "multistep"))
def test_oracle_des_zero_delay_bit_exact(variant):
    """A profiler whose delays are all zero is indistinguishable from no
    profiler: identical sim_time, scheduler-invocation count, and
    per-request timestamps — not approximately, exactly.  This is what
    licenses leaving the instrumentation compiled into the sim procs."""
    params = llama8b_tp4_params(2, preemption_policy="swap",
                                kv_capacity_tokens=4096)
    if variant == "copies":
        params = with_async_copies(params, copy_streams=2)
    elif variant == "multistep":
        params = with_multi_step(params, k=4)
    base_res, base_sig = _des_run(params)
    prof_res, prof_sig = _des_run(
        dataclasses.replace(params, inject="*=0"))
    assert prof_sig == base_sig
    assert prof_res.sched_costs == base_res.sched_costs
    # and a non-zero delay visibly moves the same signature (the oracle
    # is falsifiable: the injection path really is wired in)
    _, bumped_sig = _des_run(
        dataclasses.replace(params, inject="scheduler=5000"))
    assert bumped_sig != base_sig
    assert max(t for *_, t, _ in bumped_sig) > \
        max(t for *_, t, _ in base_sig)


# -- trace well-formedness under churn ----------------------------------------


@settings(max_examples=12, deadline=None)
@given(st.lists(st.integers(min_value=9, max_value=44), min_size=2,
                max_size=5),
       st.integers(min_value=3, max_value=10),
       st.integers(min_value=2, max_value=7))
def test_trace_wellformed_under_churn(prompt_lens, timeout, abort_every):
    """Under swap/restore churn with aborts landing at arbitrary points
    (including while a restore copy is in flight, and mid-macro): every
    span closes with non-negative duration, instants have zero duration,
    per-role events are ordered by completion time (the append order a
    lock-free list gives), and every event that names a request names
    one that was actually admitted."""
    cfg = pressure_cfg(copy_streams=2, multi_step=4)
    reqs = _reqs([(n, 2 + n % 7) for n in prompt_lens])
    prof = profiling.activate(ProfilingConfig(inject="*=0", trace=True),
                              role="churn")
    try:
        sched = Scheduler(cfg)
        backend = make("emulated", cfg)
        for r in reqs:
            sched.add_request(r)
        admitted = {r.req_id for r in reqs}
        step, n_sched_calls = 0, 0
        while sched.has_work and step < 600:
            with prof.span("scheduler", step=sched.step_id):
                plan = sched.schedule()
            n_sched_calls += 1
            if plan is None:
                break
            step += 1
            if step % abort_every == 0:
                # expire() is the abort path: anything older than the
                # timeout drops, whatever state it is in (RESTORING
                # included — the abort-while-restoring seam)
                sched.expire(float(step), float(timeout))
            res = backend.execute(plan)
            sched.complete_step(plan, float(step), res)
    finally:
        profiling.deactivate()
    events = prof.events
    assert events, "churn run recorded nothing"
    done = 0.0
    for ev in events:
        assert ev.dur >= 0.0
        if ev.instant:
            assert ev.dur == 0.0
        # append order == completion order within one role's list
        assert ev.t0 + ev.dur >= done
        done = ev.t0 + ev.dur
        if ev.req is not None:
            assert ev.req in admitted, ev
        assert ev.site in SITES or ev.site in TRACE_ONLY
    # spans balanced: one scheduler span per schedule() call, no more
    n_sched_spans = sum(1 for ev in events
                        if ev.site == "scheduler" and not ev.instant)
    assert n_sched_spans == n_sched_calls


# -- export round trip + critical-path summary --------------------------------


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=10_000), min_size=1,
                max_size=40),
       st.integers(min_value=1, max_value=3))
def test_chrome_trace_export_roundtrip(raw, n_roles):
    """Arbitrary merged event soup -> valid trace_event JSON: one record
    per event, timestamps rebased non-negative, durations non-negative,
    instants flagged, one thread_name metadata record per role."""
    pairs = []
    for i, v in enumerate(raw):
        role = f"role{v % n_roles}"
        site = SITES[v % len(SITES)] if v % 3 else "device_wait"
        pairs.append((role, SpanEvent(site, t0=100.0 + (v % 97) * 1e-4,
                                      dur=(v % 13) * 1e-5,
                                      step=v % 7 or None,
                                      req=v % 5 or None,
                                      instant=(v % 11 == 0))))
    pairs.sort(key=lambda p: p[1].t0)
    path = os.path.join(tempfile.mkdtemp(), "trace.json")
    n = export_chrome_trace(pairs, path)
    assert n == len(pairs)
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    meta = [e for e in evs if e.get("ph") == "M"]
    body = [e for e in evs if e.get("ph") in ("X", "i")]
    assert len(body) == len(pairs)
    assert len(meta) == len({role for role, _ in pairs})
    for e in body:
        assert e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        else:
            assert e["s"] == "t"
    # summary invariants: device_wait is the cover set, never a row;
    # exposed time is bounded by total time per site
    summary = critical_path_summary(pairs)
    assert "device_wait" not in summary
    for site, s in summary.items():
        assert 0.0 <= s["exposed_s"] <= s["total_s"] + 1e-12, site
        assert s["count"] >= 1
    assert format_summary(summary).splitlines()  # renders without blowing up


def test_critical_path_summary_overlap_math():
    """Hand-built timeline: a span fully covered by device time exposes
    nothing, a half-covered one exposes exactly the uncovered half."""
    pairs = events_from_stats([
        {"role": "w0", "trace_events": [SpanEvent("device_wait", 0.0,
                                                   10.0)]},
        {"role": "eng", "trace_events": [
            SpanEvent("scheduler", 2.0, 4.0),          # inside device
            SpanEvent("shm_encode", 8.0, 4.0),         # half exposed
            SpanEvent("tokenize", 20.0, 3.0),          # fully exposed
            SpanEvent("block_alloc", 1.0, 0.0, instant=True),
        ]},
    ])
    s = critical_path_summary(pairs)
    assert s["scheduler"]["exposed_s"] == pytest.approx(0.0)
    assert s["shm_encode"]["exposed_s"] == pytest.approx(2.0)
    assert s["tokenize"]["exposed_s"] == pytest.approx(3.0)
    assert s["block_alloc"]["total_s"] == 0.0          # instants: count only
    assert s["block_alloc"]["count"] == 1


# -- monotone sensitivity (the thesis as a regression test) -------------------


def _bump_run(n_cores: int, inject: str):
    params = llama8b_tp4_params(n_cores, preemption_policy="swap",
                                kv_capacity_tokens=3_520)
    params = with_async_copies(params, copy_streams=2)
    params = dataclasses.replace(params, inject=inject)
    model = ServingModel(params)
    for i in range(6):
        model.add_request(0.0, 800, max_new_tokens=256, stream=i)
    res = model.run(horizon=300.0)
    done = [r for r in res.requests if r.t_done]
    assert len(done) == 6, "sweep workload must complete"
    toks = sum(len(r.generated) for r in done)
    makespan = max(r.t_done for r in done)
    charged = model.prof.charged if model.prof is not None else 0.0
    return toks / makespan, makespan, charged


def test_scheduler_bump_monotone_and_sharper_when_starved():
    """Slowing the scheduler can only hurt: DES throughput is
    non-increasing in the injected delay at every core budget.  And the
    amplification slope — makespan seconds lost per second of delay
    actually charged — is steeper at 1 core than at 32: with cores to
    spare the bump hides behind the device (amplification ~<= 1), while
    under GPS contention every injected second also delays everyone
    sharing the core (the paper's CPU-starvation thesis, quantified)."""
    amps = {}
    for cores in (1, 32):
        tput0, makespan0, _ = _bump_run(cores, "")
        prev = tput0
        pts = []
        for delay_us in (300.0, 1000.0):
            tput, makespan, charged = _bump_run(
                cores, f"scheduler={delay_us:g}")
            assert charged > 0.0
            assert tput <= prev + 1e-9, \
                f"throughput rose with delay at {cores} cores"
            prev = tput
            pts.append((charged, makespan - makespan0))
        # least squares through the origin: seconds lost per second injected
        amps[cores] = (sum(c * d for c, d in pts)
                       / sum(c * c for c, _ in pts))
    assert amps[1] >= amps[32], amps
    # starved amplification really is contention (> 1), not pass-through
    assert amps[1] > 1.0, amps
