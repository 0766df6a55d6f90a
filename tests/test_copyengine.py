"""Async copy engine: epoch contract, overlap cost model, conformance.

What the copy engine must guarantee (docs/copy_engine.md):

  * cost — transfers hide behind compute (``max`` not ``sum``) with
    ``copy_streams >= 1``, and CPU-starved submission degrades the
    overlapped cost back to (and past) the serialized one;
  * epochs — a block is never read before its copy completes: an
    in-flight swap-out's source blocks are never reallocated in the
    submitting plan, a restoring request is never scheduled before its
    restore epoch retires, and the scheduler's in-flight bookkeeping
    drains to zero;
  * bit-identity — the physical backends' deferred page copies produce
    token streams identical to the serialized baseline for
    ``copy_streams`` in {0, 1, 2} (conformance parameterization);
  * no leaks — preempt/abort while a transfer is in flight still frees
    every device and host block and every backend-side entry.

The cost-aware victim selection, delta block tables, and the
``CpuSampler`` drift fix ride along (same PR, same seams).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.backend.cpu_decode import CpuDecodeBackend
from repro.backend.hybrid import HybridBackend
from repro.backend.jax_backend import JaxBackend
from repro.core.copyengine import CopyEngine, overlapped_seconds
from repro.core.cpuutil import CpuSampler
from repro.core.devmodel import DeviceModel
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import (BlockTableTracker, Scheduler,
                                     SchedulerConfig, StepPlan)

BLOCK, NBLOCKS, NSWAP = 8, 64, 32

# ~1.5 requests resident: forces swap churn mid-workload (mirrors the
# backend conformance suite's pressure config)
def pressure_cfg(copy_streams: int, **kw) -> SchedulerConfig:
    return SchedulerConfig(
        max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
        enable_prefix_cache=False, block_size=BLOCK,
        kv_capacity_tokens=9 * BLOCK, preemption_policy="swap",
        swap_capacity_tokens=NSWAP * BLOCK, copy_streams=copy_streams,
        **kw)


def make_physical(name: str, cfg: SchedulerConfig):
    kw = dict(block_size=cfg.block_size, num_blocks=cfg.num_kv_blocks,
              num_swap_blocks=cfg.num_swap_blocks,
              copy_streams=cfg.copy_streams, vocab=128)
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


def drive(backend, cfg, reqs, max_steps=800, check_epochs=True):
    """Run to completion, asserting the epoch-ordering invariants on
    every plan: no in-flight page is read or reallocated before its
    copy lands."""
    sched = Scheduler(cfg)
    for r in reqs:
        sched.add_request(r)
    step = 0
    while sched.has_work and step < max_steps:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        if cfg.copy_streams > 0 and check_epochs:
            # an in-flight swap-out's SOURCE blocks are held until the
            # epoch retires: no table in the submitting plan may
            # reference them (the serialized contract's same-plan-reuse
            # hazard must be impossible here)
            outgoing = {b for pairs in plan.swap_outs.values()
                        for b, _ in pairs}
            restore_targets = {d for pairs in plan.restores.values()
                               for _, d in pairs}
            for rid, table in plan.block_tables.items():
                assert not outgoing & set(table), \
                    "in-flight swap-out source reallocated same-plan"
                assert not restore_targets & set(table), \
                    "restore target read before its copy landed"
            # a restoring request re-enters the batch only after its
            # epoch completes: never scheduled in the submitting plan
            for rid in plan.restores:
                assert rid not in plan.decode
                assert all(rid != e[0] for e in plan.prefill)
        res = backend.execute(plan)
        for req in sched.complete_step(plan, float(step), res):
            if hasattr(backend, "release"):
                backend.release(req.req_id)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    if sched.copies is not None:
        assert sched.copies.in_flight == 0
    assert sched.blocks.free_blocks == sched.blocks.num_blocks
    if sched.blocks.swap_space is not None:
        assert sched.blocks.swap_space.used_blocks == 0
    return {r.req_id: list(r.generated) for r in reqs}, sched


# -- cost model ------------------------------------------------------------


def test_overlapped_seconds_hides_copies_behind_compute():
    kw = dict(copy_streams=1, t_copy_block=1e-3, t_submit_per_copy=1e-6)
    # ample compute: 10 blocks of copy (10 ms) hide behind 20 ms compute
    assert overlapped_seconds(20e-3, 10, **kw) == \
        pytest.approx(20e-3 + 10 * 1e-6)
    # copy-bound: the un-hidden drain surfaces
    assert overlapped_seconds(5e-3, 10, **kw) == \
        pytest.approx(10e-3 + 10 * 1e-6)
    # two streams halve the drain
    kw2 = dict(kw, copy_streams=2)
    assert overlapped_seconds(5e-3, 10, **kw2) == \
        pytest.approx(5e-3 + 10 * 1e-6)
    # serialized: the sum, no submission charge
    kw0 = dict(kw, copy_streams=0)
    assert overlapped_seconds(20e-3, 10, **kw0) == pytest.approx(30e-3)
    # no copies: pure compute either way
    assert overlapped_seconds(7e-3, 0, **kw) == 7e-3


def test_overlap_degrades_to_serialized_under_cpu_starvation():
    """As the CPU submission cost grows (scarce/contended cores), the
    overlapped step cost climbs monotonically back to — and past — the
    serialized cost: the engine cannot beat its own submission path."""
    serialized = overlapped_seconds(10e-3, 20, copy_streams=0,
                                    t_copy_block=1e-3, t_submit_per_copy=0)
    costs = [overlapped_seconds(10e-3, 20, copy_streams=1,
                                t_copy_block=1e-3, t_submit_per_copy=ts)
             for ts in (1e-6, 1e-4, 5e-4, 1e-3, 2e-3)]
    assert costs == sorted(costs)
    assert costs[0] < serialized          # ample CPU: transfers hidden
    assert costs[-1] > serialized         # starved: worse than inline


def test_devmodel_step_time_overlaps_swap_traffic():
    plan = StepPlan(1, [(1, 0, 100)], [2], [],
                    swap_outs={3: [(i, i) for i in range(10)]})
    base = DeviceModel(t_fixed=1e-3, t_prefill_tok=1e-5, t_decode_seq=1e-4,
                       t_block_entry=0.0, t_swap_block=1e-4)
    compute = 1e-3 + 100 * 1e-5 + 1e-4
    assert base.step_time(plan) == pytest.approx(compute + 10 * 1e-4)
    over = dataclasses.replace(base, copy_streams=1, t_submit_per_copy=1e-6)
    # 1 ms of copies hides behind 2.1 ms of compute
    assert over.step_time(plan) == pytest.approx(compute + 10 * 1e-6)
    # cpu_tier preserves the copy-engine shape
    assert over.cpu_tier().copy_streams == 1


def test_hybrid_step_cost_overlaps_handoff():
    pre_dev = DeviceModel(t_fixed=0.0, t_prefill_tok=1e-3, t_decode_seq=0.0,
                          t_block_entry=0.0, t_swap_block=0.0)
    dec_dev = DeviceModel(t_fixed=0.0, t_prefill_tok=0.0, t_decode_seq=1e-2,
                          t_block_entry=0.0, t_swap_block=0.0)
    from repro.backend.emulated import EmulatedBackend
    plan = StepPlan(1, [(1, 0, 20)], [], [], block_tables={1: [0, 1, 2]},
                    prefill_done=[1])
    serial = HybridBackend(EmulatedBackend(pre_dev, sleep=False),
                           EmulatedBackend(dec_dev, sleep=False),
                           t_handoff_block=1e-3)
    assert serial.step_cost(plan) == pytest.approx(20e-3 + 3e-3)
    overlapped = HybridBackend(EmulatedBackend(pre_dev, sleep=False),
                               EmulatedBackend(dec_dev, sleep=False),
                               t_handoff_block=1e-3, copy_streams=1,
                               t_submit_per_copy=1e-6)
    # 3 ms of handoff hides behind the 20 ms prefill
    assert overlapped.step_cost(plan) == pytest.approx(20e-3 + 3e-6)


# -- engine bookkeeping ----------------------------------------------------


def test_copy_engine_epochs_retire_in_order():
    eng = CopyEngine(1)
    order = []
    eng.submit(1, "swap_out", 7, 2, on_complete=lambda: order.append("a"))
    eng.submit(1, "restore", 8, 2, on_complete=lambda: order.append("b"))
    eng.submit(2, "swap_out", 9, 1, on_complete=lambda: order.append("c"))
    assert eng.in_flight == 3 and eng.in_flight_blocks == 5
    done = eng.retire(1)
    assert [t.req_id for t in done] == [7, 8]
    assert order == ["a", "b"]            # submission order preserved
    assert eng.retire(1) == []            # idempotent
    eng.retire(2)
    assert order == ["a", "b", "c"] and eng.in_flight == 0


# -- conformance: bit-identity across stream counts ------------------------


@pytest.fixture(scope="module")
def serialized_reference():
    """Token streams of the serialized (pre-engine) jax path under swap
    pressure — the oracle every stream count must reproduce."""
    cfg = pressure_cfg(0)
    tokens, _ = drive(make_physical("jax", cfg), cfg,
                      _reqs([(40, 8), (37, 8)]))
    return tokens


@pytest.mark.parametrize("streams", [0, 1, 2])
@pytest.mark.parametrize("name", ["jax", "cpu", "hybrid"])
def test_tokens_bit_identical_across_copy_streams(name, streams,
                                                  serialized_reference):
    """Deferred physical copies must be invisible in the output: same
    pressured workload, any backend, any stream count -> the serialized
    jax token streams, exactly."""
    cfg = pressure_cfg(streams)
    tokens, _ = drive(make_physical(name, cfg), cfg,
                      _reqs([(40, 8), (37, 8)]))
    assert _values_by_position(tokens) == \
        _values_by_position(serialized_reference)


def _values_by_position(tokens):
    """Compare by workload position (req ids differ across instances)."""
    return [tokens[k] for k in sorted(tokens)]


def test_pressure_workload_actually_swaps_with_streams():
    cfg = pressure_cfg(1)
    reqs = _reqs([(40, 8), (37, 8)])
    drive(make_physical("cpu", cfg), cfg, reqs)
    assert sum(r.n_swaps for r in reqs) >= 1, "expected swap traffic"
    assert any(any(t != 0 for t in r.generated) for r in reqs)


# -- in-flight no-leak under preempt/abort ---------------------------------


def test_abort_while_restore_in_flight_leaks_nothing():
    """A request that times out while its restore copy is in flight:
    host blocks release and device blocks free when the epoch retires,
    and the workers get a state-drop notice on the next plan."""
    cfg = pressure_cfg(1)
    be = make_physical("cpu", cfg)
    reqs = _reqs([(40, 8), (37, 8)])
    sched = Scheduler(cfg)
    for r in reqs:
        sched.add_request(r)
    aborted = None
    step = 0
    while sched.has_work and step < 800:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        if aborted is None and sched.restoring:
            # fire the client timeout while the copy is mid-flight
            victim = sched.restoring[0]
            dead = sched.expire(now=1e9, timeout=1.0)
            assert victim in dead
            assert victim.state == RequestState.TIMED_OUT
            aborted = victim
        res = be.execute(plan)
        if aborted is not None and aborted.req_id in plan.preempted:
            aborted = "notified"
        sched.complete_step(plan, float(step), res)
    assert aborted == "notified", "restore-abort drop notice never shipped"
    assert sched.copies.in_flight == 0
    assert sched.blocks.free_blocks == sched.blocks.num_blocks
    assert sched.blocks.swap_space.used_blocks == 0
    assert not be._deferred._pending


def test_preempted_rids_drop_pending_deferred_copies():
    """plan.preempted discards a request's deferred page copies — dead
    data must never land late into pages another request now owns."""
    be = make_physical("cpu", pressure_cfg(1))
    toks = [3 + (i % 60) for i in range(16)]
    be.execute(StepPlan(1, [(1, 0, 16)], [], [],
                        block_tables={1: [3, 7]}, new_tokens={1: toks}))
    be.execute(StepPlan(2, [], [], [], swap_outs={1: [(3, 0), (7, 1)]}))
    assert len(be._deferred) == 1          # copy-out deferred, not applied
    assert np.abs(be.k_swap[:, [0, 1]]).sum() == 0
    be.execute(StepPlan(3, [], [], [1]))
    assert len(be._deferred) == 0          # dropped, never flushed
    assert np.abs(be.k_swap[:, [0, 1]]).sum() == 0
    assert 1 not in be._seq_lens


def test_hybrid_flushes_idle_child_deferred_copies():
    """A hybrid child with an EMPTY sub-plan is skipped — but its pending
    deferred copies belong to an already-retired epoch and must still
    land at the boundary, or the scheduler's block reuse races them."""
    be = make_physical("hybrid", pressure_cfg(1))
    toks = [3 + (i % 60) for i in range(16)]
    # prefill req 1 to completion: handoff defers, lands at plan 2
    be.execute(StepPlan(1, [(1, 0, 16)], [], [],
                        block_tables={1: [3, 7]}, new_tokens={1: toks},
                        prefill_done=[1]))
    # decode-tier swap-out of req 1 defers inside the DECODE child
    be.execute(StepPlan(2, [], [], [], swap_outs={1: [(3, 0), (7, 1)]},
                        decode_tier_swaps=[1]))
    dec = be.decode_backend
    snap_k = dec.k_pages[:, [3, 7]].copy()
    assert np.abs(snap_k).sum() > 0          # handoff landed at plan 2
    assert len(dec._deferred) == 1           # copy-out still pending
    # plan 3 gives the decode child NOTHING — its execute is skipped,
    # but the hybrid must flush its queue anyway
    be.execute(StepPlan(3, [(2, 0, 16)], [], [],
                        block_tables={2: [4, 5]},
                        new_tokens={2: toks}))
    assert len(dec._deferred) == 0
    np.testing.assert_array_equal(dec.k_swap[:, [0, 1]], snap_k)


def test_deferred_swap_copy_lands_at_next_epoch():
    """The physical deferral itself: pages move at the NEXT execute, and
    restored contents are bit-identical."""
    be = make_physical("cpu", pressure_cfg(1))
    toks = [3 + (i % 60) for i in range(16)]
    be.execute(StepPlan(1, [(1, 0, 16)], [], [],
                        block_tables={1: [3, 7]}, new_tokens={1: toks}))
    snap_k = be.k_pages[:, [3, 7]].copy()
    be.execute(StepPlan(2, [], [], [], swap_outs={1: [(3, 0), (7, 1)]}))
    assert np.abs(be.k_swap[:, [0, 1]]).sum() == 0   # still in flight
    be.execute(StepPlan(3, [], [], []))              # epoch boundary
    np.testing.assert_array_equal(be.k_swap[:, [0, 1]], snap_k)
    be.execute(StepPlan(4, [], [], [], restores={1: [(0, 4), (1, 8)]}))
    be.execute(StepPlan(5, [], [], []))              # restore lands
    np.testing.assert_array_equal(be.k_pages[:, [4, 8]], snap_k)


# -- cost-aware victim selection -------------------------------------------


def _running_pair(victim_selection: str):
    """Two running requests under the swap policy: the OLD one holds a
    small table (cheap round trip), the YOUNG tail a large one."""
    cfg = SchedulerConfig(max_num_seqs=8, max_tokens_per_step=512,
                          prefill_chunk=512, enable_prefix_cache=False,
                          block_size=16, kv_capacity_tokens=1 << 16,
                          preemption_policy="swap",
                          swap_capacity_tokens=1 << 16,
                          victim_selection=victim_selection,
                          t_swap_block=1e-4, t_recompute_token=1e-5)
    sched = Scheduler(cfg)
    old = Request(text="", max_new_tokens=4)
    old.prompt_tokens = list(range(1 << 20, (1 << 20) + 32))     # 2 blocks
    young = Request(text="", max_new_tokens=4)
    young.prompt_tokens = list(range(2 << 20, (2 << 20) + 160))  # 10 blocks
    for r in (old, young):
        sched.add_request(r)
    plan = sched.schedule()
    sched.complete_step(plan, 1.0)       # both prefilled, both decoding
    assert old.prefilled == 32 and young.prefilled == 160
    return sched, old, young


def test_cheapest_victim_prefers_cheapest_round_trip():
    """Under the swap policy the eviction price is the transfer round
    trip: LIFO evicts the young tail (10-block table), cheapest evicts
    the old request whose 2-block trip costs a fifth of that."""
    sched, old, young = _running_pair("cheapest")
    assert sched._eviction_cost(old) < sched._eviction_cost(young)
    assert sched._pick_victim(young) is old
    sched2, old2, young2 = _running_pair("lifo")
    assert sched2._pick_victim(young2) is young2   # tail = most recent

    with pytest.raises(ValueError):
        SchedulerConfig(victim_selection="dearest")


def test_eviction_cost_ages_repeat_victims():
    """Each prior eviction inflates a victim's modeled cost (and a floor
    keeps 'free' evictions nonzero), so serial evictions rotate across
    the batch instead of starving one cache-resumable request."""
    sched, old, young = _running_pair("cheapest")
    base = sched._eviction_cost(old)
    assert base > 0                      # floor: never modeled as free
    old.n_swaps = 4
    assert sched._eviction_cost(old) == pytest.approx(base * 5)


def test_cheapest_victim_workload_completes_without_leaks():
    cfg = pressure_cfg(1, victim_selection="cheapest")
    reqs = _reqs([(40, 8), (37, 8), (25, 4)])
    drive(make_physical("cpu", cfg), cfg, reqs, check_epochs=True)
    assert sum(r.n_swaps + r.n_preemptions for r in reqs) >= 1


# -- delta block tables ----------------------------------------------------


def test_delta_tables_roundtrip_and_shrink():
    """Steady-state decode plans ship ~one entry per growing request
    instead of the full table, and the reader-side tracker reconstructs
    tables identical to the scheduler's."""
    def run(delta: bool):
        cfg = SchedulerConfig(max_num_seqs=8, max_tokens_per_step=4096,
                              prefill_chunk=4096, enable_prefix_cache=False,
                              block_size=16, kv_capacity_tokens=1 << 16,
                              delta_block_tables=delta)
        sched = Scheduler(cfg)
        for s in (1, 2):
            r = Request(text="", max_new_tokens=12)
            r.prompt_tokens = list(range(s << 20, (s << 20) + 512))
            sched.add_request(r)
        tracker = BlockTableTracker()
        sizes, step = [], 0
        while sched.has_work and step < 100:
            plan = sched.schedule()
            if plan is None:
                break
            step += 1
            full_tables = {rid: list(t)
                           for rid, t in plan.block_tables.items()}
            if delta and step > 1:
                # steady-state decode: at most one appended block per
                # growing request ships, never the 32+-entry tables
                assert plan.n_new_table_entries <= len(plan.decode)
            raw = plan.encode()
            sizes.append(len(raw))
            decoded = tracker.expand(StepPlan.decode_bytes(raw))
            assert decoded.block_tables == full_tables
            sched.complete_step(plan, float(step))
        # drop the prefill step; compare steady-state decode payloads
        return sizes[1:]

    delta_sizes = run(True)
    full_sizes = run(False)
    assert len(delta_sizes) == len(full_sizes)
    # 512-token contexts at block 16: full tables ship 32+ entries/req,
    # deltas at most one — the decode payload nearly halves (the rest
    # of the plan — input ids, framing — is untouched)
    assert sum(delta_sizes) * 1.5 < sum(full_sizes)


def test_delta_tables_resend_full_after_preemption():
    """Every table reset clears the sent-count: the first broadcast
    after a preemption carries the FULL table (base 0), so reader
    history can never go stale."""
    cfg = pressure_cfg(0, delta_block_tables=True)
    sched = Scheduler(cfg)
    reqs = _reqs([(40, 8), (37, 8)])
    for r in reqs:
        sched.add_request(r)
    tracker = BlockTableTracker()
    evicted = set()
    step = 0
    while sched.has_work and step < 800:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        for rid in list(plan.swap_outs) + list(plan.preempted):
            evicted.add(rid)
        for rid in plan.block_tables:
            if rid in evicted and plan.table_base.get(rid, 0):
                raise AssertionError(
                    f"req {rid} rebroadcast as delta after eviction")
        full = {rid: list(t) for rid, t in plan.block_tables.items()}
        decoded = tracker.expand(StepPlan.decode_bytes(plan.encode()))
        assert decoded.block_tables == full
        # once re-admitted with a fresh table, deltas may resume
        for rid in plan.restores:
            evicted.discard(rid)
        sched.complete_step(plan, float(step))
    assert evicted or sum(r.n_swaps for r in reqs), "no pressure exercised"


# -- CpuSampler drift fix --------------------------------------------------


def test_saturation_seconds_weights_actual_sample_spans():
    """Samples are weighted by measured inter-sample wall time, not the
    nominal interval — a sampler thread descheduled under CPU starvation
    covers more wall per sample, exactly the regime being measured."""
    s = CpuSampler(interval=0.05)
    s.samples = [(0.05, 0.99), (0.30, 0.99), (0.35, 0.10), (0.40, 0.99)]
    s._spans = [0.05, 0.25, 0.05, 0.05]
    # two fast saturated samples (0.05 each) + one stretched one (0.25)
    assert s.saturation_seconds(0.95) == pytest.approx(0.35)
    # the old behavior (interval * count) would have said 0.15
