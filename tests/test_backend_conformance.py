"""Backend conformance suite: every registered backend, one contract.

Parameterized over all four backends (emulated, jax, cpu, hybrid): the
same scheduled workload must complete in the same order with the same
token counts whatever executes it, the physical backends must sample
token-identical streams (execution can move between them without
changing the output), swap round-trips must restore bit-identical pages
in contract order (swap_outs -> restores -> compute, even when a freed
device block is reused within the same plan), and no backend may leak
per-request state once the workload drains.  The hybrid-specific
handoff pin — a request's KV pages bit-identical across the
prefill->decode tier copy — lives here too.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.backend import EmulatedBackend, StepResult
from repro.backend.cpu_decode import CpuDecodeBackend
from repro.backend.hybrid import HybridBackend
from repro.backend.jax_backend import JaxBackend
from repro.core.devmodel import DeviceModel
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import Scheduler, SchedulerConfig, StepPlan

BLOCK, NBLOCKS, NSWAP = 8, 64, 32
BACKENDS = ("emulated", "jax", "cpu", "hybrid")
PHYSICAL = ("jax", "cpu", "hybrid")

SCHED_CFG = SchedulerConfig(
    max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
    enable_prefix_cache=True, block_size=BLOCK,
    kv_capacity_tokens=NBLOCKS * BLOCK)

# ~1.5 requests resident: forces preemption/swap churn mid-workload
PRESSURE_CFG = SchedulerConfig(
    max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
    enable_prefix_cache=False, block_size=BLOCK,
    kv_capacity_tokens=9 * BLOCK, preemption_policy="swap",
    swap_capacity_tokens=NSWAP * BLOCK)


def make(name: str, cfg: SchedulerConfig):
    kw = dict(block_size=cfg.block_size, num_blocks=cfg.num_kv_blocks,
              num_swap_blocks=cfg.num_swap_blocks, vocab=128)
    if name == "emulated":
        return EmulatedBackend(DeviceModel(t_fixed=1e-5, t_prefill_tok=1e-8,
                                           t_decode_seq=1e-6))
    if name == "jax":
        return JaxBackend(**kw)
    if name == "cpu":
        return CpuDecodeBackend(**kw)
    if name == "hybrid":
        return HybridBackend(JaxBackend(**kw), CpuDecodeBackend(**kw),
                             t_handoff_block=1e-6)
    raise AssertionError(name)


def _workload():
    specs = [(21, 3, 1), (40, 5, 2), (21, 2, 1), (9, 4, 3)]
    reqs = []
    for n, max_new, stream in specs:
        r = Request(text="", max_new_tokens=max_new)
        base = stream << 10
        r.prompt_tokens = [3 + ((base + i) % 700) for i in range(n)]
        reqs.append(r)
    return reqs


def _drive(backend, cfg=SCHED_CFG, reqs=None, max_steps=500):
    """Run a workload to completion; returns (completion order by
    workload position, token counts, sampled tokens, scheduler)."""
    sched = Scheduler(cfg)
    reqs = reqs if reqs is not None else _workload()
    for r in reqs:
        sched.add_request(r)
    idx_of = {r.req_id: i for i, r in enumerate(reqs)}
    order, step = [], 0
    while sched.has_work and step < max_steps:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        result = backend.execute(plan)
        assert isinstance(result, StepResult)
        assert result.step_id == plan.step_id
        # token coverage: every decode id and every finished prefill
        for rid in plan.decode:
            assert rid in result.tokens or isinstance(backend,
                                                      EmulatedBackend)
        for rid in plan.prefill_done:
            assert rid in result.tokens or isinstance(backend,
                                                      EmulatedBackend)
        for req in sched.complete_step(plan, float(step), result):
            order.append(idx_of[req.req_id])
            if hasattr(backend, "release"):
                backend.release(req.req_id)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    counts = {idx_of[r.req_id]: len(r.generated) for r in reqs}
    tokens = {idx_of[r.req_id]: list(r.generated) for r in reqs}
    return order, counts, tokens, sched


@pytest.fixture(scope="module")
def reference():
    """The jax backend's completion stream — the conformance oracle."""
    return _drive(make("jax", SCHED_CFG))[:3]


@pytest.mark.parametrize("name", BACKENDS)
def test_scheduling_semantics_identical(name, reference):
    """Same workload, any backend: same completion order and counts —
    execution is a pluggable detail, scheduling semantics are not."""
    ref_order, ref_counts, ref_tokens = reference
    order, counts, tokens, _ = _drive(make(name, SCHED_CFG))
    assert order == ref_order
    assert counts == ref_counts
    if name in PHYSICAL:
        # real compute must also be token-identical to the reference —
        # this is what lets execution move between backends mid-request
        assert tokens == ref_tokens
        assert any(any(t != 0 for t in ts) for ts in tokens.values())


@pytest.mark.parametrize("name", BACKENDS)
def test_swap_round_trip_under_pressure(name):
    """A pressured workload that forces swap-out/restore churn completes
    with the same tokens as the recompute policy — restored KV is
    indistinguishable from recomputed KV — and frees every block."""
    def run(policy):
        cfg = dataclasses.replace(PRESSURE_CFG, preemption_policy=policy)
        reqs = []
        for i, (n, m) in enumerate([(40, 8), (37, 8)]):
            r = Request(text="", max_new_tokens=m)
            base = (i + 1) << 10
            r.prompt_tokens = [3 + ((base + j) % 100) for j in range(n)]
            reqs.append(r)
        _, counts, tokens, sched = _drive(make(name, cfg), cfg, reqs)
        assert sched.blocks.free_blocks == sched.blocks.num_blocks
        evictions = sum(r.n_preemptions + r.n_swaps for r in reqs)
        return counts, tokens, evictions

    rec_counts, rec_tokens, rec_ev = run("recompute")
    swp_counts, swp_tokens, swp_ev = run("swap")
    assert rec_ev >= 1 and swp_ev >= 1, "expected memory pressure"
    assert rec_counts == swp_counts
    if name in PHYSICAL:
        assert rec_tokens == swp_tokens


@pytest.mark.parametrize("name", PHYSICAL)
def test_preempt_no_leak(name):
    """After a churny workload drains (with release() per finish), no
    per-request state survives in the backend."""
    backend = make(name, PRESSURE_CFG)
    reqs = []
    for i, (n, m) in enumerate([(40, 8), (37, 8), (25, 4)]):
        r = Request(text="", max_new_tokens=m)
        r.prompt_tokens = [3 + ((((i + 1) << 10) + j) % 100)
                           for j in range(n)]
        reqs.append(r)
    _drive(backend, PRESSURE_CFG, reqs)
    children = ([backend.prefill_backend, backend.decode_backend]
                if name == "hybrid" else [backend])
    for child in children:
        assert not child._seq_lens, child._seq_lens
        assert not child._swap_pinned
    if name == "hybrid":
        assert not backend._tier
        assert not backend._swap_pinned


@pytest.mark.parametrize("name", ("jax", "cpu"))
def test_ordering_swap_out_before_same_plan_reuse(name):
    """The contract's ordering invariant, asserted directly: swap_outs
    apply before restores and compute, so a device block parked on host
    and clobbered by a prefill in the SAME plan restores bit-identical."""
    be = make(name, PRESSURE_CFG)
    toks = [3 + (i % 60) for i in range(16)]          # two full blocks
    be.execute(StepPlan(1, [(1, 0, 16)], [], [],
                        block_tables={1: [3, 7]}, new_tokens={1: toks}))
    snap_k = be.k_pages[:, [3, 7]].copy()
    snap_v = be.v_pages[:, [3, 7]].copy()
    assert np.abs(snap_k).sum() > 0               # prefill really wrote
    clobber = [60 - (i % 50) for i in range(16)]
    be.execute(StepPlan(2, [(2, 0, 16)], [], [],
                        block_tables={2: [3, 7]}, new_tokens={2: clobber},
                        swap_outs={1: [(3, 0), (7, 1)]}))
    assert not np.array_equal(be.k_pages[:, [3, 7]], snap_k)  # clobbered
    np.testing.assert_array_equal(be.k_swap[:, [0, 1]], snap_k)
    # restore into different device blocks — which may themselves have
    # been freed by a swap-out applied earlier in the same plan
    be.execute(StepPlan(3, [], [], [], restores={1: [(0, 4), (1, 8)]}))
    np.testing.assert_array_equal(be.k_pages[:, [4, 8]], snap_k)
    np.testing.assert_array_equal(be.v_pages[:, [4, 8]], snap_v)


def test_ordering_invariant_hybrid_decode_tier():
    """Same invariant through the hybrid's routing: a decode-tier
    resident's swap-out and a prefill reusing its block ids ride one
    plan; each lands on its own tier in contract order."""
    be = make("hybrid", PRESSURE_CFG)
    toks = [3 + (i % 60) for i in range(16)]
    # prefill req 1 to completion -> handoff puts its pages on decode tier
    be.execute(StepPlan(1, [(1, 0, 16)], [], [],
                        block_tables={1: [3, 7]}, new_tokens={1: toks},
                        prefill_done=[1]))
    dec = be.decode_backend
    snap_k = dec.k_pages[:, [3, 7]].copy()
    assert np.abs(snap_k).sum() > 0               # handoff really copied
    assert be._tier[1] == "decode"
    # one plan: swap req 1 out of the decode tier AND reuse its ids for
    # req 2's prefill (prefill tier — disjoint pool, no corruption)
    clobber = [60 - (i % 50) for i in range(16)]
    be.execute(StepPlan(2, [(2, 0, 16)], [], [],
                        block_tables={2: [3, 7]}, new_tokens={2: clobber},
                        swap_outs={1: [(3, 0), (7, 1)]}))
    np.testing.assert_array_equal(dec.k_swap[:, [0, 1]], snap_k)
    assert be.prefill_backend.k_swap[:, [0, 1]].sum() == 0  # routed right
    # restore lands back on the decode tier
    be.execute(StepPlan(3, [], [], [], restores={1: [(0, 4), (1, 8)]}))
    np.testing.assert_array_equal(dec.k_pages[:, [4, 8]], snap_k)


def test_hybrid_handoff_pages_bit_identical():
    """The hybrid-specific pin: at the prefill->decode transition the
    request's KV pages in the decode child's pool are bit-identical to
    what the prefill child computed, and its sequence length moves."""
    be = make("hybrid", SCHED_CFG)
    sched = Scheduler(SCHED_CFG)
    r = Request(text="", max_new_tokens=4)
    r.prompt_tokens = [3 + (i % 90) for i in range(33)]
    sched.add_request(r)
    handed = False
    step = 0
    while sched.has_work and step < 100:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        res = be.execute(plan)
        if r.req_id in plan.prefill_done:
            blocks = plan.block_tables[r.req_id]
            np.testing.assert_array_equal(
                be.decode_backend.k_pages[:, blocks],
                be.prefill_backend.k_pages[:, blocks])
            np.testing.assert_array_equal(
                be.decode_backend.v_pages[:, blocks],
                be.prefill_backend.v_pages[:, blocks])
            assert np.abs(be.decode_backend.k_pages[:, blocks]).sum() > 0
            assert be.decode_backend._seq_lens[r.req_id] == 33
            assert r.req_id not in be.prefill_backend._seq_lens
            handed = True
        sched.complete_step(plan, float(step), res)
    assert handed and r.state == RequestState.FINISHED


def test_hybrid_step_cost_is_max_plus_handoff():
    """Virtual-time contract: concurrent tiers cost max(children) plus
    the page handoff — and step_cost is pure (repeatable)."""
    pre_dev = DeviceModel(t_fixed=0.0, t_prefill_tok=1e-3, t_decode_seq=0.0,
                          t_block_entry=0.0, t_swap_block=0.0)
    dec_dev = DeviceModel(t_fixed=0.0, t_prefill_tok=0.0, t_decode_seq=1e-2,
                          t_block_entry=0.0, t_swap_block=0.0)
    be = HybridBackend(EmulatedBackend(pre_dev, sleep=False),
                       EmulatedBackend(dec_dev, sleep=False),
                       t_handoff_block=1e-3)
    # prefill 20 tokens (20 ms) + 1 decode (10 ms) -> max = 20 ms
    plan = StepPlan(1, [(1, 0, 20)], [2], [],
                    block_tables={1: [0, 1, 2], 2: [4]})
    assert be.step_cost(plan) == pytest.approx(20e-3)
    assert be.step_cost(plan) == pytest.approx(20e-3)   # pure: no drift
    # 3 decodes (30 ms) now dominate the prefill
    plan2 = StepPlan(2, [(1, 0, 20)], [2, 3, 4], [])
    assert be.step_cost(plan2) == pytest.approx(30e-3)
    # finishing prefill adds t_handoff_block per page crossing
    plan3 = StepPlan(3, [(1, 0, 20)], [], [], block_tables={1: [0, 1, 2]},
                     prefill_done=[1])
    assert be.step_cost(plan3) == pytest.approx(20e-3 + 3e-3)
    # empty decode side charges nothing (no t_fixed for an idle tier)
    plan4 = StepPlan(4, [(1, 0, 20)], [], [])
    assert be.step_cost(plan4) == pytest.approx(20e-3)
