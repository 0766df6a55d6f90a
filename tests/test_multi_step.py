"""Multi-step dispatch (docs/multi_step.md): k-step macro-plans.

The contract under test: macro-stepping is a pure latency optimization.
Token streams are bit-identical to per-step dispatch on every backend
(with and without the async copy engine), EOS / max-len early exits roll
back exactly the KV they reserved, a request aborted mid-macro
reconciles without double-frees, and drop notices never ride a
macro-plan (they ship exactly once, on a plan the workers inspect).
Plus the satellite scheduler changes: the time-to-release term in
victim pricing and the adaptive policy's sustained-overload fallback.
"""
from __future__ import annotations

import dataclasses

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container ships no hypothesis — deterministic sweep
    from _hypothesis_fallback import given, settings, strategies as st

from repro.backend import EmulatedBackend
from repro.backend.cpu_decode import CpuDecodeBackend
from repro.backend.hybrid import HybridBackend
from repro.backend.jax_backend import JaxBackend
from repro.core.devmodel import DeviceModel
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import Scheduler, SchedulerConfig, StepPlan

BLOCK = 8
BACKENDS = ("emulated", "jax", "cpu", "hybrid")


def _cfg(k: int = 1, *, blocks: int = 64, **kw) -> SchedulerConfig:
    return SchedulerConfig(
        max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
        block_size=BLOCK, kv_capacity_tokens=blocks * BLOCK,
        max_steps_per_dispatch=k, **kw)


def _backend(name: str, cfg: SchedulerConfig):
    kw = dict(block_size=cfg.block_size, num_blocks=cfg.num_kv_blocks,
              num_swap_blocks=max(cfg.num_swap_blocks, 1), vocab=128)
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


def _req(n: int, max_new: int, stream: int = 1,
         eos: int = None) -> Request:
    r = Request(text="", max_new_tokens=max_new)
    r.prompt_tokens = [3 + (((stream << 10) + j) % 100) for j in range(n)]
    r.eos_token = eos
    return r


def _drive(backend, cfg: SchedulerConfig, reqs, max_plans: int = 500):
    """Run to completion; returns (token streams, n_plans, n_macro)."""
    sched = Scheduler(cfg)
    for r in reqs:
        sched.add_request(r)
    plans = macros = 0
    while sched.has_work and plans < max_plans:
        plan = sched.schedule()
        if plan is None:
            break
        plans += 1
        macros += plan.num_steps > 1
        result = backend.execute(plan)
        for req in sched.complete_step(plan, float(plans), result):
            if hasattr(backend, "release"):
                backend.release(req.req_id)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert sched.blocks.free_blocks == sched.blocks.num_blocks
    return [list(r.generated) for r in reqs], plans, macros


# -- wire format ------------------------------------------------------------


def test_plan_roundtrip_macro_fields():
    plan = StepPlan(7, [], [1, 2], [], num_steps=4,
                    decode_steps={1: 4, 2: 2}, eos_tokens={2: 9})
    got = StepPlan.decode_bytes(plan.encode())
    assert got.num_steps == 4
    assert got.decode_steps == {1: 4, 2: 2}
    assert got.eos_tokens == {2: 9}
    assert got.last_step_id == 10


def test_plan_roundtrip_k1_carries_no_macro_fields():
    got = StepPlan.decode_bytes(StepPlan(3, [], [1], []).encode())
    assert got.num_steps == 1
    assert got.decode_steps == {} and got.eos_tokens == {}
    assert got.last_step_id == 3


# -- eligibility / budgets / step ids ---------------------------------------


def test_macro_waits_for_decode_steady():
    """No macro while prefill work or queued requests exist — only once
    the whole running set decodes (and then step ids jump by k)."""
    sched = Scheduler(_cfg(4))
    a, b = _req(20, 8, 1), _req(20, 8, 2)
    sched.add_request(a)
    plan = sched.schedule()
    assert plan.prefill and plan.num_steps == 1
    sched.add_request(b)          # queued work: still not steady
    sched.complete_step(plan, 1.0)
    p2 = sched.schedule()         # a finishes prefill, b starts its own
    assert p2.prefill and p2.num_steps == 1
    sched.complete_step(p2, 2.0)
    p3 = sched.schedule()
    assert p3.num_steps == 1      # b's prefill tail rides with a's decode
    sched.complete_step(p3, 3.0)
    p4 = sched.schedule()         # both decoding, nothing queued: macro
    assert p4.num_steps == 4
    assert sorted(p4.decode_steps) == sorted([a.req_id, b.req_id])
    assert p4.last_step_id == p4.step_id + 3
    sched.complete_step(p4, 4.0)
    p5 = sched.schedule()
    assert p5.step_id == p4.last_step_id + 1   # ids stay dense


def test_macro_budget_capped_at_remaining_decode():
    sched = Scheduler(_cfg(8))
    a, b = _req(8, 12, 1), _req(8, 3, 2)
    for r in (a, b):
        sched.add_request(r)
    plan = sched.schedule()
    sched.complete_step(plan, 1.0)      # prefills done, 1 token each
    p2 = sched.schedule()
    assert p2.num_steps == 8
    assert p2.decode_steps[a.req_id] == 8
    assert p2.decode_steps[b.req_id] == 2     # only 2 tokens left to make


def test_macro_shrinks_k_to_fit_kv():
    """The reservation never preempts: k shrinks until the extra blocks
    fit the free pool."""
    sched = Scheduler(_cfg(8, blocks=4))      # 32 token slots total
    a, b = _req(10, 12, 1), _req(10, 12, 2)
    for r in (a, b):
        sched.add_request(r)
    sched.complete_step(sched.schedule(), 1.0)
    # each request now holds 2 blocks (11 slots): the pool is fully
    # allocated, so an 8-step reservation (1 extra block per request)
    # cannot fit — k must shrink to what block 2's tail slots cover
    p = sched.schedule()
    assert 1 < p.num_steps < 8
    assert sched.blocks.free_blocks >= 0
    sched.complete_step(p, 2.0)
    assert len(a.generated) == 1 + p.decode_steps[a.req_id]


# -- device model -----------------------------------------------------------


def test_devmodel_charges_dispatch_floor_once_per_macro():
    dev = DeviceModel(t_fixed=1e-3, t_prefill_tok=0.0, t_decode_seq=1e-4,
                      t_block_entry=0.0)
    single = StepPlan(1, [], [1, 2], [])
    macro = StepPlan(1, [], [1, 2], [], num_steps=4,
                     decode_steps={1: 4, 2: 4})
    t1, tk = dev.step_time(single), dev.step_time(macro)
    assert t1 == pytest.approx(1e-3 + 2e-4)
    assert tk == pytest.approx(1e-3 + 8e-4)       # floor once, decode x8
    assert tk < 4 * t1                            # the whole point
    # partial budgets charge only the steps that will run
    part = StepPlan(1, [], [1, 2], [], num_steps=4,
                    decode_steps={1: 4, 2: 1})
    assert dev.step_time(part) == pytest.approx(1e-3 + 5e-4)


# -- bit-identity vs the k=1 oracle -----------------------------------------


@pytest.mark.parametrize("name", BACKENDS)
@pytest.mark.parametrize("streams", (0, 2))
def test_macro_tokens_bit_identical_to_k1(name, streams):
    """k=8 equals the k=1 oracle token-for-token on every backend — under
    KV pressure (swap churn) and with the async copy engine in play."""
    def cfg(k):
        return _cfg(k, blocks=12, preemption_policy="swap",
                    swap_capacity_tokens=32 * BLOCK, copy_streams=streams,
                    enable_prefix_cache=False)

    def workload():
        return [_req(40, 24, 1), _req(37, 24, 2)]

    reqs = workload()
    ref, _, _ = _drive(_backend(name, cfg(1)), cfg(1), reqs)
    swaps = sum(r.n_swaps + r.n_preemptions for r in reqs)
    assert swaps >= 1, "workload must actually churn the KV pool"
    got, _, macros = _drive(_backend(name, cfg(8)), cfg(8), workload())
    assert macros >= 1, "steady tail must have fired a macro-plan"
    if name == "emulated":                 # placeholder tokens: counts only
        assert [len(t) for t in got] == [len(t) for t in ref]
    else:
        assert got == ref


# -- EOS early exit: rollback leaves no leaks (property) --------------------


@settings(max_examples=12, deadline=None)
@given(n_prompt=st.integers(6, 30), max_new=st.integers(2, 14),
       eos_pos=st.integers(0, 10), k=st.integers(2, 8))
def test_eos_rollback_no_leak_property(n_prompt, max_new, eos_pos, k):
    """For any (prompt, tail length, EOS position, k): the macro run
    stops at the first EOS exactly like per-step dispatch, and every
    block reserved for unused inner steps is rolled back (asserted by
    ``_drive``'s all-blocks-free postcondition)."""
    oracle, _, _ = _drive(_backend("cpu", _cfg(1)), _cfg(1),
                          [_req(n_prompt, max_new, 1)])
    stream = oracle[0]
    eos = stream[eos_pos] if eos_pos < len(stream) else None
    if eos is not None:
        stream = stream[:stream.index(eos) + 1]    # oracle truncation
    ref, _, _ = _drive(_backend("cpu", _cfg(1)), _cfg(1),
                       [_req(n_prompt, max_new, 1, eos=eos)])
    got, _, _ = _drive(_backend("cpu", _cfg(k)), _cfg(k),
                       [_req(n_prompt, max_new, 1, eos=eos)])
    assert ref[0] == stream
    assert got[0] == stream


# -- abort / drop notices ---------------------------------------------------


def test_mid_macro_abort_reconciles():
    """A request aborted between a macro-plan's broadcast and its
    completion: its blocks are reclaimed once, completion skips it, the
    survivor's stream is unaffected and the pool drains clean."""
    cfg = _cfg(4)
    sched = Scheduler(cfg)
    backend = _backend("cpu", cfg)
    a, b = _req(8, 10, 1), _req(8, 10, 2)
    for r in (a, b):
        sched.add_request(r)
    sched.complete_step(sched.schedule(), 1.0)
    plan = sched.schedule()
    assert plan.num_steps > 1
    result = backend.execute(plan)
    # client disconnect mid-macro: emulate a never-streamed first token
    a.t_first_token = 0.0
    dead = sched.expire(now=1e9, timeout=1.0)
    assert dead == [a] and a.state == RequestState.TIMED_OUT
    assert not a.block_table
    freed = sched.blocks.free_blocks
    sched.complete_step(plan, 2.0, result)
    assert len(a.generated) == 1               # nothing appended post-abort
    assert sched.blocks.free_blocks >= freed   # and nothing double-freed
    while sched.has_work:
        p = sched.schedule()
        sched.complete_step(p, 3.0, backend.execute(p))
    assert b.state == RequestState.FINISHED
    assert sched.blocks.free_blocks == sched.blocks.num_blocks


def test_drop_notice_ships_exactly_once_never_on_a_macro():
    """A swapped request aborted by timeout owes the workers ONE state
    drop notice; the plan carrying it is never a macro-plan, and the
    notice does not repeat."""
    cfg = _cfg(4, blocks=12, preemption_policy="swap",
               swap_capacity_tokens=32 * BLOCK, enable_prefix_cache=False)
    sched = Scheduler(cfg)
    backend = _backend("cpu", cfg)
    a, b = _req(40, 24, 1), _req(37, 24, 2)
    for r in (a, b):
        sched.add_request(r)
    notices = []
    t = 0.0
    while sched.has_work and t < 500:
        t += 1.0
        if sched.swapped and not notices:
            # the swapped request's client disconnects before ever
            # streaming a token
            victim = sched.swapped[0]
            victim.t_arrival = -1e9
            victim.t_first_token = 0.0
            dead = sched.expire(now=t, timeout=1e6)
            assert dead == [victim]
        plan = sched.schedule()
        if plan is None:
            break
        if notices or sched._dropped_while_swapped:
            pass
        for rid in plan.preempted:
            if rid not in (r.req_id for r in sched.running):
                notices.append((plan.step_id, rid, plan.num_steps))
        sched.complete_step(plan, t, backend.execute(plan))
    dropped = [n for n in notices if n[1] == a.req_id
               or n[1] == b.req_id]
    assert len(dropped) == 1                   # exactly once
    assert dropped[0][2] == 1                  # and never on a macro
    assert sched.blocks.free_blocks == sched.blocks.num_blocks


# -- satellite: time-to-release victim pricing ------------------------------


def test_eviction_cost_prefers_short_remaining_decode():
    """Equal-size victims: the one about to release its blocks (short
    remaining decode) is cheaper to evict, and `cheapest` selection
    picks it."""
    cfg = _cfg(1, blocks=64, victim_selection="cheapest",
               t_recompute_token=1e-5, t_release_token=1e-3)
    sched = Scheduler(cfg)
    soon, later = _req(16, 20, 1), _req(16, 20, 2)
    for r in (soon, later):
        sched.add_request(r)
    sched.complete_step(sched.schedule(), 1.0)
    soon.generated = list(range(18))           # 2 tokens left to make
    later.generated = list(range(2))           # 18 tokens left
    assert sched._eviction_cost(soon) < sched._eviction_cost(later)
    order = sorted(sched.running, key=sched._eviction_cost)
    assert order[0] is soon


def test_release_term_scales_with_remaining():
    cfg = _cfg(1, t_recompute_token=0.0, t_release_token=1e-3)
    sched = Scheduler(cfg)
    r = _req(16, 20, 1)
    sched.add_request(r)
    sched.complete_step(sched.schedule(), 1.0)
    base = sched._eviction_cost(r)
    r.generated = list(range(11))              # 10 fewer remaining
    assert base - sched._eviction_cost(r) == pytest.approx(10 * 1e-3)


# -- satellite: adaptive overload fallback ----------------------------------


def _adaptive_sched() -> Scheduler:
    cfg = _cfg(1, blocks=12, preemption_policy="adaptive",
               swap_capacity_tokens=64 * BLOCK, t_swap_block=1e-6,
               t_recompute_token=1e-3, re_evict_threshold=0.5,
               re_evict_min_samples=4, enable_prefix_cache=False)
    sched = Scheduler(cfg)
    r = _req(32, 8, 1)
    sched.add_request(r)
    sched.complete_step(sched.schedule(), 1.0)
    return sched


def test_overload_fallback_flips_adaptive_to_recompute():
    sched = _adaptive_sched()
    victim = sched.running[0]
    # cheap swap, expensive recompute: adaptive prefers the round trip
    assert sched._victim_price(victim)[0] == "swap"
    # sustained overload: most restores get re-evicted
    sched._n_restores, sched._n_re_evicts = 8, 6
    assert sched._swap_overloaded()
    assert sched._victim_price(victim)[0] == "recompute"
    # below the observation floor nothing flips
    sched._n_restores, sched._n_re_evicts = 3, 3
    assert not sched._swap_overloaded()
    assert sched._victim_price(victim)[0] == "swap"


def test_overload_counters_decay_to_reprobe():
    """The window halving drains the sample count below
    ``re_evict_min_samples``, so the fallback re-probes swap after the
    churn quiets down instead of latching recompute forever."""
    sched = _adaptive_sched()
    sched._n_restores, sched._n_re_evicts = 6, 6
    assert sched._swap_overloaded()
    stream = 3
    for _ in range(2 * sched._OVERLOAD_WINDOW):
        if not sched.has_work:     # request drained: keep the engine busy
            sched.add_request(_req(32, 60, stream))
            stream += 1
        plan = sched.schedule()
        if plan is not None:
            sched.complete_step(plan, 2.0)
    assert sched._n_restores < sched.cfg.re_evict_min_samples
    assert not sched._swap_overloaded()
