"""JaxBackend specifics: determinism, registry, prefix-page sharing.

The cross-backend conformance contract (same workload -> same completion
order/counts/tokens for every registered backend) lives in
tests/test_backend_conformance.py; this file keeps the jax-backend
deep-dives — deterministic sampling, swap round-trip page contents,
prefix-page sharing — plus the paged decode kernel against its gather
reference and the make_backend registry.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import (EmulatedBackend, StepResult, jax_backend,
                           make_backend)
from repro.backend.cpu_decode import CpuDecodeBackend
from repro.backend.jax_backend import JaxBackend
from repro.core.devmodel import DeviceModel
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import Scheduler, SchedulerConfig, StepPlan

BLOCK, NBLOCKS = 8, 64
SCHED_CFG = SchedulerConfig(
    max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
    enable_prefix_cache=True, block_size=BLOCK,
    kv_capacity_tokens=NBLOCKS * BLOCK)


SPECS = [(21, 3, 1), (40, 5, 2), (21, 2, 1), (9, 4, 3)]


def _workload(specs=SPECS):
    reqs = []
    for n, max_new, stream in specs:
        r = Request(text="", max_new_tokens=max_new)
        base = stream << 10          # keep ids inside the tiny vocab range
        r.prompt_tokens = [base + (i % 700) for i in range(n)]
        reqs.append(r)
    return reqs


def _drive(backend, max_steps: int = 500, cfg: SchedulerConfig = SCHED_CFG,
           specs=SPECS):
    """Run the workload to completion; returns (completion order, counts,
    sampled tokens per request)."""
    sched = Scheduler(cfg)
    reqs = _workload(specs)
    for r in reqs:
        sched.add_request(r)
    idx_of = {r.req_id: i for i, r in enumerate(reqs)}   # workload position
    order, step = [], 0
    while sched.has_work and step < max_steps:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        result = backend.execute(plan)
        assert isinstance(result, StepResult)
        assert result.step_id == plan.step_id
        for req in sched.complete_step(plan, float(step), result):
            order.append(idx_of[req.req_id])
            if hasattr(backend, "release"):
                backend.release(req.req_id)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    counts = {idx_of[r.req_id]: len(r.generated) for r in reqs}
    tokens = {idx_of[r.req_id]: list(r.generated) for r in reqs}
    return order, counts, tokens


def test_emulated_jax_conformance():
    em_order, em_counts, _ = _drive(
        EmulatedBackend(DeviceModel(t_fixed=1e-5, t_prefill_tok=1e-8,
                                    t_decode_seq=1e-6)))
    jx_order, jx_counts, jx_tokens = _drive(
        JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS, vocab=128))
    assert em_order == jx_order
    assert em_counts == jx_counts
    # the jax backend actually samples (not the emulated placeholder 0)
    assert any(any(t != 0 for t in toks) for toks in jx_tokens.values())


def test_jax_backend_is_deterministic():
    _, _, a = _drive(JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS,
                                vocab=128))
    _, _, b = _drive(JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS,
                                vocab=128))
    assert a == b


WEIGHTS = ("_embed", "_wq", "_wk", "_wv", "_wo")


@pytest.mark.parametrize("k", (1, 4))
def test_jax_backend_hands_each_weight_to_the_device_once(monkeypatch, k):
    """The weights are final after construction: the single-step path
    (k=1) hands the projection to the device in its first step only, the
    multi-step path (k=4) each of its weights once over all its
    macro-plans; the tokens stay the CPU backend's."""
    handed, scans = [], []

    class CountingJnp:           # jax.numpy, recording what goes over
        def __getattr__(self, name):
            return getattr(jnp, name)

        def asarray(self, a, *args, **kw):
            handed.append(a)
            return jnp.asarray(a, *args, **kw)

    def decode_scan(*args, **kw):
        scans.append(kw["n_steps"])
        return real_scan(*args, **kw)

    real_scan = jax_backend.decode_scan
    monkeypatch.setattr(jax_backend, "jnp", CountingJnp())
    monkeypatch.setattr(jax_backend, "decode_scan", decode_scan)
    cfg = dataclasses.replace(SCHED_CFG, max_steps_per_dispatch=k)
    specs = [(21, 13, 1), (40, 11, 2)]
    be = JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS, vocab=128)
    _, _, tokens = _drive(be, cfg=cfg, specs=specs)
    monkeypatch.undo()
    handed_once = {name: sum(a is getattr(be, name) for a in handed)
                   for name in WEIGHTS}
    if k == 1:
        assert not scans
        assert handed_once == {**dict.fromkeys(WEIGHTS, 0), "_wo": 1}
    else:
        assert len(scans) >= 2, "the workload must run several macro-plans"
        assert handed_once == dict.fromkeys(WEIGHTS, 1)
    _, _, ref = _drive(CpuDecodeBackend(block_size=BLOCK,
                                        num_blocks=NBLOCKS, vocab=128),
                       cfg=cfg, specs=specs)
    assert tokens == ref


def test_resident_projection_reuses_the_warmed_executable(monkeypatch):
    """A worker warms ``attend_logits`` with uncommitted ``jnp.zeros`` at
    each bucket; a step at a warmed bucket, the resident projection
    included, finds that executable and compiles nothing."""
    plans = [StepPlan(1, [(1, 0, 12)], [], [], block_tables={1: [0, 1]},
                      new_tokens={1: list(range(12))}, prefill_done=[1]),
             StepPlan(2, [], [1], [], block_tables={1: [0, 1]},
                      new_tokens={1: [5]})]
    shapes = []
    real = jax_backend.attend_logits

    def attend_logits(*args, **kw):
        shapes.append([a.shape for a in args])
        return real(*args, **kw)

    monkeypatch.setattr(jax_backend, "attend_logits", attend_logits)
    be = JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS, vocab=128)
    for plan in plans:
        be.execute(plan)
    monkeypatch.undo()
    assert shapes[0] == shapes[1], "both steps must share one bucket"
    q, kc, _, bt, sl, wo = shapes[0]
    real.clear_cache()
    zeros = jnp.zeros(kc, jnp.float32)
    real(jnp.zeros(q, jnp.float32), zeros, zeros,
         jnp.full(bt, -1, jnp.int32), jnp.zeros(sl, jnp.int32),
         jnp.zeros(wo, jnp.float32)).block_until_ready()
    warmed = real._cache_size()
    be = JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS, vocab=128)
    for plan in plans:      # the first makes the resident copy
        be.execute(plan)
    assert real._cache_size() == warmed


def test_make_backend_registry():
    em = make_backend("emulated", device=DeviceModel())
    assert isinstance(em, EmulatedBackend)
    jx = make_backend("jax", scheduler_cfg=SCHED_CFG)
    assert isinstance(jx, JaxBackend)
    assert jx.num_blocks == SCHED_CFG.num_kv_blocks
    with pytest.raises(ValueError):
        make_backend("tpu")


def test_emulated_cost_includes_block_tables():
    from repro.serving.scheduler import StepPlan
    dev = DeviceModel(t_fixed=0.0, t_prefill_tok=0.0, t_decode_seq=0.0,
                      t_block_entry=1e-6)
    be = EmulatedBackend(dev, sleep=False)
    bare = StepPlan(1, [], [1], [])
    heavy = StepPlan(2, [], [1], [], block_tables={1: list(range(500))})
    assert be.step_cost(bare) == 0.0
    assert be.step_cost(heavy) == pytest.approx(500e-6)


def test_paged_kernel_matches_reference():
    import jax.numpy as jnp

    from repro.kernels.paged_decode_attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )
    rng = np.random.default_rng(7)
    B, H, KV, D, N, blk, nb = 4, 8, 2, 16, 24, 8, 5
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((KV, N, blk, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((KV, N, blk, D)), jnp.float32)
    perm = rng.permutation(N)
    bt = np.full((B, nb), -1, np.int32)
    sl = np.zeros((B,), np.int32)
    lens = [37, 8, 0, 25]
    used = 0
    for b, n_tok in enumerate(lens):
        n_pages = -(-n_tok // blk)
        bt[b, :n_pages] = perm[used:used + n_pages]
        used += n_pages
        sl[b] = n_tok
    out = paged_decode_attention(q, kp, vp, jnp.asarray(bt),
                                 jnp.asarray(sl))
    ref = paged_decode_attention_reference(q, kp, vp, jnp.asarray(bt),
                                           jnp.asarray(sl))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_jax_swap_round_trip_restores_identical_contents():
    """swap_out -> clobber the freed device pages -> restore into fresh
    pages: the restored KV is bit-identical to what was swapped out, even
    when the swap-out and the clobbering prefill ride the SAME plan (the
    Backend contract orders swap_outs before writes)."""
    from repro.serving.scheduler import StepPlan

    be = JaxBackend(block_size=8, num_blocks=16, num_swap_blocks=8,
                    vocab=64)
    toks = [3 + (i % 60) for i in range(16)]          # two full blocks
    be.execute(StepPlan(1, [(1, 0, 16)], [], [],
                        block_tables={1: [3, 7]}, new_tokens={1: toks}))
    snap_k = be.k_pages[:, [3, 7]].copy()
    snap_v = be.v_pages[:, [3, 7]].copy()
    assert np.abs(snap_k).sum() > 0               # prefill really wrote
    # one plan: park req 1's pages on host AND reuse its device blocks
    # for req 2's prefill
    clobber = [60 - (i % 50) for i in range(16)]
    be.execute(StepPlan(2, [(2, 0, 16)], [], [],
                        block_tables={2: [3, 7]}, new_tokens={2: clobber},
                        swap_outs={1: [(3, 0), (7, 1)]}))
    assert not np.array_equal(be.k_pages[:, [3, 7]], snap_k)  # clobbered
    np.testing.assert_array_equal(be.k_swap[:, [0, 1]], snap_k)
    # restore into different device blocks
    be.execute(StepPlan(3, [], [], [], restores={1: [(0, 10), (1, 11)]}))
    np.testing.assert_array_equal(be.k_pages[:, [10, 11]], snap_k)
    np.testing.assert_array_equal(be.v_pages[:, [10, 11]], snap_v)


def test_swap_policy_conformance_with_jax_backend():
    """End-to-end: the same pressured workload generates identical tokens
    under recompute and swap with the real (jax) backend — restored KV is
    indistinguishable from recomputed KV."""
    def drive(policy):
        cfg = SchedulerConfig(
            max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
            enable_prefix_cache=False, block_size=BLOCK,
            kv_capacity_tokens=9 * BLOCK,        # ~1.5 requests resident
            preemption_policy=policy,
            swap_capacity_tokens=32 * BLOCK)
        backend = JaxBackend(block_size=BLOCK, num_blocks=cfg.num_kv_blocks,
                             num_swap_blocks=cfg.num_swap_blocks,
                             vocab=128)
        sched = Scheduler(cfg)
        reqs = []
        for i, (n, m) in enumerate([(40, 8), (37, 8)]):
            r = Request(text="", max_new_tokens=m)
            base = (i + 1) << 10
            r.prompt_tokens = [3 + ((base + j) % 100) for j in range(n)]
            reqs.append(r)
            sched.add_request(r)
        step = 0
        while sched.has_work and step < 500:
            plan = sched.schedule()
            if plan is None:
                break
            step += 1
            sched.complete_step(plan, float(step), backend.execute(plan))
        assert all(r.state == RequestState.FINISHED for r in reqs)
        assert sched.blocks.free_blocks == sched.blocks.num_blocks
        evictions = sum(r.n_preemptions + r.n_swaps for r in reqs)
        return [list(r.generated) for r in reqs], evictions

    rec_tokens, rec_evictions = drive("recompute")
    swap_tokens, swap_evictions = drive("swap")
    assert rec_evictions >= 1 and swap_evictions >= 1, "expected pressure"
    assert rec_tokens == swap_tokens


def test_jax_backend_shares_prefix_pages():
    """Two requests with identical prompts: the scheduler hands the second
    the first's cached pages, and the jax backend decodes it correctly
    against KV it never wrote itself."""
    sched = Scheduler(SCHED_CFG)
    backend = JaxBackend(block_size=BLOCK, num_blocks=NBLOCKS, vocab=128)

    def run_one(stream_tokens, max_new=3):
        r = Request(text="", max_new_tokens=max_new)
        r.prompt_tokens = list(stream_tokens)
        sched.add_request(r)
        step = 0
        while sched.has_work and step < 200:
            plan = sched.schedule()
            if plan is None:
                break
            step += 1
            res = backend.execute(plan)
            sched.complete_step(plan, float(step), res)
        assert r.state == RequestState.FINISHED
        return r

    prompt = [3 + (i % 90) for i in range(33)]
    a = run_one(prompt)
    b = run_one(prompt)
    assert b.prefilled >= 33 - BLOCK - 1 and b.prefilled > 0
    # same prompt + deterministic greedy sampling -> same continuation,
    # even though b's prefix KV lives in pages written for a
    assert b.generated[:3] == a.generated[:3]
