"""Speculative decode on the hybrid seam (docs/spec_decode.md).

The contract under test: greedy speculative decoding is a pure latency
optimization — emitted token streams are bit-identical to the
non-speculative path on every backend, with or without the async copy
engine, regardless of draft quality (a bad draft costs speed, never
correctness).  Plus the int8 KV decode tier: per-page quantization with
a provable error bound, swap round-trips that preserve codes and
scales, and the prefill->decode handoff as the precision seam.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.backend import EmulatedBackend
from repro.backend.cpu_decode import CpuDecodeBackend
from repro.backend.hybrid import HybridBackend
from repro.backend.jax_backend import JaxBackend
from repro.core.devmodel import DeviceModel
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import Scheduler, SchedulerConfig, StepPlan
from repro.spec import SpeculativeBackend

BLOCK = 8
BACKENDS = ("emulated", "jax", "cpu", "hybrid")


def _cfg(spec_k: int = 0, *, blocks: int = 64, **kw) -> SchedulerConfig:
    kw.setdefault("prefill_chunk", 16)
    return SchedulerConfig(
        max_num_seqs=8, max_tokens_per_step=64,
        block_size=BLOCK, kv_capacity_tokens=blocks * BLOCK,
        speculative_k=spec_k, **kw)


def _kw(cfg: SchedulerConfig, **extra) -> dict:
    return dict(block_size=cfg.block_size, num_blocks=cfg.num_kv_blocks,
                num_swap_blocks=max(cfg.num_swap_blocks, 1), vocab=128,
                copy_streams=cfg.copy_streams, **extra)


def _target(name: str, cfg: SchedulerConfig, kv_dtype: str = "float32"):
    kw = _kw(cfg)
    if name == "emulated":
        return EmulatedBackend(DeviceModel(t_fixed=1e-5, t_prefill_tok=1e-8,
                                           t_decode_seq=1e-6))
    if name == "jax":
        return JaxBackend(**_kw(cfg, kv_dtype=kv_dtype))
    if name == "cpu":
        return CpuDecodeBackend(**_kw(cfg, kv_dtype=kv_dtype))
    if name == "hybrid":
        return HybridBackend(JaxBackend(**kw),
                             CpuDecodeBackend(**_kw(cfg, kv_dtype=kv_dtype)),
                             t_handoff_block=1e-6,
                             copy_streams=cfg.copy_streams)
    raise AssertionError(name)


def _spec(name: str, cfg: SchedulerConfig, kv_dtype: str = "float32",
          draft_seed: int | None = None):
    target = _target(name, cfg, kv_dtype)
    if name == "emulated":
        draft = EmulatedBackend(DeviceModel(t_fixed=1e-5, t_prefill_tok=1e-8,
                                            t_decode_seq=1e-6))
    else:
        kw = _kw(cfg)
        if draft_seed is not None:
            kw["seed"] = draft_seed
        draft = CpuDecodeBackend(**kw)
    return SpeculativeBackend(draft, target)


def _req(n: int, max_new: int, stream: int = 1, eos: int = None) -> Request:
    r = Request(text="", max_new_tokens=max_new)
    r.prompt_tokens = [3 + (((stream << 10) + j) % 100) for j in range(n)]
    r.eos_token = eos
    return r


def _drive(backend, cfg: SchedulerConfig, reqs, max_plans: int = 500):
    """Run to completion; returns (token streams, n_plans, n_spec_plans)."""
    sched = Scheduler(cfg)
    for r in reqs:
        sched.add_request(r)
    plans = specs = 0
    seen = []
    while sched.has_work and plans < max_plans:
        plan = sched.schedule()
        if plan is None:
            break
        plans += 1
        specs += plan.speculative
        seen.append(plan)
        result = backend.execute(plan)
        for req in sched.complete_step(plan, float(plans), result):
            if hasattr(backend, "release"):
                backend.release(req.req_id)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert sched.blocks.free_blocks == sched.blocks.num_blocks
    return [list(r.generated) for r in reqs], plans, specs, seen


# -- wire format ------------------------------------------------------------


def test_plan_roundtrip_speculative_fields():
    plan = StepPlan(7, [], [1, 2], [], num_steps=5, speculative=True,
                    decode_steps={1: 5, 2: 3},
                    draft_tokens={1: [9, 10, 11, 12], 2: [4, 5]})
    got = StepPlan.decode_bytes(plan.encode())
    assert got.speculative is True
    assert got.num_steps == 5
    assert got.decode_steps == {1: 5, 2: 3}
    # draft candidates are worker-side transient state: every worker
    # drafts deterministically from the same seed, so they never ship
    assert got.draft_tokens == {}


def test_plan_roundtrip_nonspec_carries_no_spec_fields():
    got = StepPlan.decode_bytes(StepPlan(3, [], [1], []).encode())
    assert got.speculative is False
    assert got.draft_tokens == {}


# -- scheduler: spec plan shape ---------------------------------------------


def test_scheduler_emits_spec_plans_when_decode_steady():
    """Decode-steady batches get speculative plans with a k+1 budget,
    clamped to the remaining token budget per request."""
    cfg = _cfg(spec_k=4)
    sched = Scheduler(cfg)
    a, b = _req(12, 9, 1), _req(12, 2, 2)
    sched.add_request(a)
    sched.add_request(b)
    step = 0
    spec_plans = []
    while sched.has_work and step < 50:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        if plan.speculative:
            spec_plans.append(plan)
            for rid, budget in plan.decode_steps.items():
                req = a if rid == a.req_id else b
                rem = req.max_new_tokens - len(req.generated)
                assert budget == min(5, rem)  # k + 1, clamped to rem
            assert plan.num_steps == max(plan.decode_steps.values())
            assert not plan.prefill           # decode-steady only
        sched.complete_step(plan, float(step))
    assert spec_plans, "no speculative plan fired"
    assert any(p.num_steps == 5 for p in spec_plans)  # full budget early on


def test_spec_takes_precedence_over_multi_step():
    """With both enabled, eligible batches get a speculative plan, not a
    plain macro."""
    cfg = _cfg(spec_k=3, max_steps_per_dispatch=4)
    sched = Scheduler(cfg)
    sched.add_request(_req(12, 8, 1))
    step, saw_spec = 0, False
    while sched.has_work and step < 50:
        plan = sched.schedule()
        if plan is None:
            break
        step += 1
        if plan.num_steps > 1:
            assert plan.speculative
            saw_spec = True
        sched.complete_step(plan, float(step))
    assert saw_spec


# -- bit-identity across backends x copy engine -----------------------------


def _pressure_cfg(spec_k: int, copy_streams: int) -> SchedulerConfig:
    return SchedulerConfig(
        max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
        enable_prefix_cache=False, block_size=BLOCK,
        kv_capacity_tokens=12 * BLOCK,       # pressure: forces swap churn
        preemption_policy="swap", swap_capacity_tokens=32 * BLOCK,
        copy_streams=copy_streams, speculative_k=spec_k)


def _pressure_reqs():
    return [_req(n, m, stream=i + 1)
            for i, (n, m) in enumerate([(12, 12), (20, 9), (9, 12)])]


@pytest.fixture(scope="module")
def pressure_oracle():
    cfg = _pressure_cfg(0, 0)
    toks, _, specs, _ = _drive(CpuDecodeBackend(**_kw(cfg)), cfg,
                               _pressure_reqs())
    assert specs == 0
    return toks


@pytest.mark.parametrize("name", BACKENDS)
@pytest.mark.parametrize("streams", (0, 2))
def test_spec_bit_identical_under_pressure(name, streams, pressure_oracle):
    cfg = _pressure_cfg(4, streams)
    toks, _, specs, _ = _drive(_spec(name, cfg), cfg, _pressure_reqs())
    assert specs >= 1, "no speculative plan fired"
    if name == "emulated":                   # placeholder tokens: shape only
        assert [len(t) for t in toks] == [len(t) for t in pressure_oracle]
    else:
        assert toks == pressure_oracle


def test_divergent_draft_still_bit_identical():
    """A draft with a different seed produces garbage candidates; the
    verify step rejects them and the corrected stream is still identical
    (the draft only ever costs speed)."""
    cfg = _cfg(spec_k=4)
    oracle, _, _, _ = _drive(CpuDecodeBackend(**_kw(cfg)), _cfg(0),
                             [_req(12, 10, 1), _req(9, 8, 2)])
    sb = _spec("cpu", cfg, draft_seed=7)
    toks, _, specs, _ = _drive(sb, cfg, [_req(12, 10, 1), _req(9, 8, 2)])
    assert specs >= 1
    assert toks == oracle
    assert sb.n_accepted < sb.n_drafted      # the draft really is bad


def test_spec_eos_truncation_matches_oracle():
    """EOS inside an accepted run truncates the emitted stream exactly
    where the sequential path would have stopped."""
    base, _, _, _ = _drive(CpuDecodeBackend(**_kw(_cfg(0))), _cfg(0),
                           [_req(12, 10, 1)])
    eos = base[0][len(base[0]) // 2]         # a token mid-stream
    oracle, _, _, _ = _drive(CpuDecodeBackend(**_kw(_cfg(0))), _cfg(0),
                             [_req(12, 10, 1, eos=eos)])
    assert len(oracle[0]) < len(base[0])     # it actually truncated
    toks, _, specs, _ = _drive(_spec("cpu", _cfg(4)), _cfg(4),
                               [_req(12, 10, 1, eos=eos)])
    assert specs >= 1
    assert toks == oracle


# -- per-tier macros --------------------------------------------------------


def test_per_tier_macro_coexists_with_prefill():
    """With per_tier_macros, a macro decode plan may carry prefill
    chunks for other requests — and the streams still match the
    per-step oracle."""
    reqs = lambda: [_req(40, 8, 1), _req(30, 6, 2), _req(24, 6, 3)]
    oracle, _, _, _ = _drive(CpuDecodeBackend(**_kw(_cfg(0))), _cfg(0),
                             reqs())
    cfg = _cfg(0, max_steps_per_dispatch=4, per_tier_macros=True,
               prefill_chunk=8)
    toks, _, _, seen = _drive(CpuDecodeBackend(**_kw(cfg)), cfg, reqs())
    assert toks == oracle
    assert any(p.num_steps > 1 and p.prefill for p in seen), \
        "no macro plan carried a prefill chunk"


def test_per_tier_spec_with_prefill_in_flight():
    cfg = _cfg(4, per_tier_macros=True, prefill_chunk=8)
    oracle, _, _, _ = _drive(CpuDecodeBackend(**_kw(_cfg(0))), _cfg(0),
                             [_req(40, 8, 1), _req(24, 6, 2)])
    toks, _, specs, seen = _drive(_spec("cpu", cfg), cfg,
                                  [_req(40, 8, 1), _req(24, 6, 2)])
    assert specs >= 1
    assert toks == oracle
    assert any(p.speculative and p.prefill for p in seen), \
        "no speculative plan carried a prefill chunk"


# -- int8 KV tier -----------------------------------------------------------


def test_int8_quantization_error_bound():
    """Per-(head, page) symmetric quantization: half an LSB from the
    original rounding plus at most half an LSB per requant-on-growth.
    Incremental writes at different running maxima stay within a couple
    of LSBs at the final scale (measured 1.41 at this seed)."""
    cfg = _cfg(0)
    fp = CpuDecodeBackend(**_kw(cfg))
    q8 = CpuDecodeBackend(**_kw(cfg, kv_dtype="int8"))
    table = [0, 1, 2]
    rng = np.random.default_rng(11)
    for start, n in ((0, 7), (7, 9), (16, 8)):   # forces requants
        toks = rng.integers(3, 100, n)
        fp._write(table, start, toks)
        q8._write(table, start, toks)
    kf, vf = fp._gather_pages(np.asarray(table))
    kq, vq = q8._gather_pages(np.asarray(table))
    for got, want, scales in ((kq, kf, q8.k_scales), (vq, vf, q8.v_scales)):
        err = np.abs(got - want)             # [KV, n_pages, block, D]
        lsb = scales[:, table][:, :, None, None] / 127.0
        assert np.all(err <= 2.0 * lsb + 1e-7)


def test_int8_swap_round_trip_preserves_codes_and_scales():
    """swap-out -> clobber -> restore: codes AND per-page scales travel
    together, so the restored KV dequantizes bit-identically."""
    cfg = _cfg(0, preemption_policy="swap", swap_capacity_tokens=8 * BLOCK)
    be = CpuDecodeBackend(**_kw(cfg, kv_dtype="int8"))
    rng = np.random.default_rng(3)
    be._write([0, 1], 0, rng.integers(3, 100, 16))
    k0, v0 = be._gather_pages(np.asarray([0, 1]))
    be._copy_out([(0, 0), (1, 1)])           # park in host swap tier
    be._write([0, 1], 0, rng.integers(3, 100, 16))   # clobber dev pages
    be._copy_back([(0, 4), (1, 5)])          # restore into fresh pages
    k1, v1 = be._gather_pages(np.asarray([4, 5]))
    np.testing.assert_array_equal(k0, k1)
    np.testing.assert_array_equal(v0, v1)


def test_int8_handoff_quantizes_at_the_seam():
    """The prefill child keeps fp32; import_pages on an int8 decode child
    converts whole pages in one shot, within the quantization bound."""
    cfg = _cfg(0)
    pre = JaxBackend(**_kw(cfg))
    dec = CpuDecodeBackend(**_kw(cfg, kv_dtype="int8"))
    toks = np.arange(3, 3 + 16)
    pre._write([2, 3], 0, toks)
    dec.import_pages([2, 3], *pre.export_pages([2, 3]))
    assert dec.k_pages.dtype == np.int8
    kf, vf = pre._gather_pages(np.asarray([2, 3]))
    kq, vq = dec._gather_pages(np.asarray([2, 3]))
    for got, want, scales in ((kq, kf, dec.k_scales), (vq, vf, dec.v_scales)):
        bound = scales[:, [2, 3]][:, :, None, None] / 127.0
        assert np.all(np.abs(got - want) <= bound + 1e-7)


def test_spec_int8_deterministic():
    """spec + int8 decode tier may diverge token-wise from the fp32
    oracle (quantized logits), but it is deterministic run-to-run."""
    runs = []
    for _ in range(2):
        cfg = _cfg(4)
        sb = _spec("hybrid", cfg, kv_dtype="int8")
        toks, _, specs, _ = _drive(sb, cfg, [_req(12, 8, 1), _req(9, 6, 2)])
        assert specs >= 1
        runs.append(toks)
    assert runs[0] == runs[1]


# -- paged kernel: DMA path + int8 dequant-on-load --------------------------


def _paged_case(rng, *, int8: bool):
    import jax.numpy as jnp
    B, H, KV, D, N, blk, nb = 4, 8, 2, 16, 24, 8, 5
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kf = rng.standard_normal((KV, N, blk, D)).astype(np.float32)
    vf = rng.standard_normal((KV, N, blk, D)).astype(np.float32)
    perm = rng.permutation(N)
    bt = np.full((B, nb), -1, np.int32)
    sl = np.zeros((B,), np.int32)
    used = 0
    for b, n_tok in enumerate([37, 8, 0, 25]):
        n_pages = -(-n_tok // blk)
        bt[b, :n_pages] = perm[used:used + n_pages]
        used += n_pages
        sl[b] = n_tok
    args = [jnp.asarray(bt), jnp.asarray(sl)]
    if not int8:
        return (q, jnp.asarray(kf), jnp.asarray(vf), *args), {}
    ks = np.abs(kf).max(axis=(2, 3)).astype(np.float32)      # [KV, N]
    vs = np.abs(vf).max(axis=(2, 3)).astype(np.float32)
    k8 = np.rint(kf / (ks[:, :, None, None] / 127.0)).astype(np.int8)
    v8 = np.rint(vf / (vs[:, :, None, None] / 127.0)).astype(np.int8)
    return ((q, jnp.asarray(k8), jnp.asarray(v8), *args),
            dict(k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs)))


@pytest.mark.parametrize("int8", (False, True))
def test_paged_kernel_hbm_path_matches_reference(int8):
    """Pool larger than the VMEM budget forces the DMA double-buffered
    path; it must match the gather reference (exactly for fp32, within
    the dequant bound for int8)."""
    from repro.kernels.paged_decode_attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )
    args, kw = _paged_case(np.random.default_rng(7), int8=int8)
    out = paged_decode_attention(*args, **kw, vmem_budget_bytes=1024)
    ref = paged_decode_attention_reference(*args, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_kernel_hbm_agrees_with_vmem_path():
    """Same inputs through both residency paths: identical numerics."""
    from repro.kernels.paged_decode_attention import paged_decode_attention
    args, kw = _paged_case(np.random.default_rng(9), int8=True)
    hbm = paged_decode_attention(*args, **kw, pool_in_vmem=False)
    vmem = paged_decode_attention(*args, **kw, pool_in_vmem=True)
    np.testing.assert_allclose(np.asarray(hbm), np.asarray(vmem),
                               atol=1e-6, rtol=1e-6)


def test_paged_kernel_int8_drift_vs_fp32_bounded():
    """int8 attention vs the fp32 oracle on the same values: the output
    drift stays within a loose bound (measured ~8e-3 at this shape)."""
    from repro.kernels.paged_decode_attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )
    rng = np.random.default_rng(7)
    fp_args, _ = _paged_case(rng, int8=False)
    q_args, q_kw = _paged_case(np.random.default_rng(7), int8=True)
    want = paged_decode_attention_reference(*fp_args)
    got = paged_decode_attention(*q_args, **q_kw, vmem_budget_bytes=1024)
    rows = np.asarray(fp_args[4]) > 0        # seq_len 0 rows are inert
    drift = np.abs(np.asarray(got) - np.asarray(want))[rows].max()
    assert drift < 0.05, drift


# -- DES integration --------------------------------------------------------


def test_sim_with_speculative_runs_and_fires_spec_plans():
    from repro.sim.serving import (ServingModel, llama8b_tp4_params,
                                   with_speculative)
    params = with_speculative(llama8b_tp4_params(1), k=4, accept_rate=0.8,
                              kv_dtype="int8")
    model = ServingModel(params)
    for i in range(3):
        model.add_request(0.0, 64, max_new_tokens=24, stream=i)
    res = model.run(horizon=200.0)
    assert all(r.t_done for r in res.requests)
    assert sum(p.speculative for p in model._plans.values()) >= 1
    # spec plans collapse dispatch rounds vs one-step-per-token
    assert len(model._plans) < 3 * 24
