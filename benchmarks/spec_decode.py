"""Speculative decode: fewer steps x cheaper steps (docs/spec_decode.md).

Three sections:

``sweep`` — DES decode-steady workload (everything resident, long decode
tails) on 1 host core, comparing the non-speculative per-step baseline
against speculative verify plans at ``k=4`` across the two axes that
decide whether CPU drafting pays: the **acceptance rate** (how often the
cheap draft guesses the target's token) and the **draft slowdown** (how
much slower the CPU tier decodes than the accelerator).  Each cell
reports decode-steady per-token latency and the win over the baseline;
the acceptance gate for the optimization is ``win >= 1.5x`` at
acceptance 0.7 with the default CPU tier (slowdown 8).  The crossover
row reports where drafting stops paying: the smallest swept slowdown
whose win drops below 1.0 at each acceptance rate.

``int8`` rides the same sweep: ``kv_dtype="int8"`` halves every KV byte
the decode tier's cost model charges (swap copies + the KV-bandwidth
share of decode), shifting the crossover outward.

``conformance`` — the real ``Scheduler`` + ``SpeculativeBackend``
driving all four backends (emulated / jax / cpu / hybrid) x copy
streams {0, 2} to completion under memory pressure: greedy speculative
output must be token-bit-identical to the non-speculative jax oracle
(speculation is a pure latency optimization), and at least one
speculative plan must actually have fired.

  PYTHONPATH=src python -m benchmarks.spec_decode [--fast]
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

from repro.backend import EmulatedBackend
from repro.core.devmodel import DeviceModel
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.sim.serving import (ServingModel, llama8b_tp4_params,
                               with_speculative)
from repro.spec import SpeculativeBackend

ARTIFACTS = Path(__file__).resolve().parent.parent / "artifacts"

SPEC_K = 4


# -- DES sweep: acceptance rate x draft slowdown x kv dtype -----------------

def _decode_steady_run(params, *, n_req: int, prompt: int,
                       max_new: int) -> dict:
    model = ServingModel(params)
    for i in range(n_req):
        model.add_request(0.0, prompt, max_new_tokens=max_new, stream=i)
    res = model.run(horizon=400.0)
    assert all(r.state == RequestState.FINISHED for r in res.requests)
    toks = sum(len(r.generated) for r in res.requests)
    makespan = max(r.t_done for r in res.requests)
    spec_plans = sum(1 for p in model._plans.values() if p.speculative)
    swap_blocks = sum(p.n_swapped_blocks for p in model._plans.values())
    return {"plans": len(model._plans), "spec_plans": spec_plans,
            "tokens": toks, "swap_blocks": swap_blocks,
            "makespan_s": round(makespan, 3),
            "per_token_ms": round(makespan / max(toks, 1) * 1e3, 4)}


def sweep(fast: bool = False) -> dict:
    n_req, prompt, max_new = (4, 16, 24) if fast else (8, 16, 96)
    accepts = (0.0, 0.7, 1.0) if fast else (0.0, 0.3, 0.5, 0.7, 0.9, 1.0)
    slowdowns = (8.0, 64.0) if fast else (4.0, 8.0, 16.0, 32.0, 64.0,
                                          128.0, 256.0, 512.0, 1024.0)
    base_params = llama8b_tp4_params(1)
    base = _decode_steady_run(base_params, n_req=n_req, prompt=prompt,
                              max_new=max_new)
    assert base["spec_plans"] == 0
    rows = []
    for kv_dtype in ("float32", "int8"):
        for accept in accepts:
            for slow in slowdowns:
                if accept != 0.7 and slow != 8.0:
                    continue          # the two swept axes cross at (0.7, 8)
                cell = _decode_steady_run(
                    with_speculative(base_params, k=SPEC_K,
                                     accept_rate=accept,
                                     draft_slowdown=slow,
                                     kv_dtype=kv_dtype),
                    n_req=n_req, prompt=prompt, max_new=max_new)
                assert cell["spec_plans"] >= 1, "no speculative plan fired"
                cell.update(accept=accept, draft_slowdown=slow,
                            kv_dtype=kv_dtype,
                            win_vs_baseline=round(
                                base["per_token_ms"]
                                / max(cell["per_token_ms"], 1e-9), 2))
                rows.append(cell)

    def crossover(dtype: str):
        """Smallest swept slowdown where drafting stops paying (win < 1)
        at acceptance 0.7, or None if it pays across the whole sweep."""
        losing = sorted(r["draft_slowdown"] for r in rows
                        if r["kv_dtype"] == dtype and r["accept"] == 0.7
                        and r["win_vs_baseline"] < 1.0)
        return losing[0] if losing else None

    win07 = {r["kv_dtype"]: r["win_vs_baseline"] for r in rows
             if r["accept"] == 0.7 and r["draft_slowdown"] == 8.0}
    return {"baseline": base, "rows": rows,
            "win_at_accept_0.7": win07,
            "crossover_slowdown": {d: crossover(d)
                                   for d in ("float32", "int8")}}


# -- int8 under memory pressure: the halved swap bytes ----------------------

def int8_pressure(fast: bool = False) -> dict:
    """Decode-steady cells are dispatch-floor-dominated at paper scale,
    so the int8 savings there are invisible (the sweep shows it); the
    bytes int8 actually buys back are the KV *block copies* — swap-out /
    restore churn under memory pressure (and the hybrid handoff).  This
    section reruns the speculative workload with a KV pool ~60% of the
    working set, swap-policy preemption, and decode-heavy tails (short
    prompts, long generations): everyone fits at admission but the tails
    outgrow the pool, so blocks churn through the swap tier — and every
    evicted block now moves at half the bytes.  Both the end-to-end
    per-token win AND the copy-term decomposition are reported: at paper
    scale the copy seconds halve while the end-to-end win stays near
    1.0 — the control plane, not the interconnect, still dominates the
    tail, which is the paper's thesis restated in the KV-precision
    axis."""
    n_req, prompt, max_new = (4, 120, 200) if fast else (6, 200, 400)
    working_set = n_req * (prompt + max_new)
    out = {}
    for kv_dtype in ("float32", "int8"):
        params = llama8b_tp4_params(
            1, preemption_policy="swap",
            kv_capacity_tokens=int(working_set * 0.6))
        cell = _decode_steady_run(
            with_speculative(params, k=SPEC_K, accept_rate=0.7,
                             kv_dtype=kv_dtype),
            n_req=n_req, prompt=prompt, max_new=max_new)
        dev = params.device.with_kv_dtype(kv_dtype)
        cell["swap_charge_s"] = round(
            cell["swap_blocks"] * dev.t_swap_block * dev.kv_byte_factor, 4)
        out[kv_dtype] = cell
    out["win_int8_end_to_end"] = round(
        out["float32"]["per_token_ms"]
        / max(out["int8"]["per_token_ms"], 1e-9), 3)
    out["win_int8_copy_term"] = round(
        out["float32"]["swap_charge_s"]
        / max(out["int8"]["swap_charge_s"], 1e-9), 3)
    return out


# -- conformance: spec k=4 bit-identical to the non-spec jax oracle ---------

BLOCK, NBLOCKS, NSWAP = 8, 64, 32


def _make_backend(name: str, cfg: SchedulerConfig, spec: bool):
    from repro.backend.cpu_decode import CpuDecodeBackend
    from repro.backend.hybrid import HybridBackend
    from repro.backend.jax_backend import JaxBackend
    kw = dict(block_size=cfg.block_size, num_blocks=cfg.num_kv_blocks,
              num_swap_blocks=cfg.num_swap_blocks,
              copy_streams=cfg.copy_streams, vocab=128)
    dev = DeviceModel(t_fixed=1e-5, t_prefill_tok=1e-8, t_decode_seq=1e-6)
    if name == "emulated":
        target = EmulatedBackend(dev)
    elif name == "jax":
        target = JaxBackend(**kw)
    elif name == "cpu":
        target = CpuDecodeBackend(**kw)
    elif name == "hybrid":
        target = HybridBackend(JaxBackend(**kw), CpuDecodeBackend(**kw),
                               t_handoff_block=1e-6,
                               copy_streams=cfg.copy_streams)
    else:
        raise AssertionError(name)
    if not spec:
        return target
    draft = (EmulatedBackend(dev.cpu_tier()) if name == "emulated"
             else CpuDecodeBackend(**kw))
    return SpeculativeBackend(draft, target)


def _drive(name: str, spec_k: int, copy_streams: int):
    cfg = SchedulerConfig(
        max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
        enable_prefix_cache=False, block_size=BLOCK,
        kv_capacity_tokens=12 * BLOCK,        # pressure: forces swap churn
        preemption_policy="swap", swap_capacity_tokens=NSWAP * BLOCK,
        copy_streams=copy_streams, speculative_k=spec_k)
    backend = _make_backend(name, cfg, spec=spec_k > 0)
    sched = Scheduler(cfg)
    reqs = []
    for i, (n, m) in enumerate([(12, 16), (20, 12), (9, 16)]):
        r = Request(text="", max_new_tokens=m)
        r.prompt_tokens = [3 + ((((i + 1) << 10) + j) % 100)
                           for j in range(n)]
        reqs.append(r)
        sched.add_request(r)
    plans = specs = 0
    while sched.has_work and plans < 500:
        plan = sched.schedule()
        if plan is None:
            break
        plans += 1
        specs += plan.speculative
        result = backend.execute(plan)
        for req in sched.complete_step(plan, float(plans), result):
            if hasattr(backend, "release"):
                backend.release(req.req_id)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert sched.blocks.free_blocks == sched.blocks.num_blocks
    return [list(r.generated) for r in reqs], plans, specs


def conformance(fast: bool = False) -> list:
    backends = ("emulated", "cpu") if fast else ("emulated", "jax", "cpu",
                                                 "hybrid")
    streams = (0,) if fast else (0, 2)
    oracle, oracle_plans, _ = _drive("cpu" if fast else "jax", 0, 0)
    rows = []
    for name in backends:
        for s in streams:
            got, plans, specs = _drive(name, SPEC_K, s)
            identical = (got == oracle) if name != "emulated" else (
                [len(t) for t in got] == [len(t) for t in oracle])
            assert specs >= 1, f"{name}/streams={s}: no spec plan fired"
            assert identical, \
                f"{name}/streams={s}: speculative diverged from oracle"
            rows.append({"backend": name, "copy_streams": s,
                         "plans_nonspec": oracle_plans, "plans_spec": plans,
                         "spec_plans": specs, "bit_identical": identical})
    return rows


def run(write: bool = True, fast: bool = False) -> dict:
    out = {"sweep": sweep(fast=fast),
           "int8_pressure": int8_pressure(fast=fast),
           "conformance": conformance(fast=fast)}
    win = out["sweep"]["win_at_accept_0.7"]["float32"]
    assert win >= 1.5, \
        f"decode-steady win at acceptance 0.7 below target: {win}x < 1.5x"
    if write:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        (ARTIFACTS / "spec_decode.json").write_text(json.dumps(out, indent=1))
    return out


def main(fast: bool = False) -> None:
    out = run(fast=fast)
    sw = out["sweep"]
    print(f"baseline per-token: {sw['baseline']['per_token_ms']}ms")
    print("sweep: kv_dtype,accept,draft_slowdown,per_token_ms,"
          "win_vs_baseline,spec_plans")
    for r in sw["rows"]:
        print(f"{r['kv_dtype']},{r['accept']},{r['draft_slowdown']},"
              f"{r['per_token_ms']},{r['win_vs_baseline']},"
              f"{r['spec_plans']}")
    print(f"win at accept 0.7 (slowdown 8): {sw['win_at_accept_0.7']}")
    print(f"crossover slowdown at accept 0.7: {sw['crossover_slowdown']}")
    pr = out["int8_pressure"]
    print(f"int8 under swap pressure: fp32="
          f"{pr['float32']['per_token_ms']}ms int8="
          f"{pr['int8']['per_token_ms']}ms "
          f"end_to_end={pr['win_int8_end_to_end']}x "
          f"copy_term={pr['win_int8_copy_term']}x")
    print("conformance: backend,copy_streams,plans_spec,spec_plans,"
          "bit_identical")
    for r in out["conformance"]:
        print(f"{r['backend']},{r['copy_streams']},{r['plans_spec']},"
              f"{r['spec_plans']},{r['bit_identical']}")


if __name__ == "__main__":
    main(fast="--fast" in sys.argv)
