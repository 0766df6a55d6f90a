"""Multi-step dispatch: k-step macro-plans vs the per-step control floor.

Two sections (docs/multi_step.md):

``sweep`` — DES core-count sweep over a decode-steady workload (short
prompts, long decode tails, everything resident from t=0): the whole
run is one long decode phase, so per-token cost is dominated by the
control plane when cores are scarce.  For each (cores, k) cell we
report the per-token CONTROL cost — makespan minus the device-model
execution time, divided by generated tokens — which collapses ~k-fold
as each broadcast/dispatch/barrier round trip carries k tokens.  The
acceptance gate for the optimization is the ``collapse_vs_k1`` column
at k=8 on 1 core (>= 3x).

``conformance`` — the real ``Scheduler`` driving all four backends
(emulated / jax / cpu / hybrid) to completion at k=8 and k=1: sampled
token streams must be bit-identical (macro-stepping is a pure latency
optimization), and at least one macro-plan must actually have fired.

  PYTHONPATH=src python -m benchmarks.multi_step [--fast]
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

from repro.backend import EmulatedBackend
from repro.core.devmodel import DeviceModel
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.sim.serving import ServingModel, llama8b_tp4_params, with_multi_step

ARTIFACTS = Path(__file__).resolve().parent.parent / "artifacts"

KS = (1, 2, 4, 8)


# -- DES sweep: per-token control cost vs k ---------------------------------

def _decode_steady_run(n_cores: int, k: int, *, n_req: int, prompt: int,
                       max_new: int) -> dict:
    params = with_multi_step(llama8b_tp4_params(n_cores), k=k)
    model = ServingModel(params)
    for i in range(n_req):
        model.add_request(0.0, prompt, max_new_tokens=max_new, stream=i)
    res = model.run(horizon=400.0)
    assert all(r.state == RequestState.FINISHED for r in res.requests)
    toks = sum(len(r.generated) for r in res.requests)
    # device-side execution time, as the engine charged it: everything
    # else in the makespan is control plane (schedule / serialize /
    # broadcast / dequeue / dispatch / barrier, under GPS contention)
    device_s = sum(model.backend.step_cost(p) * model._fusion_rounds(p)
                   for p in model._plans.values())
    makespan = max(r.t_done for r in res.requests)
    macro_plans = sum(1 for p in model._plans.values() if p.num_steps > 1)
    return {
        "cores": n_cores, "k": k,
        "plans": len(model._plans), "macro_plans": macro_plans,
        "tokens": toks,
        "makespan_s": round(makespan, 3),
        "device_s": round(device_s, 3),
        "per_token_control_ms": round(
            (makespan - device_s) / max(toks, 1) * 1e3, 3),
    }


def control_floor_sweep(fast: bool = False) -> list:
    cores = (1,) if fast else (1, 32)
    n_req, prompt, max_new = (4, 16, 24) if fast else (8, 16, 96)
    rows = []
    base = {}
    for c in cores:
        for k in KS:
            row = _decode_steady_run(c, k, n_req=n_req, prompt=prompt,
                                     max_new=max_new)
            if k == 1:
                base[c] = row["per_token_control_ms"]
            row["collapse_vs_k1"] = round(
                base[c] / max(row["per_token_control_ms"], 1e-9), 2)
            rows.append(row)
    return rows


# -- conformance: k=8 bit-identical to k=1 on every backend -----------------

BLOCK, NBLOCKS = 8, 64


def _make_backend(name: str, cfg: SchedulerConfig):
    from repro.backend.cpu_decode import CpuDecodeBackend
    from repro.backend.hybrid import HybridBackend
    from repro.backend.jax_backend import JaxBackend
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


def _drive(name: str, k: int):
    cfg = SchedulerConfig(
        max_num_seqs=8, max_tokens_per_step=64, prefill_chunk=16,
        block_size=BLOCK, kv_capacity_tokens=NBLOCKS * BLOCK,
        max_steps_per_dispatch=k)
    backend = _make_backend(name, cfg)
    sched = Scheduler(cfg)
    reqs = []
    for i, (n, m) in enumerate([(12, 16), (20, 12), (9, 16)]):
        r = Request(text="", max_new_tokens=m)
        r.prompt_tokens = [3 + ((((i + 1) << 10) + j) % 100)
                           for j in range(n)]
        reqs.append(r)
        sched.add_request(r)
    plans = macros = 0
    while sched.has_work and plans < 500:
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


def conformance(fast: bool = False) -> list:
    backends = ("emulated", "cpu") if fast else ("emulated", "jax", "cpu",
                                                 "hybrid")
    rows = []
    for name in backends:
        ref, plans_1, _ = _drive(name, 1)
        got, plans_8, macros = _drive(name, 8)
        identical = (got == ref) if name != "emulated" else (
            [len(t) for t in got] == [len(t) for t in ref])
        assert macros >= 1, f"{name}: no macro-plan fired"
        assert identical, f"{name}: k=8 diverged from k=1"
        rows.append({"backend": name, "plans_k1": plans_1,
                     "plans_k8": plans_8, "macro_plans": macros,
                     "bit_identical": identical})
    return rows


def run(write: bool = True, fast: bool = False) -> dict:
    out = {"sweep": control_floor_sweep(fast=fast),
           "conformance": conformance(fast=fast)}
    if write:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        (ARTIFACTS / "multi_step.json").write_text(json.dumps(out, indent=1))
    return out


def main(fast: bool = False) -> None:
    out = run(fast=fast)
    print("sweep: cores,k,plans,macro_plans,per_token_control_ms,"
          "collapse_vs_k1")
    for r in out["sweep"]:
        print(f"{r['cores']},{r['k']},{r['plans']},{r['macro_plans']},"
              f"{r['per_token_control_ms']},{r['collapse_vs_k1']}")
    print("conformance: backend,plans_k1,plans_k8,macro_plans,bit_identical")
    for r in out["conformance"]:
        print(f"{r['backend']},{r['plans_k1']},{r['plans_k8']},"
              f"{r['macro_plans']},{r['bit_identical']}")


if __name__ == "__main__":
    main(fast="--fast" in sys.argv)
