"""The comparison that decides ``correct``.

Each compared number has a limit, and the run is correct when every
number is at or under its limit:

* ``logit_gap`` and ``logit_gap_mean``: for a sample of finished
  requests drawn from the seed (the longest among them), every token the
  workers' backends sampled for them (each served token, and the sample
  at the end of each earlier prefill chunk, which the engine drops), set
  against the plain reference of the configuration's model (its module's
  ``logits``, ``bench/models/<name>.py``) run over the same input tokens:
  the gap by which the sampled token's reference logit lies below the
  reference's best, in units of the row's standard deviation; the
  widest, and the mean over the sampled tokens.  Greedy tokens only,
  which is all the program samples.  Those the configuration gives a
  limit are compared.  In a control run the control's tokens stand in
  for the workers' (``compare``).
* ``prompt_mismatch``: sampled requests whose prompt, as the worker
  received it, is not the generator's token for token.  Limit 0.
* ``length_mismatch``: finished requests whose output count differs
  from their drawn output length, as the client saw it or as the worker
  served it.  Limit 0.
* ``never_done``: requests due in the window that never finished.
  Limit 0.
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

MISSING = 1e9          # the gap of a token that is absent or out of range


def pick_sample(candidates: List[dict], seed: int,
                min_tokens: int) -> List[dict]:
    """The longest candidate (prompt plus output), then others in an
    order drawn from ``seed``, until the sample serves ``min_tokens``."""
    if not candidates:
        return []
    rest = sorted(candidates, key=lambda r: r["idx"])
    first = max(rest, key=lambda r: (len(r["prompt_ids"]) + r["max_new"],
                                     -r["idx"]))
    rest.remove(first)
    order = np.random.default_rng([seed % (1 << 64), 99]).permutation(
        len(rest))
    out, served = [first], first["max_new"]
    for i in order:
        if served >= min_tokens:
            break
        out.append(rest[i])
        served += rest[i]["max_new"]
    return out


def compare(model: ModuleType, config: dict, seed: int,
            sample: List[dict], dumps: Dict[int, dict],
            control: bool = False) -> dict:
    """Readings of the sampled requests on every worker: the widest
    ``logit_gap``, ``prompt_mismatch`` and the workers' share of
    ``length_mismatch``, against ``model``'s reference with its weights
    drawn from the run's ``seed``.  With ``control``, the control's tokens
    stand in the workers' place: at each sampled position, the token the
    model's ``logits_control`` puts first, over the same input tokens."""
    preempted = set()
    for d in dumps.values():
        preempted |= set(d["preempted"])
    sample = [r for r in sample if r["rid"] not in preempted]
    tokens = {0}
    for r in sample:
        for d in dumps.values():
            tokens |= set(d["streams"][r["rid"]])
    wt = model.draw_weights(seed, config, tokens)
    out = {"logit_gap": 0.0, "prompt_mismatch": 0, "length_mismatch": 0,
           "sampled_checked": 0, "skipped_preempted": len(preempted)}
    total = 0.0
    for r in sample:
        rid, prompt = r["rid"], r["prompt_ids"]
        per_worker = [(d["streams"][rid], d["samples"][rid],
                       d["chunk_samples"][rid]) for d in dumps.values()]
        ref, ctl, key = None, None, None
        for stream, served, chunks in per_worker:
            if stream[:len(prompt)] != prompt:
                out["prompt_mismatch"] += 1
                continue
            if len(served) != r["max_new"]:
                out["length_mismatch"] += 1
            samples = chunks + served
            if not samples:
                continue
            lens = [n for n, _ in samples]
            if key != (stream, lens):
                key = (stream, lens)
                ref = model.logits(config, wt, stream, lens)
                if control:
                    ctl = model.logits_control(config, wt, stream,
                                               lens).argmax(axis=1)
            toks = (ctl if control else
                    np.array([-1 if t is None else t for _, t in samples]))
            bad = (toks < 0) | (toks >= ref.shape[1])
            g = gaps(ref, np.where(bad, 0, toks))
            g[bad] = MISSING
            out["logit_gap"] = max(out["logit_gap"], float(g.max()))
            out["sampled_checked"] += len(toks)
            total += float(g.sum())
    out["logit_gap_mean"] = total / max(1, out["sampled_checked"])
    return out


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the row's
    best, in units of the row's standard deviation."""
    rows = np.arange(len(tokens))
    best = ref.max(axis=1)
    return (best - ref[rows, tokens]) / ref.std(axis=1)


def verdict(numbers: Dict[str, tuple]) -> bool:
    """Correct when every number is at or under its limit."""
    return all(v is not None and v <= lim for v, lim in numbers.values())


def fmt(numbers: Dict[str, tuple]) -> List[str]:
    return [f"check {k}={v} limit={lim}" for k, (v, lim) in numbers.items()]


def client_lengths(requests: List[dict]) -> int:
    """Finished requests whose client-side output count is off."""
    return sum(1 for r in requests
               if r.get("result") is not None
               and not r["result"].get("timed_out")
               and r["result"]["n_generated"] != r["max_new"])


def never_done(requests: List[dict], loop: str) -> Optional[int]:
    if loop != "open":
        return 0
    return sum(1 for r in requests if r["in_window"]
               and (r.get("result") is None
                    or r["result"].get("timed_out")))
