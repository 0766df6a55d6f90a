"""The comparison that decides ``correct``.

Each compared number has a limit, and the run is correct when every
number is at or under its limit:

* ``logit_gap`` and ``logit_gap_mean``: for a sample of finished
  requests drawn from the seed (the longest among them), every token the
  workers' backends sampled for them (each served token, and the sample
  at the end of each earlier prefill chunk, which the engine drops), set
  against the plain reference (``bench.reference``) run over the same
  input tokens: the gap by which the sampled token's reference logit
  lies below the reference's best, in units of the row's standard
  deviation; the widest, and the mean over the sampled tokens.  Greedy
  tokens only, which is all the program samples.  Those the
  configuration gives a limit are compared.  In a control run the
  control's tokens stand in for the workers' (``compare``).
* ``prompt_mismatch``: sampled requests whose prompt, as the worker
  received it, is not the generator's token for token.  Limit 0.
* ``length_mismatch``: finished requests whose output count differs
  from their drawn output length, as the client saw it or as the worker
  served it.  Limit 0.
* ``never_done``: requests due in the window that never finished.
  Limit 0.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import reference
from bench.work import Widths

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


def compare(w: Widths, weight_seed: int, sample: List[dict],
            dumps: Dict[int, dict], control: Optional[str] = None) -> dict:
    """Readings of the sampled requests on every worker: the widest
    ``logit_gap``, ``prompt_mismatch`` and the workers' share of
    ``length_mismatch``.  With ``control`` (a precision of
    ``reference.CONTROL_BELOW``), the control's tokens stand in the
    workers' place: at each sampled position, the token the reference in
    that precision puts first, over the same input tokens."""
    if control not in (None, "fp8_e4m3"):
        raise ValueError(f"no control in {control!r}")
    preempted = set()
    for d in dumps.values():
        preempted |= set(d["preempted"])
    sample = [r for r in sample if r["rid"] not in preempted]
    tokens = {0}
    for r in sample:
        for d in dumps.values():
            tokens |= set(d["streams"][r["rid"]])
    wt = reference.draw_weights(weight_seed, w, tokens)
    wo_c = reference.fp8(wt.wo) if control else None
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
                ref = reference.logits(w, wt, stream, lens)
                if control:
                    ctl = reference.logits_fp8(w, wt, stream, lens,
                                               wo_c).argmax(axis=1)
            toks = (ctl if control else
                    np.array([-1 if t is None else t for _, t in samples]))
            bad = (toks < 0) | (toks >= w.vocab)
            g = reference.gaps(ref, np.where(bad, 0, toks))
            g[bad] = MISSING
            out["logit_gap"] = max(out["logit_gap"], float(g.max()))
            out["sampled_checked"] += len(toks)
            total += float(g.sum())
    out["logit_gap_mean"] = total / max(1, out["sampled_checked"])
    return out


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
