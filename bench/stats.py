"""Helpers the metric readers share: percentiles and the window's cut
of requests, steps and spans."""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from bench.work import StepWork


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between ranks); None when empty."""
    return float(np.percentile(values, q)) if values else None


def window_requests(run) -> List[dict]:
    """Requests due in the window."""
    return [r for r in run.requests if r["in_window"]]


def finished(run) -> List[dict]:
    """Requests due in the window that finished."""
    return [r for r in window_requests(run)
            if r["result"] is not None and not r["result"].get("timed_out")]


def ttfts_ms(run) -> List[float]:
    """Time from each window request's due time to its first token.  A
    request with no first token counts as waiting until the load
    stopped."""
    out = []
    for r in window_requests(run):
        res = r["result"]
        t = res.get("t_first_token") if res is not None else None
        out.append(((t or run.t_end) - r["due"]) * 1e3)
    return out


def tpots_ms(run) -> List[float]:
    """Each finished window request's mean gap between output tokens,
    (t_done - t_first_token) / (n_generated - 1), for those with two
    tokens or more.  The program keeps no per-token times."""
    out = []
    for r in finished(run):
        res = r["result"]
        if res["n_generated"] >= 2:
            out.append((res["t_done"] - res["t_first_token"])
                       / (res["n_generated"] - 1) * 1e3)
    return out


def window_steps(run, idx: int) -> list:
    """Worker ``idx``'s steps that started in the window, as
    (step_id, t0, t1, n_out, tokens_written, rows, ctx)."""
    return [s for s in run.workers[idx]["steps"] if run.in_window(s[1])]


def window_work(run, idx: int) -> StepWork:
    w = StepWork()
    for s in window_steps(run, idx):
        w += StepWork(tokens_written=s[4], rows=s[5], ctx=s[6])
    return w


def first_worker(run) -> int:
    return min(run.workers)


def span_ms_per_plan(run, sites) -> Optional[float]:
    """Engine span time at ``sites`` in the window, per plan broadcast."""
    total, plans = 0.0, 0
    for role, ev in run.spans:
        if role != "engine" or not run.in_window(ev.t0):
            continue
        if ev.site in sites:
            total += ev.dur
        if ev.site == "shm_publish":
            plans += 1
    return total / plans * 1e3 if plans else None
