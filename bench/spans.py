"""The program's own spans inside each worker's step.

A traced worker records its step as an ``execute`` span and, inside it,
one span per phase of the backend (``kv_write``, ``page_gather``,
``upload``, ``device_wait``, ``sample``), each with the plan's step id.
The step id joins a phase span to its step.  A program that records no
such spans reads None."""
from __future__ import annotations

from typing import Callable, Dict, Optional, Set

STEP = "execute"


def per_step(run, site: str, value: Callable) -> Optional[float]:
    """Per worker, ``value(span)`` summed over its ``site`` spans of the
    steps whose ``execute`` span started in the window, over the number
    of those steps; then the mean over workers.  None when no such step
    has a ``site`` span."""
    steps: Dict[str, Set[int]] = {}
    for role, ev in run.spans:
        if ev.site == STEP and run.in_window(ev.t0):
            steps.setdefault(role, set()).add(ev.step)
    totals = {role: 0.0 for role in steps}
    found = False
    for role, ev in run.spans:
        if ev.site == site and ev.step in steps.get(role, ()):
            totals[role] += value(ev)
            found = True
    if not found:
        return None
    return sum(totals[r] / len(steps[r]) for r in steps) / len(steps)


def ms_per_step(run, site: str) -> Optional[float]:
    """Milliseconds of ``site`` spans per step (see ``per_step``)."""
    return per_step(run, site, lambda ev: ev.dur * 1e3)
