"""Scheduler: the engine's ``scheduler`` span time in the window, per
plan broadcast."""
from bench import stats


def read(run):
    return stats.span_ms_per_plan(run, ("scheduler",))
