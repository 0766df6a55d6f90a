"""Completion barrier: the engine's ``barrier`` span time in the window
(waiting for every worker to finish the step), per plan broadcast."""
from bench import stats


def read(run):
    return stats.span_ms_per_plan(run, ("barrier",))
