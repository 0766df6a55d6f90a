"""Broadcast: the engine's ``shm_encode`` and ``shm_publish`` span time
in the window, per plan broadcast."""
from bench import stats


def read(run):
    return stats.span_ms_per_plan(run, ("shm_encode", "shm_publish"))
