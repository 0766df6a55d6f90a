"""Output tokens the served path produced in steps that started inside
the window, over the window's length (the first worker's steps: every
worker of a replicated step produces the same tokens)."""
from bench import stats


def read(run):
    steps = stats.window_steps(run, stats.first_worker(run))
    return sum(s[3] for s in steps) / (run.window[1] - run.window[0])
