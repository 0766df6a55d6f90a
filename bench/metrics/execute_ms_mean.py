"""Worker backend: mean host wall time of ``execute`` over the steps that
started in the window, over every worker."""
from bench import stats


def read(run):
    d = [s[2] - s[1] for i in run.workers for s in stats.window_steps(run, i)]
    return sum(d) / len(d) * 1e3 if d else None
