"""Whole step: the served model's operations for the tokens the window
processed (counted from the model's shapes, once however many chips
replicate it), over window x chips x the chip's peak."""
from bench import stats
from bench.work import model_flops


def read(run):
    if run.peak is None:
        return None
    work = stats.window_work(run, stats.first_worker(run))
    if work.rows == 0:
        return None
    span = run.window[1] - run.window[0]
    return 100.0 * model_flops(run.widths, work) / (
        span * run.chips * run.peak["flops_per_s"])
