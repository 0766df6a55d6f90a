"""Whole step: the served model's operations for the tokens the window
processed (counted by the configuration's model module, once however
many chips replicate it), over window x chips x the chip's peak."""
from bench import stats


def read(run):
    if run.peak is None:
        return None
    work = stats.window_work(run, stats.first_worker(run))
    if work.rows == 0:
        return None
    span = run.window[1] - run.window[0]
    return 100.0 * run.model.model_flops(run.config, work) / (
        span * run.chips * run.peak["flops_per_s"])
