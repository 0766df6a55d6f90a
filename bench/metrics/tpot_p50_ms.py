"""Median, over requests due in the window that finished with at least
two tokens, of each request's mean gap between output tokens:
(t_done - t_first_token) / (n_generated - 1).  The program keeps no
per-token times, so the gap is a per-request mean."""
from bench import stats


def read(run):
    return stats.percentile(stats.tpots_ms(run), 50)
