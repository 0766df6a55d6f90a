"""Median time to first token over all requests due in the window, from
each request's due time (open loop)."""
from bench import stats


def read(run):
    return stats.percentile(stats.ttfts_ms(run), 50)
