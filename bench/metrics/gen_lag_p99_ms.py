"""Load generator: 99th percentile of send time minus due time over the
window's requests (how late the generator ran)."""
from bench import stats


def read(run):
    lags = [(r["result"]["t_arrival"] - r["due"]) * 1e3
            for r in stats.window_requests(run) if r["result"] is not None]
    return stats.percentile(lags, 99)
