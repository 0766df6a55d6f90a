"""Tokenizer: 95th percentile of each window request's tokenize time
(t_tokenize_done - t_tokenize_start)."""
from bench import stats


def read(run):
    return stats.percentile(
        [(r["result"]["t_tokenize_done"] - r["result"]["t_tokenize_start"])
         * 1e3 for r in stats.finished(run)], 95)
