"""Tokenizer pool queue: 95th percentile of the wait from each window
request's due time to the start of its tokenization."""
from bench import stats


def read(run):
    return stats.percentile(
        [(r["result"]["t_tokenize_start"] - r["due"]) * 1e3
         for r in stats.finished(run)], 95)
