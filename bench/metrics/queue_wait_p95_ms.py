"""Scheduler: 95th percentile of each finished window request's wait
from the end of its tokenization to its first plan
(t_first_scheduled - t_tokenize_done).  None for a program that does
not stamp t_first_scheduled."""
from bench import stats


def read(run):
    return stats.percentile(
        [(res["t_first_scheduled"] - res["t_tokenize_done"]) * 1e3
         for res in (r["result"] for r in stats.finished(run))
         if res.get("t_first_scheduled")], 95)
