"""Worker backend: host K/V projection and page writes (``kv_write``
spans), in ms per step over the steps that started
in the window; the mean over workers."""
from bench import spans


def read(run):
    return spans.ms_per_step(run, "kv_write")
