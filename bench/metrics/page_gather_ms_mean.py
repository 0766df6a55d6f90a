"""Worker backend: the host copy of the pages each step references
(``page_gather`` spans), in ms per step over the steps that started
in the window; the mean over workers."""
from bench import spans


def read(run):
    return spans.ms_per_step(run, "page_gather")
