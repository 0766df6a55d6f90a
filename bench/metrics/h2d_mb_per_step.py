"""Worker backend: MB (1e6 bytes) handed to the device per step, the
``n`` of the ``upload`` spans of the steps that started in the window;
the mean over workers."""
from bench import spans


def read(run):
    return spans.per_step(run, "upload", lambda ev: ev.n / 1e6)
