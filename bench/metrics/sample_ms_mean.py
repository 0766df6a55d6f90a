"""Worker backend: the host argmax over each step's logits (``sample``
spans), in ms per step over the steps that started
in the window; the mean over workers."""
from bench import spans


def read(run):
    return spans.ms_per_step(run, "sample")
