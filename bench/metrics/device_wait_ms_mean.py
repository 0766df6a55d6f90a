"""Worker backend: from the device call until the logits are back on
the host (``device_wait`` spans: dispatch, the chip's work and the
read-back), in ms per step over the steps that started in the window;
the mean over workers."""
from bench import spans


def read(run):
    return spans.ms_per_step(run, "device_wait")
