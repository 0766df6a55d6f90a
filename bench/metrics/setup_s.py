"""Set-up: process start to window open (JAX start-up, the weights, the
warm-up of every shape bucket, the ramp of traffic before the window)."""


def read(run):
    return run.setup_s
