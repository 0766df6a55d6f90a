"""The surrogate's model module with its reference's output projection
negated.  A configuration that names this module has to read not
correct: the check follows the module the configuration names."""
from bench.models import surrogate
from bench.models.surrogate import *  # noqa: F401,F403


def draw_weights(seed, config, tokens):
    wt = surrogate.draw_weights(seed, config, tokens)
    wt.wo = -wt.wo
    return wt
