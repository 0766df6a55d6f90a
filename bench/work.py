"""The chip's published peaks, what a step processed, and the least time
work could take at those peaks.  The counts of a model's operations and
bytes live with the model (``bench/models/<name>.py``)."""
from __future__ import annotations

import dataclasses

# Published peaks of one chip, by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (bf16 peak, HBM size and
# bandwidth per chip).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/work.py") from None


@dataclasses.dataclass
class StepWork:
    """What one step (or a sum of steps) processed.

    ``tokens_written``: tokens whose K/V the step wrote (prefill chunk
    tokens plus one per decode row); ``rows``: rows sampled (one per
    prefill chunk and per decode row); ``ctx``: the sum over those rows
    of the cached tokens each attended."""
    tokens_written: int = 0
    rows: int = 0
    ctx: int = 0

    def __iadd__(self, other: "StepWork") -> "StepWork":
        self.tokens_written += other.tokens_written
        self.rows += other.rows
        self.ctx += other.ctx
        return self


def least_time(flops: float, nbytes: float, pk: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
