"""Work counts from shapes, and the chip's published peaks.

The served model is the program's paged surrogate: per token it writes
K and V (projections of the token's embedding), and per sampled row it
projects a query, attends over the row's cached tokens and projects the
attention output to logits over the vocabulary.  Counts are of the
useful work of that model, two operations per multiply-add, whatever
runs it and wherever: padding to buckets is not counted.
"""
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


@dataclasses.dataclass(frozen=True)
class Widths:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    dtype_bytes: int = 4          # the KV pool and the projections: f32

    @property
    def embed(self) -> int:
        return self.n_heads * self.head_dim


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


def paged_attn_flops(w: Widths, work: StepWork) -> float:
    """q.k and p.v over every attended token, for every query head."""
    return 4.0 * w.n_heads * w.head_dim * work.ctx


def paged_attn_bytes(w: Widths, work: StepWork) -> float:
    """K and V of every attended token, read once per kv head, plus each
    row's query read and output written."""
    kv = 2.0 * w.n_kv_heads * w.head_dim * w.dtype_bytes * work.ctx
    q_out = 2.0 * w.n_heads * w.head_dim * w.dtype_bytes * work.rows
    return kv + q_out


def model_flops(w: Widths, work: StepWork) -> float:
    """The served model's operations: K/V projections per token written;
    query projection, attention and logits projection per sampled row."""
    e = w.embed
    kv_proj = 2.0 * e * 2 * w.n_kv_heads * w.head_dim
    q_proj = 2.0 * e * w.n_heads * w.head_dim
    logits = 2.0 * e * w.vocab
    return (kv_proj * work.tokens_written + (q_proj + logits) * work.rows
            + paged_attn_flops(w, work))


def least_time(flops: float, nbytes: float, pk: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
