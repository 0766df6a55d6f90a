"""Work counts (the surrogate's, ``bench/models/surrogate.py``) and
peaks, against hand counts."""
import pytest

from bench import work
from bench.models import surrogate
from bench.work import StepWork

Q05 = {"num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 64,
       "vocab_size": 151936}


def test_paged_attn_counts_by_hand():
    # two rows attending 100 and 28 cached tokens
    wk = StepWork(tokens_written=0, rows=2, ctx=128)
    # q.k and p.v: 2 matmuls x 2 ops x 14 heads x 64 x 128 tokens
    assert surrogate.paged_attn_flops(Q05, wk) == 2 * 2 * 14 * 64 * 128
    # K and V: 2 x 2 kv heads x 64 x 4 B x 128 tokens; q and out: 2 rows
    # x 2 x 14 x 64 x 4 B
    assert surrogate.paged_attn_bytes(Q05, wk) == (2 * 2 * 64 * 4 * 128
                                              + 2 * 2 * 14 * 64 * 4)


def test_model_flops_by_hand():
    wk = StepWork(tokens_written=10, rows=1, ctx=10)
    e = 14 * 64
    expect = (10 * 2 * e * (2 * 2 * 64)        # K and V projections
              + 2 * e * e                      # query projection
              + 2 * e * 151936                 # logits
              + 4 * 14 * 64 * 10)              # attention
    assert surrogate.model_flops(Q05, wk) == expect


def test_step_work_adds():
    a = StepWork(1, 2, 3)
    a += StepWork(10, 20, 30)
    assert (a.tokens_written, a.rows, a.ctx) == (11, 22, 33)


def test_least_time_picks_the_binding_peak():
    pk = work.peak("TPU v5 lite")
    t, bound = work.least_time(197e12, 1.0, pk)
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = work.least_time(1.0, 819e9, pk)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peak("cpu")
