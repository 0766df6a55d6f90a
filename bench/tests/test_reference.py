"""The surrogate's plain reference (``bench/models/surrogate.py``): the
program's weights from the seed alone, and a control (the reference one
precision below the configuration's) that reads above the limit."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import check
from bench.models import surrogate

ROOT = Path(__file__).resolve().parents[2]


def _config(n_heads, n_kv_heads, head_dim, vocab):
    return {"num_attention_heads": n_heads, "num_key_value_heads": n_kv_heads,
            "head_dim": head_dim, "vocab_size": vocab}


def test_weights_are_the_programs():
    """Drawn apart from the program, the reference's weights equal the
    served surrogate's, for a seed past 32 bits."""
    from repro.backend.surrogate import PagedSurrogateBackend
    seed = 2**31 + 99
    prog = PagedSurrogateBackend(block_size=16, num_blocks=4, n_heads=4,
                                 n_kv_heads=2, head_dim=16, vocab=300,
                                 seed=seed)
    cfg = _config(n_heads=4, n_kv_heads=2, head_dim=16, vocab=300)
    wt = surrogate.draw_weights(seed, cfg, [0, 5, 299, 301])
    for name in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(getattr(wt, name),
                                      getattr(prog, "_" + name))
    for tok in (0, 5, 299, 301):
        np.testing.assert_array_equal(wt.embed[wt.rows[tok % 300]],
                                      prog._embed[tok % 300])


def test_reference_logits_match_a_plain_loop():
    """The vectorised reference against a loop over heads and tokens."""
    cfg = _config(n_heads=4, n_kv_heads=2, head_dim=8, vocab=50)
    stream = [3, 7, 1, 0, 0, 9]
    wt = surrogate.draw_weights(5, cfg, stream)
    got = surrogate.logits(cfg, wt, stream, [4, 6])
    e = np.stack([wt.embed[wt.rows[t]] for t in stream]).astype(np.float64)
    for p, L in enumerate([4, 6]):
        q = (e[L - 1] @ wt.wq).reshape(4, 8)
        k = (e[:L] @ wt.wk).reshape(L, 2, 8)
        v = (e[:L] @ wt.wv).reshape(L, 2, 8)
        out = []
        for h in range(4):
            s = k[:, h // 2] @ q[h] / np.sqrt(8)
            a = np.exp(s - s.max())
            out.append((a / a.sum()) @ v[:, h // 2])
        want = np.concatenate(out) @ wt.wo
        np.testing.assert_allclose(got[p], want, rtol=1e-5, atol=1e-5)


def test_fp8_rounds_to_nearest_even():
    f = surrogate.fp8
    assert f(np.float32(1.0625)) == 1.0                    # tie: to even
    assert f(np.float32(1.1875)) == 1.25                   # tie: to even
    assert f(np.float32(1.07)) == 1.125
    assert f(np.float32(-3.3)) == -3.25
    assert f(np.float32(500.0)) == 448.0                   # saturates
    assert f(np.float32(3 * 2**-10)) == 2**-8              # subnormal step


# each configuration with prompts of its cell's lengths; qwen2-vl-7b's
# vocabulary is cut to what a test run holds (its other widths are whole)
CASES = {"qwen2-0.5b": (None, (400, 1200, 2500, 3900), 16),
         "qwen2-vl-7b": (16384, (2100, 4100, 8000), 48)}


@pytest.mark.parametrize("config", sorted(CASES))
def test_control_fails_the_limit(config):
    """At the configuration's widths, on prompts of its cell's lengths
    followed by the engine's decode inputs, the control in the precision
    below the configuration's puts first a token whose reference logit
    lies further below the best than the limit allows."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    vocab, prompts, n_out = CASES[config]
    cfg["vocab_size"] = vocab or cfg["vocab_size"]
    assert surrogate.control_precision(cfg) == "fp8_e4m3"
    limit = cfg["limits"]["logit_gap"]
    rng = np.random.default_rng(3)
    streams = [list(rng.integers(3, 551, size=n)) + [0] * n_out
               for n in prompts]
    wt = surrogate.draw_weights(77, cfg, range(551))
    worst = 0.0
    for s in streams:
        lens = list(range(len(s) - n_out, len(s) + 1))
        ref = surrogate.logits(cfg, wt, s, lens)
        assert check.gaps(ref, ref.argmax(axis=1)).max() == 0.0
        ctl = surrogate.logits_control(cfg, wt, s, lens)
        worst = max(worst, check.gaps(ref, ctl.argmax(axis=1)).max())
    assert worst > limit


def test_no_control_below_float32():
    with pytest.raises(ValueError):
        surrogate.control_precision({"torch_dtype": "float32"})
