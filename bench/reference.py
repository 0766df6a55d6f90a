"""Plain NumPy reference of the served surrogate, built from the seed alone.

The served model (the program's paged surrogate) is one attention layer
over a token embedding, and an output projection:

    e_t = E[x_t]                      E: [vocab, H*D]
    k_t = e_t Wk,  v_t = e_t Wv       per kv head, D wide
    q   = e_L Wq                      the row's last input token
    o_h = softmax(q_h . k_<=L / sqrt(D)) v_<=L   (head h reads kv head
                                      h // (H / KV))
    logits = o Wo                     Wo: [H*D, vocab]

Its weights are standard normals from ``numpy.random.default_rng(seed)``
drawn in float64 in the order E, Wq, Wk, Wv, Wo, the projections scaled
by 1/sqrt(H*D), each cast to float32.  This module draws them itself and
imports nothing of the program.  It keeps only the embedding rows the
checked tokens use.

``logits`` computes the reference in float64 up to the attention output
and float32 for the projection to the vocabulary.  The control is the
same model in the precision next below the one the configuration states
(``torch_dtype``; ``CONTROL_BELOW``): for bfloat16, ``logits_fp8``, with
every operand and every stored result (K, V, query, scores,
probabilities, attention output) rounded to fp8 e4m3 and products
accumulated in float32.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List

import numpy as np

from bench.work import Widths

_CHUNK = 1 << 24                       # values drawn per call

# the configuration's precision -> its control's
CONTROL_BELOW = {"bfloat16": "fp8_e4m3", "float16": "fp8_e4m3"}


def control_below(torch_dtype: str) -> str:
    """The control's precision for a configuration that states
    ``torch_dtype``."""
    if torch_dtype not in CONTROL_BELOW:
        raise ValueError(f"no control below {torch_dtype!r}")
    return CONTROL_BELOW[torch_dtype]


@dataclasses.dataclass
class Weights:
    rows: Dict[int, int]               # token id -> row of ``embed``
    embed: np.ndarray                  # [len(rows), E] f32
    wq: np.ndarray
    wk: np.ndarray
    wv: np.ndarray
    wo: np.ndarray                     # [E, vocab] f32


def _draw_rows(rng, n_rows: int, width: int, scale=None,
               keep=None) -> np.ndarray:
    """``rng.standard_normal((n_rows, width))`` (times ``scale``) cast to
    float32, drawn in chunks of rows; with ``keep``, only those rows."""
    step = max(1, _CHUNK // width)
    out = np.empty((n_rows if keep is None else len(keep), width),
                   np.float32)
    for r0 in range(0, n_rows, step):
        r1 = min(n_rows, r0 + step)
        block = rng.standard_normal((r1 - r0, width))
        if scale is not None:
            block = block * scale
        if keep is None:
            out[r0:r1] = block
        else:
            sel = (keep >= r0) & (keep < r1)
            out[sel] = block[keep[sel] - r0]
    return out


def draw_weights(seed: int, w: Widths, tokens: Iterable[int]) -> Weights:
    """The surrogate's weights for ``seed``; embedding rows of ``tokens``
    (taken modulo the vocabulary) only."""
    rng = np.random.default_rng(seed)
    e = w.embed
    scale = 1.0 / np.sqrt(e)
    keep = np.array(sorted({int(t) % w.vocab for t in tokens}), np.int64)
    embed = _draw_rows(rng, w.vocab, e, keep=keep)
    wq = _draw_rows(rng, e, w.n_heads * w.head_dim, scale)
    wk = _draw_rows(rng, e, w.n_kv_heads * w.head_dim, scale)
    wv = _draw_rows(rng, e, w.n_kv_heads * w.head_dim, scale)
    wo = _draw_rows(rng, e, w.vocab, scale)
    return Weights({int(t): i for i, t in enumerate(keep)}, embed, wq, wk,
                   wv, wo)


def _attend(w: Widths, q, k, v, lens, rnd=lambda x: x):
    """q: [P, H, D]; k, v: [n, KV, D]; row p attends positions < lens[p].
    Returns [P, H*D]."""
    r = w.n_heads // w.n_kv_heads
    n = k.shape[0]
    qg = q.reshape(len(lens), w.n_kv_heads, r, w.head_dim)
    s = rnd(np.einsum("pgrd,ngd->pgrn", qg, k) / np.sqrt(w.head_dim))
    mask = np.arange(n)[None, :] < np.asarray(lens)[:, None]     # [P, n]
    s = np.where(mask[:, None, None, :], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p = rnd(p / p.sum(axis=-1, keepdims=True))
    return rnd(np.einsum("pgrn,ngd->pgrd", p, v)).reshape(len(lens), -1)


def logits(w: Widths, wt: Weights, stream: List[int],
           lens: List[int]) -> np.ndarray:
    """Reference logits [P, vocab] of the rows that attend the first
    ``lens[p]`` tokens of ``stream`` (query: token ``lens[p] - 1``)."""
    n = max(lens)
    e = wt.embed[[wt.rows[int(t) % w.vocab] for t in stream[:n]]]
    e = e.astype(np.float64)
    k = (e @ wt.wk).reshape(n, w.n_kv_heads, w.head_dim)
    v = (e @ wt.wv).reshape(n, w.n_kv_heads, w.head_dim)
    q = (e[np.asarray(lens) - 1] @ wt.wq).reshape(len(lens), w.n_heads,
                                                  w.head_dim)
    out = _attend(w, q, k, v, lens)
    return out.astype(np.float32) @ wt.wo


def fp8(x) -> np.ndarray:
    """Round to the nearest fp8 e4m3 value (ties to even; 3 mantissa
    bits, subnormals below 2**-6, saturating at 448), held in float32."""
    x = np.asarray(x, np.float32)
    _, e = np.frexp(x)
    q = np.ldexp(np.float32(1.0), np.maximum(e, -5) - 4).astype(np.float32)
    return np.clip(np.round(x / q) * q, -448.0, 448.0).astype(np.float32)


def logits_fp8(w: Widths, wt: Weights, stream: List[int],
               lens: List[int], wo_fp8: np.ndarray) -> np.ndarray:
    """The control: ``logits`` computed in fp8 (``wo_fp8 = fp8(wt.wo)``)."""
    n = max(lens)
    e = fp8(wt.embed[[wt.rows[int(t) % w.vocab] for t in stream[:n]]])
    k = fp8(e @ fp8(wt.wk)).reshape(n, w.n_kv_heads, w.head_dim)
    v = fp8(e @ fp8(wt.wv)).reshape(n, w.n_kv_heads, w.head_dim)
    q = fp8(e[np.asarray(lens) - 1] @ fp8(wt.wq)).reshape(
        len(lens), w.n_heads, w.head_dim)
    out = _attend(w, q, k, v, lens, rnd=fp8)
    return out @ wo_fp8


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the row's
    best, in units of the row's standard deviation."""
    rows = np.arange(len(tokens))
    best = ref.max(axis=1)
    return (best - ref[rows, tokens]) / ref.std(axis=1)
