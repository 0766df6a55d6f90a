"""The served model of every configuration that names ``"model":
"surrogate"``: the program's paged surrogate, and all the benchmark knows
of it.

The interface of a model module
-------------------------------
A configuration file (``bench/configs/<config>.json``) names its served
model with ``"model": "<name>"``, and the harness loads
``bench/models/<name>.py`` (under ``BENCHMARK.json``'s ``models_dir``
where it has one).  Nothing else in ``bench/`` knows the model: to serve
another, add its module.  Below, ``config`` is the parsed configuration
file, ``work`` a ``bench.work.StepWork`` (tokens written, rows sampled,
cached tokens those rows attended), ``seed`` the run's ``--seed``.  A
model module defines:

``seeded(make_backend, seed, name, kw)``
    In each worker: the program's backend, ``make_backend(name, **kw)``,
    with its weights drawn from ``seed``.
``warm_up(backend, bounds) -> {"warm_shapes": n}``
    In each worker, before the window: compile (or load from the compile
    cache) every device program the cell's traffic can reach, and say how
    many shapes that was.  ``bounds`` are ``harness.warm_spec``'s:
    ``max_rows``, ``min_pages``, ``max_pages``, ``num_blocks``.
``draw_weights(seed, config, tokens)``
    The reference's weights, from the seed alone, importing nothing of the
    program; ``tokens`` are all the token ids the check will embed.
``logits(config, weights, stream, lens) -> [len(lens), vocab]``
    The plain reference: row ``p`` reads the first ``lens[p]`` tokens of
    ``stream`` and gives the logits of the token after them.
``control_precision(config) -> str``
    The precision next below the one the configuration states
    (``torch_dtype``); a ``ValueError`` where the module has none.
``logits_control(config, weights, stream, lens)``
    ``logits`` computed in that precision: the control, whose tokens the
    check has to refuse.
``model_flops(config, work)``
    The served model's operations for ``work``: two a multiply-add, useful
    work only (no padding), counted once however many chips replicate it.
    A layer stack multiplies by its layers here.
``<kernel>_flops(config, work)``, ``<kernel>_bytes(config, work)``
    For each kernel a ``<kernel>_roofline`` metric reads: its useful
    operations and the bytes it has to move.  Here ``paged_attn``.

The surrogate
-------------
One attention layer over a token embedding, and an output projection:

    e_t = E[x_t]                      E: [vocab, H*D]
    k_t = e_t Wk,  v_t = e_t Wv       per kv head, D wide
    q   = e_L Wq                      the row's last input token
    o_h = softmax(q_h . k_<=L / sqrt(D)) v_<=L   (head h reads kv head
                                      h // (H / KV))
    logits = o Wo                     Wo: [H*D, vocab]

Its weights are standard normals from ``numpy.random.default_rng(seed)``
drawn in float64 in the order E, Wq, Wk, Wv, Wo, the projections scaled
by 1/sqrt(H*D), each cast to float32; the program keeps them and its KV
pool in float32.  ``draw_weights`` draws them itself and keeps only the
embedding rows the checked tokens use.  ``logits`` computes in float64 up
to the attention output and in float32 for the projection.  For a
configuration in bfloat16 the control rounds every operand and every
stored result (K, V, query, scores, probabilities, attention output) to
fp8 e4m3 and accumulates products in float32.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional

import numpy as np

from bench.work import StepWork

_CHUNK = 1 << 24                       # values drawn per call

# the configuration's precision -> its control's
CONTROL_BELOW = {"bfloat16": "fp8_e4m3", "float16": "fp8_e4m3"}


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


def widths(config: dict) -> Widths:
    return Widths(n_heads=config["num_attention_heads"],
                  n_kv_heads=config["num_key_value_heads"],
                  head_dim=config["head_dim"], vocab=config["vocab_size"])


def _weight_seed(seed: int) -> int:
    """The seed the surrogate's NumPy generator takes for a run seed."""
    return seed % (1 << 64)


# -- in the worker ----------------------------------------------------------


def seeded(make_backend, seed: int, name: str, kw: dict):
    """The program's ``make_backend``, with the surrogate's weights drawn
    from ``seed`` (its constructor's ``seed``) instead of its default."""
    from repro.backend.surrogate import PagedSurrogateBackend as P
    original = P.__init__

    def init(self, *a, **k):
        k.setdefault("seed", _weight_seed(seed))
        original(self, *a, **k)

    P.__init__ = init
    try:
        return make_backend(name, **kw)
    finally:
        P.__init__ = original


def pow2_at_least(n: int, lo: int = 2) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


def pow2_range(lo: int, hi: int) -> List[int]:
    out, p = [], pow2_at_least(lo)
    while p <= pow2_at_least(hi):
        out.append(p)
        p *= 2
    return out


def warm_buckets(max_rows: int, min_pages: int, max_pages: int,
                 num_blocks: int) -> List[tuple]:
    """Every (rows, pages per row, compact pool pages) bucket that a step
    can reach when no two rows share a page: the backend pads each to a
    power of two of at least 2.  A step's pool holds at least its rows
    and at least its longest row's pages, and at most rows x pages and
    the whole pool."""
    out = []
    for r in pow2_range(2, max_rows):
        for nb in pow2_range(max(min_pages, 1), max_pages):
            hi = min(r * nb, num_blocks)
            for p in pow2_range(max(r, nb), hi):
                out.append((r, nb, p))
    return out


def warm_up(backend, bounds: dict) -> dict:
    """The backend's own ``warm_up(**bounds)`` (the number of shapes it
    compiled) where it has one; otherwise the served step,
    ``attend_logits``, at every bucket of ``warm_buckets``."""
    if hasattr(backend, "warm_up"):
        return {"warm_shapes": backend.warm_up(**bounds)}
    import jax.numpy as jnp
    from repro.backend.jax_backend import attend_logits
    b = backend
    shapes = warm_buckets(**bounds)
    wo = jnp.zeros((b.n_heads * b.head_dim, b.vocab), jnp.float32)
    for rows, nb, pool in shapes:
        kc = jnp.zeros((b.n_kv_heads, pool, b.block_size, b.head_dim),
                       jnp.float32)
        attend_logits(
            jnp.zeros((rows, b.n_heads, b.head_dim), jnp.float32),
            kc, kc, jnp.full((rows, nb), -1, jnp.int32),
            jnp.zeros((rows,), jnp.int32), wo).block_until_ready()
    return {"warm_shapes": len(shapes)}


# -- the reference ------------------------------------------------------------


@dataclasses.dataclass
class Weights:
    rows: Dict[int, int]               # token id -> row of ``embed``
    embed: np.ndarray                  # [len(rows), E] f32
    wq: np.ndarray
    wk: np.ndarray
    wv: np.ndarray
    wo: np.ndarray                     # [E, vocab] f32
    wo_fp8: Optional[np.ndarray] = None  # the control's, made once


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


def draw_weights(seed: int, config: dict, tokens: Iterable[int]) -> Weights:
    """The surrogate's weights for ``seed``; embedding rows of ``tokens``
    (taken modulo the vocabulary) only."""
    w = widths(config)
    rng = np.random.default_rng(_weight_seed(seed))
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


def logits(config: dict, wt: Weights, stream: List[int],
           lens: List[int]) -> np.ndarray:
    """Reference logits [P, vocab] of the rows that attend the first
    ``lens[p]`` tokens of ``stream`` (query: token ``lens[p] - 1``)."""
    w = widths(config)
    n = max(lens)
    e = wt.embed[[wt.rows[int(t) % w.vocab] for t in stream[:n]]]
    e = e.astype(np.float64)
    k = (e @ wt.wk).reshape(n, w.n_kv_heads, w.head_dim)
    v = (e @ wt.wv).reshape(n, w.n_kv_heads, w.head_dim)
    q = (e[np.asarray(lens) - 1] @ wt.wq).reshape(len(lens), w.n_heads,
                                                  w.head_dim)
    out = _attend(w, q, k, v, lens)
    return out.astype(np.float32) @ wt.wo


def control_precision(config: dict) -> str:
    """The control's precision for the configuration's ``torch_dtype``."""
    dtype = config["torch_dtype"]
    if dtype not in CONTROL_BELOW:
        raise ValueError(f"no control below {dtype!r}")
    return CONTROL_BELOW[dtype]


def fp8(x) -> np.ndarray:
    """Round to the nearest fp8 e4m3 value (ties to even; 3 mantissa
    bits, subnormals below 2**-6, saturating at 448), held in float32."""
    x = np.asarray(x, np.float32)
    _, e = np.frexp(x)
    q = np.ldexp(np.float32(1.0), np.maximum(e, -5) - 4).astype(np.float32)
    return np.clip(np.round(x / q) * q, -448.0, 448.0).astype(np.float32)


def logits_control(config: dict, wt: Weights, stream: List[int],
                   lens: List[int]) -> np.ndarray:
    """The control: ``logits`` computed in fp8 e4m3, the one precision
    this module has below bfloat16 and float16."""
    control_precision(config)
    w = widths(config)
    if wt.wo_fp8 is None:
        wt.wo_fp8 = fp8(wt.wo)
    n = max(lens)
    e = fp8(wt.embed[[wt.rows[int(t) % w.vocab] for t in stream[:n]]])
    k = fp8(e @ fp8(wt.wk)).reshape(n, w.n_kv_heads, w.head_dim)
    v = fp8(e @ fp8(wt.wv)).reshape(n, w.n_kv_heads, w.head_dim)
    q = fp8(e[np.asarray(lens) - 1] @ fp8(wt.wq)).reshape(
        len(lens), w.n_heads, w.head_dim)
    out = _attend(w, q, k, v, lens, rnd=fp8)
    return out @ wt.wo_fp8


# -- work counts --------------------------------------------------------------


def paged_attn_flops(config: dict, work: StepWork) -> float:
    """q.k and p.v over every attended token, for every query head."""
    w = widths(config)
    return 4.0 * w.n_heads * w.head_dim * work.ctx


def paged_attn_bytes(config: dict, work: StepWork) -> float:
    """K and V of every attended token, read once per kv head, plus each
    row's query read and output written."""
    w = widths(config)
    kv = 2.0 * w.n_kv_heads * w.head_dim * w.dtype_bytes * work.ctx
    q_out = 2.0 * w.n_heads * w.head_dim * w.dtype_bytes * work.rows
    return kv + q_out


def model_flops(config: dict, work: StepWork) -> float:
    """K/V projections per token written; query projection, attention
    and logits projection per sampled row."""
    w = widths(config)
    e = w.embed
    kv_proj = 2.0 * e * 2 * w.n_kv_heads * w.head_dim
    q_proj = 2.0 * e * w.n_heads * w.head_dim
    logits_proj = 2.0 * e * w.vocab
    return (kv_proj * work.tokens_written
            + (q_proj + logits_proj) * work.rows
            + paged_attn_flops(config, work))
