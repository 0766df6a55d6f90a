"""Pallas TPU paged decode attention over a block-indexed KV cache.

The paged variant of ``kernels/decode_attention``: instead of one
contiguous ``[B, KV, S, D]`` cache per batch, KV lives in a shared pool of
fixed-size pages ``[KV, N_blocks, block, D]`` and each sequence addresses
its pages through a block table (``repro.serving.blocks`` hands out the
ids; ``repro.backend.JaxBackend`` owns the pool).  This is the kernel-side
half of PagedAttention: the gather happens *inside* the kernel from the
block table, so sequences can share prefix pages and nothing is
recompacted between steps.

One new query token per sequence attends its ``seq_len`` cached slots.
Grid: ``(B, KV)`` — one program per (sequence, kv-head); the kernel walks
the sequence's block table with a ``fori_loop``, streaming one page per
iteration through an online-softmax carry (the flash-decoding
recurrence).  GQA group r = H/KV: the query heads of one kv head form the
rows of an ``[r, block]`` MXU tile.  Sequence lengths and block tables
(and the int8 scales) are scalar-prefetched into SMEM.

Pages are handed to the kernel folded to 128 lanes: a ``[block, D]`` page
with D < 128 is viewed as ``[block * D / 128, 128]``, so token
``t = fold * i + c`` sits in row i, lanes ``c*D:(c+1)*D``.  A TPU DMA or
VMEM tile moves whole 128-lane rows; an unfolded D=64 page is refused by
Mosaic ("slice shape ... must be aligned to tiling (128)") and would
waste half of every VMEM tile.

Two residency modes for the page pool (``pool_in_vmem``):

* ``pool_in_vmem=True`` — the kv head's whole pool is mapped into VMEM by
  the BlockSpec and pages are sliced directly.  Fast path for small pools
  (no DMA latency to hide).
* ``pool_in_vmem=False`` — the pool stays HBM-resident
  (``memory_space=ANY``); the kernel DMAs one page per loop iteration
  into a 2-deep VMEM scratch ring with ``make_async_copy``
  double-buffering (start page j+1, wait page j, compute page j), so the
  page fetch for the next iteration overlaps the MXU work of the current
  one.  Same online-softmax loop.

``pool_in_vmem=None`` (default) picks VMEM when both pools, double
buffered, fit ``vmem_budget_bytes`` (sized to stay inside v5e's default
scoped VMEM), else DMA.  Forced VMEM residency above the default raises
the kernel's scoped VMEM limit explicitly, up to ``VMEM_CAP_BYTES``.

int8 KV (``k_pages.dtype == int8`` + per-page ``k_scales``/``v_scales``
``[KV, N_blocks]``): pages move at one byte per element — half the
HBM traffic of fp16, a quarter of fp32 — and are dequantized on load
(``x = q * scale / 127``) right after the copy lands, before the softmax
update.  docs/spec_decode.md covers the quantization invariants.

``interpret=None`` runs the Pallas interpreter when JAX's default backend
is the CPU and the compiled kernel everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128

# Auto residency picks VMEM while both pools' double-buffered per-kv-head
# blocks fit here: under v5e's 16 MiB default scoped VMEM, with room for
# q/out blocks and the compiler's own scratch.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
# Forced VMEM residency raises the scoped limit up to this; a v5e core has
# 128 MiB of VMEM in all.
VMEM_CAP_BYTES = 100 * 1024 * 1024
_VMEM_HEADROOM = 4 * 1024 * 1024
_VMEM_DEFAULT_LIMIT = 16 * 1024 * 1024       # v5e's default scoped VMEM


def _fold(block: int, d: int) -> int:
    """Tokens per 128-lane row of a folded page (1 when D >= 128)."""
    if d < LANES and LANES % d == 0 and block % (LANES // d) == 0:
        return LANES // d
    return 1


def _sublanes(dtype) -> int:
    """Rows of one VMEM tile: 8 for 32-bit, 16 for 16-bit, 32 for 8-bit."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def pool_vmem_bytes(n_blocks: int, block: int, d: int, dtype) -> int:
    """VMEM taken by one kv head's K and V pools in VMEM residency: two
    pools, each double-buffered by the grid pipeline, in padded tiles."""
    fold = _fold(block, d)
    rows = -(-(block // fold) // _sublanes(dtype)) * _sublanes(dtype)
    lanes = -(-(fold * d) // LANES) * LANES
    return 2 * 2 * n_blocks * rows * lanes * jnp.dtype(dtype).itemsize


def _softmax_update(q, k, v, blk, j, seq_len, carry, *, block, fold, scale):
    """One folded page [block/fold, fold*D] of the flash-decoding online
    softmax recurrence."""
    m_prev, l_prev, acc = carry
    d = q.shape[1]
    rows = k.shape[0]
    base = (j * block
            + fold * jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1))
    scores, values = [], []
    for c in range(fold):                                 # token t = fold*i+c
        kc = k[:, c * d:(c + 1) * d]                      # [rows, D]
        s = jax.lax.dot_general(
            q, kc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [r, rows]
        valid = (base + c < seq_len) & (blk >= 0)
        scores.append(jnp.where(valid, s, NEG_INF))
        values.append(v[:, c * d:(c + 1) * d])
    m_cur = m_prev
    for s in scores:
        m_cur = jnp.maximum(m_cur, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    l_cur = l_prev * alpha
    acc = acc * alpha[:, None]
    for s, vc in zip(scores, values):
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_cur + jnp.sum(p, axis=1)
        acc = acc + jax.lax.dot_general(
            p.astype(vc.dtype), vc, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return m_cur, l_cur, acc


def _run(q_ref, o_ref, load, tbl_ref, b, seq_len, *, block, nb_max, fold,
         scale):
    """The block-table walk shared by both residency modes; ``load(j,
    page)`` returns page j's dequantized (k, v)."""
    q = q_ref[0]                                          # [r, D]
    r, d = q.shape

    def body(j, carry):
        blk = tbl_ref[b * nb_max + j]
        k, v = load(j, jnp.maximum(blk, 0))               # pad entries are -1
        return _softmax_update(q, k, v, blk, j, seq_len, carry,
                               block=block, fold=fold, scale=scale)

    m0 = jnp.full((r,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((r,), jnp.float32)
    acc0 = jnp.zeros((r, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nb_max, body, (m0, l0, acc0))
    safe = jnp.where(l == 0.0, 1.0, l)                    # fully-masked rows
    o_ref[0] = (acc / safe[:, None]).astype(o_ref.dtype)


def _dequant(x, scales_ref, idx):
    return x.astype(jnp.float32) * (scales_ref[idx] / 127.0)


def _kernel_vmem(len_ref, tbl_ref, *refs, block, nb_max, n_pages, fold,
                 scale, quantized):
    """The kv head's whole pool VMEM-resident: slice pages directly."""
    if quantized:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref = refs
    b = pl.program_id(0)
    g = pl.program_id(1)

    def load(j, page):
        k = k_ref[0, page]                                # [rows, 128]
        v = v_ref[0, page]
        if quantized:
            k = _dequant(k, ks_ref, g * n_pages + page)
            v = _dequant(v, vs_ref, g * n_pages + page)
        return k, v

    _run(q_ref, o_ref, load, tbl_ref, b, len_ref[b], block=block,
         nb_max=nb_max, fold=fold, scale=scale)


def _kernel_hbm(len_ref, tbl_ref, *refs, block, nb_max, n_pages, fold,
                scale, quantized):
    """HBM-resident pool: DMA one page per iteration into a 2-slot VMEM
    ring, double-buffered (issue j+1 before consuming j)."""
    if quantized:
        (ks_ref, vs_ref, q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, k_sem, v_sem) = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, k_sem, v_sem = refs
    b = pl.program_id(0)
    g = pl.program_id(1)

    def dma(j, slot):
        page = jnp.maximum(tbl_ref[b * nb_max + j], 0)
        return (
            pltpu.make_async_copy(k_hbm.at[g, page], k_buf.at[slot],
                                  k_sem.at[slot]),
            pltpu.make_async_copy(v_hbm.at[g, page], v_buf.at[slot],
                                  v_sem.at[slot]),
        )

    def start(j, slot):
        ck, cv = dma(j, slot)
        ck.start()
        cv.start()

    start(0, 0)                                           # warm-up fetch

    def load(j, page):
        slot = j % 2

        @pl.when(j + 1 < nb_max)
        def _():                                          # overlap next fetch
            start(j + 1, (j + 1) % 2)

        ck, cv = dma(j, slot)
        ck.wait()
        cv.wait()
        k = k_buf[slot]                                   # [rows, 128]
        v = v_buf[slot]
        if quantized:
            k = _dequant(k, ks_ref, g * n_pages + page)
            v = _dequant(v, vs_ref, g * n_pages + page)
        return k, v

    _run(q_ref, o_ref, load, tbl_ref, b, len_ref[b], block=block,
         nb_max=nb_max, fold=fold, scale=scale)


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                           k_scales=None, v_scales=None,
                           pool_in_vmem: bool | None = None,
                           vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                           interpret: bool | None = None):
    """q: [B, H, D]; k/v_pages: [KV, N_blocks, block, D];
    block_tables: [B, nb_max] i32 page ids (-1 = padding);
    seq_lens: [B] i32 valid cache length per sequence (0 = inert row);
    k/v_scales: [KV, N_blocks] f32 per-page scales, required iff the pools
    are int8 (dequant-on-load: ``x = q * scale / 127``).
    Returns [B, H, D] in q.dtype."""
    B, H, D = q.shape
    KV, N, block, _ = k_pages.shape
    assert H % KV == 0
    r = H // KV
    nb_max = block_tables.shape[1]
    scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    quantized = jnp.dtype(k_pages.dtype) == jnp.int8
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("int8 pages need k_scales/v_scales [KV, N_blocks]")
    need = pool_vmem_bytes(N, block, D, k_pages.dtype)
    if pool_in_vmem is None:
        pool_in_vmem = need <= vmem_budget_bytes
    if pool_in_vmem and need + _VMEM_HEADROOM > VMEM_CAP_BYTES:
        raise ValueError(f"a {N}-page pool needs {need} B of VMEM per kv "
                         f"head; use pool_in_vmem=False")
    fold = _fold(block, D)
    rows = block // fold
    kp = k_pages.reshape(KV, N, rows, fold * D)
    vp = v_pages.reshape(KV, N, rows, fold * D)
    qg = q.reshape(B * KV, r, D)

    prefetch = [seq_lens.astype(jnp.int32),
                block_tables.astype(jnp.int32).reshape(-1)]
    if quantized:
        prefetch += [k_scales.astype(jnp.float32).reshape(-1),
                     v_scales.astype(jnp.float32).reshape(-1)]
    q_spec = pl.BlockSpec((1, r, D), lambda b, g, *_: (b * KV + g, 0, 0))
    statics = dict(block=block, nb_max=nb_max, n_pages=N, fold=fold,
                   scale=scale, quantized=quantized)
    if pool_in_vmem:
        kernel = functools.partial(_kernel_vmem, **statics)
        pool_spec = pl.BlockSpec((1, N, rows, fold * D),
                                 lambda b, g, *_: (g, 0, 0, 0))
        scratch = []
        params = pltpu.CompilerParams(
            vmem_limit_bytes=max(need + _VMEM_HEADROOM, _VMEM_DEFAULT_LIMIT))
    else:
        kernel = functools.partial(_kernel_hbm, **statics)
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        buf = pltpu.VMEM((2, rows, fold * D), k_pages.dtype)
        scratch = [buf, buf, pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SemaphoreType.DMA((2,))]
        params = None
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, KV),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B * KV, r, D), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="paged_decode_attention",
    )(*prefetch, qg, kp, vp)
    return out.reshape(B, H, D)


def dequantize_pages(pages, scales):
    """int8 pages [KV, N, block, D] + per-page scales [KV, N] -> fp32."""
    return pages.astype(jnp.float32) * (scales[:, :, None, None] / 127.0)


def paged_decode_attention_reference(q, k_pages, v_pages, block_tables,
                                     seq_lens, *, k_scales=None,
                                     v_scales=None):
    """Gather-then-softmax reference (jnp only) for conformance tests."""
    if k_scales is not None:
        k_pages = dequantize_pages(k_pages, k_scales)
        v_pages = dequantize_pages(v_pages, v_scales)
    B, H, D = q.shape
    KV, N, block, _ = k_pages.shape
    r = H // KV
    nb_max = block_tables.shape[1]
    pages = jnp.clip(block_tables, 0, N - 1)              # [B, nb]
    k = jnp.take(k_pages, pages, axis=1)                  # [KV, B, nb, blk, D]
    v = jnp.take(v_pages, pages, axis=1)
    k = jnp.moveaxis(k, 1, 0).reshape(B, KV, nb_max * block, D)
    v = jnp.moveaxis(v, 1, 0).reshape(B, KV, nb_max * block, D)
    qg = q.reshape(B, KV, r, D)
    s = jnp.einsum("bgrd,bgsd->bgrs", qg, k) / (D ** 0.5)
    pos = jnp.arange(nb_max * block)[None, :]
    valid = (pos < seq_lens[:, None]) & jnp.repeat(
        block_tables >= 0, block, axis=1)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    # softmax that tolerates fully-masked (seq_len == 0) rows
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bgrs,bgsd->bgrd", p / jnp.where(l == 0, 1.0, l), v)
    return out.reshape(B, H, D).astype(q.dtype)
