"""GQA attention with TP-aware head layout.

Head layout
-----------
TP requires the sharded head dimension to divide the model-axis size.  We
normalize every arch to a *group* layout ``[B, S, G, n, Dh]`` where:

  * q heads are zero-padded ``H -> Hp`` (multiple of tp); padded heads feed
    zero rows of ``wo`` so outputs are exact;
  * kv heads are either used as-is (``KV % tp == 0``), zero-padded
    (``tp % KV != 0``, e.g. whisper 12 -> 16), or *duplicated* r times
    (``KV | tp``, e.g. MQA 1 -> 16) — duplication preserves GQA semantics
    exactly because each q head still attends its original kv head;
  * scores are sharded on the group dim G over ``tp``.

Prefill/train runs an unrolled q-block loop with **static triangular /
banded KV slices**, so causal and sliding-window FLOPs in the compiled HLO
are the true (halved / banded) counts, not dense-masked counts, and the
peak temp buffer is one [B, G, n, QBLK, kv_len] block.

Decode reads a [B, S, KVs, Dh] cache sharded on the *sequence* dim when kv
heads don't divide tp (flash-decoding: XLA's partial-softmax reductions
turn into small cross-shard collectives) or on kv heads when they do.

The model stack runs no Pallas kernel today, on any platform: this jnp
path is what both the chip and the dry-run execute.  The Pallas kernels
in `repro.kernels` are tested against their references but not called
from here.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import current as mesh_ctx, pad_to_multiple, shard
from repro.models.layers import apply_norm, dense_init

NEG_INF = -1e30

import contextlib
import contextvars

_DUP_KV: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_duplicate_kv", default=False)


@contextlib.contextmanager
def duplicated_kv(enabled: bool = True):
    """Store kv heads duplicated r x in the weights so they shard on tp
    (train/prefill layout; serving keeps the compact cache layout)."""
    token = _DUP_KV.set(enabled)
    try:
        yield
    finally:
        _DUP_KV.reset(token)


@dataclasses.dataclass(frozen=True)
class HeadLayout:
    h: int          # original q heads
    hp: int         # padded q heads (multiple of tp)
    kv: int         # original kv heads
    kv_store: int   # kv heads held in weights/caches (padded if tp % kv != 0)
    g: int          # group count after duplication (multiple of tp)
    r: int          # duplication factor g // kv_store
    n: int          # q heads per group = hp // g
    d_head: int

    @property
    def q_dim(self) -> int:
        return self.hp * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.kv_store * self.d_head


def head_layout(n_heads: int, n_kv_heads: int, d_head: int, tp: int) -> HeadLayout:
    hp = pad_to_multiple(n_heads, tp)
    if n_kv_heads % tp == 0:
        kv_store, g = n_kv_heads, n_kv_heads
    elif tp % n_kv_heads == 0:
        kv_store, g = n_kv_heads, tp
        # Weight-level kv duplication (train/prefill; see duplicated_kv()):
        # storing each kv head r times makes wk/wv tp-shardable, removing
        # the replicated [B,S,kv,dh] tensor whose resharding costs an
        # 805MB-class all-reduce per layer in backward (EXPERIMENTS §Perf
        # H2).  Only for small r (weights/cache cost is r x).
        if _DUP_KV.get() and tp // n_kv_heads <= 2:
            kv_store = tp
    else:  # e.g. whisper kv=12, tp=16: pad kv alongside q
        kv_store, g = pad_to_multiple(n_kv_heads, tp), pad_to_multiple(n_kv_heads, tp)
    r = g // kv_store
    # q-group correspondence: pad q so hp is a multiple of g
    hp = pad_to_multiple(hp, g)
    return HeadLayout(
        h=n_heads, hp=hp, kv=n_kv_heads, kv_store=kv_store, g=g, r=r,
        n=hp // g, d_head=d_head,
    )


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def attn_init(key, d_model: int, layout: HeadLayout, dtype, *, bias: bool = False,
              qk_norm: bool = False):
    ks = jax.random.split(key, 4)
    dh = layout.d_head
    wq = dense_init(ks[0], d_model, layout.hp * dh, dtype).reshape(d_model, layout.hp, dh)
    if layout.kv_store > layout.kv and layout.kv_store % layout.kv == 0:
        # duplicated-kv layout: tile the true kv heads r times
        rep = layout.kv_store // layout.kv
        wk = jnp.repeat(dense_init(ks[1], d_model, layout.kv * dh, dtype)
                        .reshape(d_model, layout.kv, dh), rep, axis=1)
        wv = jnp.repeat(dense_init(ks[2], d_model, layout.kv * dh, dtype)
                        .reshape(d_model, layout.kv, dh), rep, axis=1)
    else:
        wk = dense_init(ks[1], d_model, layout.kv_store * dh, dtype).reshape(
            d_model, layout.kv_store, dh)
        wv = dense_init(ks[2], d_model, layout.kv_store * dh, dtype).reshape(
            d_model, layout.kv_store, dh)
    wo = dense_init(ks[3], layout.hp * dh, d_model, dtype).reshape(layout.hp, dh, d_model)
    # zero out padding so padded heads are inert
    if layout.hp > layout.h:
        wq = wq.at[:, layout.h:].set(0)
        wo = wo.at[layout.h:].set(0)
    if layout.kv_store > layout.kv and layout.kv_store % layout.kv != 0:
        # zero-padded (not duplicated) kv heads are inert
        wk = wk.at[:, layout.kv:].set(0)
        wv = wv.at[:, layout.kv:].set(0)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if bias:
        p["bq"] = jnp.zeros((layout.hp, dh), dtype)
        p["bk"] = jnp.zeros((layout.kv_store, dh), dtype)
        p["bv"] = jnp.zeros((layout.kv_store, dh), dtype)
    if qk_norm:
        p["q_norm"] = {"scale": jnp.ones((dh,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((dh,), dtype)}
    return p


def attn_param_axes(layout: HeadLayout, *, bias: bool = False, qk_norm: bool = False):
    """Logical sharding axes per param (dims match attn_init shapes)."""
    kv_ax = "tp" if layout.kv_store % mesh_ctx().tp == 0 else None
    p = {
        "wq": (None, "tp", None),
        "wk": (None, kv_ax, None),
        "wv": (None, kv_ax, None),
        "wo": ("tp", None, None),
    }
    if bias:
        p["bq"] = ("tp", None)
        p["bk"] = (kv_ax, None)
        p["bv"] = (kv_ax, None)
    if qk_norm:
        p["q_norm"] = {"scale": (None,)}
        p["k_norm"] = {"scale": (None,)}
    return p


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------


def project_q(params, x, layout: HeadLayout, qk_norm: bool = False):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
    if qk_norm:
        q = apply_norm("rmsnorm", params["q_norm"], q)
    return shard(q, "dp", None, "tp", None)


def project_kv(params, x, layout: HeadLayout, qk_norm: bool = False):
    k = jnp.einsum("bsd,dgk->bsgk", x, params["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", x, params["wv"])
    if "bk" in params:
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    if qk_norm:
        k = apply_norm("rmsnorm", params["k_norm"], k)
    return k, v


def output_proj(params, o, layout: HeadLayout):
    # o: [B, S, Hp, Dh]
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"])


def expand_kv(k, layout: HeadLayout):
    """[B, S, KVs, Dh] -> duplicated group layout [B, S, G, Dh]."""
    if layout.r == 1:
        return k
    return jnp.repeat(k, layout.r, axis=2)


def group_q(q, layout: HeadLayout):
    """[B, S, Hp, Dh] -> [B, S, G, n, Dh]."""
    B, S = q.shape[:2]
    return q.reshape(B, S, layout.g, layout.n, layout.d_head)


# ---------------------------------------------------------------------------
# prefill / train attention: unrolled q-block loop, static causal slices
# ---------------------------------------------------------------------------


def _pick_qblk(S: int, target: int = 1024) -> int:
    # Cap the peak [.., q_blk, S] f32 score block for long sequences (the
    # per-block jax.checkpoint keeps only ~1 block's temps live, so 512 is
    # safe at 32k); real-TPU perf comes from the Pallas flash kernel which
    # streams KV blocks instead.  Smaller blocks would quadruple the HLO
    # and the SPMD-partitioning compile time at 32k.
    if S > 8_192:
        target = min(target, 512)
    if S <= target:
        return S
    blk = target
    while S % blk != 0:
        blk //= 2
    return max(blk, 128) if S % max(blk, 128) == 0 else S


def flash_attention(q, k, v, layout: HeadLayout, *, causal: bool,
                    window: Optional[int] = None, q_blk: int = 1024):
    """q: [B,S,Hp,Dh]; k,v: [B,S,KVs,Dh].  Returns [B,S,Hp,Dh].

    Unrolled loop over q blocks; KV slice per block is static:
      causal:   kv[0 : (i+1)*blk]
      windowed: kv[max(0, (i - ceil(w/blk)))*blk : (i+1)*blk]
      bidir:    full kv, single block loop over q only.
    """
    B, S, _, dh = q.shape
    qg = group_q(q, layout)                     # [B,S,G,n,Dh]
    kx = expand_kv(k, layout)                   # [B,S,G,Dh]
    vx = expand_kv(v, layout)
    kx = shard(kx, "dp", None, "tp", None)
    vx = shard(vx, "dp", None, "tp", None)
    scale = 1.0 / math.sqrt(dh)

    blk = _pick_qblk(S, q_blk)
    nb = S // blk

    def block(qi, kj, vj, i, lo, hi):
        s = jnp.einsum("bqgnd,bsgd->bgnqs", qi, kj).astype(jnp.float32) * scale
        s = shard(s, "dp", "tp", None, None, None)
        qpos = i * blk + jnp.arange(blk)
        kpos = lo + jnp.arange(hi - lo)
        mask = None
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
        if window is not None:
            wmask = kpos[None, :] > (qpos[:, None] - window)
            mask = wmask if mask is None else (mask & wmask)
        if mask is not None:
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bgnqs,bsgd->bqgnd", a.astype(vj.dtype), vj)

    if nb > 1:
        # per-block remat: backward recomputes one [.., blk, kv] score block
        # at a time, so peak live temp is a single block, not all of them.
        block = jax.checkpoint(block, static_argnums=(3, 4, 5))

    outs = []
    for i in range(nb):
        qi = qg[:, i * blk:(i + 1) * blk]       # [B,blk,G,n,Dh]
        if causal:
            hi = (i + 1) * blk
            lo = 0
            if window is not None:
                lo = max(0, (i - (window + blk - 1) // blk)) * blk
        else:
            # bidirectional: the full KV length, which differs from the
            # query length S for cross-attention (encoder context)
            lo, hi = 0, kx.shape[1]
        outs.append(block(qi, kx[:, lo:hi], vx[:, lo:hi], i, lo, hi))
    o = jnp.concatenate(outs, axis=1) if nb > 1 else outs[0]
    return shard(o.reshape(B, S, layout.hp, dh), "dp", None, "tp", None)


def cross_attention(q, k, v, layout: HeadLayout):
    """Bidirectional attention over a (short) encoder context: single dot."""
    return flash_attention(q, k, v, layout, causal=False, q_blk=q.shape[1])


# ---------------------------------------------------------------------------
# decode attention over a KV cache
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, cache_len, layout: HeadLayout, *,
                     window: Optional[int] = None,
                     cache_positions: Optional[jnp.ndarray] = None):
    """q: [B,1,Hp,Dh]; caches: [B,Sc,KVs,Dh] (seq- or head-sharded upstream).

    ``cache_len`` is the number of valid entries (scalar or [B]).  For ring
    caches (sliding-window layers) ``cache_positions`` [B,Sc] or [Sc] carries
    each slot's absolute position; invalid/overwritten slots are masked by
    position arithmetic, so slot order never matters.
    """
    B, Sc, kvs, dh = k_cache.shape
    scale = 1.0 / math.sqrt(dh)
    assert layout.hp % kvs == 0, (layout, kvs)
    qg = q.reshape(B, 1, kvs, layout.hp // kvs, dh)
    s = jnp.einsum("bqgnd,bsgd->bgnqs", qg, k_cache).astype(jnp.float32) * scale
    s = shard(s, "dp", None, None, None, ("tp",))
    if cache_positions is None:
        pos = jnp.arange(Sc)
        pos = jnp.broadcast_to(pos, (B, Sc)) if pos.ndim == 1 else pos
    else:
        pos = jnp.broadcast_to(cache_positions, (B, Sc))
    clen = jnp.asarray(cache_len)
    if clen.ndim == 0:
        clen = jnp.broadcast_to(clen, (B,))
    valid = (pos < clen[:, None]) & (pos >= 0)            # [B,Sc]
    if window is not None:
        valid = valid & (pos > (clen[:, None] - 1 - window))
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgnqs,bsgd->bqgnd", a.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, layout.hp, dh)
