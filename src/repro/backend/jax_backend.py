"""JAX backend: real batched decode against a paged, block-indexed cache.

The accelerator-class physical backend: the shared paged surrogate
(``repro.backend.surrogate``) supplies the memory system — KV in a page
pool addressed through the scheduler's block tables, a host pool for
swap traffic, contract-ordered directive application — and this class
supplies the execution engine: every step runs the
``kernels/paged_decode_attention`` pallas kernel (compiled on a TPU, in
the interpreter on the CPU) over exactly the pages the batch references.
Prefill chunks write their K/V into the request's pages; shared prefix
pages are written once and attended by every request that locks them.

The surrogate keeps the compute honest where the paper needs it — the
per-step batch really is assembled from the plan, the gather really is
block-indexed — while staying cheap enough for unit tests.  Sampling is
greedy argmax, deterministic given the seed, so the conformance contract
(same plan sequence -> same completion order and token counts) is exact.

The two jitted device programs, ``attend_logits`` and ``decode_scan``,
are module functions so they can be compiled on their own; jit keys their
executables by the power-of-2 bucketed shapes the backend pads to.  Both
read the surrogate's weights from one device copy each, made on first use
and kept; a step uploads only its queries, pages, tables and lengths.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend.surrogate import PagedSurrogateBackend, _pow2_at_least
from repro.kernels.paged_decode_attention import paged_decode_attention


@functools.partial(jax.jit, static_argnames=("interpret",))
def attend_logits(q, k_pages, v_pages, tables, seq_lens, wo, k_scales=None,
                  v_scales=None, *, interpret=None):
    """One batched decode-attention step: the paged kernel, then the
    output projection to logits [rows, vocab]."""
    out = paged_decode_attention(q, k_pages, v_pages, tables, seq_lens,
                                 k_scales=k_scales, v_scales=v_scales,
                                 interpret=interpret)
    return out.reshape(out.shape[0], -1) @ wo


@functools.partial(jax.jit, static_argnames=("n_steps", "interpret"))
def decode_scan(kc, vc, bt, sl0, tok0, bud, eos_v, embed, wq, wk, wv, wo, *,
                n_steps, interpret=None):
    """``n_steps`` greedy decode iterations as one ``lax.scan`` over a
    compact page pool [KV, pool, block, D] whose last page is scratch:
    rows past their budget or EOS write there and emit nothing."""
    kv_heads, pool, bs, d = kc.shape
    vocab = embed.shape[0]
    n_heads = wq.shape[1] // d
    scratch = pool - 1

    def body(carry, s):
        kc, vc, tok, alive = carry
        emit = alive & (s < bud)
        e = embed[tok % vocab]                            # [rows_p, E]
        pos = sl0 + s          # valid while emitting: emission is
                               # prefix-contiguous from s=0
        kn = (e @ wk).reshape(-1, kv_heads, d)
        vn = (e @ wv).reshape(-1, kv_heads, d)
        page = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
        page = jnp.where(emit, page, scratch)
        slot = pos % bs
        kc = kc.at[:, page, slot].set(jnp.swapaxes(kn, 0, 1))
        vc = vc.at[:, page, slot].set(jnp.swapaxes(vn, 0, 1))
        q = (e @ wq).reshape(-1, n_heads, d)
        sl = jnp.where(emit, pos + 1, 0)
        out = paged_decode_attention(q, kc, vc, bt, sl, interpret=interpret)
        logits = out.reshape(out.shape[0], -1) @ wo
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        alive = emit & (nxt != eos_v)
        return (kc, vc, nxt, alive), (nxt, emit)

    init = (kc, vc, tok0, jnp.ones_like(tok0, dtype=bool))
    (kc, vc, _, _), (toks, emits) = jax.lax.scan(body, init,
                                                 jnp.arange(n_steps))
    return kc, vc, toks, emits


class JaxBackend(PagedSurrogateBackend):

    def __init__(self, **kw):
        super().__init__(**kw)
        self._on_device: Dict[str, jax.Array] = {}

    def _resident(self, *names: str):
        """Device copies of the surrogate weights ``names`` (attribute
        names), and the bytes this call handed to the device.  Weights are
        final after ``__init__``, so each is copied once, by the first
        device program that needs it, and kept for the backend's life.
        ``jnp.asarray`` leaves the copy uncommitted on the default device,
        as the warm-up's ``jnp.zeros`` are, so jit keys the same
        executables."""
        sent = 0
        out = []
        for name in names:
            dev = self._on_device.get(name)
            if dev is None:
                host = getattr(self, name)
                dev = self._on_device[name] = jnp.asarray(host)
                sent += host.nbytes
            out.append(dev)
        return out, sent

    def _attend(self, q: np.ndarray, tables: np.ndarray,
                seq_lens: np.ndarray) -> np.ndarray:
        """q: [rows, H, D] -> logits [rows, vocab], via the paged kernel.

        Only the pages this batch references are gathered and shipped to
        the kernel (tables are remapped to the compact pool), so per-step
        cost scales with batch x context, not with the whole pool.  Shapes
        are padded to power-of-2 buckets so the jitted pallas call
        compiles once per bucket, not once per batch composition."""
        rows = q.shape[0]
        with self._span("page_gather"):
            used = np.unique(tables[tables >= 0])
            remap = np.full(self.num_blocks, -1, np.int32)
            remap[used] = np.arange(len(used), dtype=np.int32)
            compact = np.where(tables >= 0,
                               remap[np.clip(tables, 0, None)], -1)
            rows_p = _pow2_at_least(rows, 2)
            nb_p = _pow2_at_least(max(tables.shape[1], 1), 2)
            pool_p = _pow2_at_least(max(len(used), 1), 2)
            quant = self.kv_dtype == "int8"
            qp = np.zeros((rows_p, self.n_heads, self.head_dim), np.float32)
            qp[:rows] = q
            bt = np.full((rows_p, nb_p), -1, np.int32)
            bt[:rows, :tables.shape[1]] = compact
            sl = np.zeros((rows_p,), np.int32)
            sl[:rows] = seq_lens
            kc = np.zeros((self.n_kv_heads, pool_p, self.block_size,
                           self.head_dim),
                          np.int8 if quant else np.float32)
            vc = np.zeros_like(kc)
            kc[:, :len(used)] = self.k_pages[:, used]
            vc[:, :len(used)] = self.v_pages[:, used]
            # int8: ship codes + per-page scales; the kernel dequantizes
            # on load, so HBM->VMEM traffic is the halved-byte pool
            host_scales = {}
            if quant:
                ks = np.zeros((self.n_kv_heads, pool_p), np.float32)
                vs = np.zeros_like(ks)
                ks[:, :len(used)] = self.k_scales[:, used]
                vs[:, :len(used)] = self.v_scales[:, used]
                host_scales = dict(k_scales=ks, v_scales=vs)
        host = (qp, kc, vc, bt, sl)
        with self._span("upload") as sp:
            scales = {k: jnp.asarray(a) for k, a in host_scales.items()}
            args = [jnp.asarray(a) for a in host]
            (wo,), sent = self._resident("_wo")
            if sp is not None:
                sp.n = sent + sum(a.nbytes for a in
                                  (*host, *host_scales.values()))
        with self._span("device_wait"):
            logits = np.asarray(attend_logits(*args, wo, **scales))
        return logits[:rows]

    # -- fused multi-step decode (docs/multi_step.md) -------------------

    def _decode_multi(self, rids: List[int], tables: Dict[int, List[int]],
                      start: Dict[int, int], first: Dict[int, int],
                      budgets: Dict[int, int], eos: Dict[int, Optional[int]],
                      k: int) -> List[Dict[int, int]]:
        """The k-step decode loop as ONE jitted ``lax.scan``: each inner
        iteration embeds the carried token, projects and writes K/V into
        the (functional) compact page pool, runs the paged pallas kernel,
        samples greedily, and feeds the sample straight back — no host
        round trip between the k steps, the device-side analog of a
        captured CUDA graph.  Rows past their budget or EOS keep running
        masked (a scan has static trip count): their writes are
        redirected to a scratch page and their emissions dropped, which
        reproduces exactly the reference loop's prefix-contiguous
        stream.  The compact pool is scattered back to the host pages
        once, at the end — safe because a macro-plan's rows only append
        to refcount-exclusive tail blocks and never mutate shared prefix
        pages."""
        if self.kv_dtype == "int8":
            # int8 pool codes evolve via requant-on-growth host writes;
            # the functional scan would bypass that scale bookkeeping.
            # Run the reference per-step loop instead — each step still
            # attends through the dequant-on-load kernel path.
            return super()._decode_multi(rids, tables, start, first,
                                         budgets, eos, k)
        rows = len(rids)
        nb_max = max(max(len(tables[rid]) for rid in rids), 1)
        tb = np.full((rows, nb_max), -1, np.int32)
        for i, rid in enumerate(rids):
            tb[i, :len(tables[rid])] = tables[rid]
        used = np.unique(tb[tb >= 0])
        remap = np.full(self.num_blocks, -1, np.int32)
        remap[used] = np.arange(len(used), dtype=np.int32)
        compact = np.where(tb >= 0, remap[np.clip(tb, 0, None)], -1)

        rows_p = _pow2_at_least(rows, 2)
        nb_p = _pow2_at_least(nb_max, 2)
        # the compact pool's last page, past the gathered set, is scratch:
        # masked rows write there (decode_scan)
        pool_p = _pow2_at_least(len(used) + 1, 2)

        bt = np.full((rows_p, nb_p), -1, np.int32)
        bt[:rows, :nb_max] = compact
        sl0 = np.zeros((rows_p,), np.int32)
        sl0[:rows] = [start[rid] for rid in rids]
        tok0 = np.zeros((rows_p,), np.int32)
        tok0[:rows] = [first[rid] for rid in rids]
        bud = np.zeros((rows_p,), np.int32)   # padded rows: budget 0
        bud[:rows] = [budgets[rid] for rid in rids]
        eos_v = np.full((rows_p,), -1, np.int32)   # -1 = no EOS (argmax >= 0)
        eos_v[:rows] = [-1 if eos[rid] is None else eos[rid] for rid in rids]
        kc = np.zeros((self.n_kv_heads, pool_p, self.block_size,
                       self.head_dim), np.float32)
        vc = np.zeros_like(kc)
        kc[:, :len(used)] = self.k_pages[:, used]
        vc[:, :len(used)] = self.v_pages[:, used]

        weights, _ = self._resident("_embed", "_wq", "_wk", "_wv", "_wo")
        kc_o, vc_o, toks, emits = decode_scan(
            jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(bt),
            jnp.asarray(sl0), jnp.asarray(tok0), jnp.asarray(bud),
            jnp.asarray(eos_v), *weights, n_steps=k)
        self.k_pages[:, used] = np.asarray(kc_o)[:, :len(used)]
        self.v_pages[:, used] = np.asarray(vc_o)[:, :len(used)]
        toks = np.asarray(toks)
        emits = np.asarray(emits)
        steps: List[Dict[int, int]] = []
        for s in range(k):
            row = {rid: int(toks[s, i])
                   for i, rid in enumerate(rids) if emits[s, i]}
            if not row:
                break
            steps.append(row)
        return steps
