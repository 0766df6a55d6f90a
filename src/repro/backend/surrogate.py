"""Shared paged-KV surrogate model for the physical backends.

Every *physical* backend in this stack (one that owns pages, as opposed
to the cost-only ``EmulatedBackend``) shares the same memory system: a
deliberately tiny transformer surrogate — fixed random projections from
token embeddings to Q/K/V and to logits — whose KV lives in a page pool
``[KV, num_blocks, block_size, D]`` addressed through the block tables
the scheduler broadcasts, plus a host-memory pool that backs
swap-to-host preemption.  ``PagedSurrogateBackend`` implements all of
that once — pool ownership, swap directive application in contract
order, per-request sequence tracking, batch assembly, greedy sampling —
and leaves a single seam, ``_attend``, for subclasses to fill:

  * ``JaxBackend``        — the paged pallas kernel (accelerator class);
  * ``CpuDecodeBackend``  — a NumPy gather-softmax (CPU class).

Because both subclasses run the same float32 math over the same pages,
they sample identical tokens for identical plans — which is what lets
``HybridBackend`` hand a request's pages from one to the other at the
prefill->decode transition without changing the completion stream
(tests/test_backend_conformance.py pins this).

``kv_dtype="int8"`` stores the pools quantized — one byte per element,
symmetric per-(kv-head, page) scales carried beside the pool — with
dequant-on-gather in ``_attend`` and requantize-on-amax-growth on write;
whole pages arriving via swap restore or hybrid handoff are quantized in
the copy itself (``import_pages``), which is where the prefill->decode
tier conversion lives.  docs/spec_decode.md states the error invariants.

Sized for in-process use: construct with the scheduler's ``block_size`` /
``num_kv_blocks`` (keep ``kv_capacity_tokens`` small — the pool is dense).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from repro import profiling
from repro.backend.base import PinnedLRU, StepResult
from repro.core.copyengine import DeferredCopies
from repro.serving.scheduler import StepPlan


def _pow2_at_least(n: int, lo: int) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


class PagedSurrogateBackend:
    """Base for backends that own physical pages (see module docstring)."""

    def __init__(self, *, block_size: int, num_blocks: int,
                 num_swap_blocks: int = 0, copy_streams: int = 0,
                 n_heads: int = 4, n_kv_heads: int = 2, head_dim: int = 16,
                 vocab: int = 256, seed: int = 0,
                 kv_dtype: str = "float32"):
        if kv_dtype not in ("float32", "int8"):
            raise ValueError(f"kv_dtype must be float32|int8, got {kv_dtype}")
        self.kv_dtype = kv_dtype
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.num_swap_blocks = num_swap_blocks
        # copy_streams >= 1: swap/restore page copies are DEFERRED to the
        # next execute() — the epoch boundary of the async copy engine
        # (docs/copy_engine.md).  Safe only when the scheduler runs the
        # matching IN_FLIGHT bookkeeping (SchedulerConfig.copy_streams),
        # which guarantees no page is read or reallocated mid-copy.
        self.copy_streams = copy_streams
        self._deferred = DeferredCopies()
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.vocab = vocab
        self._embed_dim = n_heads * head_dim
        rng = np.random.default_rng(seed)
        scale = 1.0 / np.sqrt(self._embed_dim)
        self._embed = rng.standard_normal(
            (vocab, self._embed_dim)).astype(np.float32)
        self._wq = (rng.standard_normal(
            (self._embed_dim, n_heads * head_dim)) * scale).astype(np.float32)
        self._wk = (rng.standard_normal(
            (self._embed_dim, n_kv_heads * head_dim)) * scale).astype(
                np.float32)
        self._wv = (rng.standard_normal(
            (self._embed_dim, n_kv_heads * head_dim)) * scale).astype(
                np.float32)
        self._wo = (rng.standard_normal(
            (self._embed_dim, vocab)) * scale).astype(np.float32)
        # the physical page pool the block tables index into; int8 mode
        # carries per-(kv-head, page) symmetric scales beside the codes
        pool_np = np.int8 if kv_dtype == "int8" else np.float32
        self.k_pages = np.zeros(
            (n_kv_heads, num_blocks, block_size, head_dim), pool_np)
        self.v_pages = np.zeros_like(self.k_pages)
        if kv_dtype == "int8":
            self.k_scales = np.zeros((n_kv_heads, num_blocks), np.float32)
            self.v_scales = np.zeros_like(self.k_scales)
        else:
            self.k_scales = self.v_scales = None
        # host swap tier: pages parked here by plan.swap_outs, copied back
        # by plan.restores (ids from the scheduler's HostSwapSpace).  Same
        # dtype as the device pool: int8 swaps move half the bytes, and
        # the scales ride along with the pairs.
        if num_swap_blocks > 0:
            self.k_swap = np.zeros(
                (n_kv_heads, num_swap_blocks, block_size, head_dim), pool_np)
            self.v_swap = np.zeros_like(self.k_swap)
            if kv_dtype == "int8":
                self.k_swap_scales = np.zeros(
                    (n_kv_heads, num_swap_blocks), np.float32)
                self.v_swap_scales = np.zeros_like(self.k_swap_scales)
        else:
            self.k_swap = self.v_swap = None
        if kv_dtype != "int8" or num_swap_blocks <= 0:
            self.k_swap_scales = self.v_swap_scales = None
        # rids parked in the host tier: their _seq_lens entry must survive
        # arbitrary churn until the restore arrives (base.Backend contract)
        self._swap_pinned: set = set()
        # req_id -> tokens in cache (see base.PinnedLRU for the aging story)
        self._seq_lens = PinnedLRU(pinned=self._swap_pinned)
        self._last_wall = 0.0
        self._plan: Optional[StepPlan] = None    # the step in execute

    def _span(self, site: str):
        """A profiler span at ``site`` tagged with the step being
        executed; the shared no-op when this process does not trace."""
        prof = profiling.active()
        if prof is None:
            return profiling.NO_SPAN
        plan = self._plan
        if plan is None:
            return prof.span(site)
        return prof.span(site, step=plan.step_id, phase=plan.phase)

    # -- projections ---------------------------------------------------------

    def _emb(self, tokens: np.ndarray) -> np.ndarray:
        return self._embed[tokens % self.vocab]

    def _kv(self, tokens: np.ndarray):
        e = self._emb(tokens)                                  # [n, E]
        k = (e @ self._wk).reshape(-1, self.n_kv_heads, self.head_dim)
        v = (e @ self._wv).reshape(-1, self.n_kv_heads, self.head_dim)
        return k, v

    def _write(self, table: List[int], start: int, tokens: np.ndarray) -> None:
        """Write K/V for ``tokens`` at positions start.. into the pages."""
        k, v = self._kv(tokens)                  # [n, KV, D]
        bs = self.block_size
        for i in range(len(tokens)):
            pos = start + i
            page = table[pos // bs]
            slot = pos % bs
            if self.kv_dtype == "int8":
                self._quant_store(self.k_pages, self.k_scales, page, slot,
                                  k[i])
                self._quant_store(self.v_pages, self.v_scales, page, slot,
                                  v[i])
            else:
                self.k_pages[:, page, slot] = k[i]
                self.v_pages[:, page, slot] = v[i]

    @staticmethod
    def _quant_store(pages, scales, page: int, slot: int,
                     x: np.ndarray) -> None:
        """Append ``x`` [KV, D] to an int8 page with per-(head, page)
        symmetric scales.  If the new slot's amax exceeds the page scale,
        existing codes are requantized to the grown scale first
        (q' = round(q * s_old / s_new)).  The original quantization costs
        half an LSB and every requantization adds at most another half an
        LSB at the grown scale, so after R requants the element error is
        <= (R + 1)/2 * s_final/127 (docs/spec_decode.md); single-shot
        whole-page imports (R = 0) stay within half an LSB."""
        amax = np.abs(x).max(axis=1)                       # [KV]
        for h in np.nonzero(amax > scales[:, page])[0]:
            old, new = float(scales[h, page]), float(amax[h])
            if old > 0.0:
                pages[h, page] = np.clip(
                    np.rint(pages[h, page].astype(np.float32) * (old / new)),
                    -127, 127).astype(np.int8)
            scales[h, page] = new
        s = scales[:, page]
        safe = np.where(s > 0.0, s, 1.0)
        codes = np.clip(np.rint(x * (127.0 / safe[:, None])), -127, 127)
        pages[:, page, slot] = codes.astype(np.int8)

    def _gather_pages(self, idx: np.ndarray):
        """fp32 (k, v) views of pages ``idx`` (any integer index shape),
        dequantized on gather when the pool is int8 — the decode-tier
        read path pays int8 bytes and multiplies scales back on load."""
        k = self.k_pages[:, idx]
        v = self.v_pages[:, idx]
        if self.kv_dtype == "int8":
            k = k.astype(np.float32) * (
                self.k_scales[:, idx][..., None, None] / 127.0)
            v = v.astype(np.float32) * (
                self.v_scales[:, idx][..., None, None] / 127.0)
        return k, v

    # whole-page movement across tiers: the prefill->decode handoff copy
    # is exactly where fp32 -> int8 conversion lives (single-shot
    # per-page scale = amax over the full page)

    def export_pages(self, blocks: List[int]):
        """fp32 copies of whole pages (dequantized if int8)."""
        idx = np.asarray(blocks, np.int64)
        return self._gather_pages(idx)

    def import_pages(self, blocks: List[int], k: np.ndarray,
                     v: np.ndarray) -> None:
        """Install fp32 pages [KV, n, block, D]; quantize whole-page when
        this pool is int8."""
        idx = np.asarray(blocks, np.int64)
        if self.kv_dtype == "int8":
            for pages, scales, x in ((self.k_pages, self.k_scales, k),
                                     (self.v_pages, self.v_scales, v)):
                amax = np.abs(x).max(axis=(2, 3))          # [KV, n]
                safe = np.where(amax > 0.0, amax, 1.0)
                pages[:, idx] = np.clip(
                    np.rint(x * (127.0 / safe[:, :, None, None])),
                    -127, 127).astype(np.int8)
                scales[:, idx] = amax
        else:
            self.k_pages[:, idx] = k
            self.v_pages[:, idx] = v

    def _track(self, rid: int, seq_len: int) -> None:
        self._seq_lens.put(rid, seq_len)

    # -- host<->device page movement -----------------------------------------

    def _copy_out(self, pairs: List[tuple]) -> None:
        for dev_b, host_b in pairs:
            self.k_swap[:, host_b] = self.k_pages[:, dev_b]
            self.v_swap[:, host_b] = self.v_pages[:, dev_b]
            if self.kv_dtype == "int8":
                self.k_swap_scales[:, host_b] = self.k_scales[:, dev_b]
                self.v_swap_scales[:, host_b] = self.v_scales[:, dev_b]

    def _copy_back(self, pairs: List[tuple]) -> None:
        for host_b, dev_b in pairs:
            self.k_pages[:, dev_b] = self.k_swap[:, host_b]
            self.v_pages[:, dev_b] = self.v_swap[:, host_b]
            if self.kv_dtype == "int8":
                self.k_scales[:, dev_b] = self.k_swap_scales[:, host_b]
                self.v_scales[:, dev_b] = self.v_swap_scales[:, host_b]

    # -- the batched attention step ------------------------------------------

    def _attend(self, q: np.ndarray, tables: np.ndarray,
                seq_lens: np.ndarray) -> np.ndarray:
        """q: [rows, H, D] -> logits [rows, vocab] over the page pool.

        The one subclass seam: same inputs, same float32 math, different
        execution engine (pallas kernel vs NumPy)."""
        raise NotImplementedError

    # -- Backend protocol ----------------------------------------------------

    def step_cost(self, plan: StepPlan) -> float:
        """Real execution has no analytic model; report the last measured
        step so virtual-time consumers still see a plausible number."""
        return self._last_wall or 1e-3

    def execute(self, plan: StepPlan,
                block_tables: Optional[Dict[int, List[int]]] = None
                ) -> StepResult:
        t0 = time.perf_counter()
        self._plan = plan
        tables = block_tables if block_tables is not None \
            else plan.block_tables
        for rid in plan.preempted:
            # pages were reclaimed; also unpins a swap whose restore was
            # cancelled by a same-step recompute preemption, and discards
            # any deferred copy whose data is now dead
            self._seq_lens.pop(rid, None)
            self._swap_pinned.discard(rid)
            self._deferred.drop(rid)
        # epoch boundary: copies deferred by earlier steps land before
        # anything in THIS step touches the pools (the scheduler's
        # in-flight holds kept their pages unreallocated meanwhile)
        self._deferred.flush()
        # swap directives next, in contract order (base.Backend): a device
        # block freed by a swap-out may be reallocated — even as a restore
        # target — within this very plan (serialized mode; with the copy
        # engine the directives defer to the next epoch boundary instead).
        # Swapped requests keep their _seq_lens entry (pinned against LRU
        # churn): their sequence survives, only its pages move.
        for rid, pairs in plan.swap_outs.items():
            self._swap_pinned.add(rid)
            if self.copy_streams > 0:
                self._deferred.defer(
                    rid, lambda p=pairs: self._copy_out(p))
            else:
                self._copy_out(pairs)
        for rid, pairs in plan.restores.items():
            self._swap_pinned.discard(rid)
            if self.copy_streams > 0:
                self._deferred.defer(
                    rid, lambda p=pairs: self._copy_back(p))
            else:
                self._copy_back(pairs)

        # speculative verify plan (docs/spec_decode.md): score the carried
        # token plus the attached draft tokens in one batched step, emit
        # the greedy-accepted prefix + correction token.
        if plan.speculative:
            return self._execute_spec(plan, tables, t0)
        # multi-step macro-plan (docs/multi_step.md): run the k-iteration
        # decode loop and return its per-step token stream.  Macro-plans
        # carry no swap directives by scheduler construction (deferred
        # copies from the PREVIOUS epoch were just flushed above, as the
        # contract requires); with per-tier macros they MAY carry prefill
        # chunks, which run once alongside the k decode iterations.
        if plan.num_steps > 1:
            return self._execute_multi(plan, tables, t0)

        with self._span("kv_write"):
            rows = self._prefill_rows(plan, tables)
            for rid in plan.decode:
                table = tables.get(rid, [])
                tok = int(plan.new_tokens.get(rid, [0])[0])
                pos = self._seq_lens.get(rid, 0)
                self._write(table, pos, np.asarray([tok], np.int64))
                self._track(rid, pos + 1)
                rows.append((rid, tok, pos + 1, table))

        tokens = self._sample_rows(rows)
        self._last_wall = time.perf_counter() - t0
        return StepResult(step_id=plan.step_id, tokens=tokens,
                          wall_s=self._last_wall)

    def _prefill_rows(self, plan: StepPlan,
                      tables: Dict[int, List[int]]) -> List[tuple]:
        """Apply the plan's prefill chunks; returns sample rows
        (rid, q_token, seq_len, table) for the chunks' last positions —
        the sampled token counts iff the chunk completes the prompt."""
        rows: List[tuple] = []
        for rid, start, n in plan.prefill:
            table = tables.get(rid, [])
            toks = np.asarray(plan.new_tokens.get(rid, [0] * n), np.int64)
            if len(toks) == 0:        # defensive: degenerate empty chunk
                self._track(rid, start)
                continue
            self._write(table, start, toks)
            self._track(rid, start + n)
            rows.append((rid, int(toks[-1]), start + n, table))
        return rows

    def _sample_rows(self, rows: List[tuple]) -> Dict[int, int]:
        """One batched attend + greedy sample over (rid, tok, seq_len,
        table) rows."""
        tokens: Dict[int, int] = {}
        if rows:
            nb_max = max(len(t) for _, _, _, t in rows)
            q = np.zeros((len(rows), self.n_heads, self.head_dim), np.float32)
            bt = np.full((len(rows), max(nb_max, 1)), -1, np.int32)
            sl = np.zeros((len(rows),), np.int32)
            for i, (rid, tok, seq_len, table) in enumerate(rows):
                e = self._emb(np.asarray([tok]))[0]
                q[i] = (e @ self._wq).reshape(self.n_heads, self.head_dim)
                bt[i, :len(table)] = table
                sl[i] = seq_len
            logits = self._attend(q, bt, sl)
            with self._span("sample"):
                for i, (rid, _, _, _) in enumerate(rows):
                    tokens[rid] = int(np.argmax(logits[i]))
        return tokens

    # -- multi-step macro-plans (docs/multi_step.md) --------------------

    def _execute_multi(self, plan: StepPlan,
                       tables: Dict[int, List[int]], t0: float) -> StepResult:
        """Drive the k-step decode loop for a macro-plan and package its
        per-step token stream.  ``_decode_multi`` is the execution seam
        (host loop here; ``JaxBackend`` overrides it with a fused
        ``lax.scan`` so sampled tokens feed back device-side)."""
        tokens: Dict[int, int] = self._sample_rows(
            self._prefill_rows(plan, tables))     # per-tier macro prefill
        rids = list(plan.decode)
        tbls = {rid: tables.get(rid, []) for rid in rids}
        start = {rid: self._seq_lens.get(rid, 0) for rid in rids}
        first = {rid: int(plan.new_tokens.get(rid, [0])[0]) for rid in rids}
        budgets = {rid: plan.decode_steps.get(rid, plan.num_steps)
                   for rid in rids}
        eos = {rid: plan.eos_tokens.get(rid) for rid in rids}
        steps = self._decode_multi(rids, tbls, start, first, budgets, eos,
                                   plan.num_steps)
        for row in steps:
            tokens.update(row)
        for rid in rids:
            emitted = sum(1 for row in steps if rid in row)
            self._track(rid, start[rid] + emitted)
        self._last_wall = time.perf_counter() - t0
        return StepResult(step_id=plan.step_id, tokens=tokens,
                          wall_s=self._last_wall, token_steps=steps)

    # -- speculative verify (docs/spec_decode.md) ------------------------

    def _execute_spec(self, plan: StepPlan, tables: Dict[int, List[int]],
                      t0: float) -> StepResult:
        """Verify a speculative plan: for each decode row, score the
        carried token plus its attached draft tokens (``plan.draft_tokens``,
        installed worker-side by ``repro.spec.SpeculativeBackend``) at
        k+1 positions in ONE batched attend, then emit the longest
        greedy-accepted draft prefix plus the correction token.  The
        result is macro-plan-shaped (``token_steps``), so the scheduler's
        existing consumption + ``_rollback_unused`` reclaim the rejected
        suffix's KV."""
        tokens: Dict[int, int] = self._sample_rows(
            self._prefill_rows(plan, tables))     # per-tier macro prefill
        rids = list(plan.decode)
        tbls = {rid: tables.get(rid, []) for rid in rids}
        start = {rid: self._seq_lens.get(rid, 0) for rid in rids}
        first = {rid: int(plan.new_tokens.get(rid, [0])[0]) for rid in rids}
        budgets = {rid: plan.decode_steps.get(rid, plan.num_steps)
                   for rid in rids}
        eos = {rid: plan.eos_tokens.get(rid) for rid in rids}
        drafts = {rid: list(plan.draft_tokens.get(rid, ())) for rid in rids}
        steps = self._verify_multi(rids, tbls, start, first, budgets, eos,
                                   drafts)
        for row in steps:
            tokens.update(row)
        for rid in rids:
            emitted = sum(1 for row in steps if rid in row)
            self._track(rid, start[rid] + emitted)
        self._last_wall = time.perf_counter() - t0
        return StepResult(step_id=plan.step_id, tokens=tokens,
                          wall_s=self._last_wall, token_steps=steps)

    def _verify_multi(self, rids: List[int], tables: Dict[int, List[int]],
                      start: Dict[int, int], first: Dict[int, int],
                      budgets: Dict[int, int], eos: Dict[int, Optional[int]],
                      drafts: Dict[int, List[int]]) -> List[Dict[int, int]]:
        """Batched draft verification.  Inputs for row i of a request are
        ``[first, d_1, .., d_{b-1}]`` (clipped to the plan's budget b);
        K/V for ALL of them is written up front, then every (request,
        position) pair attends in one ``_attend`` call with seq_len
        ``start + i + 1`` — the output of position i is the model's true
        next token v_i after feeding inputs 0..i.  Greedy acceptance:
        accept drafts while v_i == d_{i+1}; the emitted stream is the
        accepted drafts plus the first correction token, truncated at
        EOS — bit-identical to sequential greedy decode regardless of
        draft quality (fp32 pools; int8 is numerically self-consistent
        but quantized).  Rejected-suffix positions sit beyond the final
        tracked seq_len: attention masks them and the scheduler's
        ``_rollback_unused`` frees their whole blocks."""
        inputs: Dict[int, List[int]] = {}
        rows: List[tuple] = []                             # (rid, i, tok)
        for rid in rids:
            b = max(budgets[rid], 1)
            ins = ([first[rid]] + [int(t) for t in drafts[rid]])[:b]
            inputs[rid] = ins
            self._write(tables[rid], start[rid],
                        np.asarray(ins, np.int64))
            rows.extend((rid, i, tok) for i, tok in enumerate(ins))
        nb_max = max((len(tables[rid]) for rid in rids), default=0)
        q = np.zeros((len(rows), self.n_heads, self.head_dim), np.float32)
        bt = np.full((len(rows), max(nb_max, 1)), -1, np.int32)
        sl = np.zeros((len(rows),), np.int32)
        for j, (rid, i, tok) in enumerate(rows):
            e = self._emb(np.asarray([tok]))[0]
            q[j] = (e @ self._wq).reshape(self.n_heads, self.head_dim)
            bt[j, :len(tables[rid])] = tables[rid]
            sl[j] = start[rid] + i + 1
        logits = self._attend(q, bt, sl) if rows else np.zeros((0, 1))
        verify: Dict[tuple, int] = {}
        for j, (rid, i, _) in enumerate(rows):
            verify[(rid, i)] = int(np.argmax(logits[j]))
        steps: List[Dict[int, int]] = []
        for rid in rids:
            ins = inputs[rid]
            emitted: List[int] = []
            for i in range(len(ins)):
                v = verify[(rid, i)]
                emitted.append(v)
                if eos[rid] is not None and v == eos[rid]:
                    break                                  # stream ends here
                if i + 1 >= len(ins) or v != ins[i + 1]:
                    break                 # v is the correction token
            for s_i, tok in enumerate(emitted):
                while len(steps) <= s_i:
                    steps.append({})
                steps[s_i][rid] = tok
        return steps

    def _decode_multi(self, rids: List[int], tables: Dict[int, List[int]],
                      start: Dict[int, int], first: Dict[int, int],
                      budgets: Dict[int, int], eos: Dict[int, Optional[int]],
                      k: int) -> List[Dict[int, int]]:
        """Reference k-step decode loop: each inner step writes the
        current token's K/V at the row's next position, attends, samples
        greedily, and feeds the sample back as the next input.  A row
        stops after its budget or once it samples its EOS — emission is
        prefix-contiguous, matching the Backend contract.  Runs the SAME
        per-row math as k=1 ``execute`` (rows are independent in
        ``_attend``), so the stream is bit-identical to k single steps."""
        cur = dict(first)
        pos = dict(start)
        alive = {rid: True for rid in rids}
        steps: List[Dict[int, int]] = []
        for s in range(k):
            act = [rid for rid in rids if alive[rid] and s < budgets[rid]]
            if not act:
                break
            for rid in act:
                self._write(tables[rid], pos[rid],
                            np.asarray([cur[rid]], np.int64))
                pos[rid] += 1
            nb_max = max(len(tables[rid]) for rid in act)
            q = np.zeros((len(act), self.n_heads, self.head_dim), np.float32)
            bt = np.full((len(act), max(nb_max, 1)), -1, np.int32)
            sl = np.zeros((len(act),), np.int32)
            for i, rid in enumerate(act):
                e = self._emb(np.asarray([cur[rid]]))[0]
                q[i] = (e @ self._wq).reshape(self.n_heads, self.head_dim)
                bt[i, :len(tables[rid])] = tables[rid]
                sl[i] = pos[rid]
            logits = self._attend(q, bt, sl)
            row: Dict[int, int] = {}
            for i, rid in enumerate(act):
                tok = int(np.argmax(logits[i]))
                row[rid] = tok
                cur[rid] = tok
                if eos[rid] is not None and tok == eos[rid]:
                    alive[rid] = False
            steps.append(row)
        return steps

    def release(self, req_id: int) -> None:
        """Forget a finished request's bookkeeping (pages are owned by the
        scheduler's block manager, nothing to free here)."""
        self._seq_lens.pop(req_id, None)
        self._swap_pinned.discard(req_id)
        self._deferred.drop(req_id)
