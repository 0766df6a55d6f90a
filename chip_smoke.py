#!/usr/bin/env python3
"""Smoke run on one TPU chip: the served path and the model stack.

    python chip_smoke.py              # one chip: serve phase, model phase
    python chip_smoke.py --chips 4    # four chips: --tp 4 serve vs --tp 1

A smoke run, not a benchmark: its times say that the chip ran, not how
fast.  Phases:

1. Serve.  Runs ``python -m repro.launch.serve --backend jax --tp 1
   --arch qwen2-0.5b`` in a child process at all host cores, once with
   per-step dispatch and once with ``--multi-step 4``.  Every request
   must complete with all its tokens, on a worker that reports a TPU.
2. Model.  In this process, after the serve children have exited:
   qwen2-0.5b at its published config (24 layers, bf16) from
   ``init_params(PRNGKey(seed))``, a jitted ``prefill`` over a prompt of
   a few hundred tokens and 16 jitted greedy ``decode_step``s.  The logits
   must be finite, and the last decode step's logits must match a
   ``prefill`` over prompt + generated tokens.  Then the compiled paged
   decode kernel at qwen2-0.5b's attention widths is checked against its
   reference in both residency modes.

With ``--chips 4`` only the serve phase runs, at ``--tp 4`` (one worker
per chip) next to ``--tp 1``: the four workers must hold four distinct
chips, each must dequeue every broadcast plan, and both runs must
complete the same requests.

JAX runs on the TPU only: with no TPU every phase fails.  The last line
printed is one JSON object, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
ARCH = "qwen2-0.5b"
REQUESTS = 8
MAX_NEW = 8
SERVE_TIMEOUT_S = 420
PROMPT_TOKENS = 256
DECODE_STEPS = 16
# bf16 weights and activations over 24 layers: decode and prefill reach
# the same logits by different reduction orders.  Tolerance relative to
# the largest logit.
LOGIT_RTOL = 5e-2
# f32 kernel inputs; Mosaic may round matmul operands to bf16 on the MXU
KERNEL_ATOL = 2e-2

# the phases never fall back to the CPU: JAX must find a TPU or fail
os.environ["JAX_PLATFORMS"] = "tpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs in /tmp


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- serve phase: child processes only, no JAX here ---------------------


def _run_serve(tp: int, multi_step: int) -> dict:
    """One ``repro.launch.serve`` run in its own process group; returns
    what its output reports."""
    cores = len(os.sched_getaffinity(0))
    cmd = [sys.executable, "-m", "repro.launch.serve", "--backend", "jax",
           "--tp", str(tp), "--arch", ARCH, "--cores", str(cores),
           "--requests", str(REQUESTS), "--max-new", str(MAX_NEW),
           "--multi-step", str(multi_step)]
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (str(SRC), os.environ.get("PYTHONPATH")) if p))
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=SERVE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        out, err = proc.communicate() if _kill_group(proc) else ("", "")
        raise SmokeFailure(f"serve --tp {tp} --multi-step {multi_step} "
                           f"ran past {SERVE_TIMEOUT_S} s\n{err[-4000:]}")
    finally:
        _kill_group(proc)      # the engine and workers, should any remain
    seconds = time.perf_counter() - t0
    label = f"serve --tp {tp} --multi-step {multi_step}"
    if proc.returncode != 0:
        raise SmokeFailure(f"{label} exited {proc.returncode}\n"
                           f"{out[-4000:]}\n{err[-4000:]}")
    rep = {"label": label, "seconds": seconds, "devices": {},
           "dequeues": {}}
    for line in out.splitlines():
        if m := re.match(r"\[serve\] completed (\d+)/(\d+)(.*)", line):
            rep["completed"] = int(m.group(1))
            g = re.search(r"generated min=(\d+) max=(\d+)", m.group(3))
            rep["generated"] = (int(g.group(1)), int(g.group(2))) if g \
                else None
        elif m := re.match(r"\[serve\] TTFT p50=([\d.]+)ms", line):
            rep["ttft_p50_ms"] = float(m.group(1))
        elif m := re.match(r"\[serve\] (worker\d+) device (\{.*\})", line):
            rep["devices"][m.group(1)] = json.loads(m.group(2))
        elif m := re.match(r"\[serve\] (worker\d+) dequeue .* n=(\d+)",
                           line):
            rep["dequeues"][m.group(1)] = int(m.group(2))
        elif m := re.match(r"\[serve\] sched .* broadcasts=(\d+) "
                           r"barrier p50=([\d.]+)ms", line):
            rep["broadcasts"] = int(m.group(1))
            rep["barrier_p50_ms"] = float(m.group(2))
    check(rep.get("completed") == REQUESTS,
          f"{label}: completed {rep.get('completed')}/{REQUESTS}\n{out}")
    check(rep.get("generated") == (MAX_NEW, MAX_NEW),
          f"{label}: generated {rep.get('generated')}, want {MAX_NEW} "
          f"per request")
    workers = [f"worker{i}" for i in range(tp)]
    for w in workers:
        dev = rep["devices"].get(w)
        check(dev is not None and dev["platform"] == "tpu",
              f"{label}: {w} reports device {dev}")
        check(rep["dequeues"].get(w) == rep.get("broadcasts"),
              f"{label}: {w} dequeued {rep['dequeues'].get(w)} of "
              f"{rep.get('broadcasts')} broadcasts")
    print(f"[smoke] {label}: completed {rep['completed']}/{REQUESTS} "
          f"generated={MAX_NEW} each, TTFT p50={rep['ttft_p50_ms']}ms, "
          f"barrier p50={rep['barrier_p50_ms']}ms, "
          f"broadcasts={rep['broadcasts']}, wall {seconds:.1f}s incl. "
          f"start-up and compiles (smoke run, not a benchmark)",
          flush=True)
    for w in workers:
        print(f"[smoke]   {w} device {json.dumps(rep['devices'][w])}",
              flush=True)
    return rep


def _kill_group(proc) -> bool:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
        return True
    except ProcessLookupError:
        return False


def serve_phase() -> None:
    for multi_step in (1, 4):
        _run_serve(1, multi_step)


def four_chip_phase() -> None:
    one = _run_serve(1, 1)
    four = _run_serve(4, 1)
    # each pinned worker numbers its chip 0; the device file it holds
    # open is the host's name for the chip
    files = [tuple(d["device_files"]) for d in four["devices"].values()]
    check(all(files) and len(set(files)) == 4
          and len({f for fs in files for f in fs}) == sum(map(len, files)),
          f"--tp 4 workers do not hold four distinct chips: "
          f"{four['devices']}")
    check(one["completed"] == four["completed"] == REQUESTS,
          "--tp 1 and --tp 4 completed different requests")


# -- model phase: this process holds the chip from here on ---------------


def model_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    t0 = time.perf_counter()
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    prefill = jax.jit(M.prefill, static_argnums=1)
    decode = jax.jit(M.decode_step, static_argnums=1)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (1, PROMPT_TOKENS), 0, cfg.vocab_size)
    total = PROMPT_TOKENS + DECODE_STEPS
    logits, cache = prefill(params, cfg, prompt)
    check(bool(jnp.isfinite(logits).all()), "prefill logits not finite")
    first_s = time.perf_counter() - t0
    cache = jax.tree.map(
        lambda c, s: jnp.pad(c, [(0, d - g) for g, d in
                                 zip(c.shape, s.shape)]),
        cache, M.cache_specs(cfg, 1, total))
    generated = []
    t1 = time.perf_counter()
    for i in range(DECODE_STEPS):
        nxt = jnp.argmax(logits[0, -1, :cfg.vocab_size]).astype(jnp.int32)
        generated.append(nxt)
        logits, cache = decode(params, cfg, nxt.reshape(1, 1), cache,
                               jnp.int32(PROMPT_TOKENS + i))
        check(bool(jnp.isfinite(logits).all()),
              f"decode step {i} logits not finite")
    logits.block_until_ready()
    decode_s = time.perf_counter() - t1
    full = jnp.concatenate([prompt[0], jnp.stack(generated)])[None]
    ref, _ = prefill(params, cfg, full)
    got = np.asarray(logits[0, -1, :cfg.vocab_size], np.float32)
    want = np.asarray(ref[0, -1, :cfg.vocab_size], np.float32)
    err = float(np.abs(got - want).max())
    bound = LOGIT_RTOL * float(np.abs(want).max())
    check(err <= bound, f"decode vs prefill logits differ by {err} "
                        f"(bound {bound})")
    print(f"[smoke] model {cfg.name} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}): init+prefill+compile "
          f"{first_s:.1f}s, {DECODE_STEPS} decode steps {decode_s:.1f}s "
          f"incl. compile; last-step logits vs prefill max |diff| {err:.4g}"
          f" <= {LOGIT_RTOL} x max|logit| = {bound:.4g}; greedy "
          f"agrees={bool(got.argmax() == want.argmax())}", flush=True)


def kernel_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_decode_attention import (
        paged_decode_attention, paged_decode_attention_reference)

    rng = np.random.default_rng(seed)
    B, block, nb, n_pages = 8, 64, 16, 128
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((KV, n_pages, block, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((KV, n_pages, block, D)),
                     jnp.float32)
    lens = rng.integers(0, nb * block + 1, B).astype(np.int32)
    lens[0] = 0                                   # an inert row
    perm = rng.permutation(n_pages)
    tables = np.full((B, nb), -1, np.int32)
    for b, n_tok in enumerate(lens):
        n = -(-int(n_tok) // block)
        tables[b, :n] = perm[(b * nb) % n_pages:][:n]
    bt, sl = jnp.asarray(tables), jnp.asarray(lens)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(paged_decode_attention_reference(q, kp, vp, bt,
                                                           sl))
    for in_vmem in (True, False):
        t0 = time.perf_counter()
        run = jax.jit(lambda *a: paged_decode_attention(
            *a, pool_in_vmem=in_vmem))
        compiled = run.lower(q, kp, vp, bt, sl).compile()
        check("tpu_custom_call" in compiled.as_text(),
              "paged kernel did not compile to a Mosaic kernel")
        got = np.asarray(compiled(q, kp, vp, bt, sl))
        err = float(np.abs(got - want).max())
        check(err <= KERNEL_ATOL, f"paged kernel (pool_in_vmem={in_vmem}) "
                                  f"differs from reference by {err}")
        print(f"[smoke] paged kernel {H}/{KV}/{D} pool_in_vmem={in_vmem}: "
              f"max |diff| vs reference {err:.3g} <= {KERNEL_ATOL} "
              f"(compile+run {time.perf_counter() - t0:.1f}s)", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the serve phase at --tp 4 next to "
                         "--tp 1 (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro" / "launch" / "serve.py").is_file():
        raise SmokeFailure(f"no repro sources under {SRC}")

    if args.chips == 4:
        four_chip_phase()
    else:
        serve_phase()
    # the serve children have exited: from here this process holds the chip
    sys.path.insert(0, str(SRC))
    import jax

    from repro.core.chip import enable_compile_cache
    hits = {"/jax/compilation_cache/cache_hits": 0,
            "/jax/compilation_cache/cache_misses": 0}

    def count(event, **_):
        if event in hits:
            hits[event] += 1

    jax.monitoring.register_event_listener(count)
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"JAX runs on {dev.platform}, not a TPU")
    if args.chips == 1:
        from repro.configs import get_config
        cfg = get_config(ARCH)
        t0 = time.perf_counter()
        model_phase(cfg, args.seed)
        kernel_phase(cfg, args.seed)
        print(f"[smoke] model phase {time.perf_counter() - t0:.1f}s; "
              f"compile cache {cache_dir}: "
              f"{hits['/jax/compilation_cache/cache_hits']} hits, "
              f"{hits['/jax/compilation_cache/cache_misses']} misses",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        sys.exit(1)
