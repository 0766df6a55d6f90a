"""The benchmark's view from inside each engine worker.

``install`` replaces ``repro.backend.make_backend`` in the owner before
``ServingSystem.start()`` forks, so every worker builds its backend
through ``Recorder.start``: the program's own ``make_backend``, with the
weights drawn from the run's seed by the configuration's model module
(``seeded``), wrapped in a ``Recorder`` that relies only on the Backend
protocol (``execute(plan) -> StepResult``).  In the worker the recorder

* has the model module compile (or read from the compile cache) every
  shape the cell's traffic can reach (``warm_up``), before the owner
  opens the window;
* keeps, per request, the input tokens the worker received and the
  tokens the backend sampled, for the check of outputs;
* keeps, per step, its host-clock span and the work it did;
* notes the time of each compile (or compile-cache load);
* in a traced run, holds ``jax.profiler`` over the window and wraps each
  ``execute`` in a ``TraceAnnotation``.

A control thread answers the owner's commands over queues the owner
made before the fork.
"""
from __future__ import annotations

import dataclasses
import glob
import multiprocessing as mp
import os
import queue
import shutil
import threading
import time
from types import ModuleType
from typing import Dict, List, Optional

from bench import xplane

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Channel:
    """Command queues to each worker and one reply queue, made in the
    owner before the fork."""

    def __init__(self, n_workers: int):
        ctx = mp.get_context("fork")
        self.cmd = [ctx.Queue() for _ in range(n_workers)]
        self.reply = ctx.Queue()

    def broadcast(self, *cmd) -> None:
        for q in self.cmd:
            q.put(cmd)

    def gather(self, kind: str, n: int, timeout: float,
               dead=lambda: []) -> Dict[int, object]:
        """One ``kind`` reply from each of ``n`` workers, by index.  A
        worker's error, a dead process or the timeout raises."""
        got: Dict[int, object] = {}
        deadline = time.monotonic() + timeout
        while len(got) < n:
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(f"{n - len(got)} workers sent no "
                                   f"{kind!r} within {timeout:.0f} s")
            try:
                k, idx, payload = self.reply.get(timeout=min(left, 0.5))
            except queue.Empty:
                if dead():
                    raise RuntimeError(f"{', '.join(dead())} died")
                continue
            if k == "error":
                raise RuntimeError(f"worker{idx}: {payload}")
            if k == kind:
                got[idx] = payload
        return got


@dataclasses.dataclass
class WorkerSpec:
    seed: int
    model: ModuleType               # bench/models/<name>.py
    config: dict                    # bench/configs/<config>.json
    warm: dict                      # the model's warm_up bounds
    channel: Channel
    trace_dir: Optional[str] = None  # traced run: where each worker writes


def install(spec: WorkerSpec) -> None:
    """Make every worker forked after this build its backend through
    ``Recorder.start``."""
    import repro.backend as rb
    original = rb.make_backend

    def make_backend(name, **kw):
        return Recorder.start(spec, original, name, kw)

    rb.make_backend = make_backend


class Recorder:
    """Wraps the backend; see the module docstring."""

    def __init__(self, inner, idx: int, spec: WorkerSpec):
        self.inner = inner
        self.idx = idx
        self.spec = spec
        self.lock = threading.Lock()
        self.streams: Dict[int, List[int]] = {}
        self.samples: Dict[int, List[tuple]] = {}        # served tokens
        self.chunk_samples: Dict[int, List[tuple]] = {}  # earlier chunks
        self.preempted: set = set()
        self.unsupported = 0
        # (step_id, t0, t1, n_out, tokens_written, rows, ctx)
        self.steps: List[tuple] = []
        self.compiles: List[float] = []
        self.annotate = None             # TraceAnnotation while tracing

    # -- construction, in the worker ------------------------------------

    @classmethod
    def start(cls, spec: WorkerSpec, make_backend, name: str,
              kw: dict) -> "Recorder":
        idx = int(mp.current_process().name.rsplit("-", 1)[1])
        try:
            import jax
            dev = jax.devices()[0]
            spec.channel.reply.put(("device", idx, {
                "platform": dev.platform, "device_kind": dev.device_kind}))
            inner = spec.model.seeded(make_backend, spec.seed, name, kw)
            c = spec.config
            got = (inner.n_heads, inner.n_kv_heads, inner.head_dim,
                   inner.vocab)
            want = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"], c["vocab_size"])
            if got != want:
                raise ValueError(f"backend widths {got} != config {want}")
            rec = cls(inner, idx, spec)
            info = rec._warm_up()
        except BaseException as e:
            spec.channel.reply.put(("error", idx, repr(e)))
            raise
        threading.Thread(target=rec._serve, daemon=True,
                         name="bench-control").start()
        spec.channel.reply.put(("ready", idx, info))
        return rec

    def _warm_up(self) -> dict:
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)
        t0 = time.perf_counter()
        info = self.spec.model.warm_up(self.inner, self.spec.warm)
        return {"warm_shapes": info["warm_shapes"],
                "warm_s": time.perf_counter() - t0,
                "warm_compiles": len(self.compiles)}

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append(time.perf_counter())

    # -- the timed path --------------------------------------------------

    def execute(self, plan, block_tables=None):
        t0 = time.perf_counter()
        ann = self.annotate
        if ann is None:
            res = self.inner.execute(plan, block_tables)
        else:
            with ann(xplane.EXECUTE, step=plan.step_id):
                res = self.inner.execute(plan, block_tables)
        t1 = time.perf_counter()
        self._record(plan, res, t0, t1)
        return res

    def _record(self, plan, res, t0: float, t1: float) -> None:
        with self.lock:
            if plan.num_steps > 1 or plan.speculative:
                self.unsupported += 1
                return
            for rid in plan.preempted:
                self.preempted.add(rid)
            done = set(plan.prefill_done)
            written = rows = ctx = n_out = 0
            for rid, start, n in plan.prefill:
                toks = plan.new_tokens.get(rid, [0] * n)
                s = self.streams.setdefault(rid, [])
                del s[start:]
                s.extend(toks)
                written += len(toks)
                rows += 1
                ctx += start + len(toks)
                # the sample at the end of a prefill chunk is the served
                # first token when the chunk completes the prompt; the
                # engine drops the samples of earlier chunks
                into = self.samples if rid in done else self.chunk_samples
                into.setdefault(rid, []).append(
                    (start + len(toks), res.tokens.get(rid)))
                n_out += rid in done
            for rid in plan.decode:
                s = self.streams.setdefault(rid, [])
                s.append(plan.new_tokens.get(rid, [0])[0])
                written += 1
                rows += 1
                ctx += len(s)
                n_out += 1
                self.samples.setdefault(rid, []).append(
                    (len(s), res.tokens.get(rid)))
            self.steps.append((plan.step_id, t0, t1, n_out, written, rows,
                               ctx))

    # -- the Backend protocol's other method -------------------------------

    def step_cost(self, plan) -> float:
        return self.inner.step_cost(plan)

    # -- owner commands --------------------------------------------------

    def _serve(self) -> None:
        q = self.spec.channel.cmd[self.idx]
        while True:
            cmd = q.get()
            try:
                kind, out = cmd[0], getattr(self, "_cmd_" + cmd[0])(*cmd[1:])
            except Exception as e:           # the owner raises it
                kind, out = "error", repr(e)
            self.spec.channel.reply.put((kind, self.idx, out))

    def _trace_dir(self) -> str:
        return os.path.join(self.spec.trace_dir, f"worker{self.idx}")

    def _cmd_trace_start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._trace_dir(), profiler_options=opts)
        self.annotate = jax.profiler.TraceAnnotation

    def _cmd_trace_stop(self, w0: float, w1: float, keep: bool) -> dict:
        import jax
        self.annotate = None
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self._trace_dir(), "**",
                                       "*.xplane.pb"), recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        ev = xplane.load(max(paths, key=os.path.getmtime))
        with self.lock:
            starts = {s[0]: int(s[1] * 1e9) for s in self.steps}
        off = xplane.clock_offset(ev["executes"], starts)
        if off is None:
            raise RuntimeError("no execute of the trace matches a step")
        t0, t1 = int(w0 * 1e9) - off, int(w1 * 1e9) - off
        execs = [(s, e) for _, s, e in ev["executes"]]
        out = {name: xplane.reduce_device(ops, execs, t0, t1)
               for name, ops in ev["devices"].items()}
        if not keep:
            shutil.rmtree(self._trace_dir(), ignore_errors=True)
        return out

    def _cmd_progress(self) -> int:
        """Requests that have their first token."""
        with self.lock:
            return len(self.samples)

    def _cmd_dump(self, rids: List[int]) -> dict:
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        with self.lock:
            return {"streams": {r: list(self.streams.get(r, []))
                                for r in rids},
                    "samples": {r: list(self.samples.get(r, []))
                                for r in rids},
                    "chunk_samples": {r: list(self.chunk_samples.get(r, []))
                                      for r in rids},
                    "preempted": sorted(self.preempted),
                    "unsupported": self.unsupported,
                    "steps": list(self.steps),
                    "compiles": list(self.compiles),
                    "memory_peak_bytes": stats.get("peak_bytes_in_use")}

