"""Control-plane tests: shm ring, completion board, end-to-end engine."""
from __future__ import annotations

import json
import multiprocessing as mp
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro.core.devmodel import DeviceModel
from repro.core.engine import EngineConfig, ServingSystem
from repro.core.shm_broadcast import CompletionBoard, ShmBroadcastQueue
from repro.serving.scheduler import SchedulerConfig, StepPlan

_CTX = mp.get_context("fork")


def test_ring_single_process_roundtrip():
    q = ShmBroadcastQueue.create(n_readers=2, n_slots=4, slot_bytes=256)
    try:
        w = q.writer()
        r0, r1 = q.reader(0), q.reader(1)
        msgs = [f"msg-{i}".encode() for i in range(10)]
        for i, m in enumerate(msgs):
            w.enqueue(m)
            # both readers must consume before the ring wraps
            if (i + 1) % 3 == 0 or i == len(msgs) - 1:
                while r0.seq < w.seq:
                    got, _ = r0.dequeue()
                    assert got == msgs[r0.seq - 1]
                while r1.seq < w.seq:
                    got, _ = r1.dequeue()
                    assert got == msgs[r1.seq - 1]
    finally:
        q.close()


def _reader_proc(name, idx, n, out_q):
    q = ShmBroadcastQueue.attach(name)
    r = q.reader(idx)
    acc = []
    for _ in range(n):
        payload, _ = r.dequeue(timeout=30.0)
        acc.append(payload)
    out_q.put((idx, acc))
    q.close()


def test_ring_multiprocess_broadcast():
    n_readers, n_msgs = 3, 25
    q = ShmBroadcastQueue.create(n_readers=n_readers, n_slots=4,
                                 slot_bytes=128)
    out_q = _CTX.Queue()
    procs = [_CTX.Process(target=_reader_proc,
                          args=(q.name, i, n_msgs, out_q), daemon=True)
             for i in range(n_readers)]
    try:
        for p in procs:
            p.start()
        w = q.writer()
        msgs = [f"payload-{i:04d}".encode() for i in range(n_msgs)]
        for m in msgs:
            w.enqueue(m, timeout=30.0)
        got = {}
        for _ in range(n_readers):
            idx, acc = out_q.get(timeout=30.0)
            got[idx] = acc
        for i in range(n_readers):
            assert got[i] == msgs, f"reader {i} saw wrong stream"
    finally:
        for p in procs:
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
        q.close()


def test_ring_backpressure_blocks_writer():
    """Writer must stall when a reader lags a full lap behind."""
    q = ShmBroadcastQueue.create(n_readers=1, n_slots=2, slot_bytes=64)
    try:
        w = q.writer()
        w.enqueue(b"a")
        w.enqueue(b"b")
        with pytest.raises(TimeoutError):
            w.enqueue(b"c", timeout=0.2)   # slot 0 not yet acked
        r = q.reader(0)
        r.dequeue()
        w.enqueue(b"c", timeout=5.0)       # now it fits
    finally:
        q.close()


def test_completion_board_barrier():
    b = CompletionBoard.create(3)
    try:
        b.mark(0, 5)
        b.mark(1, 5)
        with pytest.raises(TimeoutError):
            b.wait_all(5, timeout=0.2)
        b.mark(2, 5)
        st = b.wait_all(5, timeout=5.0)
        assert st.wall_s < 5.0
    finally:
        b.close()


def test_step_plan_roundtrip():
    p = StepPlan(7, [(1, 0, 128), (2, 128, 64)], [3, 4], [5])
    q = StepPlan.decode_bytes(p.encode())
    assert q.step_id == 7 and q.prefill == p.prefill and q.decode == p.decode
    assert q.n_tokens == 128 + 64 + 2


def test_step_plan_roundtrip_with_block_tables():
    p = StepPlan(9, [(1, 0, 16)], [2], [],
                 block_tables={1: [4, 7], 2: [0, 1, 2]},
                 new_tokens={1: list(range(16)), 2: [99]})
    q = StepPlan.decode_bytes(p.encode())
    assert q.block_tables == p.block_tables      # int keys survive JSON
    assert q.new_tokens == p.new_tokens
    assert q.payload_bytes == p.payload_bytes
    # the payload grows with the batch metadata — the §V-B scaling
    bare = StepPlan(9, [(1, 0, 16)], [2], [])
    assert p.payload_bytes > bare.payload_bytes
    approx = p.approx_payload_bytes()
    assert 0.5 * p.payload_bytes < approx < 2 * p.payload_bytes


def test_engine_expires_stuck_requests():
    """The live EngineCore enforces request_timeout and emits TIMED_OUT
    records, so collect() terminates even when a request can never run
    (here: a prompt larger than the whole KV pool)."""
    cfg = EngineConfig(
        tp_degree=1, pool_width=1,
        scheduler=SchedulerConfig(kv_capacity_tokens=64, block_size=8,
                                  enable_prefix_cache=False),
        device=DeviceModel(t_fixed=1e-4, t_prefill_tok=1e-7,
                           t_decode_seq=1e-5),
        yield_every=64,
        request_timeout=1.0,
    )
    sys_ = ServingSystem(cfg).start()
    try:
        sys_.submit("way too long " * 40, max_new_tokens=4)   # > 64 slots
        sys_.submit("short prompt", max_new_tokens=2)
        results = sys_.collect(2, timeout=30.0)
        assert len(results) == 2, "timed-out request must still report"
        by_timeout = {r["timed_out"] for r in results.values()}
        assert by_timeout == {True, False}
        ok = next(r for r in results.values() if not r["timed_out"])
        assert ok["n_generated"] == 2
        dead = next(r for r in results.values() if r["timed_out"])
        assert dead["t_first_token"] == 0.0
    finally:
        sys_.shutdown()


def test_submit_surfaces_encode_exceptions_at_shutdown():
    """Tokenizer-pool futures are retained when pool_width > 1: an encode
    exception must not vanish silently."""
    cfg = EngineConfig(tp_degree=1, pool_width=2,
                       device=DeviceModel(t_fixed=1e-4, t_prefill_tok=1e-7,
                                          t_decode_seq=1e-5),
                       yield_every=64)
    sys_ = ServingSystem(cfg).start()
    sys_.submit(None)                  # encode(None) raises on the pool
    with pytest.raises(TypeError):     # shutdown waits for in-flight encodes
        sys_.shutdown()


def test_async_lookahead_engine_end_to_end():
    """Async lookahead scheduling (EngineConfig(async_sched=True)): the
    EngineCore overlaps scheduling/broadcast of step k+1 with device
    execution of step k.  Every request must still complete with the full
    token count, in-flight steps must drain at shutdown, and both engine
    and worker stats must be produced."""
    cfg = EngineConfig(
        tp_degree=2, pool_width=2,
        device=DeviceModel(t_fixed=1e-4, t_prefill_tok=1e-7,
                           t_decode_seq=1e-5),
        yield_every=64,
        async_sched=True,
    )
    sys_ = ServingSystem(cfg).start()
    try:
        n = 10
        for i in range(n):
            sys_.submit(f"prompt number {i} " * (3 + i % 4),
                        max_new_tokens=5)
        results = sys_.collect(n, timeout=60.0)
        assert len(results) == n, f"only {len(results)}/{n} completed"
        for rec in results.values():
            assert rec["n_generated"] == 5
            assert rec["t_done"] >= rec["t_first_token"] > rec["t_arrival"]
    finally:
        stats = sys_.shutdown()
    roles = {s["role"] for s in stats}
    assert roles >= {"engine", "worker0", "worker1"}, roles
    eng = next(s for s in stats if s["role"] == "engine")
    assert eng["sched_cost"], "scheduler cost must be measured"
    assert eng["barrier_wall"], "lookahead barrier waits must be measured"


@pytest.mark.parametrize("async_sched", [False, True])
def test_engine_end_to_end(async_sched):
    """Full pipeline: submit -> tokenize -> schedule -> broadcast -> worker
    'compute' -> barrier -> TTFT recorded."""
    cfg = EngineConfig(
        tp_degree=2, pool_width=2,
        device=DeviceModel(t_fixed=1e-4, t_prefill_tok=1e-7,
                           t_decode_seq=1e-5),
        yield_every=64,            # be polite on the 1-core container
        async_sched=async_sched,
    )
    sys_ = ServingSystem(cfg).start()
    try:
        n = 6
        for i in range(n):
            sys_.submit("the quick brown fox " * 5, max_new_tokens=4,
                        is_victim=(i == 0))
        results = sys_.collect(n, timeout=60.0)
        assert len(results) == n
        for rec in results.values():
            assert rec["n_generated"] == 4
            assert rec["t_first_token"] > rec["t_arrival"]
            assert rec["t_tokenize_done"] >= rec["t_tokenize_start"]
    finally:
        stats = sys_.shutdown()
    roles = {s["role"] for s in stats}
    assert "engine" in roles and "worker0" in roles and "worker1" in roles
    eng = next(s for s in stats if s["role"] == "engine")
    assert eng["sched_cost"], "scheduler cost must be measured"


# Runs in a fresh interpreter: a worker forked from a process in which JAX
# has already started (as it has in a test process) can hang in JAX.
_JAX_ENGINE = """
import json
import sys
from repro.configs import get_config
from repro.core.engine import EngineConfig, ServingSystem
from repro.serving.scheduler import SchedulerConfig

cfg = EngineConfig(
    tp_degree=1, pool_width=1, backend="jax", yield_every=64,
    model=get_config("qwen2-0.5b").scaled(d_model=64, n_heads=4,
                                          n_kv_heads=2, vocab_size=256),
    scheduler=SchedulerConfig(kv_capacity_tokens=512, block_size=8))
sys_ = ServingSystem(cfg).start()
try:
    for i in range(3):
        sys_.submit("the quick brown fox " * (2 + i), max_new_tokens=3)
    results = sys_.collect(3, timeout=120.0)
finally:
    stats = sys_.shutdown()
print(json.dumps({"results": list(results.values()),
                  "devices": {s["role"]: s.get("device") for s in stats},
                  "failures": sys_.failures,
                  "owner_imported_jax": "jax" in sys.modules}))
"""


def test_engine_jax_backend_end_to_end():
    """The live engine with ``backend="jax"`` at small explicit widths: the
    worker picks the kernel's interpreter from the platform, every request
    completes, the worker reports the device it ran on, and the owner
    process never imports JAX."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    proc = subprocess.run([sys.executable, "-c", _JAX_ENGINE], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["failures"] == []
    assert len(out["results"]) == 3
    for rec in out["results"]:
        assert not rec["timed_out"] and rec["n_generated"] == 3
    dev = out["devices"]["worker0"]
    assert dev["platform"] == "cpu" and dev["device_kind"] == "cpu"
    assert isinstance(dev["id"], int)
    assert out["devices"]["engine"] is None
    assert out["owner_imported_jax"] is False


def test_worker_failure_fails_serving_promptly():
    """A worker that raises while building its backend fails ``collect``
    within seconds with its error, not after the 120 s barrier timeout,
    and shutdown does not wait out the stuck engine."""
    cfg = EngineConfig(tp_degree=2, pool_width=1, backend="cpu",
                       kv_dtype="bfloat8", yield_every=64,
                       scheduler=SchedulerConfig(kv_capacity_tokens=512,
                                                 block_size=8))
    sys_ = ServingSystem(cfg).start()
    try:
        sys_.submit("the quick brown fox", max_new_tokens=2)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="kv_dtype must be"):
            sys_.collect(1, timeout=60.0)
        assert time.monotonic() - t0 < 20.0
    finally:
        t0 = time.monotonic()
        sys_.shutdown()
        assert time.monotonic() - t0 < 15.0
    assert any(f.startswith("worker-") for f in sys_.failures)
