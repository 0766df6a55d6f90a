"""One benchmark run: set up the served path, offer the cell's traffic,
measure a window, check the outputs, print the result line.

The owner (this process) never imports JAX.  It builds the program's
``ServingSystem`` with ``backend="jax"`` as ``repro.launch.serve`` does
with its defaults, every host core given, and drives it through
``submit`` and ``collect``.  Each worker holds one chip (the program pins
it) and builds its backend through ``bench.worker`` (see there).

Everything a cell needs is found by name: its entry in
``BENCHMARK.json``, ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py`` (or,
for ``<base>.<suffix>``, ``bench/metrics/<base>.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
RUNS = BENCH / "runs"
READY_TIMEOUT_S = 1100          # start-up, weights, warm-up (cold cache)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                # bench/configs/<config>.json
    traffic: dict               # bench/traffic/<traffic>.json
    metrics: List[dict]         # BENCHMARK.json metric entries to report


def load_cell(bench_file: Path, workload: str, trace: bool) -> Cell:
    bench = json.loads(Path(bench_file).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic_dir = ROOT / bench.get("traffic_dir", "bench/traffic")
    traffic = json.loads(
        (traffic_dir / f"{w['traffic']}.json").read_text())
    listed = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = [m for m in listed
               if workload in m.get("workloads", [workload])]
    return Cell(workload, w["chips"], config, traffic, metrics)


def metric_reader(name: str):
    """``bench/metrics/<name>.py``, else ``bench/metrics/<base>.py`` for a
    suffixed ``<base>.<suffix>``: its ``read(run)``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a metric reader reads.  Times are host-clock seconds
    (CLOCK_MONOTONIC, shared by every process of the host)."""
    cell: Cell
    setup_s: float
    window: tuple                          # (open, close)
    t_end: float                           # when the load stopped
    requests: List[dict]                   # generator + client records
    workers: Dict[int, dict]               # worker dumps, by index
    traces: Dict[int, dict]                # traced run: per worker
    spans: list                            # (role, SpanEvent), traced
    widths: object
    peak: Optional[dict]
    chips: int

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]


def engine_config(config: dict, trace: bool):
    """``repro.launch.serve``'s engine at its defaults, with the
    configuration's settings (``serve --backend jax --arch <arch>
    --tp <tp> --kv-capacity <kv>``)."""
    from repro.configs import get_config
    from repro.core.devmodel import DeviceModel
    from repro.core.engine import EngineConfig
    from repro.profiling import ProfilingConfig
    from repro.serving.scheduler import SchedulerConfig
    eng = config["engine"]
    device = DeviceModel(t_fixed=1e-3, t_prefill_tok=1e-6,
                         t_decode_seq=2e-5)
    kv = eng["kv_capacity_tokens"]
    sched = SchedulerConfig(kv_capacity_tokens=kv,
                            block_size=eng.get("block_size", 64),
                            swap_capacity_tokens=kv,
                            max_num_seqs=eng.get("max_num_seqs", 64),
                            **device.preemption_calibration(),
                            **device.copy_calibration())
    return EngineConfig(
        tp_degree=eng["tp_degree"], pool_width=eng.get("pool_width", 4),
        scheduler=sched, device=device, backend="jax",
        model=get_config(config["arch"]).scaled(
            **config.get("arch_overrides", {})), yield_every=64,
        profiling=ProfilingConfig(trace=trace))


def widths_of(config: dict):
    from bench.work import Widths
    return Widths(n_heads=config["num_attention_heads"],
                  n_kv_heads=config["num_key_value_heads"],
                  head_dim=config["head_dim"],
                  vocab=config["vocab_size"])


def warm_spec(traffic: dict, sched) -> dict:
    """Bounds of the shape buckets the cell's traffic can reach."""
    bs = sched.block_size
    rows = sched.max_num_seqs
    if traffic["loop"] == "closed":
        rows = min(rows, traffic["clients"])
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    first = min(p["min"], sched.prefill_chunk)
    return {"max_rows": rows, "min_pages": -(-first // bs),
            "max_pages": -(-(p["max"] + o["max"]) // bs),
            "num_blocks": sched.num_kv_blocks}


# -- the load -------------------------------------------------------------


def _open_loop(sys_, reqs, t0: float, stop: threading.Event,
               sent: list) -> None:
    for r in reqs:
        due = t0 + r.due
        while True:
            now = time.perf_counter()
            if stop.is_set():
                return
            if now >= due:
                break
            time.sleep(min(due - now, 0.05))
        rid = sys_.submit(r.text, max_new_tokens=r.max_new)
        sent.append((r.idx, rid, due))


def _poll(sys_, n_have: int) -> None:
    """Wait up to a quarter second for one more result record."""
    sys_.collect(n_have + 1, timeout=0.25)


def drive_open(sys_, traffic, seconds: float) -> tuple:
    spec = traffic.spec
    stop = threading.Event()
    sent: list = []
    t0 = time.perf_counter() + 0.05
    w0, w1 = t0 + spec["ramp_s"], t0 + spec["ramp_s"] + seconds
    gen = threading.Thread(target=_open_loop,
                           args=(sys_, traffic.requests, t0, stop, sent),
                           daemon=True, name="bench-loadgen")
    gen.start()
    window_idx = {r.idx for r in traffic.requests if r.segment == "window"}
    try:
        while True:
            now = time.perf_counter()
            if now >= w1:
                rids = {rid for idx, rid, _ in sent if idx in window_idx}
                if (len(rids) == len(window_idx)
                        and rids <= set(sys_.results)):
                    break
                if now >= w1 + spec["drain_s"]:
                    break
            _poll(sys_, len(sys_.results))
    finally:
        stop.set()
        gen.join()
    return (w0, w1), list(sent), {r.idx: r.due + t0
                                  for r in traffic.requests}


def drive_closed(sys_, traffic, seconds: float, progress) -> tuple:
    """``clients`` requests in flight; a finished one is replaced at
    once.  The window opens once every first request has its first
    token.  Past the close the load stays on until the finished requests
    hold ``check_tokens`` output tokens to check, within ``drain_s``."""
    spec = traffic.spec
    reqs = iter(traffic.requests)
    sent: list = []
    due: Dict[int, float] = {}
    idx_of: Dict[int, int] = {}               # rid -> request index

    def send():
        r = next(reqs, None)
        if r is None:                     # the pool ran out: client idles
            return
        t = time.perf_counter()
        rid = sys_.submit(r.text, max_new_tokens=r.max_new)
        sent.append((r.idx, rid, t))
        due[r.idx] = t
        idx_of[rid] = r.idx

    for _ in range(spec["clients"]):
        send()
    deadline = time.perf_counter() + spec["first_tokens_s"]
    while progress() < spec["clients"]:
        if time.perf_counter() > deadline:
            raise TimeoutError("first requests not prefilled in time")
        time.sleep(0.05)
    w0 = time.perf_counter()
    w1 = w0 + seconds
    seen: set = set()
    while True:
        now = time.perf_counter()
        if now >= w1:
            done = sum(traffic.requests[idx_of[rid]].max_new for rid in seen)
            if done >= spec["check_tokens"] or now >= w1 + spec["drain_s"]:
                break
        _poll(sys_, len(sys_.results))
        for rid in set(sys_.results) - seen:
            seen.add(rid)
            send()
    return (w0, w1), sent, due


# -- the run ----------------------------------------------------------------


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control's tokens in place of the "
                         "workers': the reference one precision below the "
                         "configuration's (for setting limits; not part "
                         "of a run)")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates: offer each in turn for "
                         "one window, in one process, and print one line "
                         "each, with no check (for finding the knee; not "
                         "part of a run)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the profiler's files under bench/runs/")
    return ap.parse_args(argv)


def main(argv=None, *, t_start: Optional[float] = None,
         bench_file: Optional[Path] = None, allow_cpu: bool = False) -> int:
    """Run one cell; returns the exit code.  ``allow_cpu`` (tests only)
    skips the look for a chip."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = load_cell(bench_file or ROOT / "BENCHMARK.json", args.workload,
                     bool(args.trace))
    # the compile cache stays inside the checkout, at a fixed path; libtpu
    # logs nowhere
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        if args.sweep:
            _sweep(cell, args, [float(r) for r in args.sweep.split(",")],
                   allow_cpu)
            return 0
        line, checks = _run(cell, args, t_start, allow_cpu)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for s in checks:
        print(s, file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def _run(cell: Cell, args, t_start: float, allow_cpu: bool) -> tuple:
    from repro.core.engine import ServingSystem
    from repro.profiling import events_from_stats
    from repro.tokenizer.bpe import default_tokenizer

    from bench import check, loadgen, reference, worker
    from bench.work import PEAKS, peak

    config, spec = cell.config, cell.traffic
    tok = default_tokenizer()
    traffic = loadgen.build(spec, args.seed, args.seconds, tok)
    cfg = engine_config(config, bool(args.trace))
    if cfg.tp_degree != cell.chips:
        raise ValueError(f"{cell.name}: tp {cfg.tp_degree} on "
                         f"{cell.chips} chips")
    widths = widths_of(config)
    run_dir = RUNS / f"{cell.name}.{args.seed}"
    channel = worker.Channel(cfg.tp_degree)
    wspec = worker.WorkerSpec(
        seed=worker.weight_seed(args.seed), widths=widths,
        warm=warm_spec(spec, cfg.scheduler), channel=channel,
        trace_dir=str(run_dir / "trace") if args.trace else None)
    worker.install(wspec)
    sys_ = ServingSystem(cfg, tokenizer=tok).start()
    try:
        devices = channel.gather("device", cfg.tp_degree, 300, sys_._dead)
        kinds = {d["platform"] for d in devices.values()}
        if not allow_cpu and kinds != {"tpu"}:
            raise NoChip(f"workers run on {sorted(kinds)}, not a TPU")
        ready = channel.gather("ready", cfg.tp_degree, READY_TIMEOUT_S,
                               sys_._dead)
        if args.trace:
            channel.broadcast("trace_start")
            channel.gather("trace_start", cfg.tp_degree, 120, sys_._dead)

        if spec["loop"] == "open":
            window, sent, due = drive_open(sys_, traffic, args.seconds)
        else:
            def progress():
                channel.cmd[0].put(("progress",))
                return channel.gather("progress", 1, 60, sys_._dead)[0]
            window, sent, due = drive_closed(sys_, traffic, args.seconds,
                                             progress)
        t_end = time.perf_counter()
        setup_s = window[0] - t_start
        traces = {}
        if args.trace:
            channel.broadcast("trace_stop", window[0], window[1],
                              args.keep_trace)
            traces = channel.gather("trace_stop", cfg.tp_degree, 300,
                                    sys_._dead)
        requests = _records(traffic, sent, due, sys_.results, window)
        done = [r for r in requests if r["result"] is not None
                and not r["result"].get("timed_out")
                and (spec["loop"] != "open" or r["in_window"])]
        sample = check.pick_sample(done, args.seed, spec["check_tokens"])
        channel.broadcast("dump", [r["rid"] for r in sample])
        dumps = channel.gather("dump", cfg.tp_degree, 300, sys_._dead)
    finally:
        stats = _stop(sys_, 60 if args.trace and spec["loop"] == "open"
                      else 5)
    if sys_.failures:
        raise RuntimeError(f"{', '.join(sys_.failures)} died")
    if any(d["unsupported"] for d in dumps.values()):
        raise RuntimeError("the engine sent multi-step or speculative "
                           "plans, which the check does not follow")

    control = (reference.control_below(config["torch_dtype"])
               if args.control else None)
    readings = check.compare(widths, wspec.seed, sample, dumps, control)
    numbers = {k: (readings[k], lim) for k, lim in config["limits"].items()}
    numbers.update({
        "prompt_mismatch": (readings["prompt_mismatch"], 0),
        "length_mismatch": (readings["length_mismatch"]
                            + check.client_lengths(requests), 0),
        "never_done": (check.never_done(requests, spec["loop"]), 0),
    })
    kind = next(iter(devices.values()))["device_kind"]
    run = Run(cell=cell, setup_s=setup_s,
              window=window, t_end=t_end, requests=requests, workers=dumps,
              traces=traces, spans=events_from_stats(stats),
              widths=widths,
              peak=PEAKS.get(kind) if allow_cpu else peak(kind),
              chips=cell.chips)
    metrics = {}
    for m in cell.metrics:
        v = metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    peaks = [d["memory_peak_bytes"] for d in dumps.values()]
    device = {"platform": next(iter(kinds)), "kind": kind,
              "count": cell.chips,
              "memory_peak_bytes": max((p for p in peaks if p), default=0)}
    attempted = [r for r in requests if r["in_window"]]
    line = {"correct": check.verdict(numbers),
            "attempted": len(attempted),
            # open loop: a window request that never finished failed;
            # closed loop: one still in flight at the end has not
            "failed": sum(1 for r in attempted
                          if (r["result"] or {}).get("timed_out")
                          or (r["result"] is None
                              and spec["loop"] == "open")),
            "metrics": metrics, "device": device}
    busy = [t["busy_ns"] for tr in traces.values() for t in tr.values()]
    if busy:                       # a device in the trace (not the CPU)
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = window[1] - window[0]
        line["breakdown"] = _breakdown(traces)
    extra = {k: v for k, v in readings.items() if k not in numbers}
    if control:
        extra["control"] = control
    extra.update(_latencies(run))
    extra.update({f"worker{i}_{k}": r[k] for i, r in ready.items()
                  for k in ("warm_shapes", "warm_s", "warm_compiles")})
    line["readings"] = extra
    line["check"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in numbers.items()}
    return line, check.fmt(numbers)


def _latencies(run: Run) -> dict:
    """Medians and tails of both latencies, whichever the cell reports;
    and, for finding the knee, the median time to first token of the
    first and the last quarter of the window's requests (a growing
    backlog shows as the second far above the first) and how long the
    drain took."""
    from bench import stats
    t, g = stats.ttfts_ms(run), stats.tpots_ms(run)
    q = max(1, len(t) // 4)
    return {"ttft_p50": stats.percentile(t, 50),
            "ttft_p90": stats.percentile(t, 90),
            "ttft_p95": stats.percentile(t, 95),
            "tpot_p50": stats.percentile(g, 50),
            "tpot_p90": stats.percentile(g, 90),
            "tpot_p95": stats.percentile(g, 95),
            "ttft_q1_ms": stats.percentile(t[:q], 50),
            "ttft_q4_ms": stats.percentile(t[-q:], 50),
            "drain_s": run.t_end - run.window[1]}


def _sweep(cell: Cell, args, rates: List[float], allow_cpu: bool) -> None:
    """One engine, set up once; each rate's traffic (seed ``--seed`` plus
    its place in ``rates``, so no prompt repeats) offered for a ramp, one
    window and the drain, in turn, until a window's requests do not
    finish within the drain.  Prints a line per rate."""
    from repro.core.engine import ServingSystem
    from repro.tokenizer.bpe import default_tokenizer

    from bench import loadgen, worker

    if cell.traffic["loop"] != "open":
        raise SystemExit("--sweep needs an open-loop cell")
    tok = default_tokenizer()
    builder = loadgen.PromptBuilder(tok)
    cfg = engine_config(cell.config, False)
    channel = worker.Channel(cfg.tp_degree)
    worker.install(worker.WorkerSpec(
        seed=worker.weight_seed(args.seed), widths=widths_of(cell.config),
        warm=warm_spec(cell.traffic, cfg.scheduler), channel=channel))
    sys_ = ServingSystem(cfg, tokenizer=tok).start()
    try:
        devices = channel.gather("device", cfg.tp_degree, 300, sys_._dead)
        kinds = {d["platform"] for d in devices.values()}
        if not allow_cpu and kinds != {"tpu"}:
            raise NoChip(f"workers run on {sorted(kinds)}, not a TPU")
        channel.gather("ready", cfg.tp_degree, READY_TIMEOUT_S, sys_._dead)
        for k, rate in enumerate(rates):
            spec = dict(cell.traffic, rate_per_s=rate)
            traffic = loadgen.build(spec, args.seed + k, args.seconds, tok,
                                    builder)
            window, sent, due = drive_open(sys_, traffic, args.seconds)
            requests = _records(traffic, sent, due, sys_.results, window)
            run = Run(cell=cell, setup_s=0.0, window=window,
                      t_end=time.perf_counter(), requests=requests,
                      workers={}, traces={}, spans=[], widths=None,
                      peak=None, chips=cell.chips)
            line = {"rate_per_s": rate, "seed": args.seed + k,
                    "attempted": sum(r["in_window"] for r in requests)}
            line.update(_latencies(run))
            print(json.dumps(line), flush=True)
            if line["drain_s"] >= spec["drain_s"]:
                break                 # overloaded: higher rates are too
    finally:
        _stop(sys_, 5)


def _records(traffic, sent, due, results, window) -> List[dict]:
    by_idx = {idx: rid for idx, rid, _ in sent}
    out = []
    for r in traffic.requests:
        if r.idx not in by_idx:
            continue
        rid = by_idx[r.idx]
        out.append({"idx": r.idx, "rid": rid, "segment": r.segment,
                    "due": due[r.idx], "prompt_ids": r.prompt_ids,
                    "max_new": r.max_new, "result": results.get(rid),
                    "in_window": window[0] <= due[r.idx] < window[1]})
    return out


def _stop(sys_, timeout: float) -> list:
    """Stop the engine and workers, reading their stats as they exit (a
    process cannot exit while its stats sit unread in a full pipe), and
    make sure every process has ended before the check."""
    sys_.stop_ev.set()
    deadline = time.monotonic() + timeout
    while (any(p.is_alive() for p in sys_.procs)
           and time.monotonic() < deadline and not sys_._dead()):
        sys_._drain_stats()
        time.sleep(0.05)
    stats = sys_.shutdown(timeout=1.0)
    for p in sys_.procs:
        if p.is_alive():
            p.kill()
        p.join(10.0)
    return stats


def _breakdown(traces: Dict[int, dict]) -> dict:
    ops: Dict[str, int] = {}
    gaps: list = []
    for tr in traces.values():
        for dev in tr.values():
            for name, ns in dev["op_ns"].items():
                ops[name] = ops.get(name, 0) + ns
            gaps += dev["longest_gaps"]
    n = max(1, sum(len(tr) for tr in traces.values()))
    top = sorted(ops.items(), key=lambda p: -p[1])[:10]
    gaps.sort(key=lambda p: -p[1])
    return {"device_ops": [[k, v / n / 1e9] for k, v in top],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps[:10]]}
