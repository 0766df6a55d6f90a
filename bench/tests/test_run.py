"""A whole run on the CPU at a tiny size, the look for a chip skipped:
sound, it is correct; with the timed path broken underneath, it is not.

Each run is a fresh interpreter (the engine's workers fork from it and
start JAX there), driving ``bench.harness.main`` on the tiny cells of
``bench/tests/data/bench.json``."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

# faults planted in the program before the engine forks its workers
FAULTS = {
    "none": "",
    # a step that returns its state unchanged: K/V never reach the pages
    "state_unchanged": (
        "from repro.backend.surrogate import PagedSurrogateBackend as P\n"
        "P._write = lambda self, table, start, tokens: None\n"),
    # a token altered where it is produced
    "token_altered": (
        "from repro.backend.surrogate import PagedSurrogateBackend as P\n"
        "_s = P._sample_rows\n"
        "P._sample_rows = lambda self, rows: {\n"
        "    k: (v + 1) % self.vocab for k, v in _s(self, rows).items()}\n"),
    # half of each step's rows left out: their logits are never computed
    "half_batch": (
        "import numpy as np\n"
        "from repro.backend.jax_backend import JaxBackend as J\n"
        "_a = J._attend\n"
        "def _half(self, q, tables, seq_lens):\n"
        "    out = np.array(_a(self, q, tables, seq_lens))\n"
        "    out[len(out) // 2:] = 0.0\n"
        "    return out\n"
        "J._attend = _half\n"),
    # one worker of two skips its share: the chips' outputs part
    "worker_skips": (
        "import multiprocessing as mp\n"
        "from repro.backend.base import StepResult\n"
        "from repro.backend.jax_backend import JaxBackend as J\n"
        "_x = J.execute\n"
        "def _skip(self, plan, block_tables=None):\n"
        "    if mp.current_process().name == 'worker-1':\n"
        "        return StepResult(step_id=plan.step_id)\n"
        "    return _x(self, plan, block_tables)\n"
        "J.execute = _skip\n"),
}

DRIVER = """
import sys, time
T0 = time.perf_counter()
sys.path[0:0] = [{root!r}, {src!r}]
{fault}
from pathlib import Path
from bench.harness import main
sys.exit(main({argv!r}, t_start=T0, bench_file=Path({bench!r}),
              allow_cpu=True))
"""


def run_cell(workload: str, fault: str, seed: int = 2**31 + 5,
             trace: int = 0, control: int = 0, seconds: int = 2) -> dict:
    code = DRIVER.format(root=str(ROOT), src=str(ROOT / "src"),
                         fault=FAULTS[fault], bench=str(DATA / "bench.json"),
                         argv=["--workload", workload, "--seed", str(seed),
                               "--seconds", str(seconds),
                               "--trace", str(trace),
                               "--control", str(control)])
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=600,
                          env={"JAX_PLATFORMS": "cpu",
                               "PATH": "/usr/bin:/bin",
                               "HOME": str(ROOT / "bench" / "runs")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "check"
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    return line


@pytest.mark.parametrize("workload", ["tiny-open", "tiny-closed"])
def test_sound_run_is_correct(workload):
    line = run_cell(workload, "none")
    assert line["correct"] is True
    assert line["check"]["logit_gap"]["value"] == 0.0
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered",
                                   "half_batch"])
def test_broken_step_is_not_correct(fault):
    line = run_cell("tiny-open", fault)
    assert line["correct"] is False
    assert (line["check"]["logit_gap"]["value"]
            > line["check"]["logit_gap"]["limit"])


def test_a_worker_that_skips_is_not_correct():
    line = run_cell("tiny-tp2", "worker_skips")
    assert line["correct"] is False


def test_control_is_not_correct():
    """The control (the reference one precision below the configuration's)
    in the workers' place, judged by the run's own comparison."""
    line = run_cell("tiny-open", "none", control=1, seconds=6)
    assert line["correct"] is False
    assert line["readings"]["control"] == "fp8_e4m3"
    assert (line["check"]["logit_gap"]["value"]
            > line["check"]["logit_gap"]["limit"])


def _cli(cwd: Path, workload: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "3", "--seconds", "5", "--trace", "0"], cwd=cwd,
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(cwd)})


def _prints_no_result(proc) -> bool:
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    return '"correct"' not in last


def test_no_chip_exits_nonzero_without_a_result():
    proc = _cli(ROOT, "code-q05")
    assert proc.returncode != 0 and _prints_no_result(proc)
    assert "not a TPU" in proc.stderr


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("runs", "__pycache__"))
    proc = _cli(tmp_path, "code-q05")
    assert proc.returncode != 0 and _prints_no_result(proc)


def test_sweep_prints_a_line_per_rate():
    code = DRIVER.format(root=str(ROOT), src=str(ROOT / "src"), fault="",
                         bench=str(DATA / "bench.json"),
                         argv=["--workload", "tiny-open", "--seed", "9",
                               "--seconds", "2", "--sweep", "2,4"])
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=600,
                          env={"JAX_PLATFORMS": "cpu",
                               "PATH": "/usr/bin:/bin",
                               "HOME": str(ROOT / "bench" / "runs")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    assert [x["rate_per_s"] for x in lines] == [2.0, 4.0]
    assert [x["attempted"] for x in lines] == [4, 8]
    assert all(x["ttft_p50"] > 0 for x in lines)
