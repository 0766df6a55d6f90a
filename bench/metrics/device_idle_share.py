"""Device: share of the window in which no operation ran on the chip,
from the profiler trace; the mean over the cell's chips (each chip's
value goes to standard error)."""
import sys


def read(run):
    shares = []
    for idx, tr in sorted(run.traces.items()):
        for plane, dev in tr.items():
            s = 100.0 * (1 - dev["busy_ns"] / dev["window_ns"])
            print(f"device_idle_share worker{idx} {plane} {s}",
                  file=sys.stderr)
            shares.append(s)
    return sum(shares) / len(shares) if shares else None
