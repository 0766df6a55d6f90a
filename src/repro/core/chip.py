"""One accelerator per process: chip visibility, the compile cache, and
the device a process ended up on.

A TPU chip belongs to one process at a time, so each engine worker that
runs JAX is pinned to a chip of its own before JAX starts in it
(``pin_chip``).  Importing this module does not import JAX: the engine's
owner and EngineCore processes must stay off the accelerator.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, so a later run of the same checkout
# finds what an earlier one compiled (the path is part of the cache key)
_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
_TPU_PROCESS_PORT = 8476                  # libtpu's default, + chip index


def pin_chip(index: int) -> None:
    """Make TPU chip ``index`` the only chip this process sees.

    Must run before JAX initialises its backend here.  A chips-per-process
    bound of 1x1x1 is what lets libtpu load once per process on a
    multi-chip host; each process then needs its own runtime port.  An
    index past the host's chips makes JAX's TPU start-up fail."""
    os.environ.update({
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(_TPU_PROCESS_PORT + index),
    })


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX keeps the cache there; otherwise it goes to ``.jax_cache`` at the
    root of the checkout.  Every compile is cached, however short.  On the
    CPU it stays off (returns None): XLA:CPU results read back on another
    host warn of mismatched machine features."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    return str(_CACHE_DIR)


def device_info() -> dict:
    """The first JAX device of this process, as JAX reports it, and the
    accelerator device files the process holds open.  A pinned process
    numbers its one chip 0 in ``id``; the device file is the host's name
    for the chip."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "id": d.id, "device_files": _device_files()}


def _device_files() -> list:
    """Accelerator device files this process has open or mapped."""
    paths = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            paths.append(os.readlink(f"/proc/self/fd/{fd}"))
        except OSError:                   # closed since the listing
            pass
    with open("/proc/self/maps") as maps:
        paths += [line.split()[-1] for line in maps if "/dev/" in line]
    return sorted({p for p in paths
                   if p.startswith(("/dev/accel", "/dev/vfio/"))
                   and p != "/dev/vfio/vfio"})
