"""Pluggable execution backends: plan in, StepResult out.

``make_backend`` is the single construction seam used by the engine
workers and the launch drivers; the physical backends (jax, cpu) are
imported lazily so the default emulated path never pulls heavy deps
into forked worker processes.  The catalogue — what each backend is for
and how they compose — lives in docs/backends.md.
"""
from __future__ import annotations

from repro.backend.base import Backend, StepResult
from repro.backend.emulated import EmulatedBackend

__all__ = ["Backend", "BACKEND_NAMES", "CpuDecodeBackend", "EmulatedBackend",
           "HybridBackend", "JaxBackend", "StepResult", "make_backend"]

BACKEND_NAMES = ("emulated", "jax", "cpu", "hybrid")


def __getattr__(name):
    if name == "JaxBackend":
        from repro.backend.jax_backend import JaxBackend
        return JaxBackend
    if name == "CpuDecodeBackend":
        from repro.backend.cpu_decode import CpuDecodeBackend
        return CpuDecodeBackend
    if name == "HybridBackend":
        from repro.backend.hybrid import HybridBackend
        return HybridBackend
    raise AttributeError(name)


def _physical_leaf(name: str, cfg, kv_dtype: str = "float32", model=None):
    if name == "jax":
        from repro.backend.jax_backend import JaxBackend
        cls = JaxBackend
    else:
        from repro.backend.cpu_decode import CpuDecodeBackend
        cls = CpuDecodeBackend
    widths = {} if model is None else dict(
        n_heads=model.n_heads, n_kv_heads=model.n_kv_heads,
        head_dim=model.head_dim, vocab=model.vocab_size)
    return cls(block_size=cfg.block_size, num_blocks=cfg.num_kv_blocks,
               num_swap_blocks=cfg.num_swap_blocks,
               copy_streams=cfg.copy_streams, kv_dtype=kv_dtype, **widths)


def make_backend(name: str, *, device=None, scheduler_cfg=None,
                 prefill_backend: str = "emulated",
                 decode_backend: str = "emulated",
                 decode_slowdown: float = 8.0,
                 kv_dtype: str = "float32",
                 draft_backend: str = "",
                 draft_slowdown: float = 8.0,
                 spec_accept_rate=None,
                 model=None):
    """Build a backend by name (one of ``BACKEND_NAMES``).

    ``device`` feeds the emulated sleep model; ``scheduler_cfg`` sizes the
    physical page pools (their block ids must match the scheduler's
    manager) and carries ``copy_streams`` — the async-copy-engine switch
    (docs/copy_engine.md), which must be the SCHEDULER's because only its
    in-flight block holds make the backends' deferred page copies safe.
    For ``"hybrid"``, ``prefill_backend``/``decode_backend`` name the two
    children; an emulated decode child gets the device's
    ``cpu_tier(decode_slowdown=...)`` cost model (accelerator-class
    prefill, CPU-class decode — docs/backends.md), and the handoff is
    priced at the prefill device's swap bandwidth.

    ``kv_dtype="int8"`` stores the decode-tier KV pool quantized
    (docs/spec_decode.md): on a unified backend the whole pool, under
    ``"hybrid"`` only the decode child — the prefill child stays fp32
    and the handoff copy is where quantization happens.  The cost model
    and the handoff price see the halved bytes.

    When ``scheduler_cfg.speculative_k > 0`` the result is wrapped in
    ``repro.spec.SpeculativeBackend``: ``draft_backend`` names the draft
    child (default ``"cpu"`` for physical targets, ``"emulated"``
    otherwise — an emulated draft costs ``cpu_tier(draft_slowdown)`` and
    models acceptance with ``spec_accept_rate``).  The draft's pool is
    always fp32: it is the cheap CPU tier, and its candidates are only
    hints — the verify pass prices the int8 savings.

    ``model`` (a ``repro.configs.ModelConfig``) gives the physical
    backends' surrogate its heads, kv heads, head dim and vocabulary;
    without it they keep their small default widths."""
    import dataclasses

    from repro.core.devmodel import DeviceModel
    from repro.serving.scheduler import SchedulerConfig
    device = device if device is not None else DeviceModel()
    cfg = scheduler_cfg if scheduler_cfg is not None else SchedulerConfig()
    if device.copy_streams != cfg.copy_streams:
        # one switch, two consumers: the scheduler's epoch bookkeeping and
        # the device cost model must see the same stream count
        device = dataclasses.replace(device, copy_streams=cfg.copy_streams)
    if kv_dtype not in ("float32", "int8"):
        raise ValueError(f"kv_dtype must be float32|int8, got {kv_dtype!r}")

    physical = {"jax", "cpu"}
    if name == "emulated":
        base = EmulatedBackend(device.with_kv_dtype(kv_dtype))
    elif name in physical:
        base = _physical_leaf(name, cfg, kv_dtype, model)
    elif name == "hybrid":
        from repro.backend.hybrid import HybridBackend
        if "hybrid" in (prefill_backend, decode_backend):
            raise ValueError("hybrid children must be leaf backends")
        if (prefill_backend in physical) != (decode_backend in physical):
            # an emulated child computes no KV: pairing it with a physical
            # child silently yields tokens decoded from an all-zero pool
            # (emulated prefill) or a placeholder-0 stream after the first
            # token (emulated decode) — reject rather than mislead
            raise ValueError(
                f"hybrid children must be both physical (jax/cpu) or both "
                f"emulated, got prefill={prefill_backend!r} "
                f"decode={decode_backend!r}")

        def child(child_name: str, role: str):
            # int8 lives on the DECODE tier only: prefill stays fp32 and
            # the handoff copy quantizes (docs/spec_decode.md)
            tier_dtype = kv_dtype if role == "decode" else "float32"
            if child_name == "emulated":
                dev = (device.cpu_tier(decode_slowdown=decode_slowdown)
                       .with_kv_dtype(tier_dtype)
                       if role == "decode" else device)
                return EmulatedBackend(dev)
            return _physical_leaf(child_name, cfg, tier_dtype, model)

        base = HybridBackend(
            child(prefill_backend, "prefill"),
            child(decode_backend, "decode"),
            t_handoff_block=device.t_swap_block
            * (0.5 if kv_dtype == "int8" else 1.0),
            copy_streams=cfg.copy_streams,
            t_submit_per_copy=device.t_submit_per_copy)
    else:
        raise ValueError(f"unknown backend {name!r} "
                         f"(want one of {BACKEND_NAMES})")

    if cfg.speculative_k <= 0:
        return base
    from repro.spec import SpeculativeBackend
    target_physical = (name in physical
                       or (name == "hybrid" and prefill_backend in physical))
    dname = draft_backend or ("cpu" if target_physical else "emulated")
    if dname not in ("jax", "cpu", "emulated"):
        raise ValueError(f"draft_backend must be jax|cpu|emulated, "
                         f"got {dname!r}")
    if (dname in physical) != target_physical:
        # a draft without pages cannot feed a physical verify (and a
        # physical draft under an emulated target would decode garbage)
        raise ValueError(
            f"draft must match the target's physicality: "
            f"target={'physical' if target_physical else 'emulated'}, "
            f"draft_backend={dname!r}")
    if dname == "emulated":
        draft = EmulatedBackend(
            device.cpu_tier(decode_slowdown=draft_slowdown))
    else:
        draft = _physical_leaf(dname, cfg, model=model)   # fp32 draft pool
    return SpeculativeBackend(draft, base, accept_rate=spec_accept_rate)
