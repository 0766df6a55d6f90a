"""Compiles for a described TPU v5e: the paged decode kernel at real head
dims and the jitted steps of ``JaxBackend``.

Nothing runs: each test lowers and compiles for a v5e chip that is
described, not attached, so Mosaic refuses here what it would refuse on
the chip (unaligned DMA slices, SMEM block shapes, VMEM overflow).  The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backend.jax_backend import attend_logits, decode_scan
from repro.configs import get_config
from repro.kernels.paged_decode_attention import (VMEM_BUDGET_BYTES,
                                                  paged_decode_attention,
                                                  pool_vmem_bytes)

QWEN2_0_5B = get_config("qwen2-0.5b")          # 14 / 2 / 64
QWEN2_VL_7B = get_config("qwen2-vl-7b")        # 28 / 4 / 128
BLOCK = 64
SERVE_POOL_PAGES = (1 << 16) // BLOCK          # serve's 64K-token default


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _kernel_args(sharding, cfg, n_pages, dtype, rows=8, nb=16):
    kv, d = cfg.n_kv_heads, cfg.head_dim
    pool = _sds(sharding, (kv, n_pages, BLOCK, d), dtype)
    args = [_sds(sharding, (rows, cfg.n_heads, d), "float32"), pool, pool,
            _sds(sharding, (rows, nb), "int32"),
            _sds(sharding, (rows,), "int32")]
    scales = [_sds(sharding, (kv, n_pages), "float32")] * 2 \
        if dtype == "int8" else []
    return args, scales


@pytest.mark.parametrize("cfg", [QWEN2_0_5B, QWEN2_VL_7B],
                         ids=lambda c: c.name)
@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("residency,n_pages", [
    ("vmem", 256), ("hbm", SERVE_POOL_PAGES), ("auto", SERVE_POOL_PAGES)])
def test_paged_kernel_compiles(one_chip, cfg, dtype, residency, n_pages):
    args, scales = _kernel_args(one_chip, cfg, n_pages, dtype)
    pool_in_vmem = {"vmem": True, "hbm": False, "auto": None}[residency]

    def run(q, kp, vp, bt, sl, *sc):
        kw = dict(zip(("k_scales", "v_scales"), sc))
        return paged_decode_attention(q, kp, vp, bt, sl, **kw,
                                      pool_in_vmem=pool_in_vmem,
                                      interpret=False)

    compiled = jax.jit(run).lower(*args, *scales).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serve_pool_is_too_big_for_vmem_residency():
    """The auto choice keeps serve's 64K-token pool in HBM."""
    for cfg in (QWEN2_0_5B, QWEN2_VL_7B):
        assert pool_vmem_bytes(SERVE_POOL_PAGES, BLOCK, cfg.head_dim,
                               "int8") > VMEM_BUDGET_BYTES


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_attend_step_compiles(one_chip, dtype):
    """``JaxBackend._attend``'s jitted step at qwen2-0.5b widths: the paged
    kernel, then the output projection to the full vocabulary."""
    cfg = QWEN2_0_5B
    args, scales = _kernel_args(one_chip, cfg, 64, dtype)
    wo = _sds(one_chip, (cfg.n_heads * cfg.head_dim, cfg.vocab_size),
              "float32")
    kw = dict(zip(("k_scales", "v_scales"), scales))
    compiled = attend_logits.lower(*args, wo, **kw,
                                   interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_scan_compiles(one_chip):
    """``JaxBackend._decode_multi``'s fused k-step scan at qwen2-0.5b
    widths."""
    cfg = QWEN2_0_5B
    rows, nb, pool = 8, 16, 64
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    e = h * d
    pages = _sds(one_chip, (kv, pool, BLOCK, d), "float32")
    vec = _sds(one_chip, (rows,), "int32")
    compiled = decode_scan.lower(
        pages, pages, _sds(one_chip, (rows, nb), "int32"), vec, vec, vec,
        vec, _sds(one_chip, (cfg.vocab_size, e), "float32"),
        _sds(one_chip, (e, h * d), "float32"),
        _sds(one_chip, (e, kv * d), "float32"),
        _sds(one_chip, (e, kv * d), "float32"),
        _sds(one_chip, (e, cfg.vocab_size), "float32"),
        n_steps=4, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
