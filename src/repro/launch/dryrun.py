import os

# the dry-run meshes (up to 512 devices) are virtual CPU devices
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

DOC = """Multi-pod dry-run driver (deliverable e).

For every (architecture x input-shape x mesh) combination this lowers and
compiles the real step function (train_step / prefill / decode_step) against
ShapeDtypeStruct stand-ins on the production mesh, proving the sharding
config is coherent, printing memory_analysis() (fits) and cost_analysis()
(FLOPs/bytes for the roofline), and writing one JSON artifact per cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --cell train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--unroll]
"""




import argparse
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, CELLS_BY_NAME, cell_applicable, get_config, input_specs
from repro.dist.sharding import current as mesh_ctx, spec_for, use_mesh
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.roofline import collective_bytes, model_flops, roofline_terms, TPU_V5E
from repro.roofline.model import model_bytes_per_device
from repro.train import optim
from repro.train import step as train_step_mod

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _batch_shardings(cfg, cell, specs):
    """NamedShardings for the input-batch dict."""
    ctx = mesh_ctx()

    def sh(name, leaf):
        if name == "mrope_positions":           # [3, B, S]
            axes = (None, "dp", None)
        elif name == "frames":                  # [B, T, d]
            axes = ("dp", None, None)
        elif name == "cache_len":
            axes = ()
        else:                                    # tokens/targets [B, S]
            axes = ("dp", None)
        axes = axes[: len(leaf.shape)]
        return jax.sharding.NamedSharding(ctx.mesh, spec_for(leaf.shape, *axes))

    return {k: sh(k, v) for k, v in specs.items()}


def build_step(cfg, cell, *, unroll: bool = False, ce_chunks: int = 8,
               remat: bool = True):
    """Returns (fn, example_args pytree, in_shardings, donate_argnums)."""
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(functools.partial(M.init_params, cfg=cfg), key)
    p_shard = M.param_shardings(cfg, params_shape)
    specs = input_specs(cfg, cell)
    b_shard = _batch_shardings(cfg, cell, specs)

    if cell.kind == "train":
        opt_shape = jax.eval_shape(optim.init_opt_state, params_shape)
        zero1 = optim.zero1_shardings(p_shard, params_shape)
        o_shard = optim.OptState(
            step=jax.sharding.NamedSharding(mesh_ctx().mesh, spec_for(())),
            master=zero1, m=zero1, v=zero1)
        ocfg = optim.AdamWConfig()
        n_micro = train_step_mod.pick_n_micro(cfg, cell.global_batch,
                                              cell.seq_len)
        train_step = train_step_mod.make_train_step(
            cfg, ocfg, n_micro=n_micro, unroll=unroll, remat=remat,
            ce_chunks=ce_chunks, grad_shardings=zero1,
            param_shardings=p_shard)

        args = (params_shape, opt_shape, specs)
        shardings = (p_shard, o_shard, b_shard)
        return train_step, args, shardings, (0, 1)

    if cell.kind == "prefill":
        def prefill_step(params, batch):
            extras = {k: v for k, v in batch.items() if k != "tokens"}
            return M.prefill(params, cfg, batch["tokens"], extras,
                             unroll=unroll)
        return prefill_step, (params_shape, specs), (p_shard, b_shard), ()

    # decode
    cache_shape = M.cache_specs(cfg, cell.global_batch, cell.seq_len)
    c_shard = M.cache_shardings(cfg, cache_shape)

    def decode_step(params, cache, batch):
        extras = {k: v for k, v in batch.items()
                  if k not in ("tokens", "cache_len")}
        return M.decode_step(params, cfg, batch["tokens"], cache,
                             batch["cache_len"], extras, unroll=unroll)

    args = (params_shape, cache_shape, specs)
    shardings = (p_shard, c_shard, b_shard)
    return decode_step, args, shardings, (1,)


def _reduced_depth_cfg(cfg, n_periods: int):
    """Same-period-structure config with ``n_periods`` periods per stage."""
    import dataclasses as dc
    over = {}
    if cfg.local_global_ratio is not None:
        over["n_layers"] = sum(cfg.local_global_ratio) * n_periods
    elif cfg.family == "hybrid":
        over["n_layers"] = (cfg.hybrid_period or 6) * n_periods
    elif cfg.encdec is not None:
        over["n_layers"] = n_periods
        over["encdec"] = dc.replace(cfg.encdec, n_encoder_layers=n_periods)
    else:
        over["n_layers"] = n_periods
    return cfg.scaled(**over)


def _periods_total(cfg) -> float:
    if cfg.local_global_ratio is not None:
        return cfg.n_layers / sum(cfg.local_global_ratio)
    if cfg.family == "hybrid":
        return cfg.n_layers / (cfg.hybrid_period or 6)
    return float(cfg.n_layers)


def _measure(cfg, cell, *, unroll: bool):
    """Lower+compile one step; return (flops, bytes, coll_tpu_bytes,
    coll_count), scaled by n_micro for train cells (the grad-accum scan
    body is counted once by cost_analysis but runs n_micro times)."""
    fn, args, shardings, donate = build_step(cfg, cell, unroll=unroll)
    compiled = jax.jit(fn, in_shardings=shardings,
                       donate_argnums=donate).lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    colls = collective_bytes(compiled.as_text())
    scale = 1
    if cell.kind == "train":
        scale = train_step_mod.pick_n_micro(cfg, cell.global_batch,
                                            cell.seq_len)
    return (float(cost.get("flops", 0.0)) * scale,
            float(cost.get("bytes accessed", 0.0)) * scale,
            float(colls["total_bytes_tpu"]) * scale,
            int(colls["total_count"]))


def depth_extrapolate(cfg, cell):
    """Honest per-device HLO numbers for the FULL depth via two shallow
    unrolled compiles: X_total = X1 + (P-1) * (X2 - X1).

    lax.scan bodies are counted once by cost_analysis, so the scanned
    full-depth compile undercounts; unrolling the full depth is
    compile-time-prohibitive.  Depth scaling is exactly linear per period
    (embeddings/CE counted in X1), so this is exact up to XLA fusion noise
    (zamba2's fractional tail period is approximated — DESIGN.md §9).
    """
    c1 = _reduced_depth_cfg(cfg, 1)
    c2 = _reduced_depth_cfg(cfg, 2)
    f1, b1, cb1, cc1 = _measure(c1, cell, unroll=True)
    f2, b2, cb2, cc2 = _measure(c2, cell, unroll=True)
    p = _periods_total(cfg)
    return {
        "flops": f1 + (p - 1) * (f2 - f1),
        "bytes": b1 + (p - 1) * (b2 - b1),
        "coll_bytes_tpu": cb1 + (p - 1) * (cb2 - cb1),
        "coll_count": cc1 + (p - 1) * (cc2 - cc1),
        "per_period": {"flops": f2 - f1, "bytes": b2 - b1,
                       "coll_bytes_tpu": cb2 - cb1},
        "base": {"flops": f1, "bytes": b1, "coll_bytes_tpu": cb1},
        "n_periods": p,
    }


def run_cell(arch: str, cell_name: str, *, multi_pod: bool = False,
             unroll: bool = False, out_dir: Path = ARTIFACTS,
             verbose: bool = True, extrapolate: bool = True) -> dict:
    cfg = get_config(arch)
    cell = CELLS_BY_NAME[cell_name]
    ok, reason = cell_applicable(cfg, cell)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    rec = {"arch": arch, "cell": cell_name, "mesh": mesh_name,
           "status": "skip", "reason": reason}
    if not ok:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{mesh_name}__{arch}__{cell_name}.json").write_text(
            json.dumps(rec, indent=1))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with use_mesh(mesh):
        fn, args, shardings, donate = build_step(cfg, cell, unroll=unroll)
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)

    n_dev = mesh.devices.size
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    ext = None
    if extrapolate and not multi_pod:
        with use_mesh(mesh):
            ext = depth_extrapolate(cfg, cell)
        flops_r, bytes_r, coll_r = (ext["flops"], ext["bytes"],
                                    ext["coll_bytes_tpu"])
    else:
        flops_r, bytes_r = flops_dev, bytes_dev
        coll_r = float(colls["total_bytes_tpu"])
    terms = roofline_terms(flops_r, bytes_r, coll_r)
    mf = model_flops(cfg, cell)
    terms["model_flops_global"] = mf
    terms["hlo_flops_global"] = flops_r * n_dev
    terms["useful_fraction"] = (mf / (flops_r * n_dev)
                                if flops_r else float("inf"))
    # TPU-estimate memory term: analytic fused-traffic lower bound (the
    # CPU-HLO bytes are an unfused upper bound — see roofline/model.py)
    nm = (train_step_mod.pick_n_micro(cfg, cell.global_batch, cell.seq_len)
          if cell.kind == "train" else 1)
    mb = model_bytes_per_device(
        cfg, cell, tp=16, dp=n_dev // 16, n_micro=nm)
    terms["memory_s_tpu_est"] = mb / TPU_V5E.hbm_bw
    tpu_terms = {"compute_s": terms["compute_s"],
                 "memory_s": terms["memory_s_tpu_est"],
                 "collective_s": terms["collective_s"]}
    dom = max(tpu_terms, key=tpu_terms.get)
    terms["dominant_tpu"] = dom
    # MFU-style roofline fraction: useful model-FLOPs time / bounding time
    useful_time = mf / (n_dev * TPU_V5E.peak_flops)
    terms["roofline_fraction_tpu"] = (
        useful_time / tpu_terms[dom] if tpu_terms[dom] > 0 else 0.0)

    mem_rec = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            mem_rec[k] = int(v)

    rec.update(
        status="ok",
        n_devices=int(n_dev),
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        extrapolated=ext,
        collectives=colls,
        memory=mem_rec,
        roofline=terms,
        hlo_bytes=len(hlo),
    )
    if verbose:
        live = (mem_rec.get("argument_size_in_bytes", 0)
                + mem_rec.get("temp_size_in_bytes", 0)
                + mem_rec.get("output_size_in_bytes", 0)
                - mem_rec.get("alias_size_in_bytes", 0))
        print(f"[{mesh_name}] {arch} x {cell_name}: OK "
              f"compile={t_compile:.1f}s flops/dev={flops_dev:.3e} "
              f"bytes/dev={bytes_dev:.3e} "
              f"coll={colls['total_bytes']:.3e}B/{colls['total_count']}ops "
              f"live~{live/1e9:.2f}GB dominant={terms['dominant']}")
        print(f"  memory_analysis: {mem_rec}")

    out_dir.mkdir(parents=True, exist_ok=True)
    fname = out_dir / f"{mesh_name}__{arch}__{cell_name}.json"
    fname.write_text(json.dumps(rec, indent=1, default=float))
    return rec


def emit_devmodel(arch: str, out_dir: Path = ARTIFACTS,
                  prefill_cell: str = "prefill_32k",
                  decode_cell: str = "decode_32k") -> dict:
    """Calibrate the serving stack's emulated backend from dry-run cells.

    Reads the prefill + decode artifacts this driver already writes,
    derives the roofline-bound step seconds, and emits the DeviceModel
    coefficients that ``repro.backend.EmulatedBackend`` (and
    ``repro.launch.serve --devmodel``) consume — the dry-run compiler is
    thereby the calibration source for the execution backend, not a
    disconnected artifact.
    """
    import dataclasses as dc

    from repro.core.devmodel import DeviceModel

    def bound_s(cell_name: str) -> float:
        path = out_dir / f"pod_16x16__{arch}__{cell_name}.json"
        if not path.exists():
            raise SystemExit(
                f"missing {path}; run: python -m repro.launch.dryrun "
                f"--arch {arch} --cell {cell_name}")
        rec = json.loads(path.read_text())
        if rec.get("status") != "ok":
            raise SystemExit(f"{path} is status={rec.get('status')}")
        t = rec["roofline"]
        return max(t["compute_s"], t.get("memory_s_tpu_est", 0.0),
                   t["collective_s"])

    pre, dec = CELLS_BY_NAME[prefill_cell], CELLS_BY_NAME[decode_cell]
    dm = DeviceModel.from_roofline(
        bound_s(prefill_cell), pre.global_batch * pre.seq_len,
        bound_s(decode_cell), dec.global_batch)
    rec = {"arch": arch, "prefill_cell": prefill_cell,
           "decode_cell": decode_cell, "device_model": dc.asdict(dm)}
    out = out_dir / f"devmodel__{arch}.json"
    out.write_text(json.dumps(rec, indent=1))
    print(f"[dryrun] wrote {out}: {dm}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--unroll", action="store_true")
    ap.add_argument("--emit-devmodel", action="store_true",
                    help="emit the EmulatedBackend calibration from this "
                         "arch's prefill/decode artifacts and exit")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args()

    if args.emit_devmodel:
        if not args.arch:
            ap.error("--emit-devmodel requires --arch")
        emit_devmodel(args.arch, Path(args.out))
        return

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    cells = [args.cell] if args.cell else list(CELLS_BY_NAME)
    archs = [args.arch] if args.arch else sorted(ARCHS)
    if not (args.all or args.arch):
        ap.error("pass --arch/--cell or --all")

    failures = []
    for mp in meshes:
        for arch in archs:
            for cell in cells:
                try:
                    rec = run_cell(arch, cell, multi_pod=mp,
                                   unroll=args.unroll, out_dir=Path(args.out))
                    if rec["status"] == "skip":
                        print(f"[{'multipod' if mp else 'pod'}] {arch} x {cell}: "
                              f"SKIP ({rec['reason']})")
                except Exception as e:  # noqa: BLE001 — report all failures
                    traceback.print_exc()
                    failures.append((mp, arch, cell, repr(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall dry-run cells OK")


if __name__ == "__main__":
    main()
