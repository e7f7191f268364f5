import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST precede every other import (jax locks the
# device count on first init); `from __future__` is therefore omitted.
"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, WITHOUT allocating any real arrays
(ShapeDtypeStruct stand-ins).

For every cell it records:
  * memory_analysis()  — per-device argument/temp bytes (proves it fits)
  * cost_analysis()    — per-device HLO FLOPs / bytes (roofline §g)
  * collective bytes   — parsed from the compiled HLO text, with
    while-loop (lax.scan over layers) trip-count multiplication
  * the three roofline terms vs TPU v5e peaks, and the dominant one

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out experiments/dryrun
"""
import argparse
import dataclasses
import json
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, SHAPES, applicable_shapes, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.cost_model import get_hardware
from repro.distributed.sharding import (batch_pspecs, named,
                                        out_pspecs_decode, param_pspecs)
from repro.launch.hlo_analysis import (collective_bytes,
                                       convert_traffic_bytes,
                                       duplicate_op_fraction)
from repro.launch.mesh import make_production_mesh
from repro.serving.serve_step import (build_decode_fn, build_prefill_fn,
                                      cache_specs, param_specs,
                                      serve_input_specs)
from repro.training.optimizer import AdamWConfig, init_adamw
from repro.training.train_step import make_train_step

HW = get_hardware("tpu_v5e")


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode D = batch
    tokens; train counts fwd+bwd (6ND), inference counts fwd (2ND)."""
    n = cfg.active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * 1 * shape.global_batch  # decode: one token per request


def analytic_memory(cfg: ModelConfig, shape: ShapeConfig, chips: int, *,
                    fsdp: bool, microbatches: int = 1) -> Dict[str, float]:
    """TPU-side HBM estimate per chip (the CPU backend's temp analysis
    reflects host scheduling, not TPU buffer assignment).

    train: bf16 params + fp32 (master, mu, nu) + fp32 grads, all sharded
    over the whole mesh when fsdp else over model only; activations with
    remat ~= residual stream for all layers + one layer's working set.
    serve: bf16 params over model axis + the KV cache over the mesh.
    """
    n = cfg.num_params()
    model_par = 16
    mesh_par = chips if fsdp else model_par
    if shape.kind == "train":
        weights = 2 * n / mesh_par                # bf16
        opt = 3 * 4 * n / mesh_par                # fp32 master+mu+nu
        grads = 4 * n / mesh_par
        B_loc = shape.global_batch / (chips / model_par) / microbatches
        d_wide = max(cfg.d_ff, cfg.q_dim + 2 * cfg.kv_dim,
                     2 * cfg.d_inner if cfg.ssm_state else 0)
        acts = B_loc * shape.seq_len * (
            cfg.num_layers * cfg.d_model * 2            # bf16 stream
            + 6 * d_wide / model_par * 2)               # one layer, TP
        logits = B_loc * shape.seq_len * cfg.padded_vocab / model_par * 4
        total = weights + opt + grads + acts + logits
        return {"weights": weights, "opt": opt + grads, "acts": acts,
                "logits": logits, "total": total}
    weights = 2 * n / model_par
    B, S = shape.global_batch, shape.seq_len
    eff = min(S, cfg.window) if cfg.window else S
    kv = (cfg.num_layers * B * eff * 2 * cfg.kv_dim * 2) / chips
    if cfg.family == "ssm":
        kv = cfg.num_layers * B * (cfg.ssm_heads * cfg.ssm_state ** 2 * 4
                                   + 2 * cfg.d_model * 2) / chips
    acts = (B * S * cfg.d_model * 2 / (chips / model_par)
            if shape.kind == "prefill" else B * cfg.d_model * 2)
    total = weights + kv + acts
    return {"weights": weights, "kv": kv, "acts": acts, "total": total}


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
               impl: str = "reference", moe_impl: str = "sparse",
               remat: bool = True, seq_shard: bool = True,
               fsdp: bool = True, microbatches: int = 1,
               unroll: bool = False, append: str = "inline"):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    pshape = param_specs(cfg)
    if shape.kind == "train":
        ps = param_pspecs(cfg, pshape, fsdp=fsdp)
        from jax.sharding import PartitionSpec as P
        oshape = jax.eval_shape(init_adamw, pshape)
        # opt-state specs: step replicated; master/mu/nu follow params
        ospec = type(oshape)(step=P(), master=ps, mu=ps, nu=ps)
        bspec = batch_pspecs(cfg, shape, mesh)
        ins = serve_input_specs(cfg, shape)
        opt_cfg = AdamWConfig(total_steps=1000)
        step = make_train_step(cfg, opt_cfg, microbatches=microbatches,
                               impl=impl, moe_impl=moe_impl, remat=remat,
                               unroll=unroll)
        fn = jax.jit(step,
                     in_shardings=(named(mesh, ps), named(mesh, ospec),
                                   named(mesh, bspec)),
                     out_shardings=(named(mesh, ps), named(mesh, ospec),
                                    None),
                     donate_argnums=(0, 1))
        return fn, (pshape, oshape, ins)
    if shape.kind == "prefill":
        ps = param_pspecs(cfg, pshape, fsdp=False)
        bspec = batch_pspecs(cfg, shape, mesh)
        ins = serve_input_specs(cfg, shape)
        prefill = build_prefill_fn(cfg, cache_len=shape.seq_len, impl=impl,
                                   moe_impl=moe_impl, unroll=unroll)
        from jax.sharding import PartitionSpec as P
        dshape = dataclasses.replace(shape, kind="decode")
        cache_spec = batch_pspecs(cfg, dshape, mesh,
                                  seq_shard=seq_shard)["cache"]
        dp = [a for a in mesh.axis_names if a in ("pod", "data")]
        out_spec = (P(tuple(dp), "model"), cache_spec)
        fn = jax.jit(prefill,
                     in_shardings=(named(mesh, ps), named(mesh, bspec)),
                     out_shardings=named(mesh, out_spec))
        return fn, (pshape, ins)
    # decode
    ps = param_pspecs(cfg, pshape, fsdp=False)
    bspec = batch_pspecs(cfg, shape, mesh, seq_shard=seq_shard)
    ins = serve_input_specs(cfg, shape)
    decode = build_decode_fn(cfg, impl=impl, moe_impl=moe_impl,
                             unroll=unroll, append=append)
    out_spec = out_pspecs_decode(cfg, shape, mesh, seq_shard=seq_shard)
    fn = jax.jit(decode,
                 in_shardings=(named(mesh, ps), named(mesh, bspec["tokens"]),
                               named(mesh, bspec["cache"])),
                 out_shardings=named(mesh, out_spec),
                 donate_argnums=(2,))
    return fn, (pshape, ins["tokens"], ins["cache"])


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                impl: str = "reference", moe_impl: str = "sparse",
                remat: bool = True, seq_shard: bool = True,
                fsdp: bool = True, microbatches: int = 1,
                unroll: bool = False, append: str = "inline",
                verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    if shape.kind == "train" and microbatches == 0:  # auto: fit HBM
        microbatches = 1
        while (analytic_memory(cfg, shape, chips, fsdp=fsdp,
                               microbatches=microbatches)["total"]
               > 0.9 * HW.hbm_cap and microbatches < 32):
            microbatches *= 2
    microbatches = max(1, microbatches)
    from repro.distributed.context import set_mesh
    set_mesh(mesh)
    t0 = time.time()
    with mesh:
        fn, args = build_cell(cfg, shape, mesh, impl=impl, moe_impl=moe_impl,
                              remat=remat, seq_shard=seq_shard, fsdp=fsdp,
                              microbatches=microbatches, unroll=unroll,
                              append=append)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    colls = collective_bytes(hlo, num_devices=chips)
    cvt_bytes = convert_traffic_bytes(hlo)

    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    if shape.kind == "train" and microbatches > 1:
        # the microbatch grad-accumulation lax.scan is a while loop whose
        # body cost_analysis counts ONCE (the layer stack inside is
        # unrolled under --unroll, but the mb loop is not): correct by
        # the trip count.  (The collective parser already multiplies.)
        flops_dev *= microbatches
        bytes_dev *= microbatches
    compute_s = flops_dev / HW.flops
    memory_s = bytes_dev / HW.hbm_bw
    # TPU-target correction: the CPU backend materializes f32 copies of
    # every bf16 dot operand (convert ops); the TPU MXU reads bf16
    # natively, so those bytes do not exist on the target hardware.
    bytes_tpu = max(bytes_dev - cvt_bytes, 0.2 * bytes_dev)
    memory_s_tpu = bytes_tpu / HW.hbm_bw
    collective_s = colls.link_bytes / HW.link_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s_tpu,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    terms["memory_s_cpu_raw"] = memory_s
    mf = model_flops(cfg, shape)
    useful = mf / (flops_dev * chips) if flops_dev else 0.0

    report = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "impl": impl, "moe_impl": moe_impl, "remat": remat,
        "seq_shard": seq_shard, "fsdp": fsdp, "microbatches": microbatches,
        "unroll": unroll, "append": append,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "per_device": {
            "flops": flops_dev, "bytes": bytes_dev,
            "bytes_tpu": bytes_tpu, "convert_bytes": cvt_bytes,
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
        },
        "collectives": {
            "bytes_by_kind": colls.bytes_by_kind,
            "count_by_kind": colls.count_by_kind,
            "link_bytes_per_device": colls.link_bytes,
        },
        "roofline": {
            **{k: float(v) for k, v in terms.items()},
            "dominant": dominant,
            "model_flops": mf,
            "useful_flops_fraction": useful,
            "dup_dot_fraction": duplicate_op_fraction(hlo),
        },
    }
    amem = analytic_memory(cfg, shape, chips, fsdp=fsdp,
                           microbatches=microbatches)
    report["analytic_memory_per_chip"] = amem
    # args from the real compile; working set from the analytic model
    # (CPU-backend temp analysis reflects host scheduling, not TPU HBM)
    report["fits_hbm"] = bool(amem["total"] <= HW.hbm_cap)
    if verbose:
        arg_gb = (report["per_device"]["argument_bytes"] or 0) / 1e9
        tmp_gb = amem["total"] / 1e9
        print(f"[dryrun] {arch:20s} {shape_name:12s} {report['mesh']:8s} "
              f"args={arg_gb:6.2f}GB hbm~{tmp_gb:6.2f}GB "
              f"C={compute_s*1e3:9.3f}ms M={memory_s_tpu*1e3:9.3f}ms "
              f"X={collective_s*1e3:9.3f}ms dom={dominant:12s} "
              f"useful={useful:5.1%} (lower {t_lower:.0f}s "
              f"compile {t_compile:.0f}s)", flush=True)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--impl", default="reference")
    ap.add_argument("--moe-impl", default="sparse")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="grad-accumulation microbatches (0 = auto-fit HBM)")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer scan for exact cost analysis")
    ap.add_argument("--decode-append", default="inline",
                    choices=["inline", "deferred"])
    ap.add_argument("--out", default=None, help="JSON output directory")
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for sh in applicable_shapes(get_config(arch)):
                cells.append((arch, sh))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for arch, sh in cells:
        for mp in meshes:
            try:
                rep = dryrun_cell(
                    arch, sh, multi_pod=mp, impl=args.impl,
                    moe_impl=args.moe_impl, remat=not args.no_remat,
                    seq_shard=not args.no_seq_shard, fsdp=not args.no_fsdp,
                    microbatches=args.microbatches, unroll=args.unroll,
                    append=args.decode_append)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    tag = "mp" if mp else "sp"
                    path = os.path.join(args.out, f"{arch}_{sh}_{tag}.json")
                    with open(path, "w") as f:
                        json.dump(rep, f, indent=1)
            except Exception as e:  # noqa: BLE001 - report-all mode
                failures.append((arch, sh, mp, repr(e)))
                print(f"[dryrun] FAIL {arch} {sh} mp={mp}: {e!r}",
                      flush=True)
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES")
        return 1
    print("[dryrun] all cells compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
