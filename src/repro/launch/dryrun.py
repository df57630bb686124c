import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax-touching import: jax locks device count on init.

"""Multi-pod dry-run: lower + AOT-compile every (arch x shape) on the
production meshes, proving the distribution config is coherent.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape decode_32k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod both \
      --out results/dryrun.jsonl

Each combo prints/records: compile ok, memory_analysis (per-device bytes),
cost_analysis (FLOPs/bytes), collective bytes parsed from the compiled HLO,
and the three roofline terms (single-pod mesh is the roofline baseline).
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.config import SHAPES, shape_for
from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch import roofline as rl
from repro.launch.specs import input_specs, entry_fn
from repro.distributed.sharding import ShardingRules
from repro.models.transformer import n_fragment_units


def loop_trips_for(cfg, shape) -> int:
    """Layer-scan trip count (see roofline.py for how it is applied)."""
    L = cfg.n_layers
    if cfg.family == "audio":
        L = cfg.n_layers + cfg.audio.n_encoder_layers
    return max(L, 1)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               fsdp: bool = True, policy: str = "baseline",
               verbose: bool = True, save_hlo: str = "") -> dict:
    t0 = time.perf_counter()
    shape = SHAPES[shape_name]
    cfg = shape_for(get_config(arch), shape)
    import dataclasses
    kv_dt = ""
    if policy == "opt" and shape.kind == "decode" and cfg.family != "ssm":
        kv_dt = "int8"                     # beyond-paper: quantized KV cache
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dt)
    if policy == "opt" and cfg.moe and shape.kind != "decode":
        cfg = dataclasses.replace(cfg, moe_impl="expert_parallel")
    specs = input_specs(arch, shape_name, kv_cache_dtype=kv_dt)
    mesh = make_production_mesh(multi_pod=multi_pod)
    if policy == "opt":
        # §Perf policy: context-parallel KV caches when KV heads don't
        # divide the model axis; tensor parallelism off for tiny models
        # (d_model << 16 * MXU tile) in favour of sequence sharding.
        small = cfg.d_model < 1024
        rules = ShardingRules(mesh, fsdp=fsdp, tp=not small,
                              kv_seq_shard=True,
                              seq_shard_activations=small)
    else:
        rules = ShardingRules(mesh, fsdp=fsdp)
    # opt policy: gradient accumulation for the biggest models (the dots
    # remat policy is only adopted where activation headroom exists)
    mb = 1
    remat_policy = True
    if policy == "opt" and shape.kind == "train":
        n = cfg.n_params()
        mb = 16 if n > 50e9 else (8 if n > 20e9 else 1)
        # per-microbatch batch must stay shardable over the data axes, or
        # GSPMD replicates activations and every chip computes the full
        # microbatch (measured: mb=32 at B=256 on data=16 -> 5x compute)
        data_chips = mesh.devices.size // mesh.shape["model"]
        while mb > 1 and (shape.global_batch // mb) % data_chips:
            mb //= 2
        remat_policy = True if n > 20e9 else "dots"
    fn = entry_fn(cfg, shape, train_remat=remat_policy,
                  ce_impl="gather" if policy == "legacy" else "onehot",
                  microbatches=mb)

    p_sh = rules.params_shardings(specs["params"])
    args = [specs["params"]]
    in_sh = [p_sh]
    if shape.kind == "train":
        args += [specs["opt_state"], specs["batch"]]
        in_sh += [rules.opt_shardings(specs["opt_state"], specs["params"]),
                  rules.batch_shardings(specs["batch"])]
    elif shape.kind == "prefill":
        args.append(specs["tokens"])
        in_sh.append(rules.batch_shardings(specs["tokens"]))
    else:
        args += [specs["cache"], specs["tokens"]]
        in_sh += [rules.cache_shardings(specs["cache"]),
                  rules.batch_shardings(specs["tokens"])]
    if specs["extras"] is not None and shape.kind != "decode":
        args.append(specs["extras"])
        in_sh.append(rules.batch_shardings(specs["extras"]))

    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "chips": int(mesh.devices.size), "fsdp": fsdp,
           "policy": policy}
    try:
        # anchor the residual stream's batch dim (see distributed/actspec.py)
        from jax.sharding import PartitionSpec as P
        from repro.distributed.actspec import residual_spec
        UNC = P.UNCONSTRAINED
        bax = rules.batch_dim_axes(shape.global_batch)
        act_spec = P(bax, UNC, UNC) if bax and policy != "legacy" else None
        from repro.distributed.actspec import moe_mesh as moe_mesh_ctx
        with mesh, residual_spec(act_spec), moe_mesh_ctx(mesh):
            jitted = jax.jit(fn, in_shardings=tuple(in_sh))
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)
        L = loop_trips_for(cfg, shape)
        trips = [mb, L] if mb > 1 else [L]
        stats = rl.parse_hlo(hlo, loop_trips=trips)
        mf = rl.model_flops(cfg, shape)
        # three FLOPs sources: cost_analysis (counts while bodies ONCE),
        # trip-corrected per-device dot parsing (x chips = global), and the
        # analytic model. The parsed number is primary; the analytic model
        # backstops parse failures.
        hlo_flops = float(cost.get("flops", 0.0))
        parsed_global = stats.dot_flops * rec["chips"]
        flops = parsed_global if parsed_global > 0.1 * mf else mf
        hbm = max(float(cost.get("bytes accessed", 0.0)),
                  rl.hbm_bytes_estimate(cfg, shape))
        roof = rl.Roofline(chips=rec["chips"], flops=flops, hbm_bytes=hbm,
                           collective_bytes=stats.collective_bytes,
                           model_flops_=mf)
        rec.update({
            "ok": True,
            "compile_s": round(time.perf_counter() - t0, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes",
                                      getattr(mem, "temp_size_in_bytes", 0)),
            },
            "cost_analysis": {"flops": hlo_flops,
                              "bytes_accessed": float(
                                  cost.get("bytes accessed", 0.0)),
                              "parsed_dot_flops_per_dev": stats.dot_flops,
                              "n_dots": stats.n_dots},
            "collectives": {"bytes": stats.collective_bytes,
                            "per_op": stats.per_op,
                            "count": stats.n_collectives,
                            "n_while": stats.n_while,
                            "loop_trips": list(trips)},
            "roofline": roof.to_dict(),
        })
        if verbose:
            m = rec["memory"]
            print(f"[ok] {arch:24s} {shape_name:12s} {rec['mesh']:8s} "
                  f"compile={rec['compile_s']:6.1f}s "
                  f"args/dev={m['argument_bytes']/2**30:7.2f}GiB "
                  f"temp/dev={m['temp_bytes']/2**30:7.2f}GiB "
                  f"coll={stats.collective_bytes/2**30:8.2f}GiB "
                  f"dom={roof.dominant}")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "compile_s": round(time.perf_counter() - t0, 1)})
        if verbose:
            print(f"[FAIL] {arch} {shape_name} {rec['mesh']}: "
                  f"{rec['error']}")
            traceback.print_exc(limit=3)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--multi-pod", default="single",
                    choices=("single", "multi", "both"))
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--policy", default="baseline",
                    choices=("legacy", "baseline", "opt"))
    ap.add_argument("--out", default="")
    ap.add_argument("--save-hlo", default="")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True],
            "both": [False, True]}[args.multi_pod]

    recs, n_fail = [], 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                rec = dryrun_one(arch, shape, multi_pod=mp,
                                 fsdp=not args.no_fsdp, policy=args.policy,
                                 save_hlo=args.save_hlo)
                recs.append(rec)
                n_fail += 0 if rec["ok"] else 1
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    print(f"\n{len(recs) - n_fail}/{len(recs)} combos compiled")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
