import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init) — MULTI-POD DRY-RUN §0.

"""Multi-pod dry-run (deliverable (e)).

For every (architecture x input shape x mesh) cell:
  lower the step function with sharded ShapeDtypeStruct inputs,
  .compile() it, record memory_analysis() (proves it fits) and
  cost_analysis() (FLOPs/bytes for §Roofline), parse the partitioned HLO
  for collective bytes, and emit the roofline record.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
      --shape train_4k --mesh single --out dryrun_results
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import gzip
import json
import time
import traceback

import jax

from repro.configs import SHAPES, get_config, list_configs, shape_applicable
from repro.core import roofline as roof_mod
from repro.core.peaks import peaks_for
from repro.core.structure import parse_hlo
from repro.distributed import sharding as shard_mod
from repro.launch import mesh as mesh_mod
from repro.launch import specs as specs_mod
from repro.launch import steps as steps_mod
from repro.models.transformer import ModelOptions
from repro.optim.adamw import OptConfig

HBM_PER_CHIP = 16 * 1024 ** 3   # v5e: 16 GiB
# the chip the dry run sizes its meshes for (compiled on host devices)
TARGET = peaks_for("TPU v5 lite")


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             *, strategy: str = "tp", attn_schedule: str = "dense",
             kv_seq_axis: str = None, remat_policy: str = "dots_no_batch",
             moe_mode: str = "gather", loss_chunk: int = 512,
             n_microbatches: int = 1, ssm_chunk: int = 256,
             slstm_block: int = 16,
             save_hlo: bool = True, tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_desc = "pod2x16x16" if multi_pod else "pod16x16"
    label = f"{arch}_{shape_name}_{mesh_desc}" + (f"_{tag}" if tag else "")
    os.makedirs(out_dir, exist_ok=True)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_desc,
           "strategy": strategy, "tag": tag, "status": "pending"}
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        _write(out_dir, label, rec)
        return rec

    t0 = time.monotonic()
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    plan = shard_mod.make_plan(mesh, multi_pod=multi_pod, strategy=strategy,
                               moe_weight_mode=moe_mode)
    opts = ModelOptions(attn_schedule=attn_schedule,
                        remat_policy=remat_policy, loss_chunk=loss_chunk,
                        ssm_chunk=ssm_chunk, slstm_block=slstm_block)
    specs = specs_mod.input_specs(cfg, shape, plan, kv_seq_axis=kv_seq_axis)

    if shape.kind == "train":
        fn = steps_mod.make_train_step(cfg, plan, opts, OptConfig(),
                                       n_microbatches=n_microbatches)
        args = (specs["params"], specs["opt_state"], specs["batch"])
        donate = (0, 1)
    elif shape.kind == "prefill":
        fn = steps_mod.make_prefill_step(cfg, plan, opts)
        args = (specs["params"], specs["batch"])
        donate = ()
    else:
        fn = steps_mod.make_decode_step(cfg, plan, opts)
        kw = {}
        if "token" in specs:
            kw["token"] = specs["token"]
        if "embed" in specs:
            kw["embed"] = specs["embed"]
        fn = _bind_decode(fn, kw)
        args = (specs["params"], specs["cache"], specs["pos"]) + tuple(
            kw[k] for k in sorted(kw))
        donate = (1,)

    try:
        with mesh:
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.monotonic() - t0
            compiled = lowered.compile()
            t_compile = time.monotonic() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        print(f"[{label}] memory_analysis:", mem)
        print(f"[{label}] cost_analysis: flops={cost.get('flops', 0):.4g}"
              f" bytes={cost.get('bytes accessed', 0):.4g}")
        hlo_text = compiled.as_text()
        module = parse_hlo(hlo_text, name=label)
        report = roof_mod.analyze(
            label, mesh_desc, chips, cost, TARGET, module=module,
            model_flops_total=roof_mod.model_flops(cfg, shape))
        per_dev = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        rec.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_per_device": per_dev,
                "fits_hbm": bool(per_dev < HBM_PER_CHIP),
            },
            cost={k: float(v) for k, v in cost.items()
                  if isinstance(v, (int, float))},
            roofline=report.row(),
            params=cfg.n_params(),
            active_params=cfg.n_active_params(),
        )
        if save_hlo:
            hpath = os.path.join(out_dir, f"{label}.hlo.gz")
            with gzip.open(hpath, "wt") as f:
                f.write(hlo_text)
            rec["hlo"] = hpath
    except Exception as e:  # a failure here is a bug in the system
        rec.update(status="error", error=str(e)[-2000:],
                   trace=traceback.format_exc()[-4000:])
    _write(out_dir, label, rec)
    return rec


def _bind_decode(fn, kw):
    names = sorted(kw)

    def bound(params, cache, pos, *rest):
        kwargs = dict(zip(names, rest))
        return fn(params, cache, pos, **kwargs)
    return bound


def _write(out_dir: str, label: str, rec: dict):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{label}.json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results")
    ap.add_argument("--strategy", default="tp")
    ap.add_argument("--attn-schedule", default="dense")
    ap.add_argument("--kv-seq-axis", default=None)
    ap.add_argument("--remat-policy", default="dots_no_batch")
    ap.add_argument("--moe-mode", default="gather",
                    choices=("gather", "stationary"))
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ssm-chunk", type=int, default=256)
    ap.add_argument("--slstm-block", type=int, default=16)
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip cells whose record file already exists")
    args = ap.parse_args()

    archs = list_configs() if (args.all or args.arch is None) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_desc = "pod2x16x16" if mp else "pod16x16"
                label = f"{arch}_{shape}_{mesh_desc}" + (
                    f"_{args.tag}" if args.tag else "")
                path = os.path.join(args.out, f"{label}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            print(f"{label}: exists, skipping", flush=True)
                            continue
                rec = run_cell(arch, shape, mp, args.out,
                               strategy=args.strategy,
                               attn_schedule=args.attn_schedule,
                               kv_seq_axis=args.kv_seq_axis,
                               remat_policy=args.remat_policy,
                               moe_mode=args.moe_mode,
                               loss_chunk=args.loss_chunk,
                               n_microbatches=args.microbatch,
                               ssm_chunk=args.ssm_chunk,
                               slstm_block=args.slstm_block,
                               save_hlo=not args.no_hlo, tag=args.tag)
                status = rec["status"]
                extra = rec.get("reason", rec.get("error", ""))[:120]
                print(f"{arch} x {shape} x "
                      f"{'multi' if mp else 'single'}: {status} {extra}",
                      flush=True)
                failures += status == "error"
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
