#!/usr/bin/env python3
"""On-chip smoke test of the profiled main path, at published widths.

    python chip_smoke.py              # one TPU v5e chip
    python chip_smoke.py --chips 4    # one host of four chips

With one chip it runs, in one process and in order:

1. device   — JAX must see a TPU; anywhere else it exits non-zero at
              once and runs nothing;
2. kernels  — the three Pallas kernels compiled (never interpreted) at
              real widths, each against its pure-jnp oracle;
3. serving  — qwen2-1.5b (published config, random weights) served
              through ``launch.serve.serve`` bare and under a started
              ``ServingProfiler``: identical tokens, a clean flush, and
              one attribution row per batch out of the aggregated
              database;
4. training — a profiled xlstm-125m ``launch.train.train`` run with a
              checkpoint: finite losses and a written profile.

With ``--chips 4`` it runs only the sharded phase: profiled and bare
fsdp training of qwen2-1.5b on a mesh of every device, which must give
identical losses with the parameters spread evenly over the devices.

Every phase prints one line; the first failure exits non-zero.  The last
line of a passing run is one JSON object naming the device.  Profiles,
databases and checkpoints go to ``repro_chip_smoke/`` in the checkout.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# profiles, databases and checkpoints (gitignored, replaced every run)
OUT = os.path.join(REPO, "repro_chip_smoke")

# a bf16 result may differ from its fp32-accumulated oracle by a few
# units in the last place: max |kernel - oracle| / max(1, max |oracle|)
BF16_TOL = 2e-2

# the kernels at the widths the deployments use
KERNEL_WIDTHS = {
    # qwen2-1.5b prefill: 12 q heads, 2 kv heads, head_dim 128
    "flash_attention": dict(B=2, S=2048, H=12, Hkv=2, D=128),
    "flash_decode": dict(B=8, Smax=4096, H=12, Hkv=2, D=128, length=3001),
    # hymba-1.5b's mamba heads: 25 x 64, state 16, chunk 256
    "ssm_scan": dict(B=2, S=2048, nh=25, hd=64, st=16, chunk=256),
}


class SmokeFailure(Exception):
    pass


def report(phase: str, **found) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in found.items()),
          flush=True)


def expect(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# phases (each takes its sizes, so a CPU test can drive it small)
# ---------------------------------------------------------------------------
def _normalized_err(got, want) -> float:
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    return float(jnp.max(jnp.abs(got - want))) / scale


def check_kernels(widths=KERNEL_WIDTHS, seed: int = 0) -> dict:
    """Each kernel against its oracle.  Returns {name: (normalized max
    error, compiled HLO contains a tpu_custom_call)}."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.models.attention import decode_attention

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, scale=1.0, dtype=jnp.bfloat16):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    cases = {}
    w = widths["flash_attention"]
    q = normal((w["B"], w["S"], w["H"], w["D"]))
    k = normal((w["B"], w["S"], w["Hkv"], w["D"]))
    v = normal((w["B"], w["S"], w["Hkv"], w["D"]))
    cases["flash_attention"] = (
        jax.jit(functools.partial(ops.flash_attention, causal=True)),
        (q, k, v), jax.jit(ref.attention_ref))

    w = widths["flash_decode"]
    q = normal((w["B"], w["H"], w["D"]))
    kc = normal((w["B"], w["Smax"], w["Hkv"], w["D"]))
    vc = normal((w["B"], w["Smax"], w["Hkv"], w["D"]))
    length = jnp.int32(w["length"])
    cases["flash_decode"] = (jax.jit(ops.flash_decode),
                             (q, kc, vc, length), jax.jit(decode_attention))

    w = widths["ssm_scan"]
    B, S, nh, hd, st = w["B"], w["S"], w["nh"], w["hd"], w["st"]
    xv = normal((B, S, nh, hd), 0.5)
    # dt * A < 0: per-step decays between exp(-0.2) and exp(-0.01)
    logdecay = -jax.random.uniform(next(keys), (B, S, nh), jnp.float32,
                                   0.01, 0.2)
    Bm = normal((B, S, st), 0.25)
    Cm = normal((B, S, st), 0.25)
    cases["ssm_scan"] = (
        jax.jit(lambda *a: ops.ssm_scan(*a, chunk=w["chunk"])),
        (xv, logdecay, Bm, Cm, None), jax.jit(ref.ssm_scan_ref))

    found = {}
    for name, (fn, args, oracle) in cases.items():
        got, want = fn(*args), oracle(*args)
        err = max(_normalized_err(g, o) for g, o in
                  zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        hlo = fn.lower(*args).compile().as_text()
        found[name] = (err, "tpu_custom_call" in hlo)
    return found


def check_serving(cfg, out_dir: str, *, n_requests: int, batch: int,
                  prompt_len: int, gen_len: int) -> dict:
    """Serve bare, then under a started ServingProfiler; aggregate the
    profiled run and attribute device time per request batch."""
    import numpy as np
    from repro.core.aggregate import aggregate
    from repro.launch.serve import serve
    from repro.serving import ServingProfiler
    from repro.traceview.stats import request_attribution
    from repro.traceview.tracedb import TraceDB

    sizes = dict(n_requests=n_requests, batch=batch, prompt_len=prompt_len,
                 gen_len=gen_len)
    bare, _ = serve(cfg, **sizes)
    bare = np.asarray(bare)
    sp = ServingProfiler(os.path.join(out_dir, "measure")).start()
    profiled, _ = serve(cfg, serving=sp, **sizes)
    profiled = np.asarray(profiled)
    flushed = sp.profiler.flush()
    paths = sp.write()
    sp.stop()
    profs = [p for k, p in sorted(paths.items()) if "trace" not in k]
    traces = [p for k, p in sorted(paths.items()) if "trace" in k]
    db = aggregate(profs, os.path.join(out_dir, "db"), trace_paths=traces)
    rows = request_attribution(TraceDB(db.trace_db_path()).line_views(), db)
    return {"tokens": profiled.shape,
            "identical": bool(np.array_equal(bare, profiled)),
            "flushed": flushed, "batches": -(-n_requests // batch),
            "rows": [(r, by.get("prefill", 0.0), by.get("decode", 0.0))
                     for r, _, by in rows]}


def check_training(cfg, out_dir: str, *, seq: int, batch: int,
                   steps: int) -> dict:
    """A profiled training run that saves a checkpoint at its end."""
    import numpy as np
    from repro.checkpoint import CheckpointManager
    from repro.configs.base import ShapeConfig
    from repro.launch.train import seq_options, train

    ckpt_dir = os.path.join(out_dir, "ckpt")
    _, history, paths = train(
        cfg, ShapeConfig("smoke", seq, batch, "train"), n_steps=steps,
        ckpt_dir=ckpt_dir, ckpt_every=steps,
        profile_dir=os.path.join(out_dir, "measure"), opts=seq_options(seq),
        log_every=1)
    losses = [h["loss"] for h in history]
    return {"losses": losses,
            "finite": bool(np.all(np.isfinite(losses))),
            "checkpoint_step": CheckpointManager(ckpt_dir).latest_step(),
            "profile_files": len(paths),
            "profile_written": bool(paths) and all(
                os.path.getsize(p) > 0 for p in paths.values())}


def check_sharded_training(cfg, out_dir: str, *, seq: int,
                           global_batch: int, steps: int) -> dict:
    """fsdp training on a mesh of every device, profiled and bare."""
    import jax
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.train import seq_options, train

    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    shape = ShapeConfig("smoke-fsdp", seq, global_batch, "train")

    def run(profile_dir):
        params, history, paths = train(
            cfg, shape, n_steps=steps, mesh=mesh, strategy="fsdp",
            profile_dir=profile_dir, opts=seq_options(seq), log_every=1)
        per_device = {d.id: 0 for d in mesh.devices.flat}
        total = 0
        for leaf in jax.tree.leaves(params):
            total += leaf.nbytes
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] += shard.data.nbytes
        return [h["loss"] for h in history], per_device, total, paths

    profiled, per_device, total, paths = run(os.path.join(out_dir,
                                                          "measure"))
    bare, _, _, _ = run(None)
    return {"losses": profiled, "bare_losses": bare,
            "identical": profiled == bare, "param_bytes": total,
            "per_device": per_device, "profile_files": len(paths)}


# ---------------------------------------------------------------------------
# the one-chip and four-chip runs
# ---------------------------------------------------------------------------
def _peak_bytes(device) -> object:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def run_one_chip(dev) -> None:
    from repro.configs import get_config

    t = time.monotonic()
    found = check_kernels()
    report("kernels", **{name: f"max_err:{err:.3e},tpu_custom_call:{cc}"
                         for name, (err, cc) in found.items()},
           peak_bytes_in_use=_peak_bytes(dev),
           wall_s=f"{time.monotonic() - t:.1f}")
    for name, (err, custom_call) in found.items():
        expect(custom_call, f"{name}: no tpu_custom_call in compiled HLO")
        expect(err <= BF16_TOL, f"{name}: error {err} > {BF16_TOL}")

    t = time.monotonic()
    s = check_serving(get_config("qwen2-1.5b"), os.path.join(OUT, "serve"),
                      n_requests=8, batch=4, prompt_len=512, gen_len=32)
    report("serving", arch="qwen2-1.5b", tokens=s["tokens"],
           identical=s["identical"], flushed=s["flushed"],
           attribution=s["rows"], peak_bytes_in_use=_peak_bytes(dev),
           wall_s=f"{time.monotonic() - t:.1f}")
    expect(s["identical"], "profiled tokens differ from the bare run")
    expect(s["flushed"] is True, "ServingProfiler flush failed")
    expect(len(s["rows"]) == s["batches"],
           f"{len(s['rows'])} attribution rows for {s['batches']} batches")
    expect(all(p > 0 and d > 0 for _, p, d in s["rows"]),
           "a batch without prefill or decode device time")

    t = time.monotonic()
    tr = check_training(get_config("xlstm-125m"), os.path.join(OUT, "train"),
                        seq=256, batch=4, steps=3)
    report("training", arch="xlstm-125m", losses=tr["losses"],
           checkpoint_step=tr["checkpoint_step"],
           profile_files=tr["profile_files"],
           peak_bytes_in_use=_peak_bytes(dev),
           wall_s=f"{time.monotonic() - t:.1f}")
    expect(tr["finite"], "non-finite training loss")
    expect(tr["checkpoint_step"] == 3, "no checkpoint of the last step")
    expect(tr["profile_written"], "training profile not written")


def run_four_chips(devices) -> None:
    from repro.configs import get_config

    t = time.monotonic()
    r = check_sharded_training(get_config("qwen2-1.5b"),
                               os.path.join(OUT, "fsdp"), seq=1024,
                               global_batch=8, steps=3)
    shares = {i: b / r["param_bytes"] for i, b in r["per_device"].items()}
    report("sharded", arch="qwen2-1.5b", strategy="fsdp",
           devices=len(devices), losses=r["losses"],
           bare_losses=r["bare_losses"], identical=r["identical"],
           param_bytes=r["param_bytes"],
           per_device_bytes=r["per_device"],
           peak_bytes_in_use=[_peak_bytes(d) for d in devices],
           wall_s=f"{time.monotonic() - t:.1f}")
    expect(r["identical"], "profiled losses differ from the bare run")
    expect(len(shares) == len(devices)
           and all(0.2 < f < 0.3 for f in shares.values()),
           f"parameters not spread over the devices: {shares}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev.platform!r}); no phase was run", file=sys.stderr)
        return 1
    report("device", platform=dev.platform, kind=repr(dev.device_kind),
           count=len(devices))
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    report("compile_cache", dir=enable_compile_cache())
    shutil.rmtree(OUT, ignore_errors=True)
    try:
        if args.chips == 4:
            run_four_chips(devices)
        else:
            run_one_chip(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
