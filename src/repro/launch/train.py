"""Production training driver.

Wires every substrate together: config -> mesh/sharding plan -> data
pipeline -> jitted train step -> checkpoint manager (atomic/async) ->
straggler watchdog -> and, when ``--profile``, the paper's measurement
stack around every dispatch (heterogeneous CCTs, wait-free channels, PC
sample analogue, sparse profiles).

CPU-runnable end to end (examples/quickstart.py calls main() with a
reduced config); on a real TPU fleet the same file is the per-host entry
point — the mesh argument switches to the production mesh and
jax.distributed.initialize() is the only addition.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.distributed import sharding as shard_mod
from repro.ft import RestartPolicy, StragglerWatchdog
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.optim import adamw


def train(cfg: ModelConfig, shape: ShapeConfig, *, n_steps: int = 20,
          mesh=None, strategy: str = "tp", ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, profile_dir: Optional[str] = None,
          opts: Optional[T.ModelOptions] = None,
          opt_cfg: Optional[adamw.OptConfig] = None,
          grad_compression: bool = False, seed: int = 0,
          resume: bool = False, log_every: int = 10,
          host_id: int = 0, watchdog: Optional[StragglerWatchdog] = None):
    """Returns (final params, metrics history, profile paths or None)."""
    opts = opts or T.ModelOptions()
    opt_cfg = opt_cfg or adamw.OptConfig(total_steps=max(n_steps, 2))
    plan = shard_mod.make_plan(mesh, strategy=strategy)
    watchdog = watchdog or StragglerWatchdog()

    # ---- init or resume --------------------------------------------------
    key = jax.random.PRNGKey(seed)
    if mesh is not None:
        p_struct = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
        p_sh = shard_mod.param_shardings(p_struct, cfg, plan)
        with mesh:
            params = jax.jit(lambda k: T.init_params(k, cfg),
                             out_shardings=p_sh)(key)
            opt_state = jax.jit(adamw.init,
                                out_shardings=shard_mod.opt_shardings(
                                    jax.eval_shape(adamw.init, p_struct),
                                    p_sh))(params)
    else:
        params = T.init_params(key, cfg)
        opt_state = adamw.init(params)

    start_step = 0
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if mgr and resume and mgr.latest_step() is not None:
        p_sh = (shard_mod.param_shardings(params, cfg, plan)
                if mesh is not None else None)
        o_sh = (shard_mod.opt_shardings(jax.eval_shape(lambda x: x,
                                                       opt_state), p_sh)
                if mesh is not None else None)
        start_step, state = mgr.restore(
            {"params": params, "opt": opt_state},
            shardings={"params": p_sh, "opt": o_sh} if mesh is not None
            else None)
        params, opt_state = state["params"], state["opt"]

    # ---- data -------------------------------------------------------------
    ds = SyntheticLM(cfg, shape, seed=seed, host_id=host_id)
    prefetch = Prefetcher(ds, start_step=start_step)

    step_fn = steps_mod.make_train_step(cfg, plan if mesh is not None
                                        else None, opts, opt_cfg,
                                        grad_compression=grad_compression)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    # ---- optional measurement (the paper's tool) ---------------------------
    prof = None
    mid = None
    if profile_dir:
        from repro.core.profiler import Profiler
        prof = Profiler(profile_dir, tracing=True, rng_seed=seed)
        prof.start()

    history = []
    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        for step in range(start_step, n_steps):
            _, batch = next(prefetch)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if prof is not None:
                if mid is None:
                    lowered = jit_step.lower(params, opt_state, batch)
                    mid = prof.register_module(
                        "train_step", lowered.compile().as_text())
                with prof.dispatch("kernel", "train_step", stream=0,
                                  module_id=mid):
                    params, opt_state, metrics = jit_step(params, opt_state,
                                                          batch)
                    jax.block_until_ready(metrics["loss"])
            else:
                params, opt_state, metrics = jit_step(params, opt_state,
                                                      batch)
            watchdog.beat(f"host{host_id}", step)
            if step % log_every == 0 or step == n_steps - 1:
                loss = float(metrics["loss"])
                history.append({"step": step, "loss": loss,
                                "gnorm": float(metrics.get("grad_norm", 0))})
                print(f"step {step:5d} loss {loss:.4f}", flush=True)
            if mgr and ((step + 1) % ckpt_every == 0 or step == n_steps - 1):
                mgr.save(step + 1, {"params": params, "opt": opt_state},
                         block=False)
    if mgr:
        mgr.wait()
    paths = None
    if prof is not None:
        prof.flush()
        paths = prof.write()
        prof.stop()
    prefetch.close()
    return params, history, paths


def seq_options(seq: int) -> T.ModelOptions:
    """Attention/SSM/loss chunking for training at sequence length
    ``seq`` (chunks of at most 256, 128 for the SSM scan)."""
    return T.ModelOptions(q_chunk=min(256, seq), kv_chunk=min(256, seq),
                          ssm_chunk=min(128, seq), loss_chunk=min(256, seq))


class _nullcontext:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--reduced", action="store_true",
                    help="use the tiny same-family config (CPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--profile-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("custom", args.seq, args.batch, "train")
    t0 = time.monotonic()
    _, history, paths = train(
        cfg, shape, n_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, profile_dir=args.profile_dir,
        opts=seq_options(args.seq), grad_compression=args.grad_compression,
        seed=args.seed, resume=args.resume)
    print(f"done in {time.monotonic() - t0:.1f}s; "
          f"final loss {history[-1]['loss']:.4f}")
    if paths:
        print(f"profiles: {sorted(paths)[:4]} ...")


if __name__ == "__main__":
    main()
