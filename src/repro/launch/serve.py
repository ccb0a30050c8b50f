"""Batched serving driver: continuous prefill + decode with the measurement
stack attached.

Serving shape: a queue of synthetic requests (prompt lengths drawn from a
mixture) is served in fixed-size decode batches.  Prefill runs per request
batch; decode steps run against the shared KV cache.  Every GPU-side
dispatch (prefill, decode, cache copy, sync) goes through
``Profiler.dispatch`` so the §8.4-style analysis (sync_count vs
kernel_count, idleness blame) has real material — examples/
find_redundant_sync.py injects a deliberately redundant sync here and
finds it with the derived metric, reproducing the PeleC case study.
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.spans import span
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.window import DECODE, PREFILL


def _maybe_window(serving, rid: str, phase: str, tokens: int):
    if serving is None:
        return contextlib.nullcontext()
    return serving.request(rid, phase, tokens=tokens)


def serve(cfg: ModelConfig, *, n_requests: int = 8, batch: int = 4,
          prompt_len: int = 32, gen_len: int = 16, seed: int = 0,
          profile_dir: Optional[str] = None, redundant_sync: bool = False,
          opts: Optional[T.ModelOptions] = None, serving=None,
          rid_prefix: str = ""):
    """Returns (generated tokens (n_requests, gen_len), profile paths).

    ``serving`` takes a started ``repro.serving.ServingProfiler``: every
    dispatch then runs through it inside per-request/per-phase windows
    (``r<lo>`` / ``r<lo>-r<hi>`` for a batch), feeding latency stats plus
    governor/telemetry ticks; the caller owns its lifecycle and output.
    Mutually exclusive with ``profile_dir`` (which owns a plain Profiler
    internally, as before).  ``rid_prefix`` disambiguates request ids
    when several serve() passes feed one profiler (window identities
    with equal ids unify in the database).
    """
    opts = opts or T.ModelOptions(q_chunk=min(256, prompt_len),
                                  kv_chunk=min(256, prompt_len),
                                  ssm_chunk=min(64, prompt_len),
                                  loss_chunk=min(256, prompt_len))
    key = jax.random.PRNGKey(seed)
    params = T.init_params(key, cfg)
    max_len = prompt_len + gen_len

    prefill_fn = jax.jit(steps_mod.make_prefill_step(cfg, None, opts))
    # the cache is donated: each step writes its new K/V rows in place
    decode_fn = jax.jit(steps_mod.make_decode_step(cfg, None, opts),
                        donate_argnums=(1,))

    if serving is not None and profile_dir:
        raise ValueError("pass either serving= or profile_dir=, not both")
    prof = serving.profiler if serving is not None else None
    own_prof = False
    if profile_dir:
        from repro.core.profiler import Profiler
        prof = Profiler(profile_dir, tracing=True, rng_seed=seed)
        prof.start()
        own_prof = True

    # --- warm-up: compile and register BOTH modules before the measured
    # loop.  Compilation used to run lazily inside the first batch's
    # dispatch, so its trace event (and any serving latency derived from
    # it) carried the full XLA compile time.
    warm_in = {"tokens": jnp.zeros((batch, prompt_len), jnp.int32)}
    logits, cache = prefill_fn(params, warm_in)
    cache = _grow_cache(cfg, cache, batch, max_len, prompt_len)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos0 = jnp.int32(prompt_len)
    # the warm-up donates ``cache``: lower from the one it returns
    warm_logits, cache = decode_fn(params, cache, pos0, token=tok)
    jax.block_until_ready(warm_logits)
    mid_p = mid_d = None
    if prof is not None:
        mid_p = prof.register_module(
            "prefill",
            prefill_fn.lower(params, warm_in).compile().as_text())
        mid_d = prof.register_module(
            "decode_step",
            decode_fn.lower(params, cache, pos0,
                            token=tok).compile().as_text())

    rng = np.random.default_rng(seed)
    outs = []
    n_batches = (n_requests + batch - 1) // batch
    for bi in range(n_batches):
        lo, hi = bi * batch, min(bi * batch + batch, n_requests)
        rid = f"{rid_prefix}r{lo}" if hi - lo <= 1 \
            else f"{rid_prefix}r{lo}-r{hi - 1}"
        with span("serve.inputs"):
            toks = jnp.asarray(rng.integers(0, cfg.vocab,
                                            (batch, prompt_len), np.int32))
            batch_in = {"tokens": toks}
        # the last batch's cache goes before the prefill makes the next:
        # the two are never held at once
        cache = None
        # --- prefill ------------------------------------------------------
        with _maybe_window(serving, rid, PREFILL, batch * prompt_len):
            if prof is not None:
                with span("serve.dispatch", phase=PREFILL), \
                        prof.dispatch("kernel", "prefill", stream=0,
                                      module_id=mid_p):
                    with span("serve.enqueue", phase=PREFILL):
                        logits, cache = prefill_fn(params, batch_in)
                    with span("serve.sync", phase=PREFILL):
                        jax.block_until_ready(logits)
            else:
                logits, cache = prefill_fn(params, batch_in)
        # cache is sized prompt_len by prefill; decode needs max_len slots
        with span("serve.grow_cache"):
            cache = _grow_cache(cfg, cache, batch, max_len, prompt_len)
        with span("serve.next_token"):
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            gen = [tok]
            pos = jnp.int32(prompt_len)
        # --- decode ---------------------------------------------------------
        for t in range(gen_len - 1):
            with _maybe_window(serving, rid, DECODE, batch):
                if prof is not None:
                    # serve.dispatch's self time is the profiler's own
                    # enter and exit.  The step takes the KV cache
                    # donated and returns it updated in place: nothing
                    # of the cache is copied or released per step
                    with span("serve.dispatch", phase=DECODE), \
                            prof.dispatch("kernel", "decode_step", stream=0,
                                          module_id=mid_d):
                        with span("serve.enqueue", phase=DECODE):
                            logits, cache = decode_fn(params, cache, pos,
                                                      token=tok)
                        with span("serve.sync", phase=DECODE):
                            jax.block_until_ready(logits)
                    if redundant_sync:
                        # §8.4.1: a sync with no kernel between it and the
                        # previous sync — found by diff = sync - kernels
                        for _ in range(2):
                            with span("serve.dispatch", phase=DECODE), \
                                    prof.dispatch("sync", "device_sync",
                                                  stream=0):
                                jax.block_until_ready(logits)
                else:
                    logits, cache = decode_fn(params, cache, pos, token=tok)
            with span("serve.next_token"):
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                gen.append(tok)
                pos = jnp.int32(prompt_len + t + 1)
        if T.LOAD in cache:
            _record_load(cfg, cache[T.LOAD])
        outs.append(jnp.stack(gen, axis=1))
    paths = None
    if own_prof:
        prof.flush()
        paths = prof.write()
        prof.stop()
    return jnp.concatenate(outs, axis=0)[:n_requests], paths


def _record_load(cfg, load) -> None:
    """The batch's rows routed to each held expert, counted by the
    decode program on the device, as the args of a ``serve.moe_load``
    span (``expert_<id>``, and ``elsewhere`` for experts held on other
    chips): read from the device only while a trace records it."""
    with span("serve.moe_load") as live:
        if live is not None:
            rows = np.asarray(load)
            live.set_metadata(elsewhere=int(rows[-1]), **{
                f"expert_{cfg.experts_first + i}": int(r)
                for i, r in enumerate(rows[:-1])})


def _grow_cache(cfg, cache, batch, max_len, cur_len):
    """Pad the prefill's per-position caches (``T.SLOT_AXES``: attention
    K/V, latent rows) out to the slots that hold max_len
    (``T.cache_slots``)."""
    def grow(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        axis = T.SLOT_AXES.get(name)
        if axis is not None and leaf.shape[axis] == cur_len:
            # one pad op: no zero block is made beside the grown cache
            widths = [(0, 0)] * leaf.ndim
            widths[axis] = (0, T.cache_slots(name, max_len) - cur_len)
            return jnp.pad(leaf, widths)
        return leaf
    return jax.tree_util.tree_map_with_path(grow, cache)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--profile-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    t0 = time.monotonic()
    toks, paths = serve(cfg, n_requests=args.requests, batch=args.batch,
                        prompt_len=args.prompt_len, gen_len=args.gen_len,
                        profile_dir=args.profile_dir)
    dt = time.monotonic() - t0
    n_tok = toks.shape[0] * toks.shape[1]
    print(f"served {toks.shape[0]} requests x {toks.shape[1]} tokens "
          f"in {dt:.1f}s ({n_tok / dt:.1f} tok/s)")
    if paths:
        print("profiles:", sorted(paths)[:4], "...")


if __name__ == "__main__":
    main()
