#!/usr/bin/env python3
"""Record the small chip trace the latent-attention and expert readers'
tests read.

    python3 chipbench/tests/record_scopes.py   # on a TPU

Serves one batch of the tiny moonlight cell of the tests (``SIZES``:
the configuration's file with every width cut, 8 of its 64 experts
held) through the program's own prefill and decode step: a prefill of
4 prompts of 16 tokens, then five decode steps with the cache donated,
each inside the benchmark's ``dispatch+sync`` span, and the batch's
``serve.moe_load`` span after them, all under ``jax.profiler`` inside
the ``window`` span.  Copies the ``.xplane.pb`` to
``chipbench/testdata/scopes.xplane.pb``.
"""
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(os.path.dirname(HERE), "testdata", "scopes.xplane.pb")
BATCH, PROMPT, STEPS = 4, 16, 5


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scopes: needs a TPU", file=sys.stderr)
        return 2
    for p in (ROOT, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    from chipbench import harness
    from chipbench.tests import test_op_scopes
    from chipbench.trace import find_trace_file, start
    from repro.launch import serve as serve_mod
    from repro.launch import steps as steps_mod
    from repro.models import transformer as T

    cell = test_op_scopes.tiny_cell()
    cfg = harness.program_config(cell.config)
    params = cell.reference.weights(cell.config, 1)
    opts = T.ModelOptions(q_chunk=PROMPT, kv_chunk=PROMPT)
    prefill = jax.jit(steps_mod.make_prefill_step(cfg, None, opts))
    decode = jax.jit(steps_mod.make_decode_step(cfg, None, opts),
                     donate_argnums=(1,))
    toks = jax.random.randint(jax.random.PRNGKey(2), (BATCH, PROMPT), 0,
                              cfg.vocab)

    def batch():
        logits, cache = prefill(params, {"tokens": toks})
        cache = serve_mod._grow_cache(cfg, cache, BATCH, PROMPT + STEPS + 1,
                                      PROMPT)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache
    tok, cache = batch()
    jax.block_until_ready(decode(params, cache, jnp.int32(PROMPT),
                                 token=tok))
    tok, cache = batch()
    jax.block_until_ready(cache)
    span = lambda name: jax.profiler.TraceAnnotation("chipbench:" + name)
    tmp = tempfile.mkdtemp()
    start(tmp)
    with span("window"):
        for t in range(STEPS):
            with span("dispatch+sync"):
                logits, cache = decode(params, cache, jnp.int32(PROMPT + t),
                                       token=tok)
                jax.block_until_ready(logits)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        serve_mod._record_load(cfg, cache[T.LOAD])
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    shutil.copy(find_trace_file(tmp), OUT)
    shutil.rmtree(tmp)
    print(OUT, os.path.getsize(OUT))
    return 0


if __name__ == "__main__":
    sys.exit(main())
