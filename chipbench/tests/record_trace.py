#!/usr/bin/env python3
"""Record the small chip trace the reduction's tests read.

    python3 chipbench/tests/record_trace.py   # on a TPU

Runs three executions of a program named ``prefill_step`` and five of
one named ``decode_step``, with the benchmark's host spans around them
and a 5 ms host pause in each ``close`` span, under ``jax.profiler``, and
copies the ``.xplane.pb`` to ``chipbench/testdata/small.xplane.pb``.
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(os.path.dirname(HERE), "testdata", "small.xplane.pb")


def prefill_step(x):
    return jnp.tanh(x @ x)


def decode_step(x):
    return jnp.tanh(x @ x.T) * 0.5


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    from chipbench.trace import find_trace_file, start
    span = lambda name: jax.profiler.TraceAnnotation("chipbench:" + name)
    pf, dc = jax.jit(prefill_step), jax.jit(decode_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready((pf(x), dc(x)))
    tmp = tempfile.mkdtemp()
    start(tmp)
    with span("window"):
        for fn, n in ((pf, 3), (dc, 5)):
            for _ in range(n):
                with span("dispatch+sync"):
                    jax.block_until_ready(fn(x))
                with span("close (stats+tick)"):
                    time.sleep(0.005)
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    shutil.copy(find_trace_file(tmp), OUT)
    shutil.rmtree(tmp)
    print(OUT, os.path.getsize(OUT))
    return 0


if __name__ == "__main__":
    sys.exit(main())
