"""The decode step updates a donated KV cache in place.

Each step writes one new K/V row per layer into the cache it was given.
Compiled with the cache donated, the program aliases the whole cache
from input to output and needs no cache-sized scratch; run, it hands
back the very buffers it was given.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import steps as steps_mod
from repro.models import transformer as T

N_LAYERS, BATCH, MAX_LEN = 8, 4, 1024
OPTS = T.ModelOptions(q_chunk=8, kv_chunk=8, ssm_chunk=4, loss_chunk=8)


def _cfg():
    return dataclasses.replace(get_config("qwen2-1.5b").reduced(),
                               n_layers=N_LAYERS)


def _decode_fn(cfg):
    return jax.jit(steps_mod.make_decode_step(cfg, None, OPTS),
                   donate_argnums=(1,))


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def test_decode_step_aliases_the_whole_cache_without_scratch():
    cfg = _cfg()
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, BATCH, MAX_LEN))
    mem = _decode_fn(cfg).lower(
        params, cache, jax.ShapeDtypeStruct((), jnp.int32),
        token=jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    ).compile().memory_analysis()
    cache_bytes = _nbytes(cache)
    assert cache_bytes == 2 * N_LAYERS * BATCH * MAX_LEN * 2 * 16 * 4
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes / 4


def test_decode_step_returns_the_donated_buffers():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    def filled():
        return jax.tree.map(lambda a: a + 1,
                            T.init_cache(cfg, BATCH, MAX_LEN))
    # a numpy view of the cache would keep it from being donated
    before = jax.tree.map(np.asarray, filled())
    cache = filled()
    kv_in = {e: {n: c[n].unsafe_buffer_pointer() for n in ("k", "v")}
             for e, c in cache.items()}
    pos = 5
    logits, out = _decode_fn(cfg)(params, cache, jnp.int32(pos),
                                  token=jnp.zeros((BATCH,), jnp.int32))
    jax.block_until_ready(logits)
    for e, c in out.items():
        for n in ("k", "v"):
            assert c[n].unsafe_buffer_pointer() == kv_in[e][n], (e, n)
            got, old = np.asarray(c[n]), before[e][n]
            # only the slot of ``pos`` changed
            np.testing.assert_array_equal(np.delete(got, pos, axis=3),
                                          np.delete(old, pos, axis=3))
            assert not np.array_equal(got[:, :, :, pos], old[:, :, :, pos])
