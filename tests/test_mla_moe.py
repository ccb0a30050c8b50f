"""Latent attention and the layer that holds a share of the experts,
against their definitions (DeepSeek-V3), at tiny sizes on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import steps as steps_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import transformer as T

OPTS = T.ModelOptions(q_chunk=8, kv_chunk=8, ssm_chunk=4, loss_chunk=8)


def _cfg(**kw):
    return dataclasses.replace(get_config("moonlight-16b-a3b").reduced(),
                               **kw)


def test_absorbed_decode_equals_expanded_attention():
    """One decode step over the latent rows (W_kb into the query, W_vb
    into the output) gives what attention with K and V expanded from the
    latent gives at that position; slots from the position on, and the
    other layers' caches, are not read."""
    cfg = _cfg()
    p = mla_mod.init_mla_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S, smax = 2, 11, 16
    h = jax.random.normal(jax.random.PRNGKey(1), (B, S + 1, cfg.d_model))
    want, rows = mla_mod.mla_attention(p, h, cfg, jnp.arange(S + 1),
                                       q_chunk=4, kv_chunk=4)
    caches = [jnp.concatenate([r[:, :S], jax.random.normal(
        jax.random.PRNGKey(2), (B, smax - S) + r.shape[2:])], axis=1)
        for r in rows]
    # stacked over layers: this one is layer 1 of 3
    caches = [jnp.stack([c + 7.0, c, c - 7.0]) for c in caches]
    got, new = mla_mod.mla_decode(p, h[:, S:], cfg,
                                  jnp.full((B, 1), S, jnp.int32), *caches,
                                  jnp.int32(S), jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want[:, S]),
                               rtol=1e-5, atol=1e-5)
    for n, r in zip(new, rows):
        np.testing.assert_allclose(np.asarray(n[:, 0]), np.asarray(r[:, S]),
                                   rtol=1e-6, atol=1e-6)


def test_v3_routing_bias_selects_only_gates_normalised_and_scaled():
    """Top-k of sigmoid scores + bias picks the experts; the gates are
    the picked experts' scores without the bias, normalised to sum 1,
    times the scaling factor."""
    T_, d, E, K = 16, 8, 8, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (T_, d))
    wr = jax.random.normal(jax.random.PRNGKey(1), (d, E))
    bias = jnp.zeros((E,)).at[7].set(5.0)   # expert 7 always selected
    probs, gates, eidx = moe_mod.route(x, wr, bias, top_k=K,
                                       scoring="sigmoid", routed_scale=2.5)
    s = np.asarray(jax.nn.sigmoid(x @ wr))
    np.testing.assert_allclose(np.asarray(probs), s, rtol=1e-6)
    want_sel = np.argsort(-(s + np.asarray(bias)), -1)[:, :K]
    np.testing.assert_array_equal(np.sort(np.asarray(eidx), -1),
                                  np.sort(want_sel, -1))
    sel = np.take_along_axis(s, np.asarray(eidx), -1)
    np.testing.assert_allclose(np.asarray(gates),
                               sel / sel.sum(-1, keepdims=True) * 2.5,
                               rtol=1e-6)
    # expert 7 rarely has a top score: the bias, not the gate, put it in
    assert (np.argsort(-s, -1)[:, :K] != 7).all(-1).any()
    assert (np.asarray(eidx) == 7).any(-1).all()


@pytest.mark.parametrize("T_", [32, moe_mod.DENSE_MAX_TOKENS + 64],
                         ids=["every-expert", "grouped"])
def test_held_experts_drop_no_row_and_count_it(T_):
    """Every row routed to a held expert is computed, however many go to
    one expert, and counted: held experts first, then the rest; a few
    tokens through every held expert, gated, many through grouped
    matmuls over the rows routed to each."""
    d, f, E, K = 8, 16, 8, 2
    p = moe_mod.init_moe_params(jax.random.PRNGKey(0), d, f, E, jnp.float32,
                                n_held=2, select_bias=True)
    # a bias that sends every token to experts 2 (held: 2 and 3) and 5
    p["bias"] = jnp.zeros((E,)).at[2].set(9.0).at[5].set(9.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (T_, d))
    y, load = moe_mod.held_moe(p, x, top_k=K, e_first=2, scoring="sigmoid",
                               routed_scale=1.0)
    np.testing.assert_array_equal(np.asarray(load), [T_, 0, T_])
    s = jax.nn.sigmoid(x @ p["router"])
    g = s[:, 2] / (s[:, 2] + s[:, 5])
    h = jax.nn.silu(x @ p["w1"][0]) * (x @ p["w3"][0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        g[:, None] * (h @ p["w2"][0])), rtol=1e-5, atol=1e-5)


def test_latent_cache_aliased_in_place():
    """Compiled with the cache donated, the decode step aliases the whole
    latent cache and the load counter, with no cache-sized scratch; run,
    it hands back the buffers it was given, one row per layer changed."""
    cfg = _cfg(n_layers=13, experts_held=2, experts_first=1)
    B, L = 4, 2048
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, B, L))
    fn = jax.jit(steps_mod.make_decode_step(cfg, None, OPTS),
                 donate_argnums=(1,))
    mem = fn.lower(params, cache, jax.ShapeDtypeStruct((), jnp.int32),
                   token=jax.ShapeDtypeStruct((B,), jnp.int32)
                   ).compile().memory_analysis()
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert nbytes == 13 * B * L * (32 + 8) * 4 + 3 * 4
    assert mem.alias_size_in_bytes == nbytes
    assert mem.temp_size_in_bytes < nbytes / 4

    def filled():
        return jax.tree.map(lambda a: a + 1, T.init_cache(cfg, B, L))
    before = jax.tree.map(np.asarray, filled())
    c = filled()
    leaves = [(e, n) for e in ("d0", "e0") for n in ("ckv", "kpe")]
    ptr = {(e, n): c[e][n].unsafe_buffer_pointer() for e, n in leaves}
    pos = 7
    logits, out = fn(params, c, jnp.int32(pos),
                     token=jnp.zeros((B,), jnp.int32))
    jax.block_until_ready(logits)
    for e, n in leaves:
        assert out[e][n].unsafe_buffer_pointer() == ptr[(e, n)]
        got, old = np.asarray(out[e][n]), before[e][n]
        np.testing.assert_array_equal(np.delete(got, pos, axis=2),
                                      np.delete(old, pos, axis=2))
        assert not np.array_equal(got[:, :, pos], old[:, :, pos])
    # 12 MoE layers x 4 tokens x top-2 rows, each held or not, counted
    assert int(np.asarray(out[T.LOAD]).sum() - before[T.LOAD].sum()) \
        == 12 * B * cfg.moe.top_k


@pytest.mark.parametrize("max_len", [16, 128, 129])
def test_latent_caches_come_in_whole_slot_tiles(max_len):
    """Made for a batch or grown from its prefill, the latent caches hold
    max_len slots rounded up to whole 128-slot tiles, and both ways give
    the same shapes."""
    from repro.launch.serve import _grow_cache
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    made = jax.eval_shape(lambda: T.init_cache(cfg, 2, max_len))
    grown = jax.eval_shape(lambda: _grow_cache(cfg, T.prefill(
        params, cfg, jnp.zeros((2, 8), jnp.int32), opts=OPTS)[1], 2,
        max_len, 8))
    assert jax.tree.map(lambda a: a.shape, made) == \
        jax.tree.map(lambda a: a.shape, grown)
    slots = -(-max_len // 128) * 128
    for e in ("d0", "e0"):
        assert made[e]["ckv"].shape[2] == made[e]["kpe"].shape[2] == slots


@pytest.mark.parametrize("steps", [1, 3])
def test_prefill_then_decode_matches_prefill_of_the_whole(steps):
    """Prefill then decode through the latent cache, the last step at
    position max_len - 1, gives the logits a prefill of the whole sequence
    gives at its last position (the serving layer drops no row)."""
    from repro.launch.serve import _grow_cache
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    S = 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S + steps), 0,
                                cfg.vocab)
    step = jax.jit(lambda p, c, pos, tok: T.decode_step(
        p, cfg, c, token=tok, pos=pos, opts=OPTS), donate_argnums=(1,))
    _, cache = T.prefill(params, cfg, tokens[:, :S], opts=OPTS)
    cache = _grow_cache(cfg, cache, 2, S + steps, S)
    for i in range(steps):
        got, cache = step(params, cache, jnp.int32(S + i), tokens[:, S + i])
        want, _ = T.prefill(params, cfg, tokens[:, :S + i + 1], opts=OPTS)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
