"""The chip's own compiler accepts the main path (no chip needed).

Interpret mode validates BlockSpec index maps but not the TPU's tiling
rules, VMEM limits or device memory, so every kernel used to pass its
CPU tests while the chip's compiler refused two of them.  Here the TPU
compiler, installed with libtpu, compiles for a *described* v5e chip:

- the four Pallas kernels at the widths the deployments use, each
  lowering to a ``tpu_custom_call``;
- qwen2-1.5b's prefill step at published widths, whose compiled
  footprint must fit one chip's 16 GiB;
- the decode steps of the serving cells with their caches donated,
  updated in place; moonlight's on the latent-attention kernel too,
  which reads the stacked caches with no copy of them.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and under pytest-xdist each worker imports
every test file.  Compiles run in the test's own process for the same
reason.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024 ** 3      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry compiled for a described chip cannot be
    # read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash(spec):
    from repro.kernels.flash_attention import flash_attention_fwd
    # qwen2-1.5b prefill: 12 q heads, 2 kv heads, head_dim 128
    return (functools.partial(flash_attention_fwd, causal=True),
            spec((4, 2048, 12, 128)), spec((4, 2048, 2, 128)),
            spec((4, 2048, 2, 128)))


def _decode(spec):
    from repro.kernels.decode_attention import flash_decode_fwd
    return (flash_decode_fwd, spec((8, 12, 128)), spec((8, 4096, 2, 128)),
            spec((8, 4096, 2, 128)), spec((), jnp.int32))


def _ssm(spec):
    from repro.kernels.ssm_scan import ssm_scan_fwd
    # hymba-1.5b's mamba heads: 25 heads of 64, state 16, chunk 256
    return (functools.partial(ssm_scan_fwd, chunk=256),
            spec((2, 2048, 25, 64)), spec((2, 2048, 25), jnp.float32),
            spec((2, 2048, 16)), spec((2, 2048, 16)))


def _mla_decode(spec, S=1280):
    from repro.kernels.mla_decode import mla_decode_fwd
    # moonlight-16b-a3b's decode: 16 heads, latent 512, rope 64, batch
    # 128, 1,280 slots (the cell's), the 26 MoE layers' caches stacked
    B, H, r, rope, L = 128, 16, 512, 64, 26
    return (functools.partial(mla_decode_fwd, scale=192 ** -0.5),
            spec((B, H, r)), spec((B, H, rope)), spec((B, r)),
            spec((B, rope)), spec((L, B, S, r)), spec((L, B, S, rope)),
            spec((), jnp.int32), spec((), jnp.int32))


@pytest.mark.parametrize("build", [
    _flash, _decode, _ssm, _mla_decode,
    # a serving length that is not a multiple of the slot block
    functools.partial(_mla_decode, S=1256)],
    ids=["flash_attention", "flash_decode", "ssm_scan", "mla_decode",
         "mla_decode-1256-slots"])
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, *args = build(functools.partial(_spec, one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_prefill_fits_one_chip(one_chip):
    from repro.configs import get_config
    from repro.launch import steps as steps_mod
    from repro.models import transformer as T
    cfg = get_config("qwen2-1.5b")
    batch, prompt = 4, 512
    opts = T.ModelOptions(q_chunk=256, kv_chunk=256, loss_chunk=256)
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: T.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    tokens = _spec(one_chip, (batch, prompt), jnp.int32)
    step = jax.jit(steps_mod.make_prefill_step(cfg, None, opts))
    mem = step.lower(params, {"tokens": tokens}).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # the bf16 parameters alone are ~3.55 GB
    assert 3.0e9 < mem.argument_size_in_bytes < total < HBM_BYTES


def test_qwen2_decode_updates_the_donated_cache_in_place(one_chip):
    """At the serving cell's size (batch 64, 1,280 slots) the decode step
    aliases the donated KV cache and needs less scratch than one layer's
    K cache: no layer's cache is copied."""
    from repro.configs import get_config
    from repro.launch import steps as steps_mod
    from repro.models import transformer as T
    cfg = get_config("qwen2-1.5b")
    batch, max_len = 64, 1280
    opts = T.ModelOptions(q_chunk=256, kv_chunk=256, loss_chunk=256)

    def specs(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)
    params = specs(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = specs(jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len)))
    step = jax.jit(steps_mod.make_decode_step(cfg, None, opts),
                   donate_argnums=(1,))
    mem = step.lower(params, cache, _spec(one_chip, (), jnp.int32),
                     token=_spec(one_chip, (batch,), jnp.int32)
                     ).compile().memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    layer_k_bytes = cache_bytes // (2 * cfg.n_layers)
    assert cache_bytes == 2 * 28 * 64 * 1280 * 2 * 128 * 2
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < layer_k_bytes


def test_moonlight_decode_updates_the_latent_cache_in_place(one_chip):
    """At its serving cell's size (8 of 64 experts held, batch 128, 1,280
    slots) the decode step aliases the donated latent cache of all 27
    layers and the load counter, and the step fits one chip with the
    weights and the cache: no layer's cache is copied."""
    import dataclasses
    from repro.configs import get_config
    from repro.launch import steps as steps_mod
    from repro.models import transformer as T
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"),
                              experts_held=8)
    batch, max_len = 128, 1280
    opts = T.ModelOptions(q_chunk=256, kv_chunk=256, loss_chunk=256)

    def specs(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)
    params = specs(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = specs(jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len)))
    step = jax.jit(steps_mod.make_decode_step(cfg, None, opts),
                   donate_argnums=(1,))
    mem = step.lower(params, cache, _spec(one_chip, (), jnp.int32),
                     token=_spec(one_chip, (batch,), jnp.int32)
                     ).compile().memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert cache_bytes == 27 * 128 * 1280 * 576 * 2 + 9 * 4
    # the chip pads the 9-entry counter to one tile (512 B)
    assert cache_bytes <= mem.alias_size_in_bytes < cache_bytes + 1024
    layer_bytes = cache_bytes // 27
    assert mem.temp_size_in_bytes < 3 * layer_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _producers(hlo: str):
    """{instruction name: (opcode, operand names)} of an HLO text."""
    import re
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*? ([\w-]+)\((.*?)\)", line)
        if m:
            out[m.group(1)] = (m.group(2),
                               re.findall(r"%([\w.-]+)", m.group(3)))
    return out


def test_moonlight_decode_kernel_reads_the_stacked_caches_in_place(
        one_chip, monkeypatch):
    """On the latent-attention kernel (the path a TPU takes), moonlight's
    decode step at its serving cell's size still aliases the donated
    cache with less scratch than one layer's latent cache, and each
    layer's kernel call is handed the stacked caches themselves: its
    cache operands come from the parameters through bitcasts (the roped
    keys' slot-minor view) and, at most, an asynchronous move of a whole
    stack to on-chip memory, never from a slice or a copy of one."""
    _check_moonlight_decode_on_the_kernel(one_chip, monkeypatch, 1280)


def test_moonlight_decode_kernel_at_a_length_off_the_slot_tile(
        one_chip, monkeypatch):
    """The same at a serving length off the 128-slot tile (prompt 256 +
    1,000 generated): the latent caches are made in whole tiles, 1,280
    slots, so the chip keeps the roped keys slot-minor and no step
    relayouts them for the kernel."""
    cache = _check_moonlight_decode_on_the_kernel(one_chip, monkeypatch,
                                                  1256)
    assert cache["e0"]["ckv"].shape[2] == cache["e0"]["kpe"].shape[2] == 1280


def _check_moonlight_decode_on_the_kernel(one_chip, monkeypatch, max_len):
    import dataclasses
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch import steps as steps_mod
    from repro.models import mla as mla_mod
    from repro.models import transformer as T
    monkeypatch.setattr(mla_mod, "_use_kernel", lambda: True)
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"),
                              experts_held=8)
    batch = 128
    opts = T.ModelOptions(q_chunk=256, kv_chunk=256, loss_chunk=256)

    def specs(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)
    params = specs(jax.eval_shape(lambda k: T.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = specs(jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len)))
    step = jax.jit(steps_mod.make_decode_step(cfg, None, opts),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache, _spec(one_chip, (), jnp.int32),
                          token=_spec(one_chip, (batch,), jnp.int32)
                          ).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert cache_bytes <= mem.alias_size_in_bytes < cache_bytes + 1024
    layer_ckv_bytes = batch * max_len * cfg.kv_lora_rank * 2
    assert mem.temp_size_in_bytes < layer_ckv_bytes
    hlo = compiled.as_text()
    prods = _producers(hlo)
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "/mla_scores/mla_decode" in line]
    assert len(calls) == 2            # the dense layer's and the scan's
    for line in calls:
        name = line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
        operands = prods[name][1]
        # the stacked caches: (1 or 26, 128, max_len, 512) and its view
        # (., 128, 64, max_len)
        for op in operands[-2:]:
            while prods.get(op, ("",))[0] in ("bitcast", "copy-done",
                                               "copy-start"):
                op = prods[op][1][0]
            assert prods.get(op, ("parameter",))[0] in (
                "parameter", "get-tuple-element"), (name, op, prods.get(op))
    return cache
