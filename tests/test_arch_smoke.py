"""Per-architecture smoke tests (deliverable (f)): reduced same-family
config, one forward/train step + prefill/decode on CPU, output shapes +
no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_configs
from repro.configs.base import shape_applicable
from repro.launch import steps as steps_mod
from repro.models import transformer as T
from repro.optim import adamw

ARCHS = list_configs()
OPTS = T.ModelOptions(q_chunk=16, kv_chunk=16, ssm_chunk=8, loss_chunk=16)


def make_batch(cfg, B=2, S=32, with_labels=True):
    batch = {}
    if cfg.frontend == "audio":
        batch["embeds"] = jnp.full((B, S, cfg.d_model), 0.01, jnp.float32)
    elif cfg.frontend == "vlm" and cfg.frontend_tokens:
        F = min(cfg.frontend_tokens, S // 2)
        batch["embeds"] = jnp.full((B, F, cfg.d_model), 0.01, jnp.float32)
        batch["tokens"] = jnp.ones((B, S - F), jnp.int32)
    else:
        batch["tokens"] = jnp.ones((B, S), jnp.int32)
    if with_labels:
        batch["labels"] = jnp.ones((B, S), jnp.int32)
    return batch


def test_all_archs_registered():
    assert len(ARCHS) == 11
    expected = {"xlstm-125m", "yi-6b", "qwen2-1.5b", "starcoder2-15b",
                "qwen3-32b", "llava-next-mistral-7b",
                "llama4-maverick-400b-a17b", "granite-moe-1b-a400m",
                "musicgen-large", "hymba-1.5b", "moonlight-16b-a3b"}
    assert set(ARCHS) == expected


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_config(arch).reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    step = jax.jit(steps_mod.make_train_step(cfg, None, OPTS,
                                             adamw.OptConfig()))
    p2, o2, m = step(params, adamw.init(params), batch)
    assert np.isfinite(float(m["loss"])), arch
    assert float(m["loss"]) > 0
    # params actually changed (unembed always receives gradient; the embed
    # table does not for audio archs whose inputs are frame embeddings)
    d0 = params["unembed"]
    d1 = p2["unembed"]
    assert not np.allclose(np.asarray(d0, np.float32),
                           np.asarray(d1, np.float32))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_smoke(arch):
    cfg = get_config(arch).reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    batch = make_batch(cfg, B, S, with_labels=False)
    opts = T.ModelOptions(q_chunk=8, kv_chunk=8, ssm_chunk=4, loss_chunk=8)
    logits, cache = T.prefill(params, cfg, batch.get("tokens"),
                              batch.get("embeds"), opts=opts)
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all(), arch
    if cfg.frontend == "audio":
        lg2, c2 = T.decode_step(params, cfg, cache,
                                embed=jnp.full((B, 1, cfg.d_model), 0.01),
                                pos=jnp.int32(S), opts=opts)
    else:
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        lg2, c2 = T.decode_step(params, cfg, cache, token=tok,
                                pos=jnp.int32(S), opts=opts)
    assert lg2.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(lg2)).all(), arch
    assert jax.tree.structure(cache) == jax.tree.structure(c2)


@pytest.mark.parametrize("arch", ARCHS)
def test_config_exact_dims(arch):
    """The registered (full) config matches the assignment table."""
    spec = {
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "yi-6b": (32, 4096, 32, 4, 11008, 64000),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
    }[arch]
    cfg = get_config(arch)
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == spec, (arch, got, spec)
    if arch == "llama4-maverick-400b-a17b":
        assert cfg.moe.n_experts == 128 and cfg.moe.top_k == 1
    if arch == "granite-moe-1b-a400m":
        assert cfg.moe.n_experts == 32 and cfg.moe.top_k == 8
    if arch == "hymba-1.5b":
        assert cfg.ssm_state == 16
    if arch == "qwen3-32b":
        assert cfg.qk_norm
    if arch == "qwen2-1.5b":
        assert cfg.qkv_bias
    if arch == "moonlight-16b-a3b":
        assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                cfg.v_head_dim) == (512, 128, 64, 128)
        assert cfg.first_dense_layers == 1 and cfg.moe.d_expert == 1408
        assert cfg.moe.n_experts == 64 and cfg.moe.top_k == 6
        assert cfg.moe.d_shared == 2816 and cfg.moe.routed_scale == 2.446


def test_long_500k_applicability():
    """long_500k runs only for sub-quadratic sequence mixing."""
    long = SHAPES["long_500k"]
    runs = {a for a in ARCHS if shape_applicable(get_config(a), long)[0]}
    assert runs == {"xlstm-125m", "hymba-1.5b"}


@pytest.mark.parametrize("arch", ["llama4-maverick-400b-a17b",
                                  "granite-moe-1b-a400m",
                                  "moonlight-16b-a3b"])
def test_moe_param_accounting(arch):
    cfg = get_config(arch)
    assert cfg.n_active_params() < cfg.n_params()


def test_param_count_plausible():
    """Sanity: full configs land near their nameplate sizes."""
    # note: every FFN in this framework is gated (swiglu, 3 matrices);
    # starcoder2's published 15B uses a plain 2-matrix MLP, so its
    # swiglu-equivalent lands at ~22B (DESIGN.md §Arch-applicability)
    for arch, lo, hi in [("qwen2-1.5b", 1.2e9, 2.2e9),
                         ("yi-6b", 5e9, 7.5e9),
                         ("qwen3-32b", 25e9, 40e9),
                         ("starcoder2-15b", 12e9, 23e9)]:
        n = get_config(arch).n_params()
        assert lo < n < hi, (arch, n)
