"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype, scale=1.0):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,D,bq,bk", [
    (1, 128, 4, 4, 64, 64, 64),      # MHA
    (2, 256, 8, 2, 64, 128, 128),    # GQA 4:1
    (1, 256, 8, 1, 32, 64, 128),     # MQA, uneven blocks
    (1, 512, 2, 2, 128, 256, 256),   # full-size head dim
])
def test_flash_vs_ref(B, S, H, Hkv, D, bq, bk, dtype):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, S, H, D), dtype)
    k = rand(ks[1], (B, S, Hkv, D), dtype)
    v = rand(ks[2], (B, S, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, True, 0, bq, bk)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [64, 128])
def test_flash_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    B, S, H, D = 1, 256, 4, 32
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, S, H, D), jnp.float32)
    v = rand(ks[2], (B, S, H, D), jnp.float32)
    out = ops.flash_attention(q, k, v, True, window, 64, 64)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_grad_matches_ref():
    ks = jax.random.split(KEY, 3)
    B, S, H, D = 1, 128, 4, 32
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, S, H, D), jnp.float32)
    v = rand(ks[2], (B, S, H, D), jnp.float32)

    g1 = jax.grad(lambda *a: (ops.flash_attention(*a, True, 0, 64, 64)
                              ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (ref.attention_ref(*a, causal=True)
                              ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_in_model_attention_block():
    """use_flash_kernel=True path through models.transformer training."""
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("qwen2-1.5b").reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.ones((2, 64), jnp.int32)
    opts_k = T.ModelOptions(q_chunk=32, kv_chunk=32, loss_chunk=32,
                            use_flash_kernel=True)
    opts_j = T.ModelOptions(q_chunk=32, kv_chunk=32, loss_chunk=32)
    yk, _ = T.forward(params, cfg, tokens, opts=opts_k)
    yj, _ = T.forward(params, cfg, tokens, opts=opts_j)
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yj, np.float32),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,nh,hd,st_,chunk", [
    (1, 128, 2, 16, 16, 64),
    (2, 256, 4, 32, 16, 128),
    (1, 256, 1, 64, 32, 256),   # single head, chunk == S
])
def test_ssm_vs_ref(B, S, nh, hd, st_, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    xv = rand(ks[0], (B, S, nh, hd), dtype, 0.5)
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    Bm = rand(ks[2], (B, S, st_), dtype, 0.3)
    Cm = rand(ks[3], (B, S, st_), dtype, 0.3)
    h0 = jax.random.normal(ks[4], (B, nh, hd, st_), jnp.float32) * 0.1
    y, h = ops.ssm_scan(xv, ld, Bm, Cm, h0, chunk)
    yr, hr = ref.ssm_scan_ref(xv, ld, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssm_no_h0():
    ks = jax.random.split(KEY, 4)
    B, S, nh, hd, st_ = 1, 128, 2, 16, 8
    xv = rand(ks[0], (B, S, nh, hd), jnp.float32, 0.5)
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    Bm = rand(ks[2], (B, S, st_), jnp.float32, 0.3)
    Cm = rand(ks[3], (B, S, st_), jnp.float32, 0.3)
    y, h = ops.ssm_scan(xv, ld, Bm, Cm, None, 64)
    yr, hr = ref.ssm_scan_ref(xv, ld, Bm, Cm, None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


def test_ssm_grads_match_ref():
    ks = jax.random.split(KEY, 5)
    B, S, nh, hd, st_ = 1, 128, 2, 8, 8
    xv = rand(ks[0], (B, S, nh, hd), jnp.float32, 0.5)
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    Bm = rand(ks[2], (B, S, st_), jnp.float32, 0.3)
    Cm = rand(ks[3], (B, S, st_), jnp.float32, 0.3)
    h0 = jax.random.normal(ks[4], (B, nh, hd, st_), jnp.float32) * 0.1
    g1 = jax.grad(lambda *a: (ops.ssm_scan(*a, 64)[0] ** 2).sum(),
                  argnums=(0, 1, 2, 3, 4))(xv, ld, Bm, Cm, h0)
    g2 = jax.grad(lambda *a: (ref.ssm_scan_ref(*a)[0] ** 2).sum(),
                  argnums=(0, 1, 2, 3, 4))(xv, ld, Bm, Cm, h0)
    for a, b, n in zip(g1, g2, ["xv", "ld", "B", "C", "h0"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=n)


def test_ssm_kernel_in_mamba_forward():
    from repro.models import ssm
    ks = jax.random.split(KEY, 2)
    p = ssm.init_ssm_params(ks[0], 32, 2, 8, 8, jnp.float32)
    x = jax.random.normal(ks[1], (2, 64, 32)) * 0.1
    yk, _ = ssm.mamba_forward(p, x, n_heads=2, head_dim=8, state=8,
                              chunk=32, use_kernel=True)
    yj, _ = ssm.mamba_forward(p, x, n_heads=2, head_dim=8, state=8,
                              chunk=32, use_kernel=False)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yj),
                               rtol=1e-4, atol=1e-4)


def test_kernels_refuse_backends_they_are_not_written_for(monkeypatch):
    """Compiled on a TPU, interpreted on the CPU, and an error anywhere
    else — never a silent interpret-mode run on an accelerator."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._use_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._use_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        ops._use_interpret()
