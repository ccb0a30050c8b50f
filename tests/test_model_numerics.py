"""Numerical equivalence tests between model compute paths:
chunked/binary/flash attention vs naive softmax; ssd chunked vs sequential;
mlstm chunked vs recurrent; prefill+decode vs full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import attention_ref, mlstm_ref, ssm_scan_ref
from repro.models import attention as A
from repro.models import ssm, xlstm
from repro.models import transformer as T
from repro.configs import get_config

KEY = jax.random.PRNGKey(42)


def qkv(B=2, S=128, H=4, Hkv=2, D=32):
    ks = jax.random.split(KEY, 3)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, Hkv, D)),
            jax.random.normal(ks[2], (B, S, Hkv, D)))


def test_chunked_attention_matches_ref():
    q, k, v = qkv()
    out = A.chunked_attention(q, k, v, q_chunk=32, kv_chunk=32)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_binary_schedule_matches_dense():
    q, k, v = qkv(S=256)
    dense = A.chunked_attention(q, k, v, q_chunk=32, kv_chunk=32,
                                schedule="dense")
    binary = A.chunked_attention(q, k, v, q_chunk=32, kv_chunk=32,
                                 schedule="binary")
    np.testing.assert_allclose(np.asarray(binary), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_binary_schedule_grads_match():
    q, k, v = qkv(S=128, H=2, Hkv=2)
    def loss(sched):
        return lambda q_, k_, v_: (A.chunked_attention(
            q_, k_, v_, q_chunk=32, kv_chunk=32, schedule=sched) ** 2).sum()
    gd = jax.grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss("binary"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_swa_matches_ref_window():
    q, k, v = qkv(S=256, H=4, Hkv=4)
    w = 64
    out = A.swa_attention(q, k, v, w)
    want = attention_ref(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_chunked_window_matches_ref():
    q, k, v = qkv(S=256)
    w = 96  # not a multiple of chunk
    out = A.chunked_attention(q, k, v, q_chunk=64, kv_chunk=64, window=w)
    want = attention_ref(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_decode_attention_matches_last_row():
    q, k, v = qkv(S=64, H=4, Hkv=2)
    full = attention_ref(q, k, v, causal=True)
    out = A.decode_attention(q[:, -1], k, v, length=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, -1]),
                               rtol=1e-5, atol=1e-5)


def test_ssd_chunked_matches_sequential():
    ks = jax.random.split(KEY, 5)
    B, S, nh, hd, st = 2, 128, 2, 16, 8
    xv = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    ld = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    Bm = jax.random.normal(ks[2], (B, S, st)) * 0.3
    Cm = jax.random.normal(ks[3], (B, S, st)) * 0.3
    h0 = jax.random.normal(ks[4], (B, nh, hd, st)) * 0.1
    y, h = ssm.ssd_chunked(xv, ld, Bm, Cm, chunk=32, h0=h0)
    yr, hr = ssm_scan_ref(xv, ld, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-4, atol=1e-4)


def test_mamba_decode_consistent_with_prefill():
    """Running S steps of recurrent decode == chunked prefill."""
    ks = jax.random.split(KEY, 2)
    d, nh, hd, st = 32, 2, 8, 8
    p = ssm.init_ssm_params(ks[0], d, nh, hd, st, jnp.float32)
    x = jax.random.normal(ks[1], (1, 16, d)) * 0.3
    y_par, (h_par, conv_par) = ssm.mamba_forward(
        p, x, n_heads=nh, head_dim=hd, state=st, chunk=8)
    # recurrent: feed one token at a time
    h = jnp.zeros((1, nh, hd, st), jnp.float32)
    conv = jnp.zeros((1, ssm.CONV_W - 1, nh * hd), jnp.float32)
    ys = []
    for t in range(16):
        y_t, (h, conv) = ssm.mamba_forward(
            p, x[:, t:t + 1], n_heads=nh, head_dim=hd, state=st,
            ssm_state=h, conv_state=conv)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_par),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_par),
                               rtol=1e-4, atol=1e-4)


def test_mlstm_chunked_matches_recurrent():
    ks = jax.random.split(KEY, 5)
    B, S, nh, dqk, dv = 1, 64, 2, 8, 16
    q = jax.random.normal(ks[0], (B, S, nh, dqk))
    k = jax.random.normal(ks[1], (B, S, nh, dqk))
    v = jax.random.normal(ks[2], (B, S, nh, dv))
    ig = jax.random.normal(ks[3], (B, S, nh))
    fg = jax.random.normal(ks[4], (B, S, nh)) + 2.0
    h_par, (H_par, m_par) = xlstm.mlstm_chunked(q, k, v, ig, fg, chunk=16)
    h_seq, (H_seq, m_seq) = mlstm_ref(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(h_par), np.asarray(h_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(H_par), np.asarray(H_seq),
                               rtol=1e-3, atol=1e-3)


ARCHS_DECODE = ["qwen2-1.5b", "hymba-1.5b", "xlstm-125m",
                "granite-moe-1b-a400m"]


@pytest.mark.parametrize("arch,steps,window", [
    *(pytest.param(a, 1, None, id=a) for a in ARCHS_DECODE),
    # several steps, the cache carried and donated, the last one in the
    # cache's last slot
    *(pytest.param(a, 4, None, id=f"{a}-4steps") for a in ARCHS_DECODE),
    # a ring buffer the prompt fills: each step overwrites its oldest slot
    pytest.param("hymba-1.5b", 4, 8, id="hymba-1.5b-4steps-ring")])
def test_prefill_then_decode_matches_forward(arch, steps, window):
    """logits(prefill(x[:S]) + decode x[S..t]) == logits(forward(x[:t+1]))
    at every decoded position t."""
    import dataclasses
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        # capacity drops legitimately differ between a 16-token and a
        # 17-token dispatch; disable drops for the consistency check
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    if window is not None:
        cfg = dataclasses.replace(cfg, window=window)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    S = 16
    opts = T.ModelOptions(q_chunk=8, kv_chunk=8, ssm_chunk=4, loss_chunk=8)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S + steps), 0,
                                cfg.vocab)
    # full forward logits at positions S.. (each predicting the next)
    hidden, _ = T.forward(params, cfg, tokens, opts=opts)
    from repro.models.layers import rms_norm
    want = (rms_norm(hidden[:, S:], params["final_norm"])
            @ params["unembed"]).astype(jnp.float32)
    # prefill on S tokens, grow cache to S+steps slots (as the serve
    # driver does), then decode tokens S.. with the cache donated
    from repro.launch.serve import _grow_cache
    _, cache = T.prefill(params, cfg, tokens[:, :S], opts=opts)
    cache = _grow_cache(cfg, cache, 1, S + steps, S)
    step = jax.jit(lambda p, c, pos, tok: T.decode_step(
        p, cfg, c, token=tok, pos=pos, opts=opts), donate_argnums=(1,))
    for i in range(steps):
        got, cache = step(params, cache, jnp.int32(S + i), tokens[:, S + i])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, i]),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"position {S + i}")


def test_loss_label_masking():
    cfg = get_config("qwen2-1.5b").reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opts = T.ModelOptions(q_chunk=8, kv_chunk=8, loss_chunk=8)
    tokens = jnp.ones((1, 16), jnp.int32)
    all_masked = {"tokens": tokens,
                  "labels": jnp.full((1, 16), -100, jnp.int32)}
    loss, metrics = T.loss_fn(params, cfg, all_masked, opts=opts)
    assert float(metrics["ntok"]) == 0
    assert float(metrics["nll"]) == 0.0
