"""Plain reference of qwen2-1.5b, and the operations and bytes its
programs need.

The forward pass is written out in ``jax.numpy`` at float32 with the
highest matmul precision, one layer at a time, and imports nothing of
the program.  The benchmark makes the weights from the seed by the
source's recipe (``weights``) and hands them to the program; the
reference draws the same bfloat16 numbers from the same seed itself.

Decoder layer (arXiv:2407.10671, as the program runs it)::

    h = rmsnorm(x) ; q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k)                  # half-split, theta 1e6
    x = x + softmax(q k^T / sqrt(128), causal) v Wo   # 12 q / 2 kv heads
    x = x + (silu(rmsnorm(x) W1) * rmsnorm(x) W3) W2
    logits = rmsnorm(x) E^T                  # head tied to the embedding
"""
from __future__ import annotations

import functools

import numpy as np

EPS = 1e-6


def sizes(config: dict) -> dict:
    p = config["program"]
    return {"L": p["n_layers"], "d": p["d_model"], "H": p["n_heads"],
            "Hkv": p["n_kv_heads"], "D": p["head_dim"], "F": p["d_ff"],
            "V": p["vocab"], "theta": p["rope_theta"]}


# ---------------------------------------------------------------------------
# operations and bytes (used by the metric readers)
# ---------------------------------------------------------------------------
def layer_matmul_params(config: dict) -> int:
    s = sizes(config)
    d, H, Hkv, D, F = s["d"], s["H"], s["Hkv"], s["D"], s["F"]
    return d * (H + 2 * Hkv) * D + H * D * d + 3 * d * F


def weight_bytes(config: dict) -> int:
    """bf16 bytes a decode step reads once: every layer's weights,
    biases and norms, the final norm and the output head (the embedding
    table is gathered, a row per token)."""
    s = sizes(config)
    d, H, Hkv, D = s["d"], s["H"], s["Hkv"], s["D"]
    per_layer = layer_matmul_params(config) + (H + 2 * Hkv) * D + 2 * d
    return 2 * (s["L"] * per_layer + d + d * s["V"])


def token_flops(config: dict, ctx: int, logits: bool) -> float:
    """Operations one token needs at position ``ctx - 1`` (it attends to
    ``ctx`` keys): the matmuls of every layer, causal attention, and the
    output head where the program computes logits for it."""
    s = sizes(config)
    attn = 4 * s["H"] * s["D"] * ctx
    f = s["L"] * (2 * layer_matmul_params(config) + attn)
    return float(f + (2 * s["d"] * s["V"] if logits else 0))


def prefill_flops(config: dict, batch: int, prompt_len: int) -> float:
    """One prefill: every prompt token, logits at the last one only."""
    per_row = sum(token_flops(config, c + 1, False)
                  for c in range(prompt_len))
    s = sizes(config)
    return batch * (per_row + 2 * s["d"] * s["V"])


def decode_flops(config: dict, batch: int, pos: int) -> float:
    """One decode step of ``batch`` tokens at position ``pos``."""
    return batch * token_flops(config, pos + 1, True)


def decode_bytes(config: dict, batch: int, pos: int) -> float:
    """Bytes one decode step at ``pos`` needs: the weights once, the KV
    cache up to and including ``pos`` read, the new K and V written, the
    embedding rows gathered and the float32 logits written.  A copy of
    the whole cache is not needed and is not counted."""
    s = sizes(config)
    kv_row = 2 * s["Hkv"] * s["D"] * 2           # K and V, bf16, per layer
    kv = s["L"] * kv_row * batch * (pos + 1 + 1)
    return float(weight_bytes(config) + kv + batch * s["d"] * 2
                 + batch * s["V"] * 4)


# ---------------------------------------------------------------------------
# weights, drawn from the seed by the source's recipe
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _weights_fn(L, d, H, Hkv, D, F, V, std, dtype, untied_copy):
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def layer(key):
        k = jax.random.split(key, 10)
        return {"ln1": jnp.ones((d,), dt),
                "attn": {"wq": normal(k[0], (d, H, D)),
                         "wk": normal(k[1], (d, Hkv, D)),
                         "wv": normal(k[2], (d, Hkv, D)),
                         "wo": normal(k[3], (H, D, d)),
                         "bq": normal(k[4], (H, D)),
                         "bk": normal(k[5], (Hkv, D)),
                         "bv": normal(k[6], (Hkv, D))},
                "ln2": jnp.ones((d,), dt),
                "ffn": {"w1": normal(k[7], (d, F)),
                        "w3": normal(k[8], (d, F)),
                        "w2": normal(k[9], (F, d))}}

    @jax.jit
    def make(seed):
        k_emb, k_layers = jax.random.split(jax.random.PRNGKey(seed))
        w = {"embed": normal(k_emb, (V, d)),
             "final_norm": jnp.ones((d,), dt),
             "layers": {"e0": jax.vmap(layer)(
                 jax.random.split(k_layers, L))}}
        if untied_copy:
            w["unembed"] = w["embed"].T
        return w
    return make


def weights(config: dict, seed: int, untied_copy: bool = False):
    """The model's weights, made on the device in one call from ``seed``:
    every matrix, the embedding and the biases drawn from N(0,
    ``initializer_range``), the norms at 1, the output head tied to the
    embedding.  ``untied_copy`` adds the head as a leaf of its own
    (``unembed`` = embedding transposed), for a program that keeps one."""
    s = sizes(config)
    return _weights_fn(s["L"], s["d"], s["H"], s["Hkv"], s["D"], s["F"],
                       s["V"], float(config["initializer_range"]),
                       config["program"]["dtype"], untied_copy)(
                           np.uint32(seed))


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------
def _quant(x, dtype):
    """Round ``x`` to ``dtype`` (a float8 type) with one scale per
    tensor, and back to float32: the control's lower precision.  The
    gradient passes straight through (a float8 cotangent would
    overflow)."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    xs = jax.lax.stop_gradient(x)
    scale = jnp.maximum(jnp.max(jnp.abs(xs)), 1e-30) / float(
        jnp.finfo(dtype).max)
    q = (xs / scale).astype(dtype).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _ein(spec, a, b, low):
    import jax
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low is not None:
        a, b = _quant(a, low), _quant(b, low)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * w.astype(jnp.float32)


def _rope(x, theta):
    import jax.numpy as jnp
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=8)
def _layer_fn(H, Hkv, theta, low):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, w):
        B, S, _ = x.shape
        G = H // Hkv
        h = _rms(x, w["ln1"])
        a, ffn = w["attn"], w["ffn"]
        f32 = lambda a: a.astype(jnp.float32)
        q = _ein("bsd,dhk->bshk", h, a["wq"], low) + f32(a["bq"])
        k = _ein("bsd,dhk->bshk", h, a["wk"], low) + f32(a["bk"])
        v = _ein("bsd,dhk->bshk", h, a["wv"], low) + f32(a["bv"])
        q, k = _rope(q, theta), _rope(k, theta)
        D = q.shape[-1]
        qg = q.reshape(B, S, Hkv, G, D)
        s = _ein("bqhgd,bkhd->bhgqk", qg, k, low) * D ** -0.5
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _ein("bhgqk,bkhd->bqhgd", p, v, low).reshape(B, S, H, D)
        x = x + _ein("bshk,hkd->bsd", o, a["wo"], low)
        h2 = _rms(x, w["ln2"])
        g = jax.nn.silu(_ein("bsd,df->bsf", h2, ffn["w1"], low))
        u = _ein("bsd,df->bsf", h2, ffn["w3"], low)
        return x + _ein("bsf,fd->bsd", g * u, ffn["w2"], low)
    return layer


@functools.lru_cache(maxsize=4)
def _head_fn(low):
    import jax

    @jax.jit
    def head(x, final_norm, unembed):
        return _ein("bsd,dv->bsv", _rms(x, final_norm), unembed, low)
    return head


def logits_at(config: dict, w, tokens: np.ndarray, first: int,
              low=None):
    """float32 logits at positions ``first..S-1`` of each row of
    ``tokens`` (B, S).  ``low`` (a float8 type) computes every matmul
    from operands rounded to it: the control."""
    import jax
    import jax.numpy as jnp
    s = sizes(config)
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    layer = _layer_fn(s["H"], s["Hkv"], s["theta"], low)
    for i in range(s["L"]):
        x = layer(x, jax.tree.map(lambda a: a[i], w["layers"]["e0"]))
    return _head_fn(low)(x[:, first:], w["final_norm"], w["embed"].T)


def _rows(prompts, served, rows_per_block):
    n = prompts.shape[0]
    for lo in range(0, n, rows_per_block):
        hi = min(n, lo + rows_per_block)
        seq = np.concatenate([prompts[lo:hi], served[lo:hi, :-1]], axis=1)
        yield lo, hi, seq


def rows_per_block(config: dict, seq_len: int) -> int:
    """Rows of one reference block: the attention scores of a layer
    (rows x heads x S x S float32) kept near 1 GiB."""
    s = sizes(config)
    per_row = s["H"] * seq_len * seq_len * 4 * 3
    return max(1, int(2 ** 30 // max(per_row, 1)))


def served_logit_gaps(config: dict, seed: int, prompts: np.ndarray,
                      served: np.ndarray, low=None) -> np.ndarray:
    """For each served token: how far the reference's logit of it lies
    below the reference's best logit at that position (0 where they
    agree).  ``prompts`` (N, P), ``served`` (N, G) greedy tokens.

    With ``low`` set, the tokens judged are those the reference computed
    in that precision puts first at each position of the same sequences
    (the control), not the served ones."""
    import jax.numpy as jnp
    w = weights(config, seed)
    P = prompts.shape[1]
    out = []
    for lo, hi, seq in _rows(prompts, served,
                             rows_per_block(config, seq_len=P
                                            + served.shape[1])):
        ref = logits_at(config, w, seq, P - 1)
        if low is None:
            judged = jnp.asarray(served[lo:hi])
        else:
            judged = jnp.argmax(logits_at(config, w, seq, P - 1, low), -1)
        best = jnp.max(ref, -1)
        mine = jnp.take_along_axis(ref, judged[..., None], -1)[..., 0]
        out.append(np.asarray(best - mine))
    return np.concatenate(out)


def readings(config: dict, seed: int, checked, low=None) -> dict:
    """The numbers ``correct`` compares, for the sampled requests
    ``checked`` = (prompts, served tokens): the widest gap by which a
    served token's reference logit lies below the reference's best.
    ``low`` reads the control instead (see ``served_logit_gaps``)."""
    prompts, served = checked
    gaps = served_logit_gaps(config, seed, prompts, served, low=low)
    return {"max_logit_gap": float(np.max(gaps)),
            "p99_logit_gap": float(np.percentile(gaps, 99)),
            "mean_logit_gap": float(np.mean(gaps))}


def control_readings(config: dict, seed: int, checked) -> dict:
    """The control: the reference in float8 (e4m3) in the program's
    place, the step below the bfloat16 the configuration states."""
    import jax.numpy as jnp
    return readings(config, seed, checked, low=jnp.float8_e4m3fn)
