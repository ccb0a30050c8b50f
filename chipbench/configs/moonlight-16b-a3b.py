"""Plain reference of Moonlight-16B-A3B as one chip's share of an
expert-parallel deployment, and the operations and bytes its programs
need.

The forward pass is written out in ``jax.numpy`` at float32 with the
highest matmul precision, one layer at a time, with each layer's weights
drawn from the seed as it is reached, and imports nothing of the
program.  Attention is computed expanded (no absorption) and the
experts by a loop over the experts held here.  The benchmark makes the
same weights from the same seed (``weights``) and hands them to the
program.

Decoder layer (DeepSeek-V3, ``q_lora_rank`` null; the source's
config.json)::

    h = rms(x); [q_nope | q_pe] = h Wq              # per head 128 | 64
    [c | k_pe] = h Wkva; c = rms(c)                 # 512 | 64
    q_pe, k_pe = rope(q_pe), rope(k_pe)             # interleaved pairs
    k_nope, v = c Wkb, c Wvb                        # per head 128, 128
    x = x + softmax([q_nope|q_pe][k_nope|k_pe]^T / sqrt(192), causal) v Wo
    h = rms(x)
    layer 0:   x = x + swiglu_11264(h)
    layers 1-: s = sigmoid(h Wr)                    # 64 routed experts
               sel = top6(s + bias); g = s[sel] / sum(s[sel]) * 2.446
               x = x + sum_{e in sel, held here} g_e swiglu_1408,e(h)
                     + swiglu_2816(h)               # 2 shared experts
    logits = rms(x) Whead                           # untied

This chip holds experts [first, first + held) of each MoE layer
(``program.experts_first``, ``program.experts_held``); the router keeps
all ``deployment.n_routed_experts`` and selects over them, and what the
experts held elsewhere would add is left out, here as in the program.
"""
from __future__ import annotations

import functools

import numpy as np


def sizes(config: dict) -> dict:
    p = config["program"]
    return {"L": p["n_layers"], "lead": p["first_dense_layers"],
            "d": p["d_model"], "H": p["n_heads"], "r": p["kv_lora_rank"],
            "nope": p["qk_nope_head_dim"], "rope": p["qk_rope_head_dim"],
            "dv": p["v_head_dim"], "F": p["d_ff"], "V": p["vocab"],
            "theta": float(p["rope_theta"]), "eps": float(p["norm_eps"]),
            "held": p["experts_held"], "first": p["experts_first"],
            "E": config["deployment"]["n_routed_experts"],
            "K": config["num_experts_per_tok"],
            "Fe": config["moe_intermediate_size"],
            "Fs": config["moe_intermediate_size"]
            * config["n_shared_experts"],
            "scale": float(config["routed_scaling_factor"])}


# ---------------------------------------------------------------------------
# operations and bytes (used by the metric readers)
# ---------------------------------------------------------------------------
def mla_params(config: dict) -> int:
    """Parameters of one layer's latent attention (bf16)."""
    s = sizes(config)
    d, H, r, qk = s["d"], s["H"], s["r"], s["nope"] + s["rope"]
    return (d * H * qk + d * (r + s["rope"]) + r
            + r * H * (s["nope"] + s["dv"]) + H * s["dv"] * d)


def expert_bytes(config: dict) -> int:
    """bf16 bytes of the routed experts held here, over all MoE layers."""
    s = sizes(config)
    return 2 * (s["L"] - s["lead"]) * s["held"] * 3 * s["d"] * s["Fe"]


def weight_bytes(config: dict) -> int:
    """Bytes a decode step reads once: every layer's weights and norms
    (the router and its bias in float32), the final norm and the output
    head; the embedding is gathered, a row per token."""
    s = sizes(config)
    d, n_moe = s["d"], s["L"] - s["lead"]
    attn = 2 * (mla_params(config) + 2 * d)
    dense = 2 * 3 * d * s["F"]
    moe_rest = 4 * (d * s["E"] + s["E"]) + 2 * 3 * d * s["Fs"]
    return (s["L"] * attn + s["lead"] * dense + n_moe * moe_rest
            + expert_bytes(config) + 2 * (d + d * s["V"]))


def latent_bytes(config: dict, batch: int, pos: int) -> float:
    """The latent cache a decode step at ``pos`` needs: the rows of the
    ``pos`` earlier positions read and the new row written, a layer."""
    s = sizes(config)
    return float(s["L"] * batch * (pos + 1) * (s["r"] + s["rope"]) * 2)


def mla_token_flops(config: dict, ctx: int) -> float:
    """One token's latent attention in one layer, absorbed, against
    ``ctx`` keys (its own included)."""
    s = sizes(config)
    d, H, r, nope, rope, dv = (s["d"], s["H"], s["r"], s["nope"],
                               s["rope"], s["dv"])
    return float(2 * d * H * (nope + rope) + 2 * d * (r + rope)
                 + 2 * H * nope * r + 2 * H * (r + rope) * ctx
                 + 2 * H * r * ctx + 2 * H * r * dv + 2 * H * dv * d)


def routed_rows(config: dict, tokens: int) -> float:
    """Rows routed to the experts held here, over all MoE layers, for
    ``tokens`` tokens of uniform routing."""
    s = sizes(config)
    return tokens * s["K"] * s["held"] / s["E"] * (s["L"] - s["lead"])


def _ffn_flops(config: dict, tokens: int, rows: float) -> float:
    s = sizes(config)
    d, n_moe = s["d"], s["L"] - s["lead"]
    return (tokens * (s["lead"] * 6 * d * s["F"]
                      + n_moe * (2 * d * s["E"] + 6 * d * s["Fs"]))
            + rows * 6 * d * s["Fe"])


def decode_flops(config: dict, batch: int, pos: int) -> float:
    """One decode step of ``batch`` tokens at position ``pos``: absorbed
    attention over ``pos + 1`` keys, the dense and shared FFNs, the
    router, the held experts for the rows routed to them, and logits."""
    s = sizes(config)
    return (batch * (s["L"] * mla_token_flops(config, pos + 1)
                     + 2 * s["d"] * s["V"])
            + _ffn_flops(config, batch, routed_rows(config, batch)))


def decode_bytes(config: dict, batch: int, pos: int) -> float:
    """Bytes one decode step at ``pos`` needs: the weights once, the
    latent cache up to ``pos`` read and the new row written, the
    embedding rows gathered and the float32 logits written."""
    s = sizes(config)
    return float(weight_bytes(config) + latent_bytes(config, batch, pos)
                 + batch * s["d"] * 2 + batch * s["V"] * 4)


def prefill_flops(config: dict, batch: int, prompt_len: int) -> float:
    """One prefill: every prompt token through attention expanded from
    the latent (causal), the FFNs and the held experts, logits at the
    last one only."""
    s = sizes(config)
    d, H, r, nope, rope, dv = (s["d"], s["H"], s["r"], s["nope"],
                               s["rope"], s["dv"])
    per_tok = (2 * d * H * (nope + rope) + 2 * d * (r + rope)
               + 2 * r * H * (nope + dv) + 2 * H * dv * d)
    attn = sum(2 * H * (nope + rope + dv) * (c + 1)
               for c in range(prompt_len))
    tokens = batch * prompt_len
    return float(s["L"] * (tokens * per_tok + batch * attn)
                 + _ffn_flops(config, tokens, routed_rows(config, tokens))
                 + batch * 2 * d * s["V"])


def mla_decode_flops(config: dict, batch: int, pos: int) -> float:
    """The latent attention of one decode step, every layer."""
    s = sizes(config)
    return batch * s["L"] * mla_token_flops(config, pos + 1)


def mla_decode_bytes(config: dict, batch: int, pos: int) -> float:
    """The latent attention of one decode step: its weights once and the
    latent cache up to ``pos``."""
    s = sizes(config)
    return float(s["L"] * 2 * (mla_params(config) + s["r"])
                 + latent_bytes(config, batch, pos))


def expert_flops(config: dict, rows: float) -> float:
    """The held experts' three matmuls over ``rows`` routed rows."""
    s = sizes(config)
    return rows * 6 * s["d"] * s["Fe"]


def expert_io_bytes(config: dict, rows: float) -> float:
    """The held experts' weights once and their ``rows`` rows in and
    out (bf16)."""
    s = sizes(config)
    return float(expert_bytes(config) + rows * 2 * s["d"] * 2)


# ---------------------------------------------------------------------------
# weights, drawn from the seed layer by layer
# ---------------------------------------------------------------------------
def _draw(s: dict, std: float, dtype: str, moe: bool):
    """key -> one layer's weights, as the program holds them."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    d, H, r = s["d"], s["H"], s["r"]

    def normal(key, shape, t=dt):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(t)

    def experts(key, shape):
        # expert e's weights depend on its id alone, so that the shares
        # of a deployment are shares of one model
        ids = s["first"] + jnp.arange(s["held"])
        return jax.vmap(lambda e: normal(jax.random.fold_in(key, e),
                                         shape))(ids)

    def layer(key):
        k = jax.random.split(key, 11)
        w = {"ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt),
             "attn": {"wq": normal(k[0], (d, H, s["nope"] + s["rope"])),
                      "wkva": normal(k[1], (d, r + s["rope"])),
                      "kv_norm": jnp.ones((r,), dt),
                      "wkb": normal(k[2], (r, H, s["nope"])),
                      "wvb": normal(k[3], (r, H, s["dv"])),
                      "wo": normal(k[4], (H, s["dv"], d))}}
        if not moe:
            w["ffn"] = {"w1": normal(k[5], (d, s["F"])),
                        "w3": normal(k[6], (d, s["F"])),
                        "w2": normal(k[7], (s["F"], d))}
            return w
        w["moe"] = {"router": normal(k[5], (d, s["E"]), jnp.float32),
                    "bias": normal(k[6], (s["E"],), jnp.float32),
                    "w1": experts(k[7], (d, s["Fe"])),
                    "w3": experts(k[8], (d, s["Fe"])),
                    "w2": experts(k[9], (s["Fe"], d))}
        ks = jax.random.split(k[10], 3)
        w["shared"] = {"w1": normal(ks[0], (d, s["Fs"])),
                       "w3": normal(ks[1], (d, s["Fs"])),
                       "w2": normal(ks[2], (s["Fs"], d))}
        return w
    return layer


def _keys(seed):
    import jax
    return jax.random.split(jax.random.PRNGKey(seed), 4)


def _spec(config: dict):
    s = sizes(config)
    return (tuple(sorted(s.items())), float(config["initializer_range"]),
            config["program"]["dtype"])


@functools.lru_cache(maxsize=4)
def _weights_fn(spec):
    import jax
    import jax.numpy as jnp
    s, std, dtype = dict(spec[0]), spec[1], spec[2]
    dt = jnp.dtype(dtype)

    @jax.jit
    def make(seed):
        k_emb, k_head, k_lead, k_moe = _keys(seed)

        def stack(key, n, moe):
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(n))
            return jax.vmap(_draw(s, std, dtype, moe))(keys)
        normal = lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                                   * std).astype(dt)
        return {"embed": normal(k_emb, (s["V"], s["d"])),
                "unembed": normal(k_head, (s["d"], s["V"])),
                "final_norm": jnp.ones((s["d"],), dt),
                "lead": {"d0": stack(k_lead, s["lead"], False)},
                "layers": {"e0": stack(k_moe, s["L"] - s["lead"], True)}}
    return make


def weights(config: dict, seed: int, untied_copy: bool = False):
    """The program's parameters, made on the device in one call from
    ``seed``: every matrix, the embedding, the head and the selection
    bias N(0, ``initializer_range``), norms 1.  The head is a leaf of
    its own in the source too, so ``untied_copy`` changes nothing."""
    del untied_copy
    return _weights_fn(_spec(config))(np.uint32(seed))


@functools.lru_cache(maxsize=8)
def _layer_fn(spec, moe: bool):
    import jax
    s, std, dtype = dict(spec[0]), spec[1], spec[2]

    @jax.jit
    def make(seed, i):
        k_lead, k_moe = _keys(seed)[2:]
        key = jax.random.fold_in(k_moe if moe else k_lead, i)
        return _draw(s, std, dtype, moe)(key)
    return make


def layer_weights(config: dict, seed: int, i: int):
    """Layer ``i``'s weights (counted from 0 over all layers), the same
    numbers ``weights`` stacks."""
    lead = config["program"]["first_dense_layers"]
    moe = i >= lead
    return _layer_fn(_spec(config), moe)(np.uint32(seed),
                                         i - lead if moe else i)


@functools.lru_cache(maxsize=4)
def _ends_fn(spec):
    import jax
    import jax.numpy as jnp
    s, std, dtype = dict(spec[0]), spec[1], spec[2]
    dt = jnp.dtype(dtype)

    @jax.jit
    def make(seed):
        k_emb, k_head = _keys(seed)[:2]
        normal = lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                                   * std).astype(dt)
        return (normal(k_emb, (s["V"], s["d"])),
                normal(k_head, (s["d"], s["V"])))
    return make


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------
def _quant(x, dtype):
    """Round ``x`` to ``dtype`` (a float8 type) with one scale per
    tensor, and back to float32: the control's lower precision."""
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


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """Rotary embedding of interleaved pairs (x[2i], x[2i+1]) at angle
    position * theta^(-2i/D).  x: (B, S, heads, D)."""
    import jax.numpy as jnp
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape)


def _swiglu(h, w, low):
    import jax
    g = jax.nn.silu(_ein("bsd,df->bsf", h, w["w1"], low))
    return _ein("bsf,fd->bsd", g * _ein("bsd,df->bsf", h, w["w3"], low),
                w["w2"], low)


def _attention(x, w, s, low):
    import jax
    import jax.numpy as jnp
    B, S, _ = x.shape
    a, r = w["attn"], s["r"]
    h = _rms(x, w["ln1"], s["eps"])
    q = _ein("bsd,dhk->bshk", h, a["wq"], low)
    ckv = _ein("bsd,dr->bsr", h, a["wkva"], low)
    c = _rms(ckv[..., :r], a["kv_norm"], s["eps"])
    q_nope, q_pe = q[..., :s["nope"]], _rope(q[..., s["nope"]:], s["theta"])
    k_pe = _rope(ckv[..., None, r:], s["theta"])[:, :, 0]
    k_nope = _ein("bsr,rhn->bshn", c, a["wkb"], low)
    v = _ein("bsr,rhv->bshv", c, a["wvb"], low)
    sc = (_ein("bqhn,bkhn->bhqk", q_nope, k_nope, low)
          + _ein("bqhp,bkp->bhqk", q_pe, k_pe, low))
    sc = sc * (s["nope"] + s["rope"]) ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -jnp.inf)
    o = _ein("bhqk,bkhv->bqhv", jax.nn.softmax(sc, axis=-1), v, low)
    return x + _ein("bqhv,hvd->bqd", o, a["wo"], low)


def route(h, w, s, low=None):
    """DeepSeek-V3 routing of ``h`` (B, S, d): sigmoid scores of float32
    logits over all experts, the top-K of scores + bias selected, gates
    the selected scores normalised to sum 1, times the scaling factor.
    Returns (selected expert ids (B,S,K), gates (B,S,K))."""
    import jax
    import jax.numpy as jnp
    sc = jax.nn.sigmoid(_ein("bsd,de->bse", h, w["router"], low))
    _, idx = jax.lax.top_k(sc + w["bias"], s["K"])
    g = jnp.take_along_axis(sc, idx, -1)
    return idx, g / g.sum(-1, keepdims=True) * s["scale"]


def _moe(h, w, s, low):
    """The held experts' part for the rows routed to them, by a loop
    over the held experts, plus the shared experts."""
    import jax
    import jax.numpy as jnp
    m = w["moe"]
    idx, g = route(h, m, s, low)
    y = _swiglu(h, w["shared"], low)
    for e in range(s["held"]):
        ge = jnp.sum(jnp.where(idx == s["first"] + e, g, 0.0), -1)
        we = jax.tree.map(lambda a: a[e], {k: m[k] for k in
                                           ("w1", "w2", "w3")})
        y = y + ge[..., None] * _swiglu(h, we, low)
    return y, idx


def _flips(h, m, s, idx):
    """(token, layer) pairs whose selected experts change when the
    router's input is rounded to bfloat16, the program's precision."""
    import jax.numpy as jnp
    idx16, _ = route(h.astype(jnp.bfloat16), m, s)
    same = jnp.all(jnp.sort(idx16, -1) == jnp.sort(idx, -1), -1)
    return jnp.sum(~same)


@functools.lru_cache(maxsize=16)
def _layer_forward(spec, moe: bool, low):
    import jax
    s = dict(spec[0])

    @jax.jit
    def layer(x, w):
        x = _attention(x, w, s, low)
        h = _rms(x, w["ln2"], s["eps"])
        if not moe:
            return x + _swiglu(h, w["ffn"], low), 0
        y, idx = _moe(h, w, s, low)
        return x + y, _flips(h, w["moe"], s, idx)
    return layer


@functools.lru_cache(maxsize=4)
def _head_fn(spec, low):
    import jax
    s = dict(spec[0])

    @jax.jit
    def head(x, final_norm, unembed):
        return _ein("bsd,dv->bsv", _rms(x, final_norm, s["eps"]), unembed,
                    low)
    return head


def logits_at(config: dict, seed: int, tokens: np.ndarray, first: int,
              low=None):
    """(float32 logits at positions ``first..S-1`` of each row of
    ``tokens`` (B, S), routing flips counted).  ``low`` (a float8 type)
    computes every matmul from operands rounded to it: the control."""
    import jax.numpy as jnp
    spec = _spec(config)
    s = sizes(config)
    embed, head = _ends_fn(spec)(np.uint32(seed))
    x = jnp.take(embed, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    del embed
    flips = 0
    for i in range(s["L"]):
        moe = i >= s["lead"]
        x, f = _layer_forward(spec, moe, low)(
            x, layer_weights(config, seed, i))
        flips += int(f)
    ones = jnp.ones((s["d"],), jnp.float32)
    return _head_fn(spec, low)(x[:, first:], ones, head), flips


def rows_per_block(config: dict, seq_len: int) -> int:
    """Rows of one reference block: a layer's attention scores (rows x
    heads x S x S float32, a few alive at once) kept near 1 GiB."""
    s = sizes(config)
    per_row = s["H"] * seq_len * seq_len * 4 * 3
    return max(1, int(2 ** 30 // max(per_row, 1)))


def served_logit_gaps(config: dict, seed: int, prompts: np.ndarray,
                      served: np.ndarray, low=None):
    """For each served token: how far the reference's logit of it lies
    below the reference's best logit at that position (0 where they
    agree), and the routing flips of the sequences.  ``prompts`` (N, P),
    ``served`` (N, G) greedy tokens.

    With ``low`` set, the tokens judged are those the reference computed
    in that precision puts first at each position of the same sequences
    (the control), not the served ones."""
    import jax.numpy as jnp
    P = prompts.shape[1]
    n = prompts.shape[0]
    block = rows_per_block(config, P + served.shape[1])
    out, flips = [], 0
    for lo in range(0, n, block):
        hi = min(n, lo + block)
        seq = np.concatenate([prompts[lo:hi], served[lo:hi, :-1]], axis=1)
        ref, f = logits_at(config, seed, seq, P - 1)
        flips += f
        if low is None:
            judged = jnp.asarray(served[lo:hi])
        else:
            judged = jnp.argmax(logits_at(config, seed, seq, P - 1, low)[0],
                                -1)
        best = jnp.max(ref, -1)
        mine = jnp.take_along_axis(ref, judged[..., None], -1)[..., 0]
        out.append(np.asarray(best - mine))
    return np.concatenate(out), flips


def readings(config: dict, seed: int, checked, low=None) -> dict:
    """The numbers ``correct`` compares, for the sampled requests
    ``checked`` = (prompts, served tokens): the widest gap by which a
    served token's reference logit lies below the reference's best.
    ``route_flip_pct`` (not compared): the share of (token, MoE layer)
    pairs whose selected experts change when the router's input is
    rounded to bfloat16.  ``low`` reads the control instead."""
    prompts, served = checked
    gaps, flips = served_logit_gaps(config, seed, prompts, served, low=low)
    s = sizes(config)
    pairs = prompts.shape[0] * (prompts.shape[1] + served.shape[1] - 1) \
        * (s["L"] - s["lead"])
    return {"max_logit_gap": float(np.max(gaps)),
            "p99_logit_gap": float(np.percentile(gaps, 99)),
            "mean_logit_gap": float(np.mean(gaps)),
            "route_flip_pct": 100.0 * flips / pairs}


def control_readings(config: dict, seed: int, checked) -> dict:
    """The control: the reference in float8 (e4m3) in the program's
    place, the step below the bfloat16 the configuration states."""
    import jax.numpy as jnp
    return readings(config, seed, checked, low=jnp.float8_e4m3fn)
