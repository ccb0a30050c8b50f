"""Multi-head latent attention (MLA), as DeepSeek-V2/V3 define it, with no
query compression::

    h = rms(x); [q_nope | q_pe] = h Wq            # per head nope | rope
    [c | k_pe] = h Wkva; c = rms(c)               # latent, one roped key
    q_pe, k_pe = rope(q_pe), rope(k_pe)           # interleaved pairs
    k_nope = c Wkb; v = c Wvb                     # per head
    y = softmax([q_nope | q_pe] [k_nope | k_pe]^T / sqrt(nope + rope)) v Wo

A token's cache row is ``c`` (kv_lora_rank values) and ``k_pe`` (rope
values), shared by every head and kept as two caches: laid out with
the 512 latent values minor, writing one token's ``c`` touches one row
of tiles (one 576-wide cache is laid out slot-minor, and a row write
touches every tile).  Prefill expands K and V from the latent; decode
reads only the rows, with ``Wkb`` absorbed into the query
(``q_nope Wkb^T`` is a query in the latent space, scored against ``c``)
and ``Wvb`` into the output (the probabilities average ``c``, which
``Wvb`` then lifts to each head's value).  On a TPU that average is one
Pallas kernel (``kernels/mla_decode.py``) that reads each cache row up
to the position once; elsewhere two einsums over every slot, masked,
compute it and are the kernel's reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import NEG_INF, chunked_attention
from repro.models.layers import apply_rope, dense_init, rms_norm, rope_freqs


def init_mla_params(key, cfg, dtype) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], (d, h, nope + rope), dtype),
        "wkva": dense_init(ks[1], (d, r + rope), dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkb": dense_init(ks[2], (r, h, nope), dtype),
        "wvb": dense_init(ks[3], (r, h, dv), dtype),
        "wo": dense_init(ks[4], (h, dv, d), dtype),
    }


def _rope_pairs(x, positions, theta):
    """Rotary embedding of interleaved pairs (x[2i], x[2i+1]), as the
    published modeling code's permute before a half-split rotation: the
    output is in that permuted order, which scores do not see."""
    D = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (D // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (D,))
    cos, sin = rope_freqs(positions, D, theta)
    return apply_rope(x, cos, sin)


def project(params, h, cfg, positions):
    """h: (B, S, d) normed.  Returns q_nope (B,S,H,nope), q_pe (B,S,H,rope)
    roped, and the cache rows: the normed latent c (B,S,r) and the roped
    key k_pe (B,S,rope), in h's dtype."""
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla_q"):
        q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
        ckv = jnp.einsum("bsd,dr->bsr", h, params["wkva"])
        c = rms_norm(ckv[..., :r], params["kv_norm"], cfg.norm_eps)
        q_pe = _rope_pairs(q[..., nope:], positions, cfg.rope_theta)
        k_pe = _rope_pairs(ckv[..., None, r:], positions,
                           cfg.rope_theta)[:, :, 0]
    return q[..., :nope], q_pe, (c.astype(h.dtype), k_pe.astype(h.dtype))


def mla_attention(params, h, cfg, positions, *, q_chunk: int,
                  kv_chunk: int, schedule: str = "dense"):
    """Training and prefill: causal attention with K and V expanded from
    the latent.  Returns (y (B,S,d), cache rows (c, k_pe))."""
    q_nope, q_pe, (c, k_pe) = project(params, h, cfg, positions)
    H = q_nope.shape[2]
    with jax.named_scope("mla_expand"):
        k_nope = jnp.einsum("bsr,rhn->bshn", c, params["wkb"])
        v = jnp.einsum("bsr,rhv->bshv", c, params["wvb"])
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None],
                                      k_pe.shape[:2] + (H,)
                                      + k_pe.shape[2:])], axis=-1)
        dq, dv = q.shape[-1], v.shape[-1]
        # the attention core takes one head width: V padded to the
        # query's with zeros, and the padding sliced off its output
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, dq - dv),))
    out = chunked_attention(q, k, v, q_chunk=q_chunk, kv_chunk=kv_chunk,
                            schedule=schedule)[..., :dv]
    with jax.named_scope("o_proj"):
        y = jnp.einsum("bshv,hvd->bsd", out, params["wo"])
    return y, (c, k_pe)


def _use_kernel() -> bool:
    """The Pallas kernel runs on a TPU; elsewhere the einsums below are
    the path and its reference."""
    return jax.default_backend() == "tpu"


def mla_decode(params, h, cfg, positions, c_cache, pe_cache, pos, layer):
    """One decode step.  h: (B, 1, d) normed; c_cache (L, B, Smax, r) and
    pe_cache (L, B, Smax, rope) are every layer's caches, of which
    ``layer``'s is read: the rows of positions before ``pos``; the slots
    from ``pos`` on are masked, and the new token's own row is one more
    column of the same softmax.  The caches are only read.  Returns
    (y (B,1,d), the new rows (c (B,1,r), k_pe (B,1,rope)) for the caller
    to write at slot ``pos``)."""
    q_nope, q_pe, (c, k_pe) = project(params, h, cfg, positions)
    c_new = c[:, 0].astype(c_cache.dtype)                    # (B, r)
    pe_new = k_pe[:, 0].astype(pe_cache.dtype)               # (B, rope)
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0],
                           params["wkb"]).astype(c_cache.dtype)
        q_pe = q_pe[:, 0].astype(pe_cache.dtype)
    if _use_kernel():
        from repro.kernels import ops
        with jax.named_scope("mla_scores"):
            o = ops.mla_decode(q_lat, q_pe, c_new, pe_new, c_cache,
                               pe_cache, pos, layer, scale=scale)
    else:
        o = _attend(q_lat, q_pe, c_new, pe_new, c_cache[layer],
                    pe_cache[layer], pos, scale)
    with jax.named_scope("mla_out"):
        ov = jnp.einsum("bhr,rhv->bhv", o.astype(h.dtype), params["wvb"])
        y = jnp.einsum("bhv,hvd->bd", ov, params["wo"])
    return y[:, None], (c_new[:, None], pe_new[:, None])


def _attend(q_lat, q_pe, c_new, pe_new, c_cache, pe_cache, pos, scale):
    """The kernel's reference: masked scores over all of one layer's
    slots, then the probabilities against the same cache.  Returns
    (B, H, r) in f32."""
    with jax.named_scope("mla_scores"):
        valid = jnp.arange(c_cache.shape[1]) < pos
        f32 = jnp.float32
        s = (jnp.einsum("bhr,bsr->bhs", q_lat, c_cache,
                        preferred_element_type=f32)
             + jnp.einsum("bhp,bsp->bhs", q_pe, pe_cache,
                          preferred_element_type=f32)) * scale
        s = jnp.where(valid[None, None], s, NEG_INF)
        s_new = (jnp.einsum("bhr,br->bh", q_lat, c_new,
                            preferred_element_type=f32)
                 + jnp.einsum("bhp,bp->bh", q_pe, pe_new,
                              preferred_element_type=f32)) * scale
        m = jnp.maximum(s.max(axis=-1), s_new)
        e = jnp.exp(s - m[..., None])
        e_new = jnp.exp(s_new - m)
        denom = e.sum(axis=-1) + e_new
    with jax.named_scope("mla_out"):
        o = jnp.einsum("bhs,bsr->bhr", (e / denom[..., None]).astype(
            c_cache.dtype), c_cache, preferred_element_type=f32)
        return o + (e_new / denom)[..., None] * c_new[:, None].astype(f32)
