"""GQA attention: chunked (flash-style) training/prefill, sliding-window, and
single-token decode against a KV cache.

Two causal schedules are provided for the chunked path:

- ``dense``  — every (q-chunk, kv-chunk) pair is computed and masked.  This is
  the straightforward baseline; on a causal workload it spends ~2x the useful
  FLOPs (the upper triangle is masked out but still fed to the MXU).
- ``binary`` — exact triangular schedule via balanced binary decomposition:
  the strictly-lower triangle of the chunk grid is covered by log2(n) levels
  of *unmasked* square blocks (level l has 2^l squares of side n/2^(l+1)),
  plus n masked diagonal blocks.  Compiled FLOPs ~ S^2/2 + S*c.  Used by the
  perf hillclimb (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, dense_init, pick_chunk,
                                 rms_norm, rope_freqs)

NEG_INF = -1e30


def init_attn_params(key, cfg, dtype) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, dh), dtype),
        "wk": dense_init(ks[1], (d, hkv, dh), dtype),
        "wv": dense_init(ks[2], (d, hkv, dh), dtype),
        "wo": dense_init(ks[3], (h, dh, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((hkv, dh), dtype)
        p["bv"] = jnp.zeros((hkv, dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def project_qkv(params, x, cfg, positions):
    """x: (B,S,d) -> q (B,S,H,Dh), k/v (B,S,Hkv,Dh) with rope applied."""
    with jax.named_scope("qkv_proj"):
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        if cfg.qkv_bias:
            q = q + params["bq"]
            k = k + params["bk"]
            v = v + params["bv"]
        if cfg.qk_norm:
            q = rms_norm(q, params["q_norm"])
            k = rms_norm(k, params["k_norm"])
    cos, sin = rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _merge_stats(m1, l1, o1, m2, l2, o2):
    """Combine two online-softmax stat sets over the same q rows."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return m, l1 * a1 + l2 * a2, o1 * a1[..., None] + o2 * a2[..., None]


def _block_scores(q_blk, k_blk):
    """q_blk: (..., q, Hkv, G, D); k_blk: (..., k, Hkv, D) ->
    (..., Hkv, G, q, k) fp32 scaled scores."""
    scale = q_blk.shape[-1] ** -0.5
    return jnp.einsum("...qhgd,...khd->...hgqk", q_blk, k_blk,
                      preferred_element_type=jnp.float32) * scale


def _block_attn(q_blk, k_blk, v_blk, mask, m, l, o):
    """One online-softmax update.  q_blk: (B,cq,Hkv,G,D); k/v: (B,ck,Hkv,D);
    mask: (cq,ck) boolean (True = allowed) or None."""
    s = _block_scores(q_blk, k_blk)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_blk,
                    preferred_element_type=jnp.float32)
    o_new = o * alpha[..., None] + pv
    return m_new, l_new, o_new


def chunked_attention(q, k, v, *, q_chunk: int, kv_chunk: int,
                      q_offset=0, window: int = 0,
                      schedule: str = "dense") -> jax.Array:
    """Causal flash-style attention with an O(S)-memory custom VJP.

    q: (B,S,H,D), k/v: (B,Sk,Hkv,D).  ``q_offset`` is the absolute position
    of q[0] relative to k[0] (used when a prefix of KV comes from a cache).
    Returns (B,S,H,D).

    The backward pass recomputes score blocks from the saved (q,k,v,out,
    lse) — the standard flash-attention trick — because differentiating the
    nested forward scans directly stores O(n_q x n_k) block temporaries
    (measured 80 GiB/device on qwen2 train_4k before this VJP; see
    EXPERIMENTS.md §Perf).
    """
    if isinstance(q_offset, int) and q_offset == 0 and q.shape[1] == \
            k.shape[1]:
        return _flash(q, k, v, q_chunk, kv_chunk, window, schedule)
    return _chunked_attention_fwd_only(q, k, v, q_chunk=q_chunk,
                                       kv_chunk=kv_chunk, q_offset=q_offset,
                                       window=window, schedule=schedule)


def _chunked_attention_fwd_only(q, k, v, *, q_chunk, kv_chunk, q_offset=0,
                                window=0, schedule="dense") -> jax.Array:
    return _attn_core(q, k, v, q_chunk, kv_chunk, q_offset, window,
                      schedule)[0]


def _attn_core(q, k, v, q_chunk, kv_chunk, q_offset, window, schedule):
    """Online-softmax attention.  Returns (out (B,S,H,D), lse (B,Hkv,G,S))."""
    B, S, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    cq = pick_chunk(S, q_chunk)
    nq = S // cq
    ck = pick_chunk(Sk, kv_chunk)
    nk = Sk // ck

    if (schedule == "binary" and S == Sk and nq == nk and cq == ck
            and (nq & (nq - 1)) == 0 and isinstance(q_offset, int)
            and q_offset == 0 and not window):
        return _binary_causal(q, k, v, nq, cq)

    qr = q.reshape(B, nq, cq, Hkv, G, D)
    kr = k.reshape(B, nk, ck, Hkv, D)
    vr = v.reshape(B, nk, ck, Hkv, D)
    qpos = q_offset + jnp.arange(S).reshape(nq, cq)
    kpos = jnp.arange(Sk).reshape(nk, ck)

    def q_body(_, qi):
        q_blk = jax.lax.dynamic_index_in_dim(qr, qi, 1, keepdims=False)
        qp = jax.lax.dynamic_index_in_dim(qpos, qi, 0, keepdims=False)

        def kv_body(carry, kj):
            m, l, o = carry
            k_blk = jax.lax.dynamic_index_in_dim(kr, kj, 1, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vr, kj, 1, keepdims=False)
            kp = kpos[kj]
            mask = kp[None, :] <= qp[:, None]
            if window:
                mask &= kp[None, :] > qp[:, None] - window
            m, l, o = _block_attn(q_blk, k_blk, v_blk, mask, m, l, o)
            return (m, l, o), None

        init = (jnp.full((B, Hkv, G, cq), NEG_INF, jnp.float32),
                jnp.zeros((B, Hkv, G, cq), jnp.float32),
                jnp.zeros((B, Hkv, G, cq, D), jnp.float32))
        (m, l, o), _ = jax.lax.scan(kv_body, init, jnp.arange(nk))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return None, (o, lse)

    with jax.named_scope("attention_core"):
        _, (out, lse) = jax.lax.scan(q_body, None, jnp.arange(nq))
    # out: (nq, B, Hkv, G, cq, D) -> (B, S, H, D)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, D)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, Hkv, G, S)
    return out.astype(q.dtype), lse


# --------------------------------------------------------------------------
# O(S)-memory custom VJP (flash-attention backward with block recompute)
# --------------------------------------------------------------------------
import functools as _ft


@_ft.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, q_chunk, kv_chunk, window, schedule):
    return _attn_core(q, k, v, q_chunk, kv_chunk, 0, window, schedule)[0]


def _flash_fwd(q, k, v, q_chunk, kv_chunk, window, schedule):
    out, lse = _attn_core(q, k, v, q_chunk, kv_chunk, 0, window, schedule)
    # residuals: (q, k, v) ONLY.  out/lse are recomputed in the backward:
    # custom_vjp residuals are opaque to jax.checkpoint, so under
    # scan-over-layers everything saved here is stacked x n_periods — with
    # (out, lse) saved that was 14 GiB/device on qwen2 train_4k
    # (EXPERIMENTS.md §Perf A5); recomputing costs one extra attention fwd.
    return out, (q, k, v)


def _flash_bwd(q_chunk, kv_chunk, window, schedule, res, dout):
    q, k, v = res
    out, lse = _attn_core(q, k, v, q_chunk, kv_chunk, 0, window, schedule)
    B, S, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    cq = pick_chunk(S, q_chunk)
    nq = S // cq
    ck = pick_chunk(Sk, kv_chunk)
    nk = Sk // ck
    scale = D ** -0.5

    qr = q.reshape(B, nq, cq, Hkv, G, D)
    dor = dout.reshape(B, nq, cq, Hkv, G, D)
    kr = k.reshape(B, nk, ck, Hkv, D)
    vr = v.reshape(B, nk, ck, Hkv, D)
    lser = lse.reshape(B, Hkv, G, nq, cq)
    # delta = rowsum(dout * out)  (B,Hkv,G,nq,cq)
    delta = jnp.einsum("bshd,bshd->bsh", dout.astype(jnp.float32),
                       out.astype(jnp.float32))
    delta = delta.reshape(B, nq, cq, Hkv, G).transpose(0, 3, 4, 1, 2)
    qpos = jnp.arange(S).reshape(nq, cq)
    kpos = jnp.arange(Sk).reshape(nk, ck)

    def q_body(carry, qi):
        dk_acc, dv_acc = carry
        q_blk = jax.lax.dynamic_index_in_dim(qr, qi, 1, keepdims=False)
        do_blk = jax.lax.dynamic_index_in_dim(dor, qi, 1, keepdims=False)
        lse_i = jax.lax.dynamic_index_in_dim(lser, qi, 3, keepdims=False)
        dl_i = jax.lax.dynamic_index_in_dim(delta, qi, 3, keepdims=False)
        qp = jax.lax.dynamic_index_in_dim(qpos, qi, 0, keepdims=False)

        def kv_body(inner, kj):
            dq_i, dk_acc, dv_acc = inner
            k_blk = jax.lax.dynamic_index_in_dim(kr, kj, 1, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vr, kj, 1, keepdims=False)
            kp = kpos[kj]
            mask = kp[None, :] <= qp[:, None]
            if window:
                mask &= kp[None, :] > qp[:, None] - window
            s = _block_scores(q_blk, k_blk)                    # (B,h,g,cq,ck)
            p = jnp.where(mask[None, None, None],
                          jnp.exp(s - lse_i[..., None]), 0.0)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_blk, v_blk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - dl_i[..., None])                    # fp32
            dq_i = dq_i + jnp.einsum("bhgqk,bkhd->bqhgd", ds, k_blk,
                                     preferred_element_type=jnp.float32
                                     ) * scale
            dk_j = jnp.einsum("bhgqk,bqhgd->bkhd", ds, q_blk,
                              preferred_element_type=jnp.float32) * scale
            dv_j = jnp.einsum("bhgqk,bqhgd->bkhd", p, do_blk,
                              preferred_element_type=jnp.float32)
            dk_acc = jax.lax.dynamic_update_index_in_dim(
                dk_acc, jax.lax.dynamic_index_in_dim(
                    dk_acc, kj, 1, keepdims=False) + dk_j, kj, 1)
            dv_acc = jax.lax.dynamic_update_index_in_dim(
                dv_acc, jax.lax.dynamic_index_in_dim(
                    dv_acc, kj, 1, keepdims=False) + dv_j, kj, 1)
            return (dq_i, dk_acc, dv_acc), None

        dq0 = jnp.zeros((B, cq, Hkv, G, D), jnp.float32)
        (dq_i, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_body, (dq0, dk_acc, dv_acc), jnp.arange(nk))
        return (dk_acc, dv_acc), dq_i

    dk0 = jnp.zeros((B, nk, ck, Hkv, D), jnp.float32)
    dv0 = jnp.zeros((B, nk, ck, Hkv, D), jnp.float32)
    with jax.named_scope("attention_bwd"):
        (dk, dv), dq = jax.lax.scan(q_body, (dk0, dv0), jnp.arange(nq))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, D).astype(q.dtype)
    dk = dk.reshape(B, Sk, Hkv, D).astype(k.dtype)
    dv = dv.reshape(B, Sk, Hkv, D).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _binary_causal(q, k, v, n: int, c: int):
    """Exact causal attention via balanced binary decomposition.

    Chunk grid is n x n (chunk size c, n a power of two).  Work items:
      * n diagonal blocks (causal-masked within the block);
      * for level l in [0, log2 n): 2^l UNMASKED squares of side n/2^(l+1),
        square k covering q-chunks [2km+m, 2km+2m) x kv-chunks [2km, 2km+m)
        with m = n/2^(l+1).
    All squares at a level touch disjoint q rows, so each level is one
    batched (reshaped) einsum and a slice-update of the running stats —
    no scatter, no masking, ~S^2/2 exact FLOPs.
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qr = q.reshape(B, n, c, Hkv, G, D)
    kr = k.reshape(B, n, c, Hkv, D)
    vr = v.reshape(B, n, c, Hkv, D)

    with jax.named_scope("attn_binary_diag"):
        # diagonal blocks, causal-masked
        dmask = jnp.tril(jnp.ones((c, c), bool))
        s = _block_scores(qr, kr)                       # (B,n,Hkv,G,c,c)
        s = jnp.where(dmask[None, None, None, None], s, NEG_INF)
        m = s.max(axis=-1)                              # (B,n,Hkv,G,c)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        o = jnp.einsum("bnhgqk,bnkhd->bnhgqd", p, vr,
                       preferred_element_type=jnp.float32)

    level = 0
    half = n // 2
    while half >= 1:
        mm = half  # squares of side mm chunks at this level: count n/(2*mm)
        ns = n // (2 * mm)
        with jax.named_scope(f"attn_binary_l{level}"):
            # group chunks into (ns, 2, mm): [:,0] = kv side, [:,1] = q side
            qg = qr.reshape(B, ns, 2, mm * c, Hkv, G, D)[:, :, 1]
            kg = kr.reshape(B, ns, 2, mm * c, Hkv, D)[:, :, 0]
            vg = vr.reshape(B, ns, 2, mm * c, Hkv, D)[:, :, 0]
            s = _block_scores(qg, kg)                   # (B,ns,Hkv,G,Q,K)
            m2 = s.max(axis=-1)
            p = jnp.exp(s - m2[..., None])
            l2 = p.sum(axis=-1)
            o2 = jnp.einsum("bnhgqk,bnkhd->bnhgqd", p, vg,
                            preferred_element_type=jnp.float32)
            # merge into running stats at the q rows of this level
            # (B,n,Hkv,G,c) -> chunk-major rows -> (B,ns,2,Hkv,G,Q)
            mr = (m.transpose(0, 1, 4, 2, 3)
                  .reshape(B, ns, 2, mm * c, Hkv, G)
                  .transpose(0, 1, 2, 4, 5, 3))
            lr = (l.transpose(0, 1, 4, 2, 3)
                  .reshape(B, ns, 2, mm * c, Hkv, G)
                  .transpose(0, 1, 2, 4, 5, 3))
            orr = (o.transpose(0, 1, 4, 2, 3, 5)
                   .reshape(B, ns, 2, mm * c, Hkv, G, D)
                   .transpose(0, 1, 2, 4, 5, 3, 6))
            mu, lu, ou = _merge_stats(mr[:, :, 1], lr[:, :, 1], orr[:, :, 1],
                                      m2, l2, o2)
            mr = mr.at[:, :, 1].set(mu)
            lr = lr.at[:, :, 1].set(lu)
            orr = orr.at[:, :, 1].set(ou)
            m = mr.transpose(0, 1, 2, 5, 3, 4).reshape(B, n, c, Hkv, G) \
                  .transpose(0, 1, 3, 4, 2)
            l = lr.transpose(0, 1, 2, 5, 3, 4).reshape(B, n, c, Hkv, G) \
                  .transpose(0, 1, 3, 4, 2)
            o = orr.transpose(0, 1, 2, 5, 3, 4, 6).reshape(
                B, n, c, Hkv, G, D).transpose(0, 1, 3, 4, 2, 5)
        half //= 2
        level += 1

    lse = m + jnp.log(jnp.maximum(l, 1e-30))          # (B,n,Hkv,G,c)
    lse = lse.transpose(0, 2, 3, 1, 4).reshape(B, Hkv, G, S)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    out = o.transpose(0, 1, 4, 2, 3, 5).reshape(B, S, H, D)
    return out.astype(q.dtype), lse


def swa_attention(q, k, v, window: int, chunk: int = 256) -> jax.Array:
    """Sliding-window causal attention, banded schedule: O(S*(w+c)) compute
    and O(c*(w+c)) working set per scan step.

    Each q chunk of size c attends a contiguous padded-KV slice of w+c
    positions, so no quadratic masked waste (forward/prefill path; training
    SWA goes through the flash VJP with a window mask instead — see
    transformer._attention).
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    w = min(window, S)
    c = pick_chunk(S, min(chunk, w))
    if w % c or S % c:
        # misaligned: fall back to masked chunked attention
        return chunked_attention(q, k, v, q_chunk=min(chunk, S),
                                 kv_chunk=min(chunk, S), window=window)
    b = w // c                       # kv chunks of history per q chunk
    nq = S // c
    with jax.named_scope("swa_attention"):
        kp = jnp.concatenate(
            [jnp.zeros((B, w, Hkv, D), k.dtype), k], axis=1)
        vp = jnp.concatenate(
            [jnp.zeros((B, w, Hkv, D), v.dtype), v], axis=1)
        qr = q.reshape(B, nq, c, Hkv, G, D)
        qpos_rel = jnp.arange(c)
        kpos_rel = jnp.arange(w + c) - w
        mask0 = (kpos_rel[None, :] <= qpos_rel[:, None]) & \
                (kpos_rel[None, :] > qpos_rel[:, None] - w)

        def q_body(_, qi):
            q_blk = jax.lax.dynamic_index_in_dim(qr, qi, 1, keepdims=False)
            start = qi * c
            k_blk = jax.lax.dynamic_slice_in_dim(kp, start, w + c, 1)
            v_blk = jax.lax.dynamic_slice_in_dim(vp, start, w + c, 1)
            # absolute kv positions: start - w + arange(w+c); mask out the
            # zero padding (positions < 0)
            valid = (start + kpos_rel) >= 0
            mask = mask0 & valid[None, :]
            s = _block_scores(q_blk, k_blk)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(q.dtype), v_blk)
            return None, o

        _, out = jax.lax.scan(q_body, None, jnp.arange(nq))
    # (nq, B, c, Hkv, G, D) -> (B, S, H, D)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, D)


def decode_attention(q, k_cache, v_cache, length) -> jax.Array:
    """q: (B,H,D); caches: (B,Smax,Hkv,D); length: scalar valid length.
    Returns (B,H,D)."""
    valid = jnp.arange(k_cache.shape[1]) < length
    return _decode_softmax(q, k_cache.swapaxes(1, 2),
                           v_cache.swapaxes(1, 2), valid)


def _decode_softmax(q, k_cache, v_cache, valid, k_new=None, v_new=None):
    """One query row per sequence against the head-major cache slots
    (B,Hkv,Smax,D) where ``valid`` (Smax,) holds and, when given, the new
    token's own key and value (B,Hkv,D) as one more column of the same
    softmax.  Returns (B,H,D)."""
    B, H, D = q.shape
    Hkv = k_cache.shape[1]
    G = H // Hkv
    with jax.named_scope("decode_attention"):
        qr = q.reshape(B, Hkv, G, D)
        scale = D ** -0.5
        s = jnp.einsum("bhgd,bhsd->bhgs", qr, k_cache,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m = s.max(axis=-1)
        if k_new is not None:
            s_new = jnp.einsum("bhgd,bhd->bhg", qr, k_new,
                               preferred_element_type=jnp.float32) * scale
            m = jnp.maximum(m, s_new)
        e = jnp.exp(s - m[..., None])
        denom = e.sum(axis=-1)
        if k_new is not None:
            e_new = jnp.exp(s_new - m)
            denom = denom + e_new
        p = e / denom[..., None]
        # accumulate in fp32 WITHOUT materializing an fp32 copy of the
        # (B, Hkv, Smax, D) cache — the explicit astype was 1.6 GB/layer of
        # pure convert traffic on llama4 decode_32k (§Perf B2)
        out = jnp.einsum("bhgs,bhsd->bhgd", p.astype(v_cache.dtype), v_cache,
                         preferred_element_type=jnp.float32)
        if v_new is not None:
            p_new = (e_new / denom).astype(v_new.dtype)
            out = out + jnp.einsum("bhg,bhd->bhgd", p_new, v_new,
                                   preferred_element_type=jnp.float32)
    return out.reshape(B, H, D).astype(q.dtype)


def attention_block(params, x, positions, cfg, *, layer_window: int = 0,
                    kv_cache: Optional[Tuple] = None,
                    cache_pos=None, q_chunk: int = 512, kv_chunk: int = 512,
                    schedule: str = "dense", use_kernel: bool = False):
    """Full attention sub-block.  Returns (y, new_kv_rows).

    kv_cache: None for training; (k_cache, v_cache) of shape
    (B, Hkv, Smax, D) for a decode step (S == 1) at absolute position
    cache_pos.  Head-major, so that a layer's cache sliced out of the
    stack feeds the score and value matmuls unmaterialized.  Slot
    ``cache_pos % Smax`` is the new token's: a ring buffer for SWA layers
    (Smax == window), plain for the others (cache_pos < Smax).  The cache
    is only read: the new token attends to the slots before it that it
    does not overwrite, and to its own key and value.  new_kv_rows are
    those, (B, Hkv, 1, D) each in the cache's dtype, for the caller to
    write at that slot.
    """
    q, k, v = project_qkv(params, x, cfg, positions)
    new_rows = None
    if kv_cache is not None:
        k_cache, v_cache = kv_cache
        assert x.shape[1] == 1, "a KV cache is only read by a decode step"
        smax = k_cache.shape[2]
        pos = jnp.asarray(cache_pos)
        slots = jnp.arange(smax)
        valid = (slots < pos) & (slots != pos % smax)
        k_new = k[:, 0].astype(k_cache.dtype)
        v_new = v[:, 0].astype(v_cache.dtype)
        out = _decode_softmax(q[:, 0], k_cache, v_cache, valid,
                              k_new, v_new)[:, None]
        new_rows = (k_new[:, :, None], v_new[:, :, None])
    elif use_kernel:
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.flash_attention(q, k, v, causal=True,
                                         window=layer_window)
    else:
        # training: the flash VJP handles the window mask (banded SWA
        # is forward-only; its scan backward stores O(nq*nk) blocks)
        out = chunked_attention(q, k, v, q_chunk=q_chunk,
                                kv_chunk=kv_chunk, window=layer_window,
                                schedule=schedule)
    with jax.named_scope("o_proj"):
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_rows
