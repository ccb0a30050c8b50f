"""jit'd public wrappers around the Pallas kernels.

- on a TPU the kernels are compiled by Mosaic; on the CPU backend they
  run in interpret mode (the Pallas interpreter runs the kernel body in
  Python and validates the BlockSpec tiling/index maps).  Any other
  backend is an error: the kernels are written for the TPU alone.
- both wrappers are differentiable: forward = Pallas kernel, backward =
  O(S)-memory block-recompute VJP expressed in pure jnp (the flash trick;
  on TPU the backward would be a second Pallas kernel with the same
  schedule transposed).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _fd
from repro.kernels import flash_attention as _fa
from repro.kernels import mla_decode as _md
from repro.kernels import ssm_scan as _ss


def _use_interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU, "
        f"not on {backend!r}")


# ===========================================================================
# flash attention
# ===========================================================================
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 256, block_kv: int = 256):
    """q: (B,S,H,D); k/v: (B,Sk,Hkv,D) -> (B,S,H,D).  Causal (+optional
    sliding window) GQA attention; Pallas forward, custom VJP backward.
    Public wrapper (jax.custom_vjp takes positional args only)."""
    bq = min(block_q, q.shape[1])
    bk = min(block_kv, k.shape[1])
    return _flash_cv(q, k, v, bool(causal), int(window), bq, bk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_cv(q, k, v, causal, window, block_q, block_kv):
    return _fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, interpret=_use_interpret())


def _flash_vjp_fwd(q, k, v, causal, window, block_q, block_kv):
    out = _fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, interpret=_use_interpret())
    return out, (q, k, v)


def _flash_vjp_bwd(causal, window, block_q, block_kv, res, dout):
    q, k, v = res
    # O(S)-memory block-recompute backward (jnp; runs through XLA fusion)
    from repro.models.attention import chunked_attention
    _, vjp = jax.vjp(
        lambda q_, k_, v_: chunked_attention(
            q_, k_, v_, q_chunk=block_q, kv_chunk=block_kv,
            window=window),
        q, k, v)
    return vjp(dout)


_flash_cv.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_decode(q, k_cache, v_cache, length, block_kv: int = 512):
    """One-token decode attention against a KV cache (B,H,D) x
    (B,Smax,Hkv,D) -> (B,H,D).  Inference-only (no VJP needed)."""
    return _fd.flash_decode_fwd(q, k_cache, v_cache, length,
                                block_kv=block_kv,
                                interpret=_use_interpret())


# ===========================================================================
# latent-attention decode
# ===========================================================================
def mla_decode(q_lat, q_pe, c_new, pe_new, c_cache, pe_cache, pos, layer,
               *, scale: float):
    """Latent-attention decode over one layer of the stacked latent
    caches, up to ``pos``: (B,H,r) queries -> (B,H,r) averaged latent.
    Inference-only (no VJP needed)."""
    return _md.mla_decode_fwd(q_lat, q_pe, c_new, pe_new, c_cache,
                              pe_cache, pos, layer, scale=scale,
                              interpret=_use_interpret())


# ===========================================================================
# selective-SSM / SSD scan
# ===========================================================================
def ssm_scan(xv, logdecay, Bmat, Cmat, h0=None, chunk: int = 256):
    """Chunkwise SSD scan; Pallas forward, custom VJP backward.
    Returns (y (B,S,nh,hd), h_final (B,nh,hd,st) fp32).  Public wrapper
    (jax.custom_vjp takes positional args only)."""
    c = min(chunk, xv.shape[1])
    return _ssm_cv(xv, logdecay, Bmat, Cmat, h0, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssm_cv(xv, logdecay, Bmat, Cmat, h0, chunk):
    return _ss.ssm_scan_fwd(xv, logdecay, Bmat, Cmat, h0, chunk=chunk,
                            interpret=_use_interpret())


def _ssm_vjp_fwd(xv, logdecay, Bmat, Cmat, h0, chunk):
    out = _ss.ssm_scan_fwd(xv, logdecay, Bmat, Cmat, h0, chunk=chunk,
                           interpret=_use_interpret())
    return out, (xv, logdecay, Bmat, Cmat, h0)


def _ssm_vjp_bwd(chunk, res, cotangents):
    xv, logdecay, Bmat, Cmat, h0 = res
    from repro.models.ssm import ssd_chunked

    def ref(xv_, ld_, b_, c_, h0_):
        return ssd_chunked(xv_, ld_, b_, c_, chunk=chunk, h0=h0_)

    if h0 is None:
        B, S, nh, hd = xv.shape
        st = Bmat.shape[-1]
        h0_z = jnp.zeros((B, nh, hd, st), jnp.float32)
        _, vjp = jax.vjp(lambda a, b, c, d: ref(a, b, c, d, h0_z),
                         xv, logdecay, Bmat, Cmat)
        dxv, dld, dB, dC = vjp(cotangents)
        return dxv, dld, dB, dC, None
    _, vjp = jax.vjp(ref, xv, logdecay, Bmat, Cmat, h0)
    return vjp(cotangents)


_ssm_cv.defvjp(_ssm_vjp_fwd, _ssm_vjp_bwd)
