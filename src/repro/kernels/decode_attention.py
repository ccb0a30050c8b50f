"""Flash-decode attention as a Pallas TPU kernel.

The serving hot spot: one query token per sequence against a long KV
cache.  There is no parallelism in the q dimension (S_q = 1), so the TPU
schedule parallelizes over the *cache sequence*: the grid walks kv blocks
on its innermost (sequential) dimension carrying (m, l, acc) online-softmax
scratch in VMEM — the split-KV half of "flash decoding", with the final
merge happening in the same carry (TPU grids execute sequentially, so no
separate reduction kernel is needed).

GQA layout: one grid cell covers ALL kv heads of one kv block, and the
kernel loops over them — per kv head, the q block (G, D) x kv block
(bk, D) keeps the MXU busy with a (G x bk) score tile instead of G
separate (1 x bk) vector products.  The cache is read through its free
(B, Smax, Hkv*D) view, so each grid step DMAs one contiguous
(bk, Hkv*D) slab and every block's last two dimensions are whole
(TPU tiling), with no transpose of the cache.

Length masking: positions >= ``length`` (the current cache fill) are
masked with -inf before the online-softmax update; whole blocks beyond
``length`` are skipped with ``pl.when`` (no MXU work issued).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, bk: int, nk: int, n_kv: int, d: int):
    """Grid (B, nk); nk innermost/sequential."""
    ki = pl.program_id(1)
    length = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_lo = ki * bk

    @pl.when(k_lo < length)
    def _block():
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)                   # (G, D)
            k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)  # (bk, D)
            v = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)  # (bk, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)              # (G, bk)
            s *= d ** -0.5
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < length, s, NEG_INF)
            m_prev = m_scr[h]                                    # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_scr[h] * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_decode_fwd(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length, *, block_kv: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k/v_cache: (B, Smax, Hkv, D); length: scalar int32
    valid cache length.  Returns (B, H, D)."""
    B, H, D = q.shape
    Smax, Hkv = k_cache.shape[1], k_cache.shape[2]
    assert H % Hkv == 0
    G = H // Hkv
    bk = min(block_kv, Smax)
    assert Smax % bk == 0, (Smax, bk)
    nk = Smax // bk
    qh = q.reshape(B, Hkv, G, D)
    kf = k_cache.reshape(B, Smax, Hkv * D)
    vf = v_cache.reshape(B, Smax, Hkv * D)
    length = jnp.asarray(length, jnp.int32).reshape(1)

    kern = functools.partial(_decode_kernel, bk=bk, nk=nk, n_kv=Hkv, d=D)
    out = pl.pallas_call(
        kern,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Hkv, G, D), lambda b, ki: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b, ki: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((Hkv, G, 1), jnp.float32),
                        pltpu.VMEM((Hkv, G, 1), jnp.float32),
                        pltpu.VMEM((Hkv, G, D), jnp.float32)],
        interpret=interpret,
    )(length, qh, kf, vf)
    return out.reshape(B, H, D)


# kstruct annotation: grid (B, nk); ki over kv-cache blocks is the
# sequential split-KV loop carrying the online-softmax scratch
KSTRUCT_GRID_LOOPS = {1: "kv_blocks"}


def kernel_structure(*, block_kv: int = 512):
    """Recover this kernel's interior structure (repro.core.kstruct)."""
    from repro.core.kstruct import KernelStructure
    q = jnp.zeros((1, 4, 64), jnp.bfloat16)
    cache = jnp.zeros((1, 2 * block_kv, 2, 64), jnp.bfloat16)
    return KernelStructure.from_function(
        flash_decode_fwd, q, cache, cache, block_kv,
        name="decode_attention", grid_loops=KSTRUCT_GRID_LOOPS,
        block_kv=block_kv, interpret=True)
