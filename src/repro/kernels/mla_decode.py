"""Latent-attention decode as a Pallas TPU kernel.

One decode step of absorbed latent attention (``models/mla.py``) scores
each head's latent query ``q_lat`` (H, r) and roped query ``q_pe``
(H, rope) against the cache rows ``c`` (r) and ``k_pe`` (rope) of every
earlier slot, and averages ``c`` with the softmax of the scores.  As two
einsums over all slots, the latent cache is streamed twice a step and
in full.  This kernel is a flash decode over the latent cache: the grid
walks blocks of slots innermost, carrying the online softmax (m, l) and
the (H, r) average in f32 VMEM scratch, so each block is read once and
the probabilities never leave VMEM.

- Only up to the position.  ``pos``, one for the whole batch, is a
  scalar prefetch argument.  The slot-block index map is clamped to the
  block that holds slot ``pos - 1``; Pallas does not fetch a block whose
  index did not change, so blocks past the position are never read.
  Their compute is skipped with ``pl.when``.  The block that holds the
  position is masked, scores and latent rows both: past the position,
  and past the end of a cache whose slots are not a multiple of the
  block, it may hold anything, NaN included.
- The new token's own column (its rows are written after the step)
  starts the online softmax: m is its score, l is 1, the average its c.
- The caches come stacked over layers, (L, B, Smax, .), with the layer a
  second scalar prefetch argument that the index maps select: a layer's
  slice handed to a custom call would be copied first.
- The roped keys are read slot-minor, (L, B, rope, Smax): 64 values a
  row are fewer than a tile's 128 lanes, so the chip keeps that cache
  slot-minor and the view is free; the query meets each block as an
  ordinary (H, rope) x (rope, bk) product.
- A grid step takes ``BATCH_BLOCK`` rows of ``SLOT_BLOCK`` slots (2 MB
  of the latent cache at moonlight's widths), so the grid's per-step
  cost is spread over megabytes, and the block read past the position
  is at most 127 slots.  Measured on a v5e at moonlight's decode shapes,
  16 x 128 beat 8 x 256 and 16 x 256, and one batched product a block
  beat a loop of per-row products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Slots a grid step reads: one tile's lanes, since the roped keys are
# read slot-minor.
SLOT_BLOCK = 128
# Batch rows a grid step takes.
BATCH_BLOCK = 16


def _kernel(pos_ref, layer_ref, ql_ref, qp_ref, cn_ref, pn_ref, c_ref,
            pe_ref, o_ref, m_scr, l_scr, acc_scr, *, bk: int, nk: int,
            scale: float):
    """Grid (batch blocks, slot blocks); slot blocks innermost."""
    del layer_ref                    # read by the index maps alone
    f32 = jnp.float32
    ki = pl.program_id(1)
    pos = pos_ref[0]

    @pl.when(ki == 0)
    def _init():
        cn = cn_ref[...].astype(f32)                          # (bb, 1, r)
        s = (jnp.sum(ql_ref[...].astype(f32) * cn, -1, keepdims=True)
             + jnp.sum(qp_ref[...].astype(f32)
                       * pn_ref[...].astype(f32), -1, keepdims=True))
        m_scr[...] = s * scale                                # (bb, H, 1)
        l_scr[...] = jnp.ones_like(l_scr)
        acc_scr[...] = jnp.broadcast_to(cn, acc_scr.shape)

    k_lo = ki * bk

    def attend(masked: bool):
        c = c_ref[...]                                        # (bb, bk, r)
        if masked:
            live = k_lo + jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
            c = jnp.where(live < pos, c, jnp.zeros_like(c))
        s = (jnp.einsum("bhr,bsr->bhs", ql_ref[...], c,
                        preferred_element_type=f32)
             + jnp.einsum("bhp,bps->bhs", qp_ref[...], pe_ref[...],
                          preferred_element_type=f32)) * scale
        if masked:
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(kpos < pos, s, NEG_INF)             # (bb, H, bk)
        m_prev = m_scr[...]                                   # (bb, H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.einsum(
            "bhs,bsr->bhr", p.astype(c.dtype), c, preferred_element_type=f32)
        m_scr[...] = m_new

    @pl.when(k_lo + bk <= pos)
    def _block():
        attend(masked=False)

    @pl.when((k_lo < pos) & (pos < k_lo + bk))
    def _tail():
        attend(masked=True)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def mla_decode_fwd(q_lat, q_pe, c_new, pe_new, c_cache, pe_cache, pos,
                   layer, *, scale: float, interpret: bool = False):
    """Attention of one new token per sequence over its latent cache.

    q_lat (B, H, r) and q_pe (B, H, rope): the absorbed queries, in the
    caches' dtype; c_new (B, r) and pe_new (B, rope): the token's own
    rows; c_cache (L, B, Smax, r) and pe_cache (L, B, Smax, rope): every
    layer's caches, of which ``layer`` is read, slots before ``pos``.
    Returns (B, H, r) in ``q_lat``'s dtype: the softmax of
    ``scale * (q_lat . c + q_pe . k_pe)`` over those slots and the new
    token averages their ``c``."""
    B, H, r = q_lat.shape
    rope = q_pe.shape[-1]
    smax = c_cache.shape[2]
    bb = min(BATCH_BLOCK, B)
    bk = min(SLOT_BLOCK, smax)
    nk = pl.cdiv(smax, bk)
    pe_t = jnp.swapaxes(pe_cache, 2, 3)            # (L, B, rope, Smax)
    scalars = (jnp.asarray(pos, jnp.int32).reshape(1),
               jnp.asarray(layer, jnp.int32).reshape(1))

    def row(bi, ki, pos_ref, layer_ref):
        return bi, 0, 0

    def slots(bi, ki, pos_ref, layer_ref):
        # blocks past the position repeat the last one read: not fetched
        last = jnp.maximum(pos_ref[0] - 1, 0) // bk
        return layer_ref[0], bi, jnp.minimum(ki, last), 0

    def slots_t(bi, ki, pos_ref, layer_ref):
        lyr, b, k, _ = slots(bi, ki, pos_ref, layer_ref)
        return lyr, b, 0, k

    kern = functools.partial(_kernel, bk=bk, nk=nk, scale=scale)
    sq = pl.Squeezed()
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(B, bb), nk),
            in_specs=[
                pl.BlockSpec((bb, H, r), row),
                pl.BlockSpec((bb, H, rope), row),
                pl.BlockSpec((bb, 1, r), row),
                pl.BlockSpec((bb, 1, rope), row),
                pl.BlockSpec((sq, bb, bk, r), slots),
                pl.BlockSpec((sq, bb, rope, bk), slots_t),
            ],
            out_specs=pl.BlockSpec((bb, H, r), row),
            scratch_shapes=[pltpu.VMEM((bb, H, 1), jnp.float32),
                            pltpu.VMEM((bb, H, 1), jnp.float32),
                            pltpu.VMEM((bb, H, r), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode",
    )(*scalars, q_lat, q_pe, c_new[:, None], pe_new[:, None], c_cache,
      pe_t)


# kstruct annotation: grid (batch blocks, slot blocks); the slot blocks
# are the sequential loop carrying the online-softmax scratch
KSTRUCT_GRID_LOOPS = {1: "slot_blocks"}


def kernel_structure():
    """Recover this kernel's interior structure (repro.core.kstruct)."""
    from repro.core.kstruct import KernelStructure
    B, H, r, rope = 2, 4, 128, 64
    q = jnp.zeros((B, H, r), jnp.bfloat16)
    q_pe = jnp.zeros((B, H, rope), jnp.bfloat16)
    c = jnp.zeros((1, B, 2 * SLOT_BLOCK, r), jnp.bfloat16)
    pe = jnp.zeros((1, B, 2 * SLOT_BLOCK, rope), jnp.bfloat16)
    return KernelStructure.from_function(
        mla_decode_fwd, q, q_pe, q[:, 0], q_pe[:, 0], c, pe, SLOT_BLOCK, 0,
        name="mla_decode", grid_loops=KSTRUCT_GRID_LOOPS, scale=0.1,
        interpret=True)
