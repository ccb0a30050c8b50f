"""Chunkwise-parallel selective-SSM (SSD) scan as a Pallas TPU kernel.

TPU-native adaptation of the Mamba-2 SSD chunked algorithm: a GPU
implementation leans on warp-level scan primitives; on TPU the profitable
decomposition is three MXU matmuls per chunk plus an O(1) state carry:

    intra:  y_intra = (tril(exp(cum_t - cum_tau)) * (C B^T)) @ X
    inter:  y_inter = (C * exp(cum)) @ h^T
    state:  h <- exp(total) * h + X^T @ (B * exp(total - cum))

Grid: (B, n_chunks) with the chunk dimension innermost — TPU executes
the grid sequentially, so the per-head (hd, st) fp32 states live in VMEM
scratch across chunk steps (the same carry idiom as the flash kernel's
(m, l, acc)).

Blocks: one grid cell takes ALL heads of one chunk and loops over them:
X (1, c, nh*hd) through the free (B, S, nh*hd) view, logdecay (1, c, nh),
B/C (1, c, st) — every block's last two dimensions are whole or
(8, 128)-aligned (TPU tiling), with no transpose of the activations.
B/C are shared across heads (ngroups=1), so C B^T is formed once per
chunk for all heads.  VMEM per step ~ 2*c*nh*hd*2B (x, y) +
c*(2*st + nh)*4B + c*c*4B + nh*hd*st*4B: at c = 256, nh = 25, hd = 64,
st = 16 that is ~2 MB.

The kernel computes the *forward*; ops.py wires a custom VJP whose backward
differentiates the pure-jnp chunked reference (the recompute-from-chunks
trick, O(S) memory).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, ld_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
                h_scr, *, c: int, n: int, nh: int, hd: int, with_h0: bool):
    """One (b, chunk) grid cell over all heads; chunk innermost."""
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        if with_h0:
            h_scr[...] = h0_ref[0].astype(jnp.float32)
        else:
            h_scr[...] = jnp.zeros_like(h_scr)

    ld = ld_ref[0].astype(jnp.float32)                 # (c, nh)
    Bm = b_ref[0].astype(jnp.float32)                  # (c, st)
    Cm = c_ref[0].astype(jnp.float32)                  # (c, st)

    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    causal = row >= col
    # inclusive prefix sums of the log decays, all heads at once, in
    # both orientations: cum (c, nh) and cum_t (nh, c)
    tri = causal.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(tri, ld, (((1,), (0,)), ((), ())),
                              precision=hi,
                              preferred_element_type=jnp.float32)
    cum_t = jax.lax.dot_general(ld, tri, (((0,), (1,)), ((), ())),
                                precision=hi,
                                preferred_element_type=jnp.float32)
    total = jnp.sum(ld, axis=0, keepdims=True)         # (1, nh)

    # intra-chunk scores shared by every head (B/C are head-independent)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (c, c)

    for h in range(nh):
        x = x_ref[0, :, h * hd:(h + 1) * hd].astype(jnp.float32)  # (c, hd)
        cum_h = cum[:, h:h + 1]                        # (c, 1)
        tot_h = total[:, h:h + 1]                      # (1, 1)

        # ---- intra-chunk: masked decaying linear attention -------------
        dec = cum_h - cum_t[h:h + 1, :]                # (t, tau)
        g = jnp.where(causal, jnp.exp(jnp.where(causal, dec, 0.0)),
                      0.0) * cb
        y = jax.lax.dot_general(g, x, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

        # ---- inter-chunk: contribution of the carried state -------------
        hs = h_scr[h]                                  # (hd, st)
        y = y + jnp.exp(cum_h) * jax.lax.dot_general(
            Cm, hs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        # ---- state update ------------------------------------------------
        bw = Bm * jnp.exp(tot_h - cum_h)               # (c, st)
        dh = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        h_scr[h] = hs * jnp.exp(tot_h) + dh

        y_ref[0, :, h * hd:(h + 1) * hd] = y.astype(y_ref.dtype)

    @pl.when(ci == n - 1)
    def _finish():
        hout_ref[0] = h_scr[...]


def ssm_scan_fwd(xv: jax.Array, logdecay: jax.Array, Bmat: jax.Array,
                 Cmat: jax.Array, h0: Optional[jax.Array] = None, *,
                 chunk: int = 256, interpret: bool = False):
    """xv: (B,S,nh,hd); logdecay: (B,S,nh); Bmat/Cmat: (B,S,st);
    h0: (B,nh,hd,st) or None.  Returns (y (B,S,nh,hd), h_fin fp32)."""
    B, S, nh, hd = xv.shape
    st = Bmat.shape[-1]
    c = min(chunk, S)
    assert S % c == 0, (S, c)
    n = S // c
    with_h0 = h0 is not None
    if h0 is None:
        h0 = jnp.zeros((B, nh, hd, st), jnp.float32)

    kern = functools.partial(_ssd_kernel, c=c, n=n, nh=nh, hd=hd,
                             with_h0=with_h0)
    y, h_fin = pl.pallas_call(
        kern,
        grid=(B, n),
        in_specs=[
            pl.BlockSpec((1, c, nh * hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, c, nh), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, c, st), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, c, st), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, nh, hd, st), lambda b, ci: (b, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, nh * hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, nh, hd, st), lambda b, ci: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, nh * hd), xv.dtype),
            jax.ShapeDtypeStruct((B, nh, hd, st), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nh, hd, st), jnp.float32)],
        interpret=interpret,
    )(xv.reshape(B, S, nh * hd), logdecay, Bmat, Cmat, h0)
    return y.reshape(B, S, nh, hd), h_fin


# kstruct annotation: grid (B, n_chunks); the chunk axis is the
# sequential scan loop carrying the per-head (hd, st) state scratch
KSTRUCT_GRID_LOOPS = {1: "chunks"}


def kernel_structure(*, chunk: int = 128):
    """Recover this kernel's interior structure (repro.core.kstruct)."""
    from repro.core.kstruct import KernelStructure
    xv = jnp.zeros((1, 2 * chunk, 2, 64), jnp.bfloat16)
    ld = jnp.zeros((1, 2 * chunk, 2), jnp.float32)
    Bm = jnp.zeros((1, 2 * chunk, 64), jnp.bfloat16)
    return KernelStructure.from_function(
        ssm_scan_fwd, xv, ld, Bm, Bm, name="ssm_scan",
        grid_loops=KSTRUCT_GRID_LOOPS, chunk=chunk, interpret=True)
