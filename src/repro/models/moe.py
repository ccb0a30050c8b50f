"""Mixture-of-Experts FFN with expert parallelism over the ``model`` mesh
axis, written with shard_map + explicit collectives.

Design (see DESIGN.md §5): activations enter replicated over ``model`` (the
attention block's row-parallel output is all-reduced), so each model shard
sees every local-data token.  Shard ``i`` owns experts
[i*E_loc, (i+1)*E_loc); it routes its local tokens, keeps only slots bound
for its own experts, runs the expert FFN over a capacity-bounded dispatch
buffer, scatters results back, and a single psum over ``model`` merges the
shards — the same collective a row-parallel dense FFN would need, i.e. EP
costs no extra collective versus TP.  Expert weights are FSDP-sharded over
``data`` on the d_model dim and all-gathered just-in-time (explicit
overlap-friendly FSDP).

Tokens routed beyond an expert's capacity C = top_k * T_loc / E * cf are
dropped (standard Switch/GShard semantics); the aux load-balance loss keeps
the router near-uniform.

A layer told which experts it holds (``held_moe``: experts
[e_first, e_first + n_held) of the router's E) is expert parallelism's
share of one chip with no mesh: it routes every token over all E, and
computes its own experts' part for the rows routed to them, dropping
none.  Serving takes this path; training the capacity-bounded one
above, over the same experts.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init


class MoEMeshArgs(NamedTuple):
    mesh: object          # jax.sharding.Mesh
    dp_axes: tuple        # axes the batch is sharded over, e.g. ("pod","data")
    fsdp_axis: Optional[str]   # axis expert weights' d_model dim is sharded on
    model_axis: str       # expert-parallel axis
    # "gather": FSDP weights, all-gather per invocation (amortizes when the
    #   token batch is large — training).
    # "stationary": weights stay resident with the ffn-hidden dim sharded
    #   over fsdp_axis; the (small) token batch is all-gathered instead and
    #   partial expert outputs are psum'd — decode/serving wins (§Perf B).
    weight_mode: str = "gather"


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int, dtype,
                    n_held: Optional[int] = None, select_bias: bool = False):
    """Router over ``n_experts``; expert weights for the ``n_held`` (all,
    by default) this layer holds.  ``select_bias``: a float32 bias added
    to the scores to select experts only (DeepSeek-V3's correction)."""
    ks = jax.random.split(key, 4)
    e = n_experts if n_held is None else n_held
    p = {
        "router": dense_init(ks[0], (d_model, n_experts), jnp.float32),
        "w1": dense_init(ks[1], (e, d_model, d_ff), dtype),
        "w3": dense_init(ks[2], (e, d_model, d_ff), dtype),
        "w2": dense_init(ks[3], (e, d_ff, d_model), dtype),
    }
    if select_bias:
        p["bias"] = jnp.zeros((n_experts,), jnp.float32)
    return p


def route(x, wr, bias=None, *, top_k: int, scoring: str = "softmax",
          routed_scale: float = 1.0):
    """Router.  x: (T, d).  Scores (T, E) float32 from float32 logits,
    by softmax or sigmoid; the top-k of scores + ``bias`` are selected
    (the bias selects only); gates are the selected scores normalised to
    sum 1, times ``routed_scale``.  Returns (scores, gates, eidx)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), wr)
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)            # (T, E)
    if bias is None:
        gates, eidx = jax.lax.top_k(probs, top_k)          # (T, k)
    else:
        _, eidx = jax.lax.top_k(probs + bias, top_k)
        gates = jnp.take_along_axis(probs, eidx, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if routed_scale != 1.0:
        gates = gates * routed_scale
    return probs, gates, eidx


# Up to this many tokens every held expert runs on every token, gated to
# zero where it was not selected: its weights, read once either way,
# bound the time while an expert's rows are fewer than the chip's FLOPs
# per byte (~240 on a v5e), and no expert's weights are sliced out of
# the layer stack for a grouped kernel (a copy of each, every layer).
# Past it, rows are sorted by expert into grouped matmuls.
DENSE_MAX_TOKENS = 256


def held_moe(params, x, *, top_k: int, e_first: int, scoring: str,
             routed_scale: float):
    """Dropless MoE over the experts this layer holds.  x: (T, d).

    A decode step's few tokens run through every held expert, gated; a
    prefill's many are sorted by the held expert each row goes to, rows
    for experts held elsewhere last, and three grouped matmuls
    (``ragged_dot``) run the held experts over their rows only
    (``DENSE_MAX_TOKENS``).  Returns (y (T, d), load (n_held + 1,)
    int32): rows per held expert, then rows routed to experts held
    elsewhere."""
    T, d = x.shape
    n_held = params["w1"].shape[0]
    with jax.named_scope("moe_router"):
        _, gates, eidx = route(x, params["router"], params.get("bias"),
                               top_k=top_k, scoring=scoring,
                               routed_scale=routed_scale)
    with jax.named_scope("moe_dispatch"):
        le = jnp.where((eidx >= e_first) & (eidx < e_first + n_held),
                       eidx - e_first, n_held)               # (T, k)
        # one-hot sums, not scatters: a TPU serialises a scatter-add
        # whose indices repeat
        hit = le[..., None] == jnp.arange(n_held + 1)        # (T, k, E+1)
        load = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    if T <= DENSE_MAX_TOKENS:
        with jax.named_scope("moe_experts"):
            g = jax.nn.silu(jnp.einsum("td,edf->etf", x, params["w1"]))
            u = jnp.einsum("td,edf->etf", x, params["w3"])
            eo = jnp.einsum("etf,efd->etd", g * u, params["w2"])
        with jax.named_scope("moe_combine"):
            gh = jnp.sum(jnp.where(hit[..., :n_held], gates[..., None], 0.0),
                         axis=1)                             # (T, E)
            y = jnp.einsum("etd,te->td", eo.astype(jnp.float32), gh)
        return y.astype(x.dtype), load
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(le.reshape(-1), stable=True)
        xs = jnp.take(x, order // top_k, axis=0)            # (T*k, d)
    with jax.named_scope("moe_experts"):
        gs = load[:n_held]
        g = jax.nn.silu(jax.lax.ragged_dot(xs, params["w1"], gs))
        u = jax.lax.ragged_dot(xs, params["w3"], gs)
        eo = jax.lax.ragged_dot(g * u, params["w2"], gs)
    with jax.named_scope("moe_combine"):
        # rows back in (token, k) order by the inverse permutation, a
        # gather; rows past the held experts' groups are not computed
        eo = jnp.take(eo, jnp.argsort(order), axis=0).reshape(T, top_k, d)
        y = jnp.einsum("tkd,tk->td", jnp.where(
            (le < n_held)[..., None], eo.astype(jnp.float32), 0.0), gates)
    return y.astype(x.dtype), load


def _local_moe(x, wr, w1, w3, w2, *, n_experts: int, top_k: int,
               capacity: int, e_loc: int, model_axis: Optional[str],
               fsdp_axis: Optional[str], dp_axes: tuple,
               weight_mode: str = "gather", bias=None, e_first: int = 0,
               scoring: str = "softmax", routed_scale: float = 1.0):
    """Per-shard MoE.  x: (T_loc, d) local tokens.  Expert weights are local
    slices (E_loc, d[/fsdp], f) for "gather" / (E_loc, d, f/fsdp) for
    "stationary".  Returns (y (T_loc, d), aux_loss scalar)."""
    T, d = x.shape
    stationary = weight_mode == "stationary" and fsdp_axis is not None
    t_loc = T
    if stationary:
        # weights stay put; replicate the (small) token batch over the
        # fsdp axis instead, psum partial f-slices back at the end
        with jax.named_scope("moe_token_allgather"):
            x = jax.lax.all_gather(x, fsdp_axis, axis=0, tiled=True)
        T = x.shape[0]
    elif fsdp_axis is not None:
        with jax.named_scope("moe_fsdp_allgather"):
            w1 = jax.lax.all_gather(w1, fsdp_axis, axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, fsdp_axis, axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, fsdp_axis, axis=2, tiled=True)

    with jax.named_scope("moe_router"):
        probs, gates, eidx = route(x, wr, bias, top_k=top_k,
                                   scoring=scoring,
                                   routed_scale=routed_scale)

    # aux load-balance loss (Switch): E * sum_e importance_e * load_e
    with jax.named_scope("moe_aux"):
        importance = probs.mean(axis=0)                    # (E,)
        load = jnp.zeros((n_experts,), jnp.float32)
        for j in range(top_k):
            load = load + jnp.bincount(
                eidx[:, j], length=n_experts).astype(jnp.float32)
        load = load / (T * top_k)
        aux = n_experts * jnp.sum(importance * load)

    e0 = (jax.lax.axis_index(model_axis) * e_loc
          if model_axis is not None else e_first)

    with jax.named_scope("moe_dispatch_index"):
        le = eidx - e0                                      # (T, k) local ids
        mine = (le >= 0) & (le < e_loc)
        le_flat = jnp.where(mine, le, e_loc).reshape(-1)    # (T*k,)
        onehot = jax.nn.one_hot(le_flat, e_loc, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot           # slot within expert
        pos_flat = jnp.sum(pos * onehot, axis=1)            # (T*k,)
        keep = mine.reshape(-1) & (pos_flat < capacity)
        slot = jnp.where(keep, le_flat * capacity + pos_flat,
                         e_loc * capacity)                  # dump row

    with jax.named_scope("moe_dispatch"):
        buf = jnp.zeros((e_loc * capacity + 1, d), x.dtype)
        for j in range(top_k):
            sj = slot.reshape(T, top_k)[:, j]
            buf = buf.at[sj].set(x, mode="drop")
        expert_in = buf[:-1].reshape(e_loc, capacity, d)

    with jax.named_scope("moe_experts"):
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w1))
        u = jnp.einsum("ecd,edf->ecf", expert_in, w3)
        eo = jnp.einsum("ecf,efd->ecd", g * u, w2)
        out_flat = jnp.concatenate(
            [eo.reshape(e_loc * capacity, d),
             jnp.zeros((1, d), eo.dtype)], axis=0)

    with jax.named_scope("moe_combine"):
        y = jnp.zeros((T, d), jnp.float32)
        for j in range(top_k):
            sj = slot.reshape(T, top_k)[:, j]
            kj = keep.reshape(T, top_k)[:, j]
            contrib = out_flat[sj].astype(jnp.float32)
            y = y + contrib * (gates[:, j] * kj)[:, None]
        if stationary:
            # merge partial f-slices (fsdp) and partial experts (model) in
            # one fused reduction, then slice this shard's tokens back out
            axes = (fsdp_axis,) + ((model_axis,) if model_axis else ())
            y = jax.lax.psum(y, axes)
            idx = jax.lax.axis_index(fsdp_axis) * t_loc
            y = jax.lax.dynamic_slice_in_dim(y, idx, t_loc, axis=0)
            aux = jax.lax.pmean(aux, tuple(dp_axes) + (
                (model_axis,) if model_axis else ()))
        elif model_axis is not None:
            y = jax.lax.psum(y, model_axis)
            axes = tuple(dp_axes) + (model_axis,)
            aux = jax.lax.pmean(aux, axes)
    return y.astype(x.dtype), aux


def moe_ffn(params, x, *, n_experts: int, top_k: int,
            capacity_factor: float, mesh_args: Optional[MoEMeshArgs],
            e_first: int = 0, scoring: str = "softmax",
            routed_scale: float = 1.0):
    """MoE FFN.  x: (B, S, d).  Returns (y (B,S,d), aux scalar).  With
    no mesh the layer holds the experts ``params`` has, from
    ``e_first`` on."""
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    if mesh_args is None or mesh_args.mesh is None:
        cap = max(top_k, int(B * S * top_k / n_experts * capacity_factor))
        y, aux = _local_moe(
            x2, params["router"], params["w1"], params["w3"], params["w2"],
            n_experts=n_experts, top_k=top_k, capacity=cap,
            e_loc=params["w1"].shape[0], model_axis=None, fsdp_axis=None,
            dp_axes=(), bias=params.get("bias"), e_first=e_first,
            scoring=scoring, routed_scale=routed_scale)
        return y.reshape(B, S, d), aux

    mesh = mesh_args.mesh
    n_dp = 1
    for a in mesh_args.dp_axes:
        n_dp *= mesh.shape[a]
    n_model = mesh.shape[mesh_args.model_axis]
    t_loc = (B * S) // n_dp
    e_loc = n_experts // n_model
    fsdp = mesh_args.fsdp_axis
    mode = mesh_args.weight_mode
    d_ff = params["w1"].shape[-1]
    if mode == "stationary":
        if fsdp is not None and d_ff % mesh.shape[fsdp] != 0:
            fsdp = None     # f not divisible: weights replicate anyway
        n_gather = mesh.shape[fsdp] if fsdp is not None else 1
        cap = max(top_k, int(t_loc * n_gather * top_k / n_experts
                             * capacity_factor))
        # weights resident: f dim sharded over fsdp, never gathered
        w_d = P(mesh_args.model_axis, None, fsdp)
        w_f = P(mesh_args.model_axis, fsdp, None)
    else:
        if fsdp is not None and d % mesh.shape[fsdp] != 0:
            fsdp = None  # replicate d when not divisible
        cap = max(top_k, int(t_loc * top_k / n_experts * capacity_factor))
        w_d = P(mesh_args.model_axis, fsdp, None)
        w_f = P(mesh_args.model_axis, None, fsdp)

    dp = P(tuple(mesh_args.dp_axes))
    fn = functools.partial(
        _local_moe, n_experts=n_experts, top_k=top_k, capacity=cap,
        e_loc=e_loc, model_axis=mesh_args.model_axis, fsdp_axis=fsdp,
        dp_axes=tuple(mesh_args.dp_axes), weight_mode=mode)
    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(tuple(mesh_args.dp_axes), None), P(None, None),
                  w_d, w_d, w_f),
        out_specs=(P(tuple(mesh_args.dp_axes), None), P()),
        check_vma=False,
    )(x2, params["router"], params["w1"], params["w3"], params["w2"])
    return y.reshape(B, S, d), aux
