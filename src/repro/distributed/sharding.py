"""Sharding rules: parameter/optimizer/batch/cache PartitionSpecs for the
production mesh (DESIGN.md §5).

Strategy ``tp`` (default): megatron-style tensor parallel over ``model``
(q-heads / ffn-hidden / vocab / experts), FSDP over ``data`` on the
complementary matrix dim, batch over (``pod``, ``data``).

Strategy ``dp_only`` (hillclimb option for small archs): replicate params,
shard batch over every mesh axis — avoids padding waste when heads % 16
!= 0 at the price of replicated optimizer state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.moe import MoEMeshArgs


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    mesh: Any
    dp_axes: Tuple[str, ...]
    fsdp_axis: Optional[str]
    model_axis: Optional[str]
    strategy: str = "tp"
    moe_weight_mode: str = "gather"   # gather | stationary (see moe.py)

    def moe_args(self) -> Optional[MoEMeshArgs]:
        if self.mesh is None:
            return None
        if self.strategy == "dp_only" or self.model_axis is None:
            return None
        return MoEMeshArgs(self.mesh, self.dp_axes, self.fsdp_axis,
                           self.model_axis,
                           weight_mode=self.moe_weight_mode)

    # -- helpers -----------------------------------------------------------
    def ns(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def batch_spec(self) -> P:
        if self.strategy == "dp_only":
            axes = tuple(self.dp_axes) + ((self.model_axis,)
                                          if self.model_axis else ())
            return P(axes)
        return P(tuple(self.dp_axes))


def make_plan(mesh, *, multi_pod: bool = False, strategy: str = "tp",
              moe_weight_mode: str = "gather") -> ShardingPlan:
    if mesh is None:
        return ShardingPlan(None, (), None, None, strategy)
    names = mesh.axis_names
    if strategy == "fsdp":
        # ZeRO-3: batch over EVERY axis, parameters fully sharded over
        # ("data", "model") (one divisible dim each; GSPMD all-gathers
        # just-in-time), no tensor parallelism.  The win over "tp" for
        # archs whose head counts don't divide the model axis (e.g.
        # qwen2's 12 heads vs 16): no replicated attention compute and a
        # 16x smaller per-device activation footprint (§Perf cell A).
        dp = tuple(a for a in ("pod", "data", "model") if a in names)
        return ShardingPlan(mesh, dp, None, None, strategy)
    dp = tuple(a for a in ("pod", "data") if a in names)
    model = "model" if "model" in names else None
    fsdp = "data" if "data" in names and mesh.shape.get("data", 1) > 1 \
        else None
    return ShardingPlan(mesh, dp or names[:1], fsdp, model, strategy,
                        moe_weight_mode)


# --------------------------------------------------------------------------
# Parameter specs, by tree-path name matching
# --------------------------------------------------------------------------
def _param_spec(path: str, ndim: int, plan: ShardingPlan,
                divisible: Dict[str, bool]) -> P:
    if plan.strategy == "dp_only":
        return P()
    f = plan.fsdp_axis
    m = plan.model_axis
    leaf = path.split("/")[-1]
    stacked = path.startswith("layers/")
    pre: Tuple = (None,) if stacked else ()

    def spec(*s):
        full = pre + s
        assert len(full) == ndim, (path, ndim, full)
        return P(*full)

    if path == "embed":
        return P(m, f)
    if path == "unembed":
        return P(f, m)
    if leaf in ("final_norm", "ln1", "ln2", "out_norm", "b", "b_if", "beta",
                "dt_bias", "A_log", "D", "q_norm", "k_norm"):
        return P(*([None] * ndim))
    if leaf in ("wq", "wk", "wv") and ndim == 4:       # (P, d|i, H, Dh)
        return spec(f, m, None)
    if leaf == "wo":                                   # (P, H, Dh, d)
        return spec(m, None, f)
    if leaf in ("bq", "bk", "bv"):                     # (P, H, Dh)
        return spec(m, None)
    if leaf in ("w1", "w3"):
        if ndim == 4:                                  # moe (P, E, d, f)
            if plan.moe_weight_mode == "stationary":
                return spec(m, None, f)                # f-dim sharded
            return spec(m, f, None)
        return spec(f, m)                              # dense (P, d, f)
    if leaf == "w2":
        if ndim == 4:                                  # moe (P, E, f, d)
            if plan.moe_weight_mode == "stationary":
                return spec(m, f, None)
            return spec(m, None, f)
        return spec(m, f)                              # dense (P, f, d)
    if leaf == "router":                               # (P, d, E)
        return spec(None, None)
    if leaf in ("up_proj", "in_proj", "wx", "up1", "up2"):  # (P, d, inner)
        return spec(f, m)
    if leaf in ("down_proj", "out_proj", "down"):      # (P, inner, d)
        return spec(m, f)
    if leaf == "r":                                    # (P, nh, dh, 4dh)
        return spec(m, None, None)
    if leaf == "conv":                                 # (P, w, inner)
        return spec(None, m)
    if leaf in ("wBC", "wdt"):                         # (P, inner, k)
        return spec(m, None)
    if leaf == "wif":                                  # (P, inner, nh, 2)
        return spec(f, m, None)
    return P(*([None] * ndim))


def _tree_path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _fsdp_spec(path: str, shape, plan: ShardingPlan) -> P:
    """ZeRO-3 rule: shard the largest divisible dim over ("data","model")
    combined; fall back to a single axis; else replicate.  The stacked
    period dim of layer params (dim 0) is never sharded."""
    sizes = dict(plan.mesh.shape)
    combined = tuple(a for a in ("data", "model") if a in sizes)
    n_comb = int(np.prod([sizes[a] for a in combined]))
    stacked = path.startswith("layers/")
    dims = list(enumerate(shape))
    if stacked:
        dims = dims[1:]
    dims.sort(key=lambda kv: -kv[1])
    for axes, n in ((combined, n_comb),) + tuple(
            ((a,), sizes[a]) for a in combined):
        for i, d in dims:
            if n > 1 and d % n == 0:
                spec = [None] * len(shape)
                spec[i] = axes if len(axes) > 1 else axes[0]
                return P(*spec)
    return P(*([None] * len(shape)))


def param_shardings(params_shape, cfg: ModelConfig, plan: ShardingPlan):
    """Map a params (or ShapeDtypeStruct) tree to NamedShardings."""
    if plan.mesh is None:
        return jax.tree.map(lambda _: None, params_shape)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    if plan.strategy == "fsdp":
        return jax.tree_util.tree_unflatten(treedef, [
            NamedSharding(plan.mesh,
                          _fsdp_spec(_tree_path_str(p), leaf.shape, plan))
            for p, leaf in flat])
    out = []
    for path, leaf in flat:
        spec = _param_spec(_tree_path_str(path), len(leaf.shape), plan, {})
        # explicit input shardings must divide exactly (no GSPMD padding on
        # declared in_shardings) — non-divisible dims fall back to
        # replication and are reported in the roofline notes
        sizes = dict(plan.mesh.shape)
        fixed = []
        for dim, ax in zip(leaf.shape, spec + (None,) * len(leaf.shape)):
            if ax is None:
                fixed.append(None)
                continue
            n = np.prod([sizes[a] for a in (ax if isinstance(ax, tuple)
                                            else (ax,))])
            fixed.append(ax if dim % n == 0 else None)
        out.append(NamedSharding(plan.mesh, P(*fixed)))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shardings(batch_shape, plan: ShardingPlan):
    if plan.mesh is None:
        return jax.tree.map(lambda _: None, batch_shape)
    bs = plan.batch_spec()
    sizes = dict(plan.mesh.shape)
    n_dp = int(np.prod([sizes[a] for a in (bs[0] if isinstance(bs[0], tuple)
                                           else (bs[0],))])) if bs else 1

    def spec(leaf):
        if len(leaf.shape) == 0 or leaf.shape[0] % n_dp != 0:
            return NamedSharding(plan.mesh, P())   # tiny batch: replicate
        extra = (None,) * (len(leaf.shape) - 1)
        return NamedSharding(plan.mesh, P(*(tuple(bs) + extra)))
    return jax.tree.map(spec, batch_shape)


def cache_shardings(cache_shape, cfg: ModelConfig, plan: ShardingPlan,
                    kv_seq_axis: Optional[str] = None):
    """Cache tree: (period, B, ...) leaves — batch over dp.

    ``kv_seq_axis``: optionally shard the KV-cache sequence dim over this
    axis (flash-decode style; a §Perf hillclimb lever).
    """
    if plan.mesh is None:
        return jax.tree.map(lambda _: None, cache_shape)
    bs = plan.batch_spec()
    # the batch-dim axes as ONE PartitionSpec entry (a flat tuple of axis
    # names; re-wrapping it with tuple(bs) nests tuples and is rejected)
    dp = bs[0] if len(bs) else None
    m = plan.model_axis if plan.strategy != "dp_only" else None
    sizes = dict(plan.mesh.shape)

    n_dp = 1
    for a in (dp if isinstance(dp, tuple) else (dp,) if dp else ()):
        n_dp *= sizes.get(a, 1)
    ms = sizes.get(m, 1) if m else 1

    def spec(path, leaf):
        name = _tree_path_str(path).split("/")[-1]
        nd = len(leaf.shape)
        if name in ("k", "v") and nd == 5:     # (Pd, B, Hkv, S, Dh)
            hkv, smax = leaf.shape[2], leaf.shape[3]
            if kv_seq_axis and smax % sizes.get(kv_seq_axis, 1) == 0:
                s = P(None, dp, None, kv_seq_axis, None)
            elif m and hkv % ms == 0:
                s = P(None, dp, m, None, None)
            elif m and smax % ms == 0:
                # flash-decode style: shard cache sequence over model
                s = P(None, dp, None, m, None)
            else:
                s = P(None, dp, None, None, None)
        elif name == "ssm" and nd == 5:        # (Pd, B, nh, hd, st)
            s = P(None, dp, m, None, None)
        elif name == "conv" and nd == 4:       # (Pd, B, w, inner)
            s = P(None, dp, None, m)
        elif name == "H" and nd == 5:          # (Pd, B, nh, dqk, dv+1)
            s = P(None, dp, m, None, None)
        elif nd >= 2:
            s = P(None, dp)
        else:
            s = P(None)
        # divisibility guards: explicit in_shardings must divide exactly
        dims = list(s)
        for i, ax in enumerate(dims):
            if ax is None:
                continue
            if isinstance(ax, tuple):
                if leaf.shape[i] % n_dp != 0:
                    dims[i] = None
            elif leaf.shape[i] % sizes.get(ax, 1) != 0:
                dims[i] = None
        return NamedSharding(plan.mesh, P(*dims))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def opt_shardings(opt_shape, params_sharding, *,
                  zero1_axis: Optional[str] = None):
    """AdamState(step, mu, nu): mu/nu mirror params, step replicated.

    ``zero1_axis``: opt-in ZeRO-1 — mu/nu additionally shard their largest
    still-unsharded divisible dim over that axis (for llama4-400B the fp32
    optimizer state alone is 12.5 GB/device on one pod).  NOTE: with plain
    GSPMD annotations the update gathers state instead of scattering
    grads (measured: +240 s collective on llama4 multi-pod — EXPERIMENTS
    §Perf); a production ZeRO-1 needs the explicit
    reduce-scatter/update/all-gather structure in shard_map, which is why
    this stays opt-in."""
    from repro.optim.adamw import AdamState
    mesh = None
    for s in jax.tree.leaves(params_sharding):
        mesh = s.mesh
        break
    step_s = NamedSharding(mesh, P()) if mesh is not None else None
    mom = params_sharding
    if mesh is not None and zero1_axis in mesh.axis_names \
            and mesh.shape[zero1_axis] > 1:
        n_z = mesh.shape[zero1_axis]

        def zshard(shape_leaf, sharding):
            spec = list(sharding.spec) + [None] * (
                len(shape_leaf.shape) - len(sharding.spec))
            # largest unsharded dim divisible by the pod size
            cands = sorted(
                ((d, i) for i, (d, ax) in
                 enumerate(zip(shape_leaf.shape, spec))
                 if ax is None and d % n_z == 0),
                reverse=True)
            if cands:
                spec[cands[0][1]] = zero1_axis
            return NamedSharding(mesh, P(*spec))

        # opt_shape is AdamState(step, mu, nu); mu mirrors params' tree
        mom = jax.tree.map(zshard, opt_shape.mu, params_sharding)
    return AdamState(step=step_s, mu=mom, nu=mom)
