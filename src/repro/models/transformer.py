"""Model assembly: config-driven decoder stack covering every assigned
architecture family (dense GQA, MoE, xLSTM, mamba-hybrid, VLM/audio
backbones).

Layers are grouped into *periods* (one period = one repetition of the
block pattern x MoE interleave), and the stack is a lax.scan over periods
with stacked parameters — this keeps HLO size O(period), which is what makes
512-device dry-run compiles tractable (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN, HYBRID, MLA, MLSTM, SLSTM, SWA, MAMBA,
                                ModelConfig)
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import dense_init, rms_norm, swiglu


class EntrySpec(NamedTuple):
    kind: str
    use_moe: bool


# Cache leaves that hold one row per position, and the axis of the slot
# in the leaf stacked over layers: a decode step writes its new row
# there, and serving grows the prefill's cache along it.
SLOT_AXES = {"k": 3, "v": 3, "ckv": 2, "kpe": 2}
# Cache leaves that a decode step reads whole, stacked over layers, at
# the layer's index (``mla.mla_decode``): a layer's slice of them handed
# to a kernel would be copied first.
WHOLE = ("ckv", "kpe")
# Their slots come in whole tiles of this many: the chip then keeps the
# 64-wide roped keys slot-minor, the layout the latent-attention kernel
# reads as it is; at a length off the tile it puts the batch axis minor,
# and every step would relayout the whole stack for the kernel.
SLOT_TILE = 128


def cache_slots(name: str, max_len: int) -> int:
    """Slots of cache leaf ``name`` that hold sequences of ``max_len``."""
    return -(-max_len // SLOT_TILE) * SLOT_TILE if name in WHOLE \
        else max_len


# The cache leaf in which the decode program counts the rows routed to
# each held expert (then to experts held elsewhere), over layers and
# steps; zero after a prefill.
LOAD = "moe_load"


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Build-time knobs (perf hillclimb surface)."""
    remat: bool = True
    remat_policy: str = "dots_no_batch"   # dots_no_batch | nothing | everything
    q_chunk: int = 512
    kv_chunk: int = 512
    ssm_chunk: int = 256
    slstm_block: int = 16         # sLSTM timesteps per scan iteration
    attn_schedule: str = "dense"          # dense | binary
    use_flash_kernel: bool = False        # Pallas kernel (TPU only)
    loss_chunk: int = 512


def layer_plan(cfg: ModelConfig) -> Tuple[Tuple[EntrySpec, ...], int]:
    """Returns (period entries, n_periods) of the layers after the
    leading dense ones."""
    period = len(cfg.block_pattern)
    if cfg.moe is not None:
        period = math.lcm(period, cfg.moe.moe_every)
    lead = cfg.first_dense_layers
    assert (cfg.n_layers - lead) % period == 0, (cfg.name, cfg.n_layers,
                                                 period)
    moe_layers = set(cfg.moe_layers())
    entries = tuple(EntrySpec(cfg.blocks[lead + i], lead + i in moe_layers)
                    for i in range(period))
    return entries, (cfg.n_layers - lead) // period


def lead_plan(cfg: ModelConfig) -> Tuple[Tuple[EntrySpec, ...], int]:
    """(entries, n) of the leading dense layers, which run before the
    period scan, stacked as one entry; ((), 0) for a model with none."""
    lead = cfg.first_dense_layers
    if not lead:
        return (), 0
    kinds = set(cfg.blocks[:lead])
    assert len(kinds) == 1, (cfg.name, kinds)
    return (EntrySpec(cfg.blocks[0], False),), lead


def _groups(cfg: ModelConfig):
    """(params key, entry name prefix, entries, n) of each stack of
    layers, in the order they run."""
    out = []
    entries, n = lead_plan(cfg)
    if n:
        out.append(("lead", "d", entries, n))
    entries, n = layer_plan(cfg)
    out.append(("layers", "e", entries, n))
    return out


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------
def _init_ffn(key, cfg, dtype, width: int = 0):
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, width or cfg.d_ff
    return {"w1": dense_init(ks[0], (d, f), dtype),
            "w3": dense_init(ks[1], (d, f), dtype),
            "w2": dense_init(ks[2], (f, d), dtype)}


def _init_entry(key, spec: EntrySpec, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: Dict[str, Any] = {"ln1": jnp.ones((d,), dtype)}
    if spec.kind in (ATTN, SWA, MLA):
        p["attn"] = (mla_mod.init_mla_params if spec.kind == MLA else
                     attn_mod.init_attn_params)(ks[0], cfg, dtype)
        p["ln2"] = jnp.ones((d,), dtype)
        if spec.use_moe:
            p["moe"] = moe_mod.init_moe_params(
                ks[1], d, cfg.d_expert, cfg.moe.n_experts, dtype,
                n_held=cfg.experts_held or None,
                select_bias=cfg.moe.scoring == "sigmoid")
            if cfg.moe.shared_expert:
                p["shared"] = _init_ffn(ks[2], cfg, dtype, cfg.moe.d_shared)
        elif cfg.d_ff:
            p["ffn"] = _init_ffn(ks[1], cfg, dtype)
    elif spec.kind == MLSTM:
        p["mlstm"] = xlstm_mod.init_mlstm_params(
            ks[0], d, cfg.n_heads, cfg.head_dim, dtype)
    elif spec.kind == SLSTM:
        p["slstm"] = xlstm_mod.init_slstm_params(ks[0], d, cfg.n_heads, dtype)
    elif spec.kind == HYBRID:
        p["attn"] = attn_mod.init_attn_params(ks[0], cfg, dtype)
        p["mamba"] = ssm_mod.init_ssm_params(
            ks[1], d, cfg.n_heads, cfg.head_dim, cfg.ssm_state, dtype)
        p["beta"] = jnp.ones((2,), jnp.float32)
        p["ln2"] = jnp.ones((d,), dtype)
        if cfg.d_ff:
            p["ffn"] = _init_ffn(ks[2], cfg, dtype)
    elif spec.kind == MAMBA:
        p["mamba"] = ssm_mod.init_ssm_params(
            ks[0], d, cfg.n_heads, cfg.head_dim, cfg.ssm_state, dtype)
    else:
        raise ValueError(spec.kind)
    return p


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_out, k_layers = jax.random.split(key, 3)
    params = {
        "embed": dense_init(k_emb, (cfg.vocab, cfg.d_model), dtype,
                            scale=cfg.d_model ** 0.5),  # ~N(0,1) rows
        "unembed": dense_init(k_out, (cfg.d_model, cfg.vocab), dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    groups = _groups(cfg)
    gkeys = [k_layers] if len(groups) == 1 else \
        jax.random.split(k_layers, len(groups))
    for gkey, (group, prefix, entries, n) in zip(gkeys, groups):
        params[group] = {}
        lkeys = jax.random.split(gkey, len(entries))
        for i, spec in enumerate(entries):
            per_period = jax.random.split(lkeys[i], n)
            params[group][f"{prefix}{i}"] = jax.vmap(
                lambda k: _init_entry(k, spec, cfg, dtype))(per_period)
    return params


# ---------------------------------------------------------------------------
# Caches (serving state per entry)
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Zero cache pytree, stacked over periods: {'e0': {...}, ...}; the
    leading dense layers' entries are 'd0', ...; a model whose layers
    are told which experts they hold has the load counter too."""
    cache = {}
    for _, prefix, entries, n in _groups(cfg):
        for i, spec in enumerate(entries):
            cache[f"{prefix}{i}"] = _entry_cache(cfg, spec, n, batch,
                                                 max_len)
    if cfg.experts_held:
        cache[LOAD] = _zero_load(cfg)
    return cache


def _zero_load(cfg: ModelConfig):
    return jnp.zeros((cfg.n_experts_held + 1,), jnp.int32)


def _entry_cache(cfg: ModelConfig, spec: EntrySpec, n_periods: int,
                 batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    inner = cfg.n_heads * cfg.head_dim
    c: Dict[str, Any] = {}
    if spec.kind == MLA:
        slots = cache_slots("ckv", max_len)
        c["ckv"] = jnp.zeros((n_periods, batch, slots, cfg.kv_lora_rank),
                             dtype)
        c["kpe"] = jnp.zeros((n_periods, batch, slots,
                              cfg.qk_rope_head_dim), dtype)
    if spec.kind in (ATTN, SWA, HYBRID):
        smax = min(cfg.window, max_len) if spec.kind in (SWA, HYBRID) \
            and cfg.window else max_len
        c["k"] = jnp.zeros((n_periods, batch, cfg.n_kv_heads, smax,
                            cfg.head_dim), dtype)
        c["v"] = jnp.zeros_like(c["k"])
    if spec.kind == HYBRID or spec.kind == MAMBA:
        c["ssm"] = jnp.zeros((n_periods, batch, cfg.n_heads,
                              cfg.head_dim, cfg.ssm_state), jnp.float32)
        c["conv"] = jnp.zeros((n_periods, batch, ssm_mod.CONV_W - 1,
                               inner), dtype)
    if spec.kind == MLSTM:
        dv = 2 * d // cfg.n_heads
        c["H"] = jnp.zeros((n_periods, batch, cfg.n_heads,
                            cfg.head_dim, dv + 1), jnp.float32)
        c["m"] = jnp.full((n_periods, batch, cfg.n_heads), -1e30,
                          jnp.float32)
    if spec.kind == SLSTM:
        for name in ("c", "n", "h"):
            c[name] = jnp.zeros((n_periods, batch, d), jnp.float32)
        c["m"] = jnp.full((n_periods, batch, d), -1e30, jnp.float32)
    return c


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------
def _apply_ffn(p, x, cfg, mesh_args, opts, mode="train", new_cache=None):
    """Dense or MoE FFN sub-block.  Returns (y, aux).

    A MoE layer told which experts it holds (``cfg.experts_held``)
    serves dropless, and in a decode step puts the rows it routed to
    each of them, then to experts held elsewhere, in ``new_cache[LOAD]``.
    """
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        m = cfg.moe
        if cfg.experts_held and mode != "train":
            B, S, d = x.shape
            y, load = moe_mod.held_moe(
                p["moe"], x.reshape(B * S, d), top_k=m.top_k,
                e_first=cfg.experts_first, scoring=m.scoring,
                routed_scale=m.routed_scale)
            y = y.reshape(B, S, d)
            if mode == "decode":
                new_cache[LOAD] = load
        else:
            y, aux = moe_mod.moe_ffn(
                p["moe"], x, n_experts=m.n_experts, top_k=m.top_k,
                capacity_factor=m.capacity_factor, mesh_args=mesh_args,
                e_first=cfg.experts_first, scoring=m.scoring,
                routed_scale=m.routed_scale)
        if "shared" in p:
            with jax.named_scope("moe_shared"):
                y = y + swiglu(x, p["shared"]["w1"], p["shared"]["w3"],
                               p["shared"]["w2"])
    elif "ffn" in p:
        y = swiglu(x, p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"])
    else:
        return jnp.zeros_like(x), aux
    return y, aux


def _apply_entry(p, spec: EntrySpec, x, positions, cfg, mesh_args, opts,
                 mode: str, cache=None, cache_pos=None, layer=None):
    """One block; ``layer`` is its index in the stack whose ``WHOLE``
    cache leaves ``cache`` holds.  Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {} if cache is not None or mode != "train" else None
    h = rms_norm(x, p["ln1"], cfg.norm_eps)

    if spec.kind in (ATTN, SWA, MLA):
        if spec.kind == MLA:
            y, kv = _mla(p["attn"], h, positions, cfg, opts, mode, cache,
                         cache_pos, layer)
        else:
            window = cfg.window if spec.kind == SWA else 0
            y, kv = _attention(p["attn"], h, positions, cfg, window, opts,
                               mode, cache, cache_pos)
        if kv is not None:
            new_cache.update(kv)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        y2, aux = _apply_ffn(p, h2, cfg, mesh_args, opts, mode, new_cache)
        x = x + y2
    elif spec.kind == MLSTM:
        state = (cache["H"], cache["m"]) if cache is not None else None
        y, st = xlstm_mod.mlstm_forward(
            p["mlstm"], h, n_heads=cfg.n_heads, dqk=cfg.head_dim,
            chunk=opts.ssm_chunk, state=state,
            use_kernel=opts.use_flash_kernel)
        if mode != "train":
            new_cache.update({"H": st[0], "m": st[1]})
        x = x + y
    elif spec.kind == SLSTM:
        state = cache if cache is not None else None
        if state is not None:
            state = {k: cache[k] for k in ("c", "n", "h", "m")}
        y, st = xlstm_mod.slstm_forward(p["slstm"], h, n_heads=cfg.n_heads,
                                        state=state,
                                        time_block=opts.slstm_block)
        if mode != "train":
            new_cache.update(st)
        x = x + y
    elif spec.kind == HYBRID:
        window = cfg.window
        kv_in = None
        ssm_state = conv_state = None
        if cache is not None:
            kv_in = cache
            ssm_state, conv_state = cache["ssm"], cache["conv"]
        ya, kv = _attention(p["attn"], h, positions, cfg, window, opts,
                            mode, kv_in, cache_pos)
        ym, (st, cv) = ssm_mod.mamba_forward(
            p["mamba"], h, n_heads=cfg.n_heads, head_dim=cfg.head_dim,
            state=cfg.ssm_state, chunk=opts.ssm_chunk,
            ssm_state=ssm_state, conv_state=conv_state,
            use_kernel=opts.use_flash_kernel)
        beta = p["beta"].astype(x.dtype)
        y = 0.5 * (beta[0] * ya + beta[1] * ym)
        if mode != "train":
            new_cache.update(kv or {})
            new_cache.update({"ssm": st, "conv": cv})
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        y2, aux = _apply_ffn(p, h2, cfg, mesh_args, opts, mode, new_cache)
        x = x + y2
    elif spec.kind == MAMBA:
        ssm_state = conv_state = None
        if cache is not None:
            ssm_state, conv_state = cache["ssm"], cache["conv"]
        y, (st, cv) = ssm_mod.mamba_forward(
            p["mamba"], h, n_heads=cfg.n_heads, head_dim=cfg.head_dim,
            state=cfg.ssm_state, chunk=opts.ssm_chunk,
            ssm_state=ssm_state, conv_state=conv_state)
        if mode != "train":
            new_cache.update({"ssm": st, "conv": cv})
        x = x + y

    if mesh_args is not None and mesh_args.mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(
                mesh_args.mesh, P(tuple(mesh_args.dp_axes), None, None)))
    return x, new_cache, aux


def _mla(ap, h, positions, cfg, opts, mode, cache, cache_pos, layer):
    """Latent attention across the three modes.  Returns (y, cache rows):
    a prefill's (B, S, ...), a decode step's new row (B, 1, ...), of the
    latent (``ckv``) and of the roped key (``kpe``)."""
    if mode == "decode":
        y, rows = mla_mod.mla_decode(ap, h, cfg, positions, cache["ckv"],
                                     cache["kpe"], cache_pos, layer)
    else:
        y, rows = mla_mod.mla_attention(ap, h, cfg, positions,
                                        q_chunk=opts.q_chunk,
                                        kv_chunk=opts.kv_chunk,
                                        schedule=opts.attn_schedule)
    if mode == "train":
        return y, None
    return y, dict(zip(("ckv", "kpe"), rows))


def _attention(ap, h, positions, cfg, window, opts, mode, cache, cache_pos):
    """Attention sub-block across the three modes.  Returns (y, cache)."""
    if mode == "train":
        y, _ = attn_mod.attention_block(
            ap, h, positions, cfg, layer_window=window,
            q_chunk=opts.q_chunk, kv_chunk=opts.kv_chunk,
            schedule=opts.attn_schedule, use_kernel=opts.use_flash_kernel)
        return y, None
    if mode == "prefill":
        # build cache from scratch: compute qkv, then keep (window or full)
        q, k, v = attn_mod.project_qkv(ap, h, cfg, positions)
        if window:
            out = attn_mod.swa_attention(q, k, v, window)
            # ring cache: slot i must hold absolute position p with
            # p % w == i, so the kept tail is rolled by S % w.
            S = h.shape[1]
            w = min(window, S)
            kc = jnp.roll(k[:, -w:], S % w, axis=1)
            vc = jnp.roll(v[:, -w:], S % w, axis=1)
        else:
            out = attn_mod.chunked_attention(
                q, k, v, q_chunk=opts.q_chunk, kv_chunk=opts.kv_chunk,
                schedule=opts.attn_schedule)
            kc, vc = k, v
        with jax.named_scope("o_proj"):
            y = jnp.einsum("bshk,hkd->bsd", out, ap["wo"])
        # stored head-major, (B, Hkv, S, D): see attention_block
        return y, {"k": kc.swapaxes(1, 2).astype(jnp.dtype(cfg.dtype)),
                   "v": vc.swapaxes(1, 2).astype(jnp.dtype(cfg.dtype))}
    # decode: the cache is read, and the new token's K/V rows returned
    y, kv = attn_mod.attention_block(
        ap, h, positions, cfg, layer_window=window,
        kv_cache=(cache["k"], cache["v"]), cache_pos=cache_pos,
        q_chunk=opts.q_chunk, kv_chunk=opts.kv_chunk,
        schedule=opts.attn_schedule)
    return y, {"k": kv[0], "v": kv[1]}


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------
def _remat_policy(opts: ModelOptions):
    if opts.remat_policy == "dots_no_batch":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    if opts.remat_policy == "everything":
        return jax.checkpoint_policies.everything_saveable
    return jax.checkpoint_policies.nothing_saveable


def embed_inputs(params, cfg: ModelConfig, tokens, embeds):
    """tokens: (B, S_text) int32 or None; embeds: (B, S_front, d) or None."""
    parts = []
    if embeds is not None:
        parts.append(embeds.astype(jnp.dtype(cfg.dtype)))
    if tokens is not None:
        with jax.named_scope("embed"):
            parts.append(jnp.take(params["embed"], tokens, axis=0))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _stack_forward(params, x, cfg, mesh_args, opts, mode,
                   cache=None, cache_pos=None, positions=None):
    """Runs the leading dense layers, if any, then the scan over periods.
    Returns (x, new_cache, aux_sum); in a decode step ``new_cache[LOAD]``
    holds the rows the step routed to each held expert, over layers."""
    aux_sum = None
    new_cache = {} if mode != "train" else None
    for group, prefix, entries, _ in _groups(cfg):
        names = [f"{prefix}{i}" for i in range(len(entries))]
        gcache = None if cache is None else {n: cache[n] for n in names}
        x, gnew, aux = _scan_layers(
            params[group], entries, names, x, cfg, mesh_args, opts, mode,
            gcache, cache_pos, positions,
            scope="block" if prefix == "e" else "dense")
        aux_sum = aux if aux_sum is None else aux_sum + aux
        if gnew is not None:
            new_cache.update(gnew)
    if mode == "decode":
        loads = [c.pop(LOAD).sum(0) for c in new_cache.values()
                 if LOAD in c]
        if loads:
            new_cache[LOAD] = sum(loads)
    return x, new_cache, aux_sum


def _scan_layers(layer_params, entries, names, x, cfg, mesh_args, opts,
                 mode, cache, cache_pos, positions, scope):
    """One stack of layers as a scan.  Returns (x, new_cache, aux_sum)."""

    # a decode step's WHOLE leaves stay out of the scanned slices: every
    # layer is handed them stacked, with its index
    whole = {}
    if mode == "decode" and cache is not None:
        whole = {e: {n: c[n] for n in WHOLE if n in c}
                 for e, c in cache.items()}
        cache = {e: {n: v for n, v in c.items() if n not in WHOLE}
                 for e, c in cache.items()}

    def body(carry, xs):
        x, aux_sum = carry
        layer_p = xs["params"]
        layer_c = xs.get("cache")
        new_c = {}
        for i, (spec, ename) in enumerate(zip(entries, names)):
            c = layer_c[ename] if layer_c is not None else None
            if whole.get(ename):
                c = {**c, **whole[ename]}
            with jax.named_scope(f"{scope}_{spec.kind}{i}"):
                x, nc, aux = _apply_entry(
                    layer_p[ename], spec, x, positions, cfg, mesh_args, opts,
                    mode, cache=c, cache_pos=cache_pos,
                    layer=xs.get("layer"))
            new_c[ename] = nc
            aux_sum = aux_sum + aux
        return (x, aux_sum), (new_c if mode != "train" else None)

    if opts.remat and mode == "train":
        body = jax.checkpoint(body, policy=_remat_policy(opts),
                              prevent_cse=False)

    xs = {"params": layer_params}
    if cache is not None:
        xs["cache"] = cache
    if jax.tree.leaves(whole):
        xs["layer"] = jnp.arange(jax.tree.leaves(whole)[0].shape[0])
    (x, aux_sum), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                           xs)
    return x, new_cache, aux_sum


def forward(params, cfg: ModelConfig, tokens=None, embeds=None, *,
            mesh_args=None, opts: ModelOptions = ModelOptions()):
    """Training forward.  Returns (hidden (B,S,d), aux)."""
    x = embed_inputs(params, cfg, tokens, embeds)
    S = x.shape[1]
    positions = jnp.arange(S)
    x, _, aux = _stack_forward(params, x, cfg, mesh_args, opts, "train",
                               positions=positions)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def lm_loss(params, cfg: ModelConfig, hidden, labels, *,
            mesh_args=None, opts: ModelOptions = ModelOptions(),
            z_loss: float = 1e-4):
    """Chunked cross-entropy over the unembedding.  labels: (B,S) int32,
    positions with label < 0 are masked.  Returns (loss, n_tokens)."""
    B, S, d = hidden.shape
    from repro.models.layers import pick_chunk
    c = pick_chunk(S, opts.loss_chunk)
    n = S // c
    hs = hidden.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, c).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_loss(carry, xs):
        h, lab = xs
        with jax.named_scope("unembed"):
            logits = jnp.einsum("bcd,dv->bcv", h,
                                params["unembed"]).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # gather (not one-hot einsum): avoids materializing a second
        # (B, c, V) fp32 temporary — see EXPERIMENTS.md §Perf
        lab_logit = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        nll = (lse - lab_logit) * mask
        zl = z_loss * jnp.square(lse) * mask
        loss, ntok = carry
        return (loss + jnp.sum(nll + zl), ntok + jnp.sum(mask)), None

    (loss, ntok), _ = jax.lax.scan(
        chunk_loss, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls))
    return loss, ntok


def loss_fn(params, cfg: ModelConfig, batch, *, mesh_args=None,
            opts: ModelOptions = ModelOptions()):
    """Scalar-mean LM loss + MoE aux.  batch: dict(tokens?, embeds?, labels)."""
    hidden, aux = forward(params, cfg, batch.get("tokens"),
                          batch.get("embeds"), mesh_args=mesh_args, opts=opts)
    loss, ntok = lm_loss(params, cfg, hidden, batch["labels"],
                         mesh_args=mesh_args, opts=opts)
    total = loss / jnp.maximum(ntok, 1.0) + 0.01 * aux
    return total, {"nll": loss / jnp.maximum(ntok, 1.0), "aux": aux,
                   "ntok": ntok}


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None, *,
            mesh_args=None, opts: ModelOptions = ModelOptions()):
    """Serving prefill.  Returns (last_logits (B,V), cache)."""
    x = embed_inputs(params, cfg, tokens, embeds)
    S = x.shape[1]
    positions = jnp.arange(S)
    x, cache, _ = _stack_forward(params, x, cfg, mesh_args, opts, "prefill",
                                 positions=positions)
    if cfg.experts_held:
        cache[LOAD] = _zero_load(cfg)
    h_last = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    with jax.named_scope("unembed"):
        logits = jnp.einsum("bsd,dv->bsv", h_last, params["unembed"])
    return logits[:, 0].astype(jnp.float32), cache


def decode_step(params, cfg: ModelConfig, cache, token=None, embed=None,
                pos=None, *, mesh_args=None,
                opts: ModelOptions = ModelOptions()):
    """One serving step: one new token against the cache.

    token: (B,) int32 (or embed: (B,1,d) for audio).  pos: scalar int32
    absolute position of this token.  Returns (logits (B,V), new_cache).

    The layer scan only reads the K/V (and latent) caches, the latent
    ones whole at each layer's index, and yields the new token's rows,
    (n_periods, B, Hkv, 1, D) (latent: (n_periods, B, 1, r) and
    (n_periods, B, 1, rope)); they are written after it, one update per
    cache at its ``SLOT_AXES`` axis, so a caller that donates ``cache``
    has it updated in place.  Recurrent states come out of the scan whole; the
    held experts' load counter, if any, grows by this step's rows.
    """
    if embed is None:
        x = jnp.take(params["embed"], token[:, None], axis=0)
    else:
        x = embed.astype(jnp.dtype(cfg.dtype))
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    x, new_cache, _ = _stack_forward(params, x, cfg, mesh_args, opts,
                                     "decode", cache=cache, cache_pos=pos,
                                     positions=positions)
    for ename, c in cache.items():
        if ename == LOAD:
            new_cache[LOAD] = c + new_cache[LOAD]
            continue
        for name, axis in SLOT_AXES.items():
            if name in c:
                slot = pos % c[name].shape[axis]
                new_cache[ename][name] = jax.lax.dynamic_update_slice_in_dim(
                    c[name], new_cache[ename][name], slot, axis)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("unembed"):
        logits = jnp.einsum("bsd,dv->bsv", h, params["unembed"])
    return logits[:, 0].astype(jnp.float32), new_cache
