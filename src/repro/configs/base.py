"""Config system for repro.

Every assigned architecture is a ``ModelConfig`` registered under its public id
(e.g. ``"qwen3-32b"``).  Configs are plain frozen dataclasses so they are
hashable (usable as jit static args) and trivially serializable.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds — per-layer building blocks a model may stack.
# ---------------------------------------------------------------------------
ATTN = "attn"            # full causal attention (GQA)
SWA = "swa"              # sliding-window causal attention
MLSTM = "mlstm"          # xLSTM matrix-memory block
SLSTM = "slstm"          # xLSTM scalar-memory block
HYBRID = "hybrid"        # parallel attention + mamba heads (Hymba)
MAMBA = "mamba"          # selective SSM block
MLA = "mla"              # multi-head latent attention (DeepSeek-V2/V3)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    shared_expert: bool = False  # llama4-style always-on shared expert
    moe_every: int = 1           # every Nth layer is MoE (llama4: 2)
    d_expert: int = 0            # routed expert width (0: the model's d_ff)
    d_shared: int = 0            # shared expert width (0: the model's d_ff)
    # softmax | sigmoid: DeepSeek-V3's, with a bias added to the scores
    # that selects experts only
    scoring: str = "softmax"
    routed_scale: float = 1.0    # routed gates scaled after normalising


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int
    # --- block structure -----------------------------------------------
    block_pattern: Tuple[str, ...] = (ATTN,)   # tiled over n_layers
    window: int = 0             # sliding window size for SWA blocks
    # --- attention details ----------------------------------------------
    qk_norm: bool = False       # qwen3
    qkv_bias: bool = False      # qwen2
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6      # RMSNorm epsilon
    # --- latent attention (MLA blocks; head_dim = nope + rope) ----------
    kv_lora_rank: int = 0       # width of the latent K/V is cached in
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0   # one roped key per token, shared by heads
    v_head_dim: int = 0
    first_dense_layers: int = 0  # leading layers with a dense FFN
    # --- MoE --------------------------------------------------------------
    moe: Optional[MoEConfig] = None
    # experts [experts_first, experts_first + experts_held) of each MoE
    # layer live here (expert parallelism's share of one chip); 0 holds
    # them all
    experts_held: int = 0
    experts_first: int = 0
    # --- SSM / recurrent ---------------------------------------------------
    ssm_state: int = 0          # mamba state size (hymba) / mlstm uses head_dim
    # --- modality frontend (stub): extra embedded inputs ------------------
    frontend: str = "none"      # none | vlm | audio
    frontend_tokens: int = 0    # number of stub embedding positions prepended
    # --- numerics ----------------------------------------------------------
    dtype: str = "bfloat16"
    # --- citation ----------------------------------------------------------
    source: str = ""

    @property
    def blocks(self) -> Tuple[str, ...]:
        """Per-layer block kinds, tiling block_pattern over n_layers."""
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.n_layers))

    @property
    def is_subquadratic(self) -> bool:
        """True if no block requires full quadratic attention."""
        return all(b not in (ATTN, MLA) for b in self.blocks)

    @property
    def has_kv_cache(self) -> bool:
        return any(b in (ATTN, SWA, HYBRID, MLA) for b in self.blocks)

    @property
    def n_experts_held(self) -> int:
        """Routed experts of each MoE layer that this chip holds."""
        return self.experts_held or self.moe.n_experts

    @property
    def d_expert(self) -> int:
        return self.moe.d_expert or self.d_ff

    def _mla_params(self) -> int:
        d, h = self.d_model, self.n_heads
        r, nope = self.kv_lora_rank, self.qk_nope_head_dim
        rope, dv = self.qk_rope_head_dim, self.v_head_dim
        return (d * h * (nope + rope) + d * (r + rope) + r
                + r * h * (nope + dv) + h * dv * d)

    def n_params(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        qd = self.n_heads * self.head_dim
        kvd = self.n_kv_heads * self.head_dim
        total = v * d * 2  # embed + unembed (untied)
        for i, b in enumerate(self.blocks):
            if b in (ATTN, SWA):
                total += d * (qd + 2 * kvd) + qd * d          # qkv + o
                total += self._ffn_params(i)
            elif b == MLA:
                total += self._mla_params() + self._ffn_params(i)
            elif b == MLSTM:
                # up-proj 2x, qkv over inner dim, gates, down-proj
                inner = 2 * d
                total += d * inner * 2 + inner * d + 3 * inner * self.head_dim
            elif b == SLSTM:
                inner = d
                total += 4 * d * inner + inner * d + d * (4 * d) // 3
            elif b == MAMBA:
                inner = 2 * d
                total += d * inner * 2 + inner * d + inner * (2 * self.ssm_state + 2)
            elif b == HYBRID:
                total += d * (qd + 2 * kvd) + qd * d
                inner = qd  # mamba path sized like attention path
                total += d * inner * 2 + inner * d + inner * (2 * self.ssm_state + 2)
                total += self._ffn_params(i)
            total += 2 * d  # norms
        return total

    def moe_layers(self) -> Tuple[int, ...]:
        """Layer indices whose FFN is MoE."""
        if self.moe is None:
            return ()
        ev, lead = self.moe.moe_every, self.first_dense_layers
        return tuple(i for i in range(lead, self.n_layers)
                     if (i - lead) % ev == ev - 1
                     and self.blocks[i] in (ATTN, SWA, MLA))

    def _ffn_params(self, layer: int = 0) -> int:
        d, f = self.d_model, self.d_ff
        if self.moe is not None and layer in self.moe_layers():
            e = self.moe.n_experts
            # experts (gated mlp) + router
            p = e * 3 * d * self.d_expert + d * e
            if self.moe.shared_expert:
                p += 3 * d * (self.moe.d_shared or f)
            return p
        if f == 0:
            return 0
        return 3 * d * f  # gated (swiglu) mlp

    def n_active_params(self) -> int:
        """Active params per token (MoE: only routed experts count)."""
        if self.moe is None:
            return self.n_params()
        full = self.n_params()
        e, k = self.moe.n_experts, self.moe.top_k
        inactive = (len(self.moe_layers()) * (e - k) * 3 * self.d_model
                    * self.d_expert)
        return full - inactive

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe, n_experts=min(4, self.moe.n_experts),
                top_k=min(2, self.moe.top_k),
                d_expert=min(self.moe.d_expert, 64),
                d_shared=min(self.moe.d_shared, 64))
        mla = {}
        if self.kv_lora_rank:
            mla = dict(kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16)
        return dataclasses.replace(
            self,
            n_layers=min(2, self.n_layers) if len(self.block_pattern) <= 2
            else len(self.block_pattern),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(2, self.n_kv_heads) if self.n_kv_heads < self.n_heads else 4,
            d_ff=128 if self.d_ff else 0,
            vocab=256,
            head_dim=24 if mla else 16,
            window=min(self.window, 64) if self.window else 0,
            frontend_tokens=min(self.frontend_tokens, 8),
            moe=moe,
            experts_held=min(self.experts_held, 4),
            experts_first=0,
            dtype="float32",
            **mla,
        )


# ---------------------------------------------------------------------------
# Input shapes.  decode_*/long_* lower ``serve_step`` (one token against a KV
# cache of ``seq_len``); train_* lower ``train_step``; prefill_* lower the
# prefill half of ``serve_step``.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """(runs?, reason).  long_500k requires sub-quadratic sequence mixing."""
    if shape.name == "long_500k":
        quad = [b for b in set(model.blocks) if b in (ATTN, MLA)]
        if quad:
            return False, ("SKIP: pure full-attention blocks are quadratic/"
                           "O(S) KV at 512k; per DESIGN.md only sub-quadratic "
                           "archs run long_500k")
    return True, ""


_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if not _REGISTRY:
        _load_all()
    return _REGISTRY[name]


def list_configs() -> Tuple[str, ...]:
    if not _REGISTRY:
        _load_all()
    return tuple(sorted(_REGISTRY))


def _load_all() -> None:
    # import side effect registers each config
    from repro.configs import (  # noqa: F401
        xlstm_125m, yi_6b, qwen2_1_5b, starcoder2_15b, qwen3_32b,
        llava_next_mistral_7b, llama4_maverick_400b_a17b,
        granite_moe_1b_a400m, musicgen_large, hymba_1_5b,
        moonlight_16b_a3b)
