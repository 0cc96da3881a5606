"""Model configuration system + architecture registry.

Every assigned architecture registers an exact ``ModelConfig`` (from public
literature, see per-file citations) plus a ``reduced()`` variant for CPU
smoke tests.  Shapes (the assignment's per-arch input-shape set) are global
and defined here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace

__all__ = ["ModelConfig", "ShapeSpec", "SHAPES", "register", "get_config",
           "list_configs", "reduced"]


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0                # 0 -> d_model // n_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    norm_type: str = "rms"             # rms | layer
    mlp_type: str = "swiglu"           # swiglu | geglu | gelu
    pos_emb: str = "rope"              # rope | sinusoidal | none
    # --- MoE ---
    n_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    moe_group_size: int = 2048     # GShard-style dispatch group
    shared_expert_ff: int = 0      # width of an always-on shared expert (0: none)
    experts_held: int = 0          # experts held on this device (0: all)
    expert_offset: int = 0         # index of the first held expert
    # --- SSM (Mamba-2 / SSD) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    # --- hybrid (RecurrentGemma / Griffin) ---
    block_pattern: tuple = ("attn",)   # layer kinds of one scanned group
    tail_pattern: tuple = ()           # remainder layers (not scanned)
    local_window: int = 0              # local attention window (0 = full)
    lru_width: int = 0                 # RG-LRU recurrence width (0 = d_model)
    logits_soft_cap: float = 0.0
    # --- scalar multipliers (Granite 4.0); the defaults change nothing ----
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0   # scales each residual branch
    attention_multiplier: float = 0.0  # score scale; 0 -> 1/sqrt(head_dim)
    logits_scaling: float = 1.0        # logits are divided by it
    # --- modality frontend stub ---
    frontend: str | None = None        # None | "audio_frames" | "vision_patches"
    n_prefix: int = 0                  # frontend embedding positions
    # --- numerics / runtime ---
    dtype: str = "bfloat16"
    remat: str = "full"                # full | dots | none
    scan_layers: bool = True
    attn_chunk: int = 1024             # KV-chunk for memory-bounded attention
    loss_chunk: int = 0                # 0 = unchunked vocab loss
    # --- mesh padding (set by pad_for_mesh; 0 = unpadded) -------------------
    # jit argument shardings require exact divisibility, so dims sharded over
    # the model axis are padded in the PARAMETERS and masked inert at runtime
    # (zero gradients, zero forward contribution) — the logical architecture
    # is unchanged.
    n_heads_padded: int = 0
    n_kv_heads_padded: int = 0
    vocab_padded: int = 0
    n_experts_padded: int = 0
    # provenance
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    @property
    def heads_p(self) -> int:
        return self.n_heads_padded or self.n_heads

    @property
    def kv_heads_p(self) -> int:
        return self.n_kv_heads_padded or self.n_kv_heads

    @property
    def vocab_p(self) -> int:
        return self.vocab_padded or self.vocab_size

    @property
    def experts_p(self) -> int:
        return self.n_experts_padded or self.n_experts

    @property
    def experts_here(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def n_groups(self) -> int:
        """Number of scanned groups of ``block_pattern``."""
        body = self.n_layers - len(self.tail_pattern)
        assert body % len(self.block_pattern) == 0, (
            f"{self.name}: {body} layers not divisible by pattern "
            f"{self.block_pattern}")
        return body // len(self.block_pattern)

    @property
    def is_attention_free(self) -> bool:
        kinds = set(self.block_pattern) | set(self.tail_pattern)
        return not kinds & {"attn", "local_attn", "moe", "attn_moe"}

    @property
    def is_subquadratic(self) -> bool:
        """True if the arch never attends over the full sequence ("moe"
        blocks carry full GQA attention)."""
        kinds = set(self.block_pattern) | set(self.tail_pattern)
        return not kinds & {"attn", "moe", "attn_moe"}

    def supports_shape(self, shape: ShapeSpec) -> tuple[bool, str]:
        if shape.name == "long_500k" and not self.is_subquadratic:
            return False, "full-attention arch: 500k decode skipped per assignment"
        return True, ""

    # -- parameter counting (for 6ND roofline term) -------------------------
    def param_count(self) -> int:
        d, dh = self.d_model, self.head_dim
        n_attn = self.d_model * dh * (self.n_heads + 2 * self.n_kv_heads) \
            + self.n_heads * dh * d
        if self.qkv_bias:
            n_attn += dh * (self.n_heads + 2 * self.n_kv_heads)
        n_mlp_dense = 3 * d * self.d_ff          # SwiGLU
        n_moe = self.n_experts * 3 * d * self.d_ff + d * self.n_experts
        d_inner = self.ssm_expand * d
        n_heads_ssm = d_inner // self.ssm_head_dim if self.ssm_head_dim else 0
        n_ssm = (d * (2 * d_inner + 2 * self.ssm_state + n_heads_ssm)
                 + self.ssm_conv * (d_inner + 2 * self.ssm_state)
                 + 2 * n_heads_ssm + d_inner * d)
        w = self.lru_width or d
        n_rglru = (d * 2 * w) + 4 * w * 2 + 2 * w + w * d  # proj + conv4 + gates + out
        # the served MoE: held experts, the full router, the shared expert;
        # its Mamba-2 mixer counted whole (conv bias, dt bias, gated norm)
        n_moe_served = (self.experts_here * 3 * d * self.d_ff
                        + d * self.n_experts + 3 * d * self.shared_expert_ff)
        n_mamba = (n_ssm + (d_inner + 2 * self.ssm_state) + n_heads_ssm
                   + d_inner)
        per_kind = {"attn": n_attn + n_mlp_dense,
                    "local_attn": n_attn + n_mlp_dense,
                    "moe": n_attn + n_moe,
                    "ssm": n_ssm,
                    "rglru": n_rglru + n_mlp_dense,
                    "ssm_moe": n_mamba + n_moe_served,
                    "attn_moe": n_attn + n_moe_served}
        kinds = list(self.block_pattern) * self.n_groups + list(self.tail_pattern)
        total = sum(per_kind[k] for k in kinds)
        total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        total += d * (2 * self.n_layers + 1)     # norms
        return int(total)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        if self.n_experts == 0:
            return self.param_count()
        dense_like = replace(self, n_experts=self.experts_per_token,
                             experts_held=0, expert_offset=0)
        return dense_like.param_count()


_REGISTRY: dict[str, "ModelConfig"] = {}
_REDUCED: dict[str, "ModelConfig"] = {}


def register(cfg: ModelConfig, reduced_cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    _REDUCED[cfg.name] = reduced_cfg
    return cfg


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    _ensure_loaded()
    table = _REDUCED if reduced else _REGISTRY
    if name not in table:
        raise KeyError(f"unknown arch '{name}'; known: {sorted(_REGISTRY)}")
    return table[name]


def reduced(name: str) -> ModelConfig:
    return get_config(name, reduced=True)


def list_configs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def pad_for_mesh(cfg: ModelConfig, tp: int, pad_kv: bool = False) -> ModelConfig:
    """Pad model-axis-sharded dims up to multiples of the TP size.

    Padded slots are inert (masked in attention output / router / logits),
    so the logical architecture is exactly the published config; the cost is
    idle compute on the padded fraction, reported in the roofline notes.
    """
    def up(n: int, m: int) -> int:
        return -(-n // m) * m

    hp = up(cfg.n_heads, tp) if cfg.n_heads % tp else cfg.n_heads
    kvp = cfg.n_kv_heads
    if cfg.n_kv_heads == cfg.n_heads:          # MHA: pad KV with the heads
        kvp = hp
    elif pad_kv and cfg.n_kv_heads % tp:
        # decode kv-shard policy: pad KV heads up to the model axis so the
        # cache shards by head.  Heads must pad to kvp × G with the ORIGINAL
        # group size G — real q head h then keeps its original index and its
        # original kv head h//G (padding kv without this breaks the GQA
        # grouping for real heads).  Each model shard gets exactly its kv
        # heads' aligned q-head groups — fully local attention.
        kvp = up(cfg.n_kv_heads, tp)
        g = cfg.n_heads // cfg.n_kv_heads
        hp = kvp * g
    elif hp % cfg.n_kv_heads:
        raise ValueError(f"{cfg.name}: padded heads {hp} not divisible by "
                         f"kv heads {cfg.n_kv_heads}")
    vp = up(cfg.vocab_size, tp) if cfg.vocab_size % tp else cfg.vocab_size
    ep = cfg.n_experts
    if cfg.n_experts and cfg.n_experts % tp:
        ep = up(cfg.n_experts, tp)
    return replace(cfg, n_heads_padded=hp, n_kv_heads_padded=kvp,
                   vocab_padded=vp, n_experts_padded=ep)


_LOADED = []


def _ensure_loaded() -> None:
    # a config module imported on its own registers itself: that does not
    # count as loading the registry
    if _LOADED:
        return
    from repro.configs import (glm4_9b, granite_moe_3b_a800m, internvl2_1b,  # noqa: F401
                               granite_4_0_h_small, mamba2_130m,
                               musicgen_medium, qwen2_0_5b,
                               qwen2_5_14b, qwen2_5_3b, qwen3_moe_235b_a22b,
                               recurrentgemma_2b)
    _LOADED.append(True)
