"""Config system: model / shape / run configuration dataclasses.

Every assigned architecture gets one module in ``repro/configs`` exporting a
``CONFIG`` (full-size, exercised only via the dry-run) and a ``SMOKE_CONFIG``
(reduced, same family, runnable on CPU). ``repro.configs.registry`` maps
``--arch`` ids to modules.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters (exact dims from the assignment block)."""

    name: str
    family: str                      # dense | moe | hybrid | audio | ssm | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0                # 0 -> d_model // num_heads
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    moe_every: int = 1               # every k-th layer is MoE (1 = all)
    # the device's share of each expert layer: experts [expert_offset,
    # expert_offset + experts_held) of the num_experts routed over, dropless
    # (0 = all experts, on the capacity-bounded path)
    experts_held: int = 0
    expert_offset: int = 0
    # --- attention ---
    attention_kind: str = "full"     # full | sliding | hybrid_local (rg-lru 1:2)
    sliding_window: int = 0
    rope_theta: float = 10_000.0
    # one period of layer kinds, "sliding" or "full", repeated through the
    # stack (empty = every layer as attention_kind says)
    layer_pattern: Tuple[str, ...] = ()
    # YaRN rotary scaling of the pattern's full layers (factor 0 = off), as
    # transformers' _compute_yarn_parameters defines it; the attention
    # factor 0 takes its default, 0.1 ln(factor) + 1
    yarn_factor: float = 0.0
    yarn_original_max_positions: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 0.0
    # --- hybrid / ssm ---
    local_window: int = 2048         # recurrentgemma local-attn window
    conv_width: int = 4              # temporal conv width in recurrent block
    rglru_c: float = 8.0             # RG-LRU constant c
    slstm_every: int = 0             # xlstm: every k-th block is sLSTM (0 = none)
    # --- enc-dec ---
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    src_len_ratio: float = 1.0       # encoder frame len = seq * ratio
    # --- vlm ---
    num_patches: int = 0             # pixtral: patch-embedding prefix length
    # --- numerics ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    # --- distribution defaults (mimdram planner hints) ---
    remat: str = "block"             # none | block | full
    optimizer: str = "adamw"         # adamw | adafactor
    scan_layers: bool = True
    microbatches_hint: int = 0       # per-arch grad-accumulation override
    # --- beyond-paper perf knobs (hillclimb; default = paper-faithful off) ---
    attn_block_skip: bool = False    # skip fully-masked causal kv tiles
    tp_pad_heads: int = 0            # pad q heads to this count for TP divisibility
    attn_chunk_q: int = 512          # flash tile sizes (HBM<->VMEM blocking)
    attn_chunk_kv: int = 1024
    # notes for DESIGN/EXPERIMENTS
    source: str = ""

    def __post_init__(self):
        # a configuration file gives the pattern as a list
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_subquadratic(self) -> bool:
        """True if the arch supports 500k-token decode (bounded state)."""
        return (
            self.family in ("hybrid", "ssm")
            or (self.attention_kind == "sliding" and self.sliding_window > 0)
        )

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(1, self.num_kv_heads)

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    mode: str                        # train | prefill | decode

    def replace(self, **kw: Any) -> "ShapeConfig":
        return dataclasses.replace(self, **kw)


# The four assigned LM shape cells.
TRAIN_4K = ShapeConfig("train_4k", seq_len=4_096, global_batch=256, mode="train")
PREFILL_32K = ShapeConfig("prefill_32k", seq_len=32_768, global_batch=32, mode="prefill")
DECODE_32K = ShapeConfig("decode_32k", seq_len=32_768, global_batch=128, mode="decode")
LONG_500K = ShapeConfig("long_500k", seq_len=524_288, global_batch=1, mode="decode")
SHAPES: Tuple[ShapeConfig, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in SHAPES}


@dataclass(frozen=True)
class RunConfig:
    """Training / serving run options (launcher-level)."""

    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    seed: int = 0
    # distribution
    mesh_shape: Tuple[int, ...] = (1,)
    mesh_axes: Tuple[str, ...] = ("data",)
    microbatches: int = 0            # 0 = auto; >1 grad accumulation / PP chunks
    pipeline_stages: int = 0         # >0 enables PP over the 'pod' axis
    # proteus runtime
    proteus_enabled: bool = False
    proteus_grad_bits: int = 8       # quantized all-reduce payload width
    proteus_block: int = 256         # per-block scale granularity
    # checkpointing
    checkpoint_dir: str = ""
    checkpoint_every: int = 200
    keep_checkpoints: int = 3

    def replace(self, **kw: Any) -> "RunConfig":
        return dataclasses.replace(self, **kw)


def tokens_per_step(shape: ShapeConfig) -> int:
    if shape.mode == "decode":
        return shape.global_batch            # one new token per sequence
    return shape.global_batch * shape.seq_len


def param_count(cfg: ModelConfig) -> int:
    """Analytic parameter count (embedding + blocks + head)."""
    d, h = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    attn = d * (nq * h) + 2 * d * (nkv * h) + (nq * h) * d
    if cfg.family == "ssm":
        # xlstm block: qkv + gates + out (mLSTM) approximation, no separate FFN
        blk = 4 * d * d + 2 * d
        n_blocks = cfg.num_layers
        total_blocks = n_blocks * blk
    else:
        if cfg.num_experts > 0:
            held = cfg.experts_held or cfg.num_experts
            ffn = 3 * d * cfg.d_ff * held + d * cfg.num_experts
        else:
            ffn = 3 * d * cfg.d_ff
        blk = attn + ffn + 2 * d
        total_blocks = cfg.num_layers * blk
        if cfg.is_encoder_decoder:
            # encoder blocks (self-attn + ffn) + decoder cross-attn
            total_blocks += cfg.num_encoder_layers * (attn + 3 * d * cfg.d_ff + 2 * d)
            total_blocks += cfg.num_layers * attn
    emb = cfg.vocab_size * d
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * d
    return emb + head + total_blocks


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: only routed experts count)."""
    if cfg.num_experts == 0:
        return param_count(cfg)
    dense_like = cfg.replace(num_experts=0, experts_per_token=0)
    base = param_count(dense_like)
    d = cfg.d_model
    per_expert = 3 * d * cfg.d_ff
    # subtract the single dense ffn counted in base, add k routed experts + router
    return (
        base
        - cfg.num_layers * per_expert
        + cfg.num_layers * (cfg.experts_per_token * per_expert + d * cfg.num_experts)
    )
