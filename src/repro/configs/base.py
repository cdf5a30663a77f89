"""Architecture + shape configuration system (``--arch``, ``--shape``)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int              # routed experts the router scores
    top_k: int
    d_expert: int                 # per-expert FFN width
    interleave: int = 1           # MoE every Nth layer (1 = every layer)
    shared_expert: bool = False   # llama4-style always-on shared expert
    d_shared: int = 0             # shared-expert width (0 = d_expert)
    # router scoring: "softmax" (top-k of the softmax, renormalised) or
    # "sigmoid" (DeepSeek-V3 noaux_tc: top-k of sigmoid + a selection
    # bias; the chosen sigmoid scores renormalised), then x routed_scale
    score: str = "softmax"
    routed_scale: float = 1.0
    # expert parallelism: (first, count) of the contiguous range of routed
    # experts this chip holds; None = all.  The router still scores every
    # expert; the absent experts' part of the layer is left out.
    held: Optional[Tuple[int, int]] = None

    @property
    def shared_width(self) -> int:
        return self.d_shared or self.d_expert

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held or (0, self.num_experts)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    headdim: int = 64
    expand: int = 2
    d_conv: int = 4
    chunk: int = 256              # SSD chunk length


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    sliding_window: Optional[int] = None     # local window (gemma2 local layers)
    local_global_period: int = 0             # 2 => alternate local/global
    logit_softcap: Optional[float] = None    # gemma2: 50.0
    qk_norm: bool = False                    # qwen3
    qkv_bias: bool = False                   # qwen2
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V3, arXiv:2412.19437) without a
    query low-rank path: K and V come up from one normalised latent per
    position, and a rotary key part is shared by every head."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    attn: AttnConfig = AttnConfig()
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    # layer-pattern description: list of (mixer, ffn) strings, repeated to
    # reach n_layers.  mixer in {attn, attn_local, attn_global, mamba, mla};
    # ffn in {dense, moe, geglu_dense}.
    pattern: Tuple[Tuple[str, str], ...] = (("attn", "dense"),)
    # leading layers with a dense FFN of width d_ff before the pattern
    # starts (DeepSeek-V3's first_k_dense_replace); counted in n_layers
    first_k_dense: int = 0
    # encoder-decoder
    n_encoder_layers: int = 0
    # frontends (vlm/audio stubs): number of precomputed embedding positions
    frontend_positions: int = 0
    tie_embeddings: bool = False
    final_softcap: Optional[float] = None    # gemma2: 30.0
    act: str = "silu"                        # silu | gelu
    post_norms: bool = False                 # gemma2 pre+post block norms
    scale_embed: bool = True                 # embeddings x sqrt(d_model)
    norm_eps: float = 1e-6
    # dtypes: big-MoE models run bf16 optimizer state (see DESIGN.md §4)
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    opt_state_dtype: str = "float32"

    @property
    def n_pattern_repeats(self) -> int:
        n = self.n_layers - self.first_k_dense
        assert n % len(self.pattern) == 0, (
            f"{self.name}: {n} pattern layers not divisible by "
            f"pattern length {len(self.pattern)}"
        )
        return n // len(self.pattern)

    @property
    def lead_pattern(self) -> Tuple[Tuple[str, str], ...]:
        """The pattern of the leading dense layers (repeated
        ``first_k_dense`` times): the pattern's first mixer, a dense FFN."""
        return ((self.pattern[0][0], "dense"),)

    def param_count(self) -> int:
        """Approximate total parameter count (for 6ND roofline math)."""
        c = self
        emb = c.vocab * c.d_model * (1 if c.tie_embeddings else 2)
        per_attn = c.d_model * c.d_head * (c.n_heads + 2 * c.n_kv_heads) + (
            c.n_heads * c.d_head * c.d_model
        )
        if c.mla is not None:
            m = c.mla
            per_attn = (c.d_model * c.n_heads * m.qk_head_dim            # wq
                        + c.d_model * (m.kv_lora_rank + m.qk_rope_head_dim)
                        + m.kv_lora_rank * c.n_heads
                        * (m.qk_nope_head_dim + m.v_head_dim)         # wkv_b
                        + c.n_heads * m.v_head_dim * c.d_model)       # wo
        per_dense_ffn = 3 * c.d_model * c.d_ff
        per_mamba = 0
        if c.ssm is not None:
            d_in = c.ssm.expand * c.d_model
            per_mamba = (
                c.d_model * (2 * d_in + 2 * c.ssm.d_state)  # in_proj(z,x,B,C)
                + d_in * c.d_model                          # out_proj
                + d_in * c.ssm.d_conv                       # conv
            )
        total = emb
        layers = [(mixer, ffn, self.n_pattern_repeats)
                  for mixer, ffn in c.pattern]
        layers += [(mixer, ffn, c.first_k_dense)
                   for mixer, ffn in c.lead_pattern]
        for mixer, ffn, reps in layers:
            if mixer.startswith("attn") or mixer == "mla":
                total += reps * per_attn
            elif mixer == "mamba":
                total += reps * per_mamba
            if ffn == "dense":
                total += reps * per_dense_ffn
            elif ffn == "moe":
                assert c.moe is not None
                e = c.moe.num_experts * 3 * c.d_model * c.moe.d_expert
                if c.moe.shared_expert:
                    e += 3 * c.d_model * c.moe.shared_width
                e += c.d_model * c.moe.num_experts  # router
                total += reps * e
        if c.n_encoder_layers:
            # encoder layers + decoder cross-attention
            total += c.n_encoder_layers * (per_attn + per_dense_ffn)
            total += c.n_layers * per_attn  # cross-attn in each decoder layer
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of num_experts)."""
        if self.moe is None:
            return self.param_count()
        c = self
        full_moe = c.moe.num_experts * 3 * c.d_model * c.moe.d_expert
        act_moe = c.moe.top_k * 3 * c.d_model * c.moe.d_expert
        n_moe_layers = sum(
            self.n_pattern_repeats for _, ffn in c.pattern if ffn == "moe"
        )
        return self.param_count() - n_moe_layers * (full_moe - act_moe)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                    # train | prefill | decode
    seq_len: int
    global_batch: int
    microbatches: int = 1        # grad-accumulation steps (train only)


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}

# Archs whose long_500k cell RUNS (constant-state SSM / sparse-KV hybrid
# families); every pure full-attention arch skips it — the sanctioned skip
# list; see DESIGN.md §3.
LONG_CONTEXT_ARCHS = ("mamba2-780m", "jamba-1.5-large-398b")


def shape_for(arch: ArchConfig, shape_name: str, microbatches: int = None) -> ShapeConfig:
    s = SHAPES[shape_name]
    if microbatches is not None:
        s = dataclasses.replace(s, microbatches=microbatches)
    return s
