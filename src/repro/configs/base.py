"""Config system: model/mesh/shape/train configs and the arch registry.

Every assigned architecture lives in ``src/repro/configs/<id>.py`` exposing
``CONFIG`` (exact published numbers) and ``SMOKE`` (reduced same-family
variant for CPU tests).  ``registry.get(name)`` resolves either.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Literal


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int                   # per-expert FFN hidden size
    n_shared: int = 0               # always-on shared experts (deepseek-v2)
    capacity_factor: float = 1.25   # dispatch capacity multiplier
    router_aux_weight: float = 1e-2
    norm_topk_prob: bool = True     # renormalise the top-k gates to sum to 1
    # the experts this chip holds (expert parallelism): n_held experts from
    # held_from; 0 holds every expert.  The router still scores all
    # n_experts; entries routed to an expert held elsewhere add nothing here.
    held_from: int = 0
    n_held: int = 0

    @property
    def held(self) -> tuple[int, int]:
        """(first, count) of the experts held here."""
        return self.held_from, self.n_held or self.n_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2)."""

    kv_lora_rank: int = 512
    q_lora_rank: int | None = 1536  # None: queries projected uncompressed
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling as DeepSeek-V2 publishes it (``rope_scaling`` of
    type "yarn"): rope frequencies blended between interpolated and original
    over a ramp of frequency indices, and the softmax scale multiplied by
    ``mscale(factor, mscale_all_dim) ** 2``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2-style selective state space block."""

    state_dim: int = 64
    head_dim: int = 64              # per-SSM-head channel width
    expand: int = 2                 # d_inner = expand * d_model
    conv_width: int = 4
    chunk: int = 128                # chunked-scan block length


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM: alternating sLSTM / mLSTM blocks."""

    slstm_every: int = 2            # every k-th block is sLSTM, rest mLSTM
    n_heads: int = 4
    proj_factor: float = 2.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["decoder", "encdec", "xlstm", "hybrid", "moe", "vlm"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None      # default d_model // n_heads (gemma: 256)
    act: str = "silu"                # "silu"(swiglu) | "geglu" | "gelu"(plain)
    qkv_bias: bool = False           # qwen-style attention bias
    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm"
    pos_embed: str = "rope"          # "rope" | "learned" | "none"
    norm_eps: float = 1e-6
    rope_theta: float = 1e4
    rope_scaling: YarnScaling | None = None
    tie_embeddings: bool = False
    dense_d_ff: int = 0              # FFN width of the first_k_dense layers
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    xlstm: XLSTMConfig | None = None
    # hybrid (zamba2): attn block shared across periodic insertions
    attn_every: int = 0              # 0 = no interleaved shared attention
    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 1500          # whisper 30s @ 50Hz after conv stub
    # modality frontend stubs
    frontend: Literal[None, "patch_embed", "audio_frames"] = None
    frontend_seq: int = 0            # patches/frames prepended to the LM
    # learned-position table size (whisper-style models)
    learned_pos_max: int = 32768
    # long-context capability (sub-quadratic families only)
    subquadratic: bool = False
    sliding_window: int | None = None  # used by hybrid attn at long context
    first_k_dense: int = 0           # deepseek-v2: first k layers dense FFN

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows, rounded to 256 so the vocab dim shards over
        the 16-way model axis (standard production padding; the pad logits
        are masked to -1e9 in unembed_apply)."""
        return -(-self.vocab_size // 256) * 256


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


# The four assigned LM shapes (applied to every architecture)
SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False

    @property
    def shape(self) -> tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def n_devices(self) -> int:
        return math.prod(self.shape)


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    seed: int = 0
    microbatches: int = 1            # gradient accumulation splits
    param_dtype: str = "float32"     # master copy
    compute_dtype: str = "bfloat16"
    opt_state_dtype: str = "float32"
    grad_accum_dtype: str = "float32"  # microbatch accumulator (bf16 at 100B+)
    remat: str = "full"              # "none" | "full" | "dots"
    fsdp: bool = False               # shard params/opt over the data axis
    # --- the paper's technique, first-class ---
    sync_algorithm: str = "auto"     # auto|psum|ring|rd|bt|wrht|hier_faithful|
                                     # hier_scatter|planned|planned_sharded|
                                     # planned_pipelined|planned_compressed|
                                     # planned_sharded_compressed
    # planned_pipelined only: buckets in flight between their RS and AG
    # phases — bucket k+1's reduce-scatter is issued before bucket k's
    # all-gather so the two ride one composed ring schedule (DESIGN.md §13)
    pipeline_depth: int = 2
    # wire dtype for explicit gradient sync: f32 default (the XLA *CPU*
    # backend aborts on some bf16 collectives — see EXPERIMENTS §Perf-10);
    # set "bfloat16" on TPU for 2x fewer wire bytes
    sync_dtype: str = "float32"
    sync_m: int = 17                 # WRHT branching (2w+1 analogue)
    bucket_bytes: int = 32 * 2**20
    compress_pod_axis: bool = False  # int8+EF on the pod axis
    # planned_compressed / planned_sharded_compressed only: the per-bucket
    # wire-width sweep the planner runs at setup (DESIGN.md §15).  Each
    # bucket independently picks the cheapest width — small latency-bound
    # buckets decline compression (stay 32) because the quantize/dequant
    # overhead exceeds the β saving; the chosen widths are then frozen for
    # the run so an online re-plan never retraces.
    compress_bits: tuple[int, ...] = (32, 8, 4)
    compress_block: int = 1024       # per-block scale granularity (EF quant)
    compress_fused_kernel: bool = False  # fused pallas quantize+bucketize


def smoke_variant(cfg: ModelConfig, **over) -> ModelConfig:
    """Reduced same-family config: tiny dims, same structural features."""
    kw = dict(
        n_layers=min(cfg.n_layers, 2 if cfg.attn_every == 0 else 4),
        d_model=128,
        n_heads=4,
        n_kv_heads=max(1, min(4, cfg.n_kv_heads * 4 // max(cfg.n_heads, 1)) or 1),
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=512,
        head_dim=64 if cfg.head_dim else None,
        encoder_layers=min(cfg.encoder_layers, 2),
        encoder_seq=32 if cfg.encoder_layers else cfg.encoder_seq,
        frontend_seq=8 if cfg.frontend else 0,
        attn_every=min(cfg.attn_every, 2) if cfg.attn_every else 0,
        first_k_dense=min(cfg.first_k_dense, 1),
    )
    if cfg.moe:
        kw["moe"] = replace(cfg.moe, n_experts=4, top_k=2, d_expert=64,
                            n_shared=min(cfg.moe.n_shared, 1))
    if cfg.mla:
        kw["mla"] = MLAConfig(kv_lora_rank=32,
                              q_lora_rank=48 if cfg.mla.q_lora_rank else None,
                              qk_nope_head_dim=32, qk_rope_head_dim=16,
                              v_head_dim=32)
    if cfg.ssm:
        kw["ssm"] = replace(cfg.ssm, state_dim=16, head_dim=32, chunk=16)
    if cfg.xlstm:
        kw["xlstm"] = replace(cfg.xlstm, n_heads=2)
    kw.update(over)
    return replace(cfg, name=cfg.name + "-smoke", **kw)
