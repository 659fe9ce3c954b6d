"""Config system: model / shape / mesh / run configs and the arch registry.

Every assigned architecture provides a ``ModelConfig`` via
``repro.configs.get_config(arch_id)``. Shapes are global (same four cells for
every LM arch, per assignment). Nothing in this module touches jax device
state at import time.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0            # 0 => dense FFN
    top_k: int = 2
    dense_residual: bool = False    # arctic: dense FFN in parallel with MoE
    router_dtype: str = "float32"
    # capacity = ceil(tokens * top_k * capacity_factor / num_experts).
    # >= num_experts / top_k makes dispatch dropless (smoke/eval exactness);
    # 1.25-2.0 is the usual training trade-off.
    capacity_factor: float = 2.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """State-space / recurrent block parameters (hymba, xlstm)."""
    state_size: int = 16
    conv_width: int = 4
    expand: int = 2
    # xlstm: positions of sLSTM blocks (others are mLSTM)
    slstm_every: int = 0            # 0 => no sLSTM blocks


@dataclasses.dataclass(frozen=True)
class BinaryConfig:
    """COBRA binarization knobs."""
    enabled: bool = True
    # Execution path for binary matmuls: popcount | mxu | dense | auto
    impl: str = "auto"
    # Execution path for deploy attention scores (q x k^T, Eq. 7):
    # auto | popcount | mxu | dense.  "auto" keys on the queries a slot
    # brings: up to 16 (decode, drafts, verify) scores run directly on the
    # packed uint32 words (pad-corrected ``2*popcount(XNOR) - (d_h +
    # 2*pad)``); a prefill chunk of more runs score and context as exact
    # int8 matmuls on the MXU.  "popcount"/"mxu"/"dense" pin one path as
    # selectable bitwise oracles.
    score_impl: str = "auto"
    # SPS threshold granularity: layer | head | row
    sps_granularity: str = "head"
    # attention mode: sps (COBRA) | bit_softmax (BiT teacher/baseline)
    attn_mode: str = "sps"
    # flip row-parallel projections (wo, w2) to column-parallel: the wire
    # then carries packed BITS via all-gather (32x smaller) instead of f32
    # partial sums via all-reduce — COBRA's bandwidth insight applied to
    # the collective schedule (beyond-paper §Perf optimization)
    gather_bits_collectives: bool = False
    # deploy MoE: dispatch packed activation bits to expert buffers
    # (32-128x smaller dispatch traffic; beyond-paper §Perf optimization)
    moe_dispatch_bits: bool = False
    # paged decode: fused Pallas gather-decode kernel
    # (repro.kernels.paged_attn) resolves block tables in-kernel instead
    # of materializing the gathered ring view; False keeps the gather +
    # _attend_cache escape hatch (also the kernel's bitwise reference)
    paged_kernel: bool = False
    # Keep first/last layers (embedding, lm head) full precision (standard
    # practice in BiT/BinaryBERT; embeddings binarized separately).
    binarize_embeddings: bool = False
    # Eq.11 FFN blocking factor (R). 0 = derive from ffn_mult.
    ffn_block_r: int = 0
    # Latent (trainable) weight dtype for binary layers.
    latent_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | encdec | hybrid | ssm | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0               # 0 => d_model // num_heads
    # attention
    attn_bias: bool = False         # qwen: QKV bias
    rope_theta: float = 10_000.0
    window_size: int = 0            # 0 => full attention (SWA if > 0)
    # gemma3-style local:global interleaving. 0 => uniform.
    local_global_ratio: int = 0     # e.g. 5 => 5 local : 1 global
    causal: bool = True
    # encoder-decoder
    num_encoder_layers: int = 0
    # frontends (vlm/audio): number of stub embedding tokens in input_specs
    frontend_tokens: int = 0
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    act: str = "silu"               # silu | gelu | relu
    glu: bool = True                # gated FFN (silu(xW1)*xW3)W2
    tie_embeddings: bool = False
    moe: MoEConfig = dataclasses.field(default_factory=MoEConfig)
    ssm: Optional[SSMConfig] = None
    binary: BinaryConfig = dataclasses.field(default_factory=BinaryConfig)
    # distribution
    param_dtype: str = "float32"    # latent params (AdamW master weights)
    compute_dtype: str = "bfloat16"  # activation/matmul container dtype
    optim_moment_dtype: str = "float32"
    # block-boundary activation sharding: "seq" (Megatron-SP style, saves
    # remat memory, costs per-layer gathers) | "none" (replicated-on-model)
    act_shard: str = "seq"
    # decode attention reads the KV cache grouped by kv-head instead of
    # materializing a q-heads-wide repeat (beyond-paper §Perf optimization)
    decode_grouped_gqa: bool = False
    # O(S*W) sliced-window attention chunks for static-SWA archs
    # (beyond-paper §Perf optimization; False = dense mask baseline)
    window_chunking: bool = True
    # shard latent params (and thus optimizer state) over the data axes
    fsdp: bool = True
    remat: str = "block"            # none | block | full
    # which shape cells are valid; long_500k auto-filtered by subquadratic
    subquadratic: bool = False
    skip_decode: bool = False       # encoder-only archs (none assigned)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def truncated(self, num_layers: int) -> "ModelConfig":
        """First-``num_layers``-prefix of this config — the shape of a
        layer-truncated self-speculative draft.  The per-layer plan
        (window interleaving, MoE placement) is index-deterministic, so
        a truncated config's layers are exactly the prefix of the full
        stack's and can share its (packed) weights."""
        if not 1 <= num_layers <= self.num_layers:
            raise ValueError(
                f"truncated({num_layers}) outside [1, {self.num_layers}]")
        return self.with_(num_layers=num_layers)

    def param_count(self) -> int:
        """Analytic parameter count (embeddings included once)."""
        d, ff, hd = self.d_model, self.d_ff, self.resolved_head_dim
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.glu and ff:
            ffn_dense = 3 * d * ff
        else:
            ffn_dense = 2 * d * ff
        ffn = ffn_dense
        if self.moe.num_experts:
            ffn = self.moe.num_experts * ffn_dense + d * self.moe.num_experts
            if self.moe.dense_residual:
                ffn += ffn_dense
        if self.ssm is not None and self.family == "ssm":
            # xlstm: no FFN; block has ~(2*expand + expand^2-ish) projections,
            # approximate with in/out proj of expanded dim.
            e = self.ssm.expand
            ffn = 2 * d * (e * d)
        block = attn + ffn + 2 * d
        if self.family == "hybrid":
            e = self.ssm.expand if self.ssm else 2
            block += 2 * d * (e * d)  # parallel mamba branch
        layers = self.num_layers + self.num_encoder_layers
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return layers * block + emb

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k experts only)."""
        if not self.moe.num_experts:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        dense_expert = (3 if self.glu else 2) * d * ff
        total = self.param_count()
        inactive = (self.moe.num_experts - self.moe.top_k) * dense_expert
        return total - (self.num_layers * inactive)


# ---------------------------------------------------------------------------
# Shape cells
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def valid_shapes(cfg: ModelConfig) -> Dict[str, ShapeConfig]:
    out = {}
    for name, s in SHAPES.items():
        if name == "long_500k" and not cfg.subquadratic:
            continue  # needs sub-quadratic attention; recorded as SKIP
        if s.kind == "decode" and cfg.skip_decode:
            continue
        out[name] = s
    return out


# ---------------------------------------------------------------------------
# Mesh config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD = MeshConfig((16, 16), ("data", "model"))
MULTI_POD = MeshConfig((2, 16, 16), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS: Sequence[str] = (
    "mixtral-8x22b",
    "arctic-480b",
    "qwen1.5-32b",
    "gemma3-27b",
    "smollm-135m",
    "granite-3-2b",
    "seamless-m4t-large-v2",
    "hymba-1.5b",
    "xlstm-350m",
    "internvl2-76b",
    "bert-base-cobra",  # the paper's own evaluation model
)

_MODULE_FOR: Dict[str, str] = {
    "mixtral-8x22b": "mixtral_8x22b",
    "arctic-480b": "arctic_480b",
    "qwen1.5-32b": "qwen15_32b",
    "gemma3-27b": "gemma3_27b",
    "smollm-135m": "smollm_135m",
    "granite-3-2b": "granite_3_2b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "hymba-1.5b": "hymba_15b",
    "xlstm-350m": "xlstm_350m",
    "internvl2-76b": "internvl2_76b",
    "bert-base-cobra": "bert_base_cobra",
}


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULE_FOR:
        raise KeyError(f"unknown arch {arch_id!r}; known: {list(_MODULE_FOR)}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch_id]}")
    return mod.CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch_id]}")
    return mod.smoke_config()


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
