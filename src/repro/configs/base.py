"""Config system for the Ling reproduction framework.

Every architecture (the paper's own Ling models plus the 10 assigned
public-literature architectures) is described by a single `ModelConfig`
dataclass.  Input shapes are described by `ShapeConfig`.  The registry at the
bottom is what ``--arch <id>`` resolves against.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Fine-grained MoE settings (paper §3.2.1–3.2.2)."""

    n_experts: int                 # routed experts (fine-grained)
    top_k: int                     # experts activated per token
    expert_d_ff: int               # intermediate size of each routed expert
    n_shared_experts: int = 0      # always-on shared experts (Eq. 2)
    shared_d_ff: Optional[int] = None  # defaults to expert_d_ff * n_shared
    capacity_factor: float = 2.0   # EP-path buffer headroom (dropless path ignores)
    # Preferred dispatch mode for this arch: "auto" | "fused" | "ragged" |
    # "batched" | "ep".  "auto" defers to the runtime heuristic in
    # core/moe.py::moe_ffn (interpret builds: fused at tp=1, ep at tp>1;
    # real TPUs: ragged/batched until the ROADMAP tile sweep).  A RunFlags
    # override (models/model.py) takes precedence over this knob.
    dispatch: str = "auto"
    balance_loss_coef: float = 0.015   # paper §3.4.1
    z_loss_coef: float = 1e-4          # paper §3.4.1
    router_warmup_steps: int = 100     # stochastic routing warmup W (Eq. 3)
    first_dense_layers: int = 0    # leading layers that use a dense FFN

    @property
    def shared_ff(self) -> int:
        if self.shared_d_ff is not None:
            return self.shared_d_ff
        return self.expert_d_ff * max(self.n_shared_experts, 1)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1):
    keys and values are expanded per head from one shared latent
    `c_kv` of width `kv_lora_rank`, and a single rotary key of width
    `qk_rope_head_dim` is shared by every head.  Queries are projected
    straight from the hidden state (no query latent)."""

    kv_lora_rank: int              # latent width (c_kv)
    qk_nope_head_dim: int          # per-head query/key width without rope
    qk_rope_head_dim: int          # rotary width (one shared key)
    v_head_dim: int                # per-head value width

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Width of one cached row: c_kv then the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling (arXiv:2309.00071), as DeepSeek-V2 publishes
    it: frequencies blended between interpolated (÷ factor) and
    original by a linear ramp over the rotary pairs, and the softmax
    scaled by mscale(mscale_all_dim)^2."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A composable decoder (or encoder-decoder) transformer description."""

    arch_id: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    source: str                    # citation for the config numbers

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: Optional[int] = None     # defaults to d_model // n_heads
    # Per-layer block kinds, cycled over layers.  Kinds:
    #   "attn"   full causal self attention
    #   "swa"    sliding-window attention (window = attn_window)
    #   "rglru"  RG-LRU recurrent block (RecurrentGemma)
    #   "rwkv"   RWKV6 time-mix block (attention free)
    #   "mla"    causal multi-head latent attention (needs `mla`)
    block_pattern: Tuple[str, ...] = ("attn",)
    attn_window: Optional[int] = None  # sliding/local attention window
    mlp_act: str = "swiglu"            # swiglu | squared_relu | gelu
    norm_type: str = "rmsnorm"         # rmsnorm | layernorm
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YaRN] = None
    use_rope: bool = True
    mla: Optional[MLAConfig] = None
    tie_embeddings: bool = False

    moe: Optional[MoEConfig] = None
    norm_head: bool = True             # paper §3.2.3 NormHead (C4)

    # Encoder-decoder (whisper-style).  The modality frontend is the one
    # allowed stub: input_specs() provides precomputed frame embeddings.
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_seq_len: int = 0           # e.g. 1500 audio frames

    # VLM early fusion: image tokens are ordinary vocabulary entries
    # (Chameleon); the VQ image tokenizer is the stubbed frontend.
    early_fusion_vlm: bool = False

    # rwkv6 specifics
    rwkv_head_dim: int = 64

    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if ("mla" in self.block_pattern) != (self.mla is not None):
            raise ValueError(f"{self.arch_id}: an 'mla' block needs `mla` "
                             f"dims, and `mla` needs an 'mla' block")

    # -- derived ------------------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.moe is not None

    def block_kind(self, layer: int) -> str:
        return self.block_pattern[layer % len(self.block_pattern)]

    @property
    def uniform_blocks(self) -> bool:
        return len(set(self.block_pattern)) == 1

    @property
    def sub_quadratic(self) -> bool:
        """True if no block requires O(S^2) full attention (long_500k gate)."""
        return all(k not in ("attn", "mla") for k in self.block_pattern)

    def param_count(self) -> int:
        """Analytic total parameter count (embeddings included once)."""
        d, v = self.d_model, self.vocab_size
        hd = self.head_dim
        n = v * d  # embedding
        if not self.tie_embeddings:
            n += v * d  # lm head
        enc = 0
        if self.is_encoder_decoder:
            enc = self.encoder_layers * (
                (self.n_heads + 2 * self.n_kv_heads) * hd * d + self.n_heads * hd * d
                + self._mlp_params(self.d_ff) + 2 * d)
            # decoder cross attention
            n += self.n_layers * ((self.n_heads + 2 * self.n_kv_heads) * hd * d
                                  + self.n_heads * hd * d + d)
        for layer in range(self.n_layers):
            kind = self.block_kind(layer)
            if kind in ("attn", "swa"):
                n += (self.n_heads + 2 * self.n_kv_heads) * hd * d
                n += self.n_heads * hd * d
            elif kind == "mla":
                a = self.mla
                n += d * self.n_heads * a.qk_head_dim + d * a.latent_width
                n += a.kv_lora_rank * (1 + self.n_heads * (
                    a.qk_nope_head_dim + a.v_head_dim))
                n += self.n_heads * a.v_head_dim * d
            elif kind == "rglru":
                dr = _rglru_dim(d)
                n += 2 * d * dr + dr * d + 3 * dr + 2 * dr * (dr // _RGLRU_BLOCKS)
            elif kind == "rwkv":
                nh = d // self.rwkv_head_dim
                n += 4 * d * d + d * nh * self.rwkv_head_dim  # r,k,v,o,g approx
                n += 2 * (d * 32 + 32 * d)  # lora-style decay/mix
            n += self._ffn_params(layer)
            n += 2 * d  # norms
        return n + enc

    def _mlp_params(self, ff: int) -> int:
        mult = 3 if self.mlp_act == "swiglu" else 2
        return mult * self.d_model * ff

    def _ffn_params(self, layer: int) -> int:
        if self.moe is None or layer < self.moe.first_dense_layers:
            return self._mlp_params(self.d_ff)
        m = self.moe
        n = m.n_experts * self._mlp_params(m.expert_d_ff)
        if m.n_shared_experts:
            n += self._mlp_params(m.shared_ff)
        n += self.d_model * m.n_experts  # router
        return n

    def active_param_count(self) -> int:
        """Parameters activated per token (MoE: top_k + shared only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        full_moe_layers = self.n_layers - m.first_dense_layers
        inactive = full_moe_layers * (m.n_experts - m.top_k) * self._mlp_params(m.expert_d_ff)
        return self.param_count() - inactive


_RGLRU_BLOCKS = 1


def _rglru_dim(d_model: int) -> int:
    """RecurrentGemma uses an RNN width slightly larger than d_model."""
    return d_model


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "phi3-mini-3.8b",
    "rwkv6-3b",
    "chameleon-34b",
    "h2o-danube-1.8b",
    "deepseek-moe-16b",
    "granite-moe-3b-a800m",
    "moonshot-v1-16b-a3b",
    "whisper-tiny",
    "recurrentgemma-2b",
    "nemotron-4-15b",
    "ling-lite",
    "ling-plus",
    "deepseek-v2-lite",
]

_MODULE_FOR = {a: a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULE_FOR:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULE_FOR)}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch_id]}")
    return mod.CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    """Reduced same-family variant: <=2 layers, d_model<=512, <=4 experts."""
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch_id]}")
    return mod.smoke_config()


def supported_shapes(cfg: ModelConfig) -> Sequence[str]:
    """long_500k only for sub-quadratic (SSM / hybrid / SWA) architectures."""
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.sub_quadratic or all(k in ("swa", "rglru", "rwkv") for k in cfg.block_pattern):
        out.append("long_500k")
    return out
