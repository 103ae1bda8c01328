"""Model / shape / parallelism configuration for the CLEX-JAX framework.

Every assigned architecture is expressed as a ``ModelConfig``; the registry
maps ``--arch <id>`` to its config module.  Shapes (``--shape <id>``) are the
four assigned input-shape cells.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Optional

__all__ = [
    "MoEConfig",
    "RopeScaling",
    "SSMConfig",
    "MLAConfig",
    "FrontendConfig",
    "ModelConfig",
    "ShapeConfig",
    "ParallelConfig",
    "SHAPES",
    "ARCH_IDS",
    "get_config",
    "registry",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert_ff: int
    layer_period: int = 1  # MoE on layers where i % period == offset
    layer_offset: int = 0
    capacity_factor: float = 1.25
    router_jitter: bool = False
    # CLEX technique knobs (Sec. 3 of docs/ARCHITECTURE.md)
    hierarchical_a2a: bool = True  # two-stage all-to-all dispatch
    valiant_shuffle: bool = False  # randomized token indirection
    # DeepSeek-style layers: always-on shared experts (one SwiGLU of width
    # n_shared_experts * d_expert_ff), top-k weights left unnormalised and
    # scaled by routed_scaling_factor
    n_shared_experts: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # expert parallelism's share: the layer holds experts
    # [held_first, held_first + n_held) of the n_experts it routes over and
    # computes, with no capacity and nothing dropped, the part of the
    # result they give (moe.moe_held).  n_held 0: the capacity paths
    held_first: int = 0
    n_held: int = 0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128
    conv_width: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 768  # 0: queries straight from the hidden state (w_q)
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2's modelling code
    applies it: frequencies blended between interpolated and original over a
    ramp of dimensions, and the softmax scale multiplied by
    ``mscale(factor, mscale_all_dim) ** 2``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    kind: str  # "vision" | "audio"
    d_frontend: int  # embedding dim produced by the (stubbed) modality encoder
    n_tokens: int  # patches / frames prepended to the text sequence


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0  # 0 -> d_model // n_heads
    attn_type: str = "full"  # full | swa | mla
    sliding_window: int = 0  # for swa
    qk_norm: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    # hybrid interleave: layer i is attention iff i % attn_period == attn_offset
    # (attn_period == 1 -> all layers attention; 0 -> attention-free / SSM only)
    attn_period: int = 1
    attn_offset: int = 0
    enc_dec: bool = False
    n_encoder_layers: int = 0
    frontend: Optional[FrontendConfig] = None
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None
    use_rope: bool = True  # Jamba relies on Mamba for position information
    # leading layers with a dense MLP before the repeated pattern
    # (DeepSeek's first_k_dense_replace); they run unstacked, once
    n_dense_layers: int = 0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    sequence_parallel: bool = True  # shard saved residuals over `model` (SP)
    max_seq_len: int = 524288

    @property
    def head_dim(self) -> int:
        if self.d_head:
            return self.d_head
        return self.d_model // self.n_heads if self.n_heads else 0

    def layer_is_attention(self, i: int) -> bool:
        if self.attn_period == 0:
            return False
        return i % self.attn_period == self.attn_offset

    def layer_is_moe(self, i: int) -> bool:
        if self.moe is None or i < self.n_dense_layers:
            return False
        return i % self.moe.layer_period == self.moe.layer_offset

    def ffn_width(self, i: int) -> int:
        """Width of layer ``i``'s MLP: one expert's in an MoE layer."""
        return self.moe.d_expert_ff if self.layer_is_moe(i) else self.d_ff

    def pattern_period(self) -> int:
        """Smallest period of the (mixer, ffn) layer pattern after the
        leading dense layers — scan unit."""
        first, n = self.n_dense_layers, self.n_layers - self.n_dense_layers
        period = 1
        for p in range(1, n + 1):
            if n % p:
                continue
            ok = all(
                self.layer_is_attention(i) == self.layer_is_attention(first + (i - first) % p)
                and self.layer_is_moe(i) == self.layer_is_moe(first + (i - first) % p)
                for i in range(first, self.n_layers)
            )
            if ok:
                period = p
                break
        return period

    def supports_long_context(self) -> bool:
        """Sub-quadratic path exists: SSM / hybrid / sliding-window."""
        return self.attn_period != 1 or self.attn_type == "swa" or self.family in ("ssm", "hybrid")

    def active_params(self) -> int:
        """Approximate active (per-token) parameter count."""
        return self._param_count(active_only=True)

    def total_params(self) -> int:
        return self._param_count(active_only=False)

    def _param_count(self, active_only: bool) -> int:
        d, h = self.d_model, self.head_dim
        total = self.vocab * d * (1 if self.tie_embeddings else 2)
        n_blocks = self.n_layers + (self.n_encoder_layers if self.enc_dec else 0)
        for i in range(n_blocks):
            li = i % max(self.n_layers, 1)
            if self.layer_is_attention(li):
                if self.attn_type == "mla" and self.mla is not None:
                    m = self.mla
                    qk = self.n_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                    total += (d * m.q_lora_rank + m.q_lora_rank * qk) if m.q_lora_rank else d * qk
                    total += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                    total += m.kv_lora_rank * self.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
                    total += self.n_heads * m.v_head_dim * d
                else:
                    total += d * self.n_heads * h + 2 * d * self.n_kv_heads * h + self.n_heads * h * d
            elif self.ssm is not None:
                c = self.ssm
                d_inner = c.expand * d
                total += d * (2 * d_inner + 2 * c.state_dim) + d_inner * d
            if self.layer_is_moe(li):
                moe = self.moe
                experts = moe.top_k if active_only else moe.n_experts
                total += d * moe.n_experts  # router
                total += (experts + moe.n_shared_experts) * 3 * d * moe.d_expert_ff
            elif self.d_ff:
                total += 3 * d * self.d_ff
        return total


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """How model/optimizer state and activations map onto the mesh."""

    dp_axes: tuple[str, ...] = ("pod", "data")  # batch sharding
    tp_axis: str = "model"  # heads / ff / experts / vocab
    shard_kv_seq: bool = False  # split-KV decode for long contexts
    hierarchical_grad_sync: bool = True  # CLEX-style staged all-reduce
    compress_cross_pod: bool = False  # int8 error-feedback on the pod axis
    remat_policy: str = "block"  # none | block | dots


ARCH_IDS = [
    "jamba-v0.1-52b",
    "granite-moe-1b-a400m",
    "olmoe-1b-7b",
    "minicpm3-4b",
    "internlm2-1.8b",
    "h2o-danube-1.8b",
    "qwen3-32b",
    "seamless-m4t-large-v2",
    "mamba2-1.3b",
    "phi-3-vision-4.2b",
    "deepseek-v2-lite",
]

_MODULES = {
    "jamba-v0.1-52b": "jamba_v01_52b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "minicpm3-4b": "minicpm3_4b",
    "internlm2-1.8b": "internlm2_1_8b",
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "qwen3-32b": "qwen3_32b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "mamba2-1.3b": "mamba2_1_3b",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def registry() -> dict[str, Callable[[], ModelConfig]]:
    out = {}
    for arch, mod in _MODULES.items():
        out[arch] = lambda mod=mod: importlib.import_module(f"repro.configs.{mod}").CONFIG
    return out


def get_config(arch: str, reduced: bool = False) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.REDUCED if reduced else mod.CONFIG
