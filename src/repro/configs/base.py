"""Configuration system for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``; run-time
behaviour (mesh, batching, checkpointing, scheduler) lives in ``RunConfig``.
Configs are plain frozen dataclasses: hashable (usable as jit static args),
serializable to/from dict, and overridable via ``replace``.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    n_experts: int = 0
    top_k: int = 2
    d_expert: int = 0           # per-expert hidden dim (d_ff of one expert)
    dense_residual: bool = False  # arctic-style parallel dense FFN
    d_dense_residual: int = 0     # hidden dim of the dense residual branch
    every: int = 1               # MoE on layers where (layer % every == offset)
    offset: int = 0
    router_jitter: float = 0.0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01

    @property
    def enabled(self) -> bool:
        return self.n_experts > 0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-1 selective-scan block configuration."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model/16)
    # RMSNorm on dt (width dt_rank), B and C (width d_state) between the
    # x-projection and dt_proj, as every Jamba's mixer has them
    inner_norms: bool = False


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block configuration (sLSTM + mLSTM interleave)."""

    slstm_every: int = 2      # sLSTM on layers where layer % every == offset
    slstm_offset: int = 0
    proj_factor_mlstm: float = 2.0
    proj_factor_slstm: float = 1.3333


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"  # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0          # 0 -> d_model // n_heads
    d_ff: int = 1024           # dense FFN hidden (0 for pure-SSM archs)
    vocab_size: int = 1024
    act: str = "swiglu"        # swiglu | geglu | gelu
    norm: str = "rmsnorm"
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0  # chatglm-style partial/2d rope: 0.5
    tie_embeddings: bool = False
    max_seq_len: int = 8192
    attn_logit_softcap: float = 0.0
    sliding_window: int = 0     # 0 = full attention
    # hybrid (jamba): attention on layers where layer % attn_every == attn_offset,
    # SSM elsewhere. attn_every=1 means all-attention.
    attn_every: int = 1
    attn_offset: int = 0
    moe: MoEConfig = field(default_factory=MoEConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    xlstm: XLSTMConfig = field(default_factory=XLSTMConfig)
    # modality frontend stub: when set, the model consumes precomputed
    # embeddings of this dim for the first `frontend_tokens` positions.
    frontend: str = "none"      # none | vision | audio
    frontend_dim: int = 0
    dtype: str = "bfloat16"
    # layer-stack scan period: layers are grouped into n_layers//scan_period
    # scan steps whose body unrolls `scan_period` (possibly heterogeneous)
    # layers. 0 -> auto from family (LCM of interleave periods).
    scan_period: int = 0
    remat: str = "block"        # none | block | full

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 (Megatron-style) so the
        vocab dimension shards over any reasonable TP degree."""
        pad_to = 256
        return -(-self.vocab_size // pad_to) * pad_to

    @property
    def resolved_scan_period(self) -> int:
        if self.scan_period:
            return self.scan_period
        period = 1
        if self.family in ("hybrid",):
            period = _lcm(period, self.attn_every)
        if self.moe.enabled and self.moe.every > 1:
            period = _lcm(period, self.moe.every)
        if self.family == "ssm":
            period = _lcm(period, self.xlstm.slstm_every)
        return period

    @property
    def n_groups(self) -> int:
        p = self.resolved_scan_period
        assert self.n_layers % p == 0, (self.n_layers, p)
        return self.n_layers // p

    def layer_kind(self, layer_idx: int) -> str:
        """Kind of layer at absolute index: attn | ssm | slstm | mlstm."""
        if self.family == "ssm":
            x = self.xlstm
            return "slstm" if layer_idx % x.slstm_every == x.slstm_offset else "mlstm"
        if self.family == "hybrid":
            if layer_idx % self.attn_every == self.attn_offset:
                return "attn"
            return "ssm"
        return "attn"

    def layer_is_moe(self, layer_idx: int) -> bool:
        m = self.moe
        return m.enabled and (layer_idx % m.every == m.offset)

    def param_count(self) -> Dict[str, float]:
        """Analytic parameter counts (total and active-per-token)."""
        d, hd = self.d_model, self.resolved_head_dim
        nq, nkv = self.n_heads, self.n_kv_heads
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        total = emb
        active = emb
        for li in range(self.n_layers):
            kind = self.layer_kind(li)
            if kind == "attn":
                blk = d * hd * (nq + 2 * nkv) + nq * hd * d  # qkv + out
            elif kind == "ssm":
                s = self.ssm
                d_in = s.expand * d
                dtr = s.dt_rank or -(-d // 16)
                blk = (d * 2 * d_in + d_in * s.d_conv + d_in * (dtr + 2 * s.d_state)
                       + dtr * d_in + d_in * s.d_state + d_in + d_in * d)
                if s.inner_norms:
                    blk += dtr + 2 * s.d_state
            elif kind == "mlstm":
                d_in = int(self.xlstm.proj_factor_mlstm * d)
                blk = 2 * d * d_in + 3 * d_in * d_in // max(self.n_heads, 1) + d_in * d
                blk = 2 * d * d_in + d_in * d  # up/gate + down
                blk += 4 * d_in * (d_in // max(self.n_heads, 1))  # qkv+i/f gates approx
            else:  # slstm
                d_in = int(self.xlstm.proj_factor_slstm * d)
                blk = 4 * d * d + 2 * d * d_in  # recurrent gates + ffn
            total += blk
            active += blk
            # FFN / MoE
            if kind in ("attn", "ssm") and self.d_ff:
                nmat = 3 if self.act in ("swiglu", "geglu") else 2
                if self.layer_is_moe(li):
                    m = self.moe
                    per = nmat * d * m.d_expert
                    total += m.n_experts * per
                    active += m.top_k * per
                    if m.dense_residual:
                        dd = nmat * d * (m.d_dense_residual or self.d_ff)
                        total += dd
                        active += dd
                else:
                    total += nmat * d * self.d_ff
                    active += nmat * d * self.d_ff
        return {"total": float(total), "active": float(active)}

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _lcm(a: int, b: int) -> int:
    import math

    return a * b // math.gcd(a, b)


# ---------------------------------------------------------------------------
# Run/shape configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    """One assigned (workload) input-shape cell."""

    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


# The four assigned LM shapes.
ASSIGNED_SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", "train", 4096, 256),
    ShapeConfig("prefill_32k", "prefill", 32768, 32),
    ShapeConfig("decode_32k", "decode", 32768, 128),
    ShapeConfig("long_500k", "decode", 524288, 1),
)

SHAPES_BY_NAME = {s.name: s for s in ASSIGNED_SHAPES}


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig = field(default_factory=ModelConfig)
    seq_len: int = 512
    global_batch: int = 8
    microbatch: int = 0          # 0 = no accumulation
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    seed: int = 0
    checkpoint_dir: str = ""
    checkpoint_every: int = 200
    grad_compression: str = "none"  # none | int8 | topk
    use_pallas: bool = False


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = (
    "jamba_v01_52b",
    "jamba2_3b",
    "arctic_480b",
    "granite_moe_1b_a400m",
    "phi4_mini_3_8b",
    "codeqwen15_7b",
    "gemma_2b",
    "chatglm3_6b",
    "xlstm_1_3b",
    "internvl2_2b",
    "musicgen_large",
)

_ALIASES = {
    "jamba-v0.1-52b": "jamba_v01_52b",
    "jamba2-3b": "jamba2_3b",
    "arctic-480b": "arctic_480b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "codeqwen1.5-7b": "codeqwen15_7b",
    "gemma-2b": "gemma_2b",
    "chatglm3-6b": "chatglm3_6b",
    "xlstm-1.3b": "xlstm_1_3b",
    "internvl2-2b": "internvl2_2b",
    "musicgen-large": "musicgen_large",
}


def get_config(arch: str) -> ModelConfig:
    """Load the full-size config for an architecture id (dashes ok)."""
    arch = _ALIASES.get(arch, arch).replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    """Load the reduced same-family smoke config for an architecture id."""
    arch = _ALIASES.get(arch, arch).replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.SMOKE_CONFIG


def supports_shape(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    """long_500k requires sub-quadratic attention (SSM/hybrid families)."""
    if shape.name == "long_500k":
        return cfg.family in ("ssm", "hybrid")
    return True
