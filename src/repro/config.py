"""Config system for the repro framework.

Every architecture is described by an :class:`ArchConfig` dataclass and
registered in ``repro.configs``.  Shapes (seq_len x global_batch cells) are
described by :class:`ShapeConfig`.  The launcher selects both via
``--arch <id> --shape <id>``.

The config system is deliberately dependency-free (no flax / ml_collections):
plain frozen dataclasses + a registry, so it is importable anywhere (including
before jax initializes devices, which the dry-run requires).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Block kinds (layer-pattern vocabulary)
# ---------------------------------------------------------------------------
ATTN = "attn"            # full softmax attention (GQA/MQA/MHA)
ATTN_LOCAL = "attn_local"  # sliding-window attention
ATTN_MLA = "attn_mla"    # DeepSeek multi-head latent attention
MAMBA = "mamba"          # selective SSM block
RWKV = "rwkv"            # RWKV6 time-mix block
DENSE_FF = "ff"          # dense (possibly gated) FFN
MOE_FF = "moe"           # routed mixture-of-experts FFN


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int               # routed experts: the router's width
    num_experts_per_tok: int
    num_shared_experts: int = 0
    expert_d_ff: int = 0           # intermediate size of each routed expert
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.01
    # group-limited greedy routing (DeepSeek-V2): only the experts of the
    # ``topk_group`` groups (of ``n_group``) with the best single expert
    # score compete for the top k; 1 group is plain top-k
    n_group: int = 1
    topk_group: int = 1
    # top-k weights: renormalised to sum 1, or else scaled by this factor
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # expert parallelism: this chip holds the routed experts
    # [first_expert, first_expert + experts_held) and computes only their
    # part of the layer (0 = all of them)
    first_expert: int = 0
    experts_held: int = 0

    @property
    def held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    decay_lora: int = 64
    gate_lora: int = 32


@dataclass(frozen=True)
class ArchConfig:
    """Architecture description. All dims are exact per the assignment."""

    name: str
    family: str                     # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int                  # query heads (0 for attn-free archs)
    num_kv_heads: int
    d_ff: int                       # dense FFN intermediate size
    vocab_size: int
    head_dim: int = 0               # 0 -> d_model // num_heads

    # layer pattern: the stack is ``prefix_pattern`` (unscanned layers) followed
    # by N periods of ``layer_pattern`` scanned with lax.scan, where
    # N = (num_layers - len(prefix_pattern)) / len(layer_pattern) must divide
    # exactly.  Homogeneous archs use a single-entry pattern and no prefix.
    layer_pattern: Tuple[Tuple[str, str], ...] = ((ATTN, DENSE_FF),)
    prefix_pattern: Tuple[Tuple[str, str], ...] = ()

    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None

    # sliding-window attention
    window_size: int = 0            # 0 -> no local attention layers

    # encoder-decoder (whisper): encoder layer count (decoder = num_layers)
    encoder_layers: int = 0
    encoder_seq_len: int = 0        # e.g. 1500 audio frames
    # vlm: number of vision-patch embeddings prepended (stub frontend)
    vision_tokens: int = 0

    # misc
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YarnConfig] = None   # latent attention's rope
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    gated_ffn: bool = True          # SwiGLU-style if True, GELU MLP otherwise
    dtype: str = "bfloat16"
    # parallelism hints
    remat: bool = True              # activation checkpointing in train_step
    fsdp: bool = True               # shard params/optimizer over the data axis (ZeRO-3)

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # ---- derived quantities -------------------------------------------------
    @property
    def attn_free(self) -> bool:
        return all(m in (MAMBA, RWKV) for m, _ in self.layer_pattern)

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def num_periods(self) -> int:
        n = self.num_layers - len(self.prefix_pattern)
        if n % len(self.layer_pattern) != 0:
            raise ValueError(
                f"{self.name}: {n} scanned layers not divisible by period "
                f"{len(self.layer_pattern)}")
        return n // len(self.layer_pattern)

    def layer_kinds(self) -> List[Tuple[str, str]]:
        """Expanded per-layer (mixer, ffn) kinds, length == num_layers."""
        out: List[Tuple[str, str]] = list(self.prefix_pattern)
        out.extend(list(self.layer_pattern) * self.num_periods)
        assert len(out) == self.num_layers
        return out

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        kinds = self.layer_kinds()
        d = self.d_model
        total = self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d  # lm head
        for mixer, ffn in kinds:
            total += self._mixer_params(mixer) + self._ffn_params(ffn)
            total += 2 * d  # two norms
        if self.is_encdec:
            # encoder blocks: self-attn + ffn + norms, plus cross-attn in dec
            enc = self.encoder_layers * (
                self._mixer_params(ATTN) + self._ffn_params(DENSE_FF) + 2 * d)
            cross = self.num_layers * (self._mixer_params(ATTN) + d)
            total += enc + cross
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed top-k experts)."""
        kinds = self.layer_kinds()
        d = self.d_model
        total = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        for mixer, ffn in kinds:
            total += self._mixer_params(mixer) + 2 * d
            if ffn == MOE_FF:
                assert self.moe is not None
                e_p = self._expert_params()
                total += (self.moe.num_experts_per_tok
                          + self.moe.num_shared_experts) * e_p
                total += d * self.moe.num_experts  # router
            else:
                total += self._ffn_params(ffn)
        return total

    def _expert_params(self) -> int:
        assert self.moe is not None
        dff = self.moe.expert_d_ff or self.d_ff
        mult = 3 if self.gated_ffn else 2
        return mult * self.d_model * dff

    def _ffn_params(self, kind: str) -> int:
        d = self.d_model
        if kind == MOE_FF:
            assert self.moe is not None
            total = self.moe.held * self._expert_params()
            total += self.moe.num_shared_experts * self._expert_params()
            total += d * self.moe.num_experts  # router
            return total
        mult = 3 if self.gated_ffn else 2
        return mult * d * self.d_ff

    def _mixer_params(self, kind: str) -> int:
        d = self.d_model
        if kind in (ATTN, ATTN_LOCAL):
            q = d * self.num_heads * self.head_dim
            kv = 2 * d * self.num_kv_heads * self.head_dim
            o = self.num_heads * self.head_dim * d
            return q + kv + o
        if kind == ATTN_MLA:
            assert self.mla is not None
            m = self.mla
            qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
            p = d * m.q_lora_rank                      # q down
            p += m.q_lora_rank * self.num_heads * qk_dim  # q up
            p += d * (m.kv_lora_rank + m.qk_rope_head_dim)  # kv down + shared k_rope
            p += m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
            p += self.num_heads * m.v_head_dim * d     # out
            return p
        if kind == MAMBA:
            assert self.mamba is not None
            mc = self.mamba
            d_in = mc.expand * d
            dt_rank = mc.dt_rank or -(-d // 16)
            p = d * 2 * d_in                 # in_proj (x and z)
            p += d_in * mc.d_conv            # conv1d
            p += d_in * (dt_rank + 2 * mc.d_state)  # x_proj
            p += dt_rank * d_in              # dt_proj
            p += d_in * mc.d_state           # A_log
            p += d_in                        # D
            p += d_in * d                    # out_proj
            return p
        if kind == RWKV:
            assert self.rwkv is not None
            # r,k,v,g,o projections + decay/mix loras + ln_x
            p = 5 * d * d
            p += d * (self.rwkv.decay_lora + self.rwkv.gate_lora) * 2
            p += 6 * d  # token-shift mix params
            return p
        raise ValueError(f"unknown mixer kind {kind}")


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# Archs allowed to run long_500k (sub-quadratic / O(1)-state paths).
LONG_CONTEXT_ARCHS = ("rwkv6-3b", "jamba-1.5-large-398b", "gemma3-1b")


def cells_for(arch: "ArchConfig") -> List[str]:
    """The runnable shape cells for an architecture (skips noted in DESIGN.md)."""
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    if arch.name in LONG_CONTEXT_ARCHS:
        cells.append("long_500k")
    return cells


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate arch {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded() -> None:
    # configs register themselves on import
    import repro.configs  # noqa: F401


def smoke_variant(cfg: ArchConfig) -> ArchConfig:
    """A reduced config of the same family for CPU smoke tests."""
    changes: Dict[str, Any] = dict(
        name=cfg.name + "-smoke",
        num_layers=len(cfg.prefix_pattern) + max(2, len(cfg.layer_pattern)) if
        len(cfg.layer_pattern) > 1 or cfg.prefix_pattern else 2,
        d_model=64,
        d_ff=128,
        vocab_size=257,
        head_dim=16 if cfg.num_heads else 0,
        num_heads=4 if cfg.num_heads else 0,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads else 0,
        window_size=min(cfg.window_size, 8) if cfg.window_size else 0,
        encoder_layers=2 if cfg.encoder_layers else 0,
        encoder_seq_len=16 if cfg.encoder_seq_len else 0,
        vision_tokens=4 if cfg.vision_tokens else 0,
        remat=False,
        fsdp=False,
    )
    if cfg.moe is not None:
        changes["moe"] = MoEConfig(
            num_experts=4, num_experts_per_tok=2,
            num_shared_experts=min(cfg.moe.num_shared_experts, 1),
            expert_d_ff=32)
    if cfg.mla is not None:
        changes["mla"] = MLAConfig(kv_lora_rank=16, q_lora_rank=24,
                                   qk_nope_head_dim=16, qk_rope_head_dim=8,
                                   v_head_dim=16)
    if cfg.mamba is not None:
        changes["mamba"] = MambaConfig(d_state=4, d_conv=2, expand=2, dt_rank=4)
    if cfg.rwkv is not None:
        changes["rwkv"] = RWKVConfig(head_size=16, decay_lora=8, gate_lora=8)
    new = dataclasses.replace(cfg, **changes)
    # not registered: smoke variants are anonymous
    return new
