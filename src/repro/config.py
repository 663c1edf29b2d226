"""Configuration system for the repro framework.

Frozen dataclasses + a registry keyed by architecture id. Every assigned
architecture lives in ``repro.configs.<module>`` and registers one
``ModelConfig`` built from the exact public-literature dimensions, plus a
``reduced()`` variant used by CPU smoke tests.

Nothing in this module touches jax device state; it is safe to import from
conftest, launch scripts, and the dry-run alike.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

# Architecture families understood by the model builder.
FAMILIES = (
    "dense",      # decoder-only transformer (GQA/MQA/MHA)
    "moe",        # decoder-only with mixture-of-experts FFN
    "hybrid",     # Mamba2 backbone + shared attention blocks (zamba2)
    "ssm",        # attention-free recurrent (rwkv6)
    "encdec",     # encoder-decoder (seamless)
    "vlm",        # decoder-only with vision-embedding prefix + M-RoPE
    "resnet",     # the paper's own CNN (ResNet-32 / CIFAR-10)
)


@dataclass(frozen=True)
class ModelConfig:
    """One architecture. Fields unused by a family stay at their defaults."""

    name: str
    family: str

    # --- transformer trunk -------------------------------------------------
    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0              # explicit; not always d_model // num_heads
                                   # (the no-rotary q/k width under latent
                                   # attention)
    d_ff: int = 0                  # dense FFN width
    vocab_size: int = 0
    norm_eps: float = 1e-5
    qkv_bias: bool = False         # qwen2.5 uses attention QKV bias
    gated_mlp: bool = True         # SwiGLU when True, GeLU 4x when False
    tie_embeddings: bool = False
    rope_theta: float = 1e4
    use_mrope: bool = False        # qwen2-vl multimodal rotary (t,h,w)

    # --- local/global attention pattern (gemma3) ---------------------------
    sliding_window: int = 0        # 0 = every layer global
    global_every: int = 0          # e.g. 6 -> layers 5,11,... are global

    # --- latent attention (MLA, deepseek-v3 / moonlight) --------------------
    # kv_lora_rank > 0: keys and values come up from a normed latent of
    # this rank; queries and keys carry head_dim no-rotary dims plus
    # qk_rope_head_dim rotary ones (the rotary key shared by all heads);
    # values are v_head_dim wide
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE ---------------------------------------------------------------
    num_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0              # per-expert width; 0 = d_ff
    num_shared_experts: int = 0    # moonlight/deepseek-style always-on experts
    dense_ff: int = 0              # width of arctic's dense-residual MLP
    first_dense_layers: int = 0    # moonlight: first k layers use a dense
                                   # FFN of width d_ff
    router_aux_coef: float = 0.001
    # "softmax": the capacity paths (moe_impl), which drop tokens past a
    # capacity. "sigmoid": the dropless held-expert layer, which chooses
    # experts by sigmoid score plus a correction bias and weights them by
    # the unbiased scores normalised over the top-k (deepseek-v3's
    # noaux_tc with norm_topk_prob); the fields below apply to it
    router_scoring: str = "softmax"
    routed_scaling: float = 1.0    # factor on the routed experts' weights
    # the experts this chip holds: experts_held from first_held_expert
    # (0 = all); the layer routes over all num_experts and computes the
    # held experts' part of each token's sum
    experts_held: int = 0
    first_held_expert: int = 0

    # --- SSM / Mamba2 (zamba2) ---------------------------------------------
    ssm_state: int = 0             # N, state dimension per head
    ssm_heads: int = 0             # Mamba2 value heads
    ssm_head_dim: int = 0          # P, head channel dim
    ssm_expand: int = 2            # d_inner = expand * d_model
    ssm_chunk: int = 128           # SSD chunk length
    shared_attn_every: int = 0     # zamba2: shared attn block cadence

    # --- RWKV6 ---------------------------------------------------------------
    rwkv_head_dim: int = 64

    # --- encoder-decoder ----------------------------------------------------
    enc_layers: int = 0
    dec_layers: int = 0

    # --- modality stub ------------------------------------------------------
    # Fraction of the sequence fed as precomputed frontend embeddings
    # (vision patches / audio frames). The rest are ordinary tokens.
    modality_prefix_frac: float = 0.0

    # --- resnet -------------------------------------------------------------
    resnet_n: int = 0              # ResNet-(6n+2); n=5 -> ResNet-32
    image_size: int = 32
    num_classes: int = 10

    # --- numerics -----------------------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    # --- implementation selection (xla = pure jnp; pallas = TPU kernel) ----
    # attn_impl "auto": full attention takes the flash kernel where the
    # program compiles for a TPU and the shapes tile (models/attention.py);
    # decode keeps the XLA path unless "pallas"
    attn_impl: str = "auto"
    ssm_impl: str = "xla"
    rwkv_impl: str = "xla"
    moe_impl: str = "gspmd"        # "gspmd" (auto) | "ep" (shard_map expert
                                   # parallelism: combine on (B,S,D), not on
                                   # the E*C dispatch buffers)
    # q-chunk size for the blockwise XLA attention path (memory control)
    attn_chunk: int = 1024

    # ------------------------------------------------------------------
    @property
    def kv_groups(self) -> int:
        return max(1, self.num_heads // max(1, self.num_kv_heads))

    @property
    def attn_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def is_global_layer(self, layer_idx: int) -> bool:
        """gemma3-style 5:1 local:global pattern."""
        if self.sliding_window == 0 or self.global_every == 0:
            return True
        return (layer_idx + 1) % self.global_every == 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # Parameter count (analytic; exact for our construction).
    def param_count(self) -> int:
        return _param_count(self)

    def active_param_count(self) -> int:
        return _param_count(self, active_only=True)


def _moe_ffn_params(cfg: ModelConfig, active_only: bool) -> int:
    """Per-layer FFN params for an MoE layer (the held experts only)."""
    e = cfg.top_k if active_only else cfg.held_experts
    routed = e * 3 * cfg.d_model * cfg.expert_ff
    shared = cfg.num_shared_experts * 3 * cfg.d_model * cfg.expert_ff
    router = cfg.d_model * cfg.num_experts
    if cfg.router_scoring == "sigmoid":
        router += cfg.num_experts                 # the correction bias
    # arctic-style parallel dense branch
    dense = 3 * cfg.d_model * cfg.dense_ff if cfg.dense_ff else 0
    return routed + shared + router + dense


def _attn_params(cfg: ModelConfig) -> int:
    if cfg.kv_lora_rank:
        H, r, rope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
        v = cfg.v_head_dim or cfg.head_dim
        q = cfg.d_model * H * (cfg.head_dim + rope)
        down = cfg.d_model * (r + rope) + r             # with the latent norm
        up = r * H * (cfg.head_dim + v)
        return q + down + up + H * v * cfg.d_model
    q = cfg.d_model * cfg.num_heads * cfg.head_dim
    kv = 2 * cfg.d_model * cfg.num_kv_heads * cfg.head_dim
    o = cfg.num_heads * cfg.head_dim * cfg.d_model
    return q + kv + o


def _dense_ffn_params(cfg: ModelConfig) -> int:
    mult = 3 if cfg.gated_mlp else 2
    return mult * cfg.d_model * cfg.d_ff


def _param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    if cfg.family == "resnet":
        # ResNet-(6n+2) on CIFAR: ~1.9M for n=5; compute exactly via the
        # builder in models/resnet.py when instantiated; here use the known
        # closed form for 3x3 convs with widths 16/32/64.
        n = cfg.resnet_n
        w = [16, 32, 64]
        total = 3 * 3 * 3 * 16 + 16  # stem
        for si, width in enumerate(w):
            prev = 16 if si == 0 else w[si - 1]
            for b in range(n):
                cin = prev if b == 0 else width
                total += 3 * 3 * cin * width + width      # conv1 + bn-ish
                total += 3 * 3 * width * width + width    # conv2
                if b == 0 and cin != width:
                    total += cin * width                  # projection
        total += 64 * cfg.num_classes + cfg.num_classes
        return total

    emb = cfg.vocab_size * cfg.d_model
    out = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model

    if cfg.family == "ssm" :  # rwkv6
        # time-mix: r,k,v,g,o projections + decay/ddlerp small params
        per_layer = 5 * cfg.d_model * cfg.d_model + 2 * cfg.d_model * cfg.d_ff
        return emb + out + cfg.num_layers * per_layer

    if cfg.family == "hybrid":  # zamba2: mamba2 backbone + 1 shared attn blk
        d_in = cfg.ssm_d_inner
        conv = 4 * (d_in + 2 * cfg.ssm_heads * cfg.ssm_state)
        per_mamba = (
            cfg.d_model * (2 * d_in + 2 * cfg.ssm_heads * cfg.ssm_state + cfg.ssm_heads)
            + conv + d_in * cfg.d_model
        )
        shared = _attn_params(cfg) + _dense_ffn_params(cfg)
        return emb + out + cfg.num_layers * per_mamba + shared

    n_layers = cfg.num_layers
    if cfg.family == "encdec":
        n_layers = cfg.enc_layers + cfg.dec_layers

    total = emb + out
    for i in range(n_layers):
        total += _attn_params(cfg)
        if cfg.family == "encdec" and i >= cfg.enc_layers:
            total += _attn_params(cfg)  # cross attention
        if cfg.family == "moe" and i >= cfg.first_dense_layers:
            total += _moe_ffn_params(cfg, active_only)
        elif cfg.family == "moe":
            total += 3 * cfg.d_model * cfg.d_ff      # dense first layer(s)
        else:
            total += _dense_ffn_params(cfg)
    return total


# ---------------------------------------------------------------------------
# Input shapes (assigned per-arch shape set)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


TRAIN_4K = ShapeConfig("train_4k", "train", 4096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524288, 1)

LM_SHAPES: Tuple[ShapeConfig, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES: Dict[str, ShapeConfig] = {s.name: s for s in LM_SHAPES}

# Archs allowed to run long_500k (sub-quadratic sequence mixing).
SUBQUADRATIC_ARCHS = ("zamba2-1.2b", "rwkv6-7b")


def shape_applicable(arch: str, shape: ShapeConfig, family: str) -> Tuple[bool, str]:
    """(runs?, reason-if-skipped) for an (arch, shape) cell."""
    if shape.name == "long_500k" and arch not in SUBQUADRATIC_ARCHS:
        return False, "long_500k skipped: full-attention arch is quadratic at 512k (per spec; see DESIGN.md §4)"
    return True, ""


# ---------------------------------------------------------------------------
# Training / runtime configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "momentum"        # paper's optimizer (Table II) | "adamw"
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    # paper C6: linear-scaling LR by the number of ACTIVE workers
    adaptive_lr: bool = True
    base_workers: int = 1


@dataclass(frozen=True)
class ScheduleConfig:
    kind: str = "cosine"           # "constant" | "cosine" | "step"
    warmup_steps: int = 200
    total_steps: int = 64_000      # paper's workload: 64K steps
    min_ratio: float = 0.1
    # paper's ResNet-32 schedule is step-decay at 32k/48k
    step_boundaries: Tuple[int, ...] = (32_000, 48_000)
    step_factors: Tuple[float, ...] = (0.1, 0.01)


@dataclass(frozen=True)
class TrainConfig:
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    schedule: ScheduleConfig = field(default_factory=ScheduleConfig)
    microbatches: int = 1          # gradient accumulation factor
    remat: str = "full"            # "none" | "full"
    layout: str = "tp"             # one of sharding.LAYOUTS
    grad_dtype: str = "float32"    # "bfloat16" halves grad-reduce wire bytes
    checkpoint_every: int = 1000
    seed: int = 0

    def __post_init__(self):
        if self.remat not in ("none", "full"):
            raise ValueError(f"remat must be 'none' or 'full', not "
                             f"{self.remat!r}")


@dataclass(frozen=True)
class MeshConfig:
    """Production mesh shape. multi_pod adds the leading 'pod' axis."""
    data: int = 16
    model: int = 16
    pods: int = 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.pods > 1 else ("data", "model")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.pods, self.data, self.model) if self.pods > 1 else (self.data, self.model)

    @property
    def num_devices(self) -> int:
        n = self.data * self.model * max(1, self.pods)
        return n


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}
_REDUCED: Dict[str, Callable[[], ModelConfig]] = {}


def register(arch_id: str, full: Callable[[], ModelConfig],
             reduced: Callable[[], ModelConfig]) -> None:
    _REGISTRY[arch_id] = full
    _REDUCED[arch_id] = reduced


def get_config(arch_id: str, reduced: bool = False) -> ModelConfig:
    _ensure_loaded()
    table = _REDUCED if reduced else _REGISTRY
    if arch_id not in table:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(table)}")
    return table[arch_id]()


def list_archs() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


ASSIGNED_ARCHS = (
    "zamba2-1.2b", "qwen2.5-14b", "granite-20b", "gemma3-27b",
    "starcoder2-3b", "moonlight-16b-a3b", "arctic-480b",
    "seamless-m4t-large-v2", "rwkv6-7b", "qwen2-vl-7b",
)


def _ensure_loaded() -> None:
    # Import the configs package once so every module registers itself.
    if not _REGISTRY:
        from repro import configs as _  # noqa: F401
