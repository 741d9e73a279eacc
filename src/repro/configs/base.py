"""Architecture + run configuration dataclasses.

Every assigned architecture gets a module in ``repro/configs/<id>.py`` exposing
``CONFIG`` (the exact published shape, cited) and ``SMOKE`` (a reduced variant
of the same family: ≤2 layers, d_model ≤ 512, ≤4 experts) used by the CPU
smoke tests.  The full configs are exercised only via the dry-run.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp

BlockKind = Literal["attn", "local", "moe", "local_moe", "mamba", "shared_attn"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                        # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    source: str = ""                   # citation [arXiv:....]

    head_dim: int = 0                  # 0 → d_model // n_heads
    layer_pattern: tuple[BlockKind, ...] = ()   # len == n_layers; () → all "attn"

    # multi-head latent attention (MLA: DeepSeek-V3, Kimi K2), on when
    # kv_lora_rank > 0: q = RMSNorm(x W_qa) W_qb, [c_kv | k_rope] = x W_kva,
    # [k_nope | v] = RMSNorm(c_kv) W_kvb; k_rope is shared by the heads
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # attention features
    sliding_window: int = 0            # window for "local" blocks
    attn_softcap: float = 0.0          # gemma2 logit soft-capping
    final_softcap: float = 0.0         # gemma2 final-logit soft-capping
    qkv_bias: bool = False             # qwen2
    causal: bool = True                # BERT-family encoders set False
    rope_theta: float = 10_000.0
    pos_emb: str = "rope"              # rope | learned | sinusoidal | none
    max_position: int = 1 << 20
    # YaRN RoPE scaling (factor 0 → plain RoPE); the DeepSeek-V3 form
    yarn_factor: float = 0.0
    yarn_original_max: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.5
    router_aux_coef: float = 0.01
    # "softmax": capacity-bounded dispatch with a load-balance loss;
    # "sigmoid": DeepSeek-V3's dropless routing — top_k of sigmoid scores
    # plus a frozen correction bias, weights normalised and scaled by
    # routed_scale, no auxiliary loss (models/moe.py)
    router_score: str = "softmax"
    routed_scale: float = 1.0
    n_shared_experts: int = 0          # always-on experts of width d_ff each
    first_k_dense: int = 0             # leading dense layers of a moe family
    d_ff_dense: int = 0                # their FFN width (0 → d_ff)
    # the chip's share under expert parallelism: this chip holds experts
    # [expert_shard · experts_held, (expert_shard + 1) · experts_held) of
    # the n_experts the router scores (0 → all of them)
    experts_held: int = 0
    expert_shard: int = 0

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128

    # encoder-decoder
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0

    # modality stubs (assignment carve-out): precomputed frontend embeddings
    modality: str = "text"             # text | vision | audio
    n_prefix_embeds: int = 0           # vision patches prepended to the sequence

    # norms / activations / embeddings
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    rms_offset: bool = False           # gemma-style (1 + w) scale
    post_block_norm: bool = False      # gemma2 post-norms
    act: str = "silu"                  # silu (SwiGLU) | gelu (plain FFN)
    glu: bool = True                   # gated FFN (w1⊙act, w3) vs single w1
    tie_embeddings: bool = True
    embed_scale: bool = False          # gemma multiplies embeddings by sqrt(d)

    # classification head (paper's BERT-family repro)
    n_classes: int = 0

    # dtypes
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # federated steps recompute each block in the backward pass (saving
    # only block inputs): for widths whose activations would not fit
    train_remat: bool = False

    # PEFT policy (the paper's technique)
    adapter_targets: tuple[str, ...] = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")
    adapter_rank: int = 8
    adapter_alpha: float = 16.0        # paper fixes α = 16

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.layer_pattern:
            kind: BlockKind = "attn"
            if self.family == "moe":
                kind = "moe"
            elif self.family == "ssm":
                kind = "mamba"
            k = self.first_k_dense if kind == "moe" else 0
            object.__setattr__(self, "layer_pattern",
                               ("attn",) * k + (kind,) * (self.n_layers - k))
        if len(self.layer_pattern) != self.n_layers:
            raise ValueError(
                f"{self.name}: layer_pattern has {len(self.layer_pattern)} "
                f"entries for n_layers={self.n_layers}")
        if self.experts_held and (
                self.n_experts % self.experts_held
                or not 0 <= self.expert_shard
                < self.n_experts // self.experts_held):
            raise ValueError(
                f"{self.name}: {self.experts_held} held experts (share "
                f"{self.expert_shard}) do not divide {self.n_experts} "
                f"experts into whole shares")

    # ---- derived -----------------------------------------------------------
    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def held(self) -> tuple[int, int]:
        """(first expert id, count) of the experts this chip holds."""
        n = self.experts_held or self.n_experts
        return self.expert_shard * n, n

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def d_inner(self) -> int:          # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def subquadratic(self) -> bool:
        """Eligible for long_500k decode (see DESIGN.md table)."""
        kinds = set(self.layer_pattern)
        full_attn = {"attn", "moe"} & kinds
        return not full_attn or self.sliding_window > 0

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                          # train | prefill | decode


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}
