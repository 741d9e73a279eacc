"""Kimi K2 Instruct [https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json]
(``model_type`` kimi_k2, DeepSeek-V3's layer equations): 61 layers of width
7,168; multi-head latent attention with 64 heads (q_lora 1,536, kv_lora 512,
q/k heads 128 + 64 RoPE dims, v heads 128) and YaRN RoPE (θ 50,000, factor
32 over 4,096 positions); layer 0 is dense (FFN 18,432), the other 60 route
each token to 8 of 384 sigmoid-scored experts of width 2,048 (correction
bias for the choice only, weights normalised and scaled by 2.827) beside one
shared expert; vocabulary 163,840, untied; RMSNorm eps 1e-6.  About 1.03 T
parameters, 32.9 B active a token (``launch/analysis.active_params``).

``CHIP`` is one chip's share of a stated deployment, every width as
published: the first pipeline stage's dense layer and 4 MoE layers, 8 of the
384 experts of each MoE layer (48-way expert parallelism: experts 0–7), and
an eighth of the vocabulary (8-way vocabulary parallelism).  MLA, the dense
FFN, the router (all 384 outputs, top 8) and the shared expert are what
every chip holds.  It fine-tunes a 20-class sequence classifier (mean
pooling): the classifier holds no LM head.  BEA adapters sit on the five
MLA projections, the dense FFN and the shared expert; the routed experts,
the router and its bias stay frozen without adapters, and there is no
auxiliary loss (nothing it could train is trainable).
"""

from repro.configs.base import ArchConfig

ADAPTED = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w1", "w3", "w2")

CONFIG = ArchConfig(
    name="kimi-k2-instruct", family="moe",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=64,
    head_dim=192, d_ff=2048, d_ff_dense=18_432, vocab_size=163_840,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
    first_k_dense=1, n_experts=384, top_k=8, n_shared_experts=1,
    router_score="sigmoid", routed_scale=2.827, router_aux_coef=0.0,
    rope_theta=50_000.0, max_position=131_072,
    yarn_factor=32.0, yarn_original_max=4096, yarn_beta_fast=1.0,
    yarn_beta_slow=1.0, yarn_mscale=1.0, yarn_mscale_all_dim=1.0,
    act="silu", glu=True, tie_embeddings=False,
    adapter_targets=ADAPTED, adapter_rank=12,
    source="https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/"
           "config.json",
)

CHIP = CONFIG.with_(
    name="kimi-k2-chip", n_layers=5, layer_pattern=(), vocab_size=20_480,
    experts_held=8, expert_shard=0, n_classes=20, train_remat=True)

SMOKE = CONFIG.with_(
    name="kimi-smoke", n_layers=3, layer_pattern=(), d_model=128,
    n_heads=4, n_kv_heads=4, head_dim=24, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff=32,
    d_ff_dense=96, vocab_size=512, n_experts=4, top_k=2, experts_held=2,
    yarn_original_max=8, param_dtype="float32", compute_dtype="float32",
    adapter_rank=4)

# the CHIP cut at smoke widths: the classifier the benchmark's smoke runs
CHIP_SMOKE = SMOKE.with_(name="kimi-chip-smoke", n_classes=20)
