"""Plain reference of the ``kimi_k2`` configuration (Kimi K2 Instruct's layer
equations, DeepSeek-V3's form, at one chip's share) as a 20-class sequence
classifier, and its FLOP count.  Imports nothing of the program.

Per layer, pre-norm (RMSNorm, eps from the file): multi-head latent
attention, then layer 0's dense SwiGLU FFN or a MoE layer.

- MLA: ``c_q = RMSNorm(x W_qa)``, ``[q_nope | q_rope] = c_q W_qb`` per head;
  ``[c_kv | k_rope] = x W_kva`` with k_rope shared by the heads, ``[k_nope |
  v] = RMSNorm(c_kv) W_kvb`` per head; YaRN RoPE (DeepSeek-V3's frequencies
  and temperature) on q_rope and k_rope only; causal scores ``(q_nope·k_nope
  + q_rope·k_rope) · (nope + rope)^-½ · m²``, ``m = 0.1·mscale_all_dim·ln
  factor + 1``; ``concat(softmax · v) W_o``.
- MoE: ``s = sigmoid(x W_r)``; the top ``num_experts_per_tok`` of ``s + b``
  (the correction bias picks, it never weighs); weights ``s`` over the
  chosen experts, normalised, times ``routed_scaling_factor``; the chip's
  share is experts ``[expert_shard · experts_held, (expert_shard + 1) ·
  experts_held)`` of the router's ``router_experts``, each held expert
  computed on every token and weighted by its routing weight (zero where it
  was not chosen), pairs on absent experts left out; plus the shared
  expert.
- mean pooling over all positions after the final RMSNorm, into a linear
  head of ``n_classes`` outputs.

Adapters are the paper's BEA form ``y += s · ((x Aᵀ) ⊙ (E ⊙ m)) Bᵀ``, ``s =
α / r``, on W_qa, W_kva, the fused per-head maps of W_qb, W_kvb and W_o,
the dense FFN and the shared expert.  The router, its bias and the routed
experts are frozen and carry none.

Every matmul upcasts its operands to float32 itself, so the base may come
in its stored dtype or already in float32 (``ref_fed.run`` casts it).  ``mode`` picks the arithmetic: ``"ref"``
float32 at ``Precision.HIGHEST``; ``"fp8"`` the control one precision below
the configuration's bfloat16: every matmul operand rounded to float8 e4m3
with one scale per tensor, then multiplied exactly.  RoPE pairs dimension i
with i + rope/2 (DeepSeek's code pairs 2i with 2i + 1: the same map up to a
fixed permutation of W_qb's and W_kva's RoPE columns, which random weights
do not see).

``check_widths`` holds the file's widths against the leaves it is given and
fails at once, naming the leaf.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def fp8(x):
    """x rounded to float8 e4m3 under one per-tensor scale (largest
    magnitude to 448).  Gradients pass straight through the rounding."""
    x = x.astype(jnp.float32)
    s = jax.lax.stop_gradient(448.0 / jnp.maximum(jnp.max(jnp.abs(x)),
                                                  1e-30))
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def _ein(spec, a, b, mode):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms_norm(x, p, cfg):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + cfg["rms_norm_eps"])
    return y * p["scale"].astype(jnp.float32)


def adapter(x, ad, mask, scaling, mode):
    """Δy for one adapted projection; x (..., d_in) → (..., d_out)."""
    if ad is None:
        return 0.0
    u = _ein("...i,ri->...r", x, ad["A"], mode)
    e = ad["E"].astype(jnp.float32)
    if mask is not None:
        e = e * mask.astype(jnp.float32)
    return scaling * _ein("...r,or->...o", u * e, ad["B"], mode)


def dense(x, w, ad, mask, scaling, mode):
    """x (..., d_in) @ w (d_in, d_out) + its adapter."""
    return _ein("...i,io->...o", x, w, mode) + adapter(x, ad, mask, scaling,
                                                      mode)


# ------------------------------------------------------------------- rope ---

def rope_freqs(cfg):
    """DeepSeek-V3's YaRN inverse frequencies for the RoPE dimensions."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    ys = cfg["rope_scaling"]
    i = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** i
    inter = 1.0 / (ys["factor"] * base ** i)

    def corr(rot):
        return dim * math.log(ys["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr(ys["beta_fast"])), 0)
    hi = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    extra_mask = 1.0 - ramp
    return inter * (1.0 - extra_mask) + extra * extra_mask


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope(x, cfg):
    """x (B, S, ..., rope) turned by position (axis 1)."""
    ys = cfg["rope_scaling"]
    s = x.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * rope_freqs(cfg)[None]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + ang.shape[-1:])
    m = _mscale(ys["factor"], ys["mscale"]) \
        / _mscale(ys["factor"], ys["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ------------------------------------------------------------------ layers --

def mla(p, ad, m, x, cfg, mode):
    s = cfg["adapter_alpha"] / cfg["adapter_rank"]
    b, n, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, kr = cfg["v_head_dim"], cfg["kv_lora_rank"]
    qr = cfg["q_lora_rank"]

    def a(name):
        return ad.get(name), m.get(name)

    c_q = rms_norm(dense(x, p["wq_a"]["w"], *a("wq_a"), s, mode),
                   p["q_norm"], cfg)
    q = dense(c_q, p["wq_b"]["w"].reshape(qr, -1), *a("wq_b"), s,
              mode).reshape(
        b, n, h, nope + rd)
    kv_a = dense(x, p["wkv_a"]["w"], *a("wkv_a"), s, mode)
    c_kv = rms_norm(kv_a[..., :kr], p["kv_norm"], cfg)
    k_rope = rope(kv_a[..., kr:], cfg)                        # (b, n, rd)
    kv = dense(c_kv, p["wkv_b"]["w"].reshape(kr, -1), *a("wkv_b"), s,
               mode).reshape(
        b, n, h, nope + vd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], cfg)
    ys = cfg["rope_scaling"]
    scale = (nope + rd) ** -0.5 * _mscale(ys["factor"],
                                          ys["mscale_all_dim"]) ** 2
    sc = (_ein("bqhd,bkhd->bhqk", q_nope, kv[..., :nope], mode)
          + _ein("bqhd,bkd->bhqk", q_rope, k_rope, mode)) * scale
    causal = jnp.tril(jnp.ones((n, n), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _ein("bhqk,bkhd->bqhd", pr, kv[..., nope:], mode).reshape(
        b, n, h * vd)
    return dense(o, p["wo"]["w"].reshape(h * vd, -1), *a("wo"), s, mode)


def ffn(p, ad, m, x, cfg, mode):
    """SwiGLU with adapters on w1, w3 and w2."""
    s = cfg["adapter_alpha"] / cfg["adapter_rank"]
    ad, m = ad or {}, m or {}

    def lin(name, v):
        return dense(v, p[name]["w"], ad.get(name), m.get(name), s, mode)

    u = lin("w1", x)
    return lin("w2", jax.nn.silu(u) * lin("w3", x))


def moe(p, ad, m, x, cfg, mode):
    # the router is float32 in both modes, as the configuration states
    sc = jax.nn.sigmoid(_ein("bsd,de->bse", x, p["router"]["w"], "ref"))
    _, ids = jax.lax.top_k(sc + p["router"]["b"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(sc, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    n = cfg["experts_held"]
    e0 = cfg["expert_shard"] * n
    y = ffn(p["shared"], ad.get("shared"), m.get("shared"), x, cfg, mode)
    for e in range(n):
        g = (w * (ids == e0 + e)).sum(-1)                      # (b, s)
        u = _ein("bsd,df->bsf", x, p["w1"]["w"][e], mode)
        v = _ein("bsd,df->bsf", x, p["w3"]["w"][e], mode)
        y = y + g[..., None] * _ein("bsf,fd->bsd", jax.nn.silu(u) * v,
                                    p["w2"]["w"][e], mode)
    return y


def layer_kinds(cfg) -> list[str]:
    k = cfg["first_k_dense_replace"]
    return ["dense"] * k + ["moe"] * (cfg["num_hidden_layers"] - k)


def layers(tree):
    """Per-layer subtrees of a program-layout tree (unrolled: dec.tail)."""
    if tree is None:
        return None
    tail = tree["dec"]["tail"]
    return [tail[f"t{i}"] for i in range(len(tail))]


def check_widths(base, trainable, cfg):
    """Every MLA and MoE width the file states, held against the leaves."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    qr, kr, vd = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["v_head_dim"]
    ff, mf = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"]
    if held != cfg["n_routed_experts"]:
        raise ValueError(f"n_routed_experts {cfg['n_routed_experts']} is "
                         f"not experts_held {held}")
    want = {"embed.tok": (cfg["vocab_size"], d),
            "final_norm.scale": (d,)}
    for i, kind in enumerate(layer_kinds(cfg)):
        at = f"dec.tail.t{i}.attn."
        want.update({at + "wq_a.w": (d, qr), at + "q_norm.scale": (qr,),
                     at + "wq_b.w": (qr, h, nope + rd),
                     at + "wkv_a.w": (d, kr + rd),
                     at + "kv_norm.scale": (kr,),
                     at + "wkv_b.w": (kr, h, nope + vd),
                     at + "wo.w": (h, vd, d)})
        if kind == "dense":
            f = f"dec.tail.t{i}.mlp."
            want.update({f + "w1.w": (d, ff), f + "w3.w": (d, ff),
                         f + "w2.w": (ff, d)})
            continue
        mo = f"dec.tail.t{i}.moe."
        sf = mf * cfg["n_shared_experts"]
        want.update({mo + "router.w": (d, cfg["router_experts"]),
                     mo + "router.b": (cfg["router_experts"],),
                     mo + "w1.w": (held, d, mf), mo + "w3.w": (held, d, mf),
                     mo + "w2.w": (held, mf, d),
                     mo + "shared.w1.w": (d, sf), mo + "shared.w3.w": (d, sf),
                     mo + "shared.w2.w": (sf, d)})
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(base)[0]:
        got[".".join(str(getattr(k, "key", k)) for k in path)] = leaf.shape
    for path, shape in want.items():
        if tuple(got.get(path, ())) != shape:
            raise ValueError(f"{path}: the file states {shape}, the base "
                             f"holds {got.get(path)}")
    hw = trainable["head"]["w"].shape
    if hw != (d, cfg["n_classes"]):
        raise ValueError(f"head.w: the file states {(d, cfg['n_classes'])}, "
                         f"the trainable holds {hw}")


def logits(base, trainable, masks, tokens, cfg, mode="ref"):
    """(B, S) int tokens → (B, n_classes) float32 logits."""
    x = jnp.take(base["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    ads = layers(trainable["adapters"])
    ms = layers(masks) or [None] * len(ads)
    for p, ad, m in zip(layers(base), ads, ms):
        ad, m = ad or {}, m or {}
        x = x + mla(p["attn"], ad.get("attn", {}), m.get("attn", {}),
                    rms_norm(x, p["ln1"], cfg), cfg, mode)
        y = rms_norm(x, p["ln2"], cfg)
        if "mlp" in p:
            x = x + ffn(p["mlp"], ad.get("mlp"), m.get("mlp"), y, cfg, mode)
        else:
            x = x + moe(p["moe"], ad.get("moe", {}), m.get("moe", {}), y,
                        cfg, mode)
    pooled = rms_norm(x, base["final_norm"], cfg).mean(axis=1)
    h = trainable["head"]
    return jnp.dot(pooled, h["w"].astype(jnp.float32), precision=HI) \
        + h["b"].astype(jnp.float32)


def cls_loss(trainable, base, masks, tokens, labels, row_w, cfg, mode="ref"):
    """Mean cross-entropy over the rows with weight 1."""
    check_widths(base, trainable, cfg)
    lg = logits(base, trainable, masks, tokens, cfg, mode)
    lp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1)[:, 0]
    return (nll * row_w).sum() / jnp.maximum(row_w.sum(), 1.0)


# ------------------------------------------------------------------ flops ---
# Multiply-adds ×2.  The routed experts count at their expected share: each
# token's top-k pairs land on the held experts with probability
# experts_held / router_experts.

def _mla_proj(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    qr, kr, vd = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["v_head_dim"]
    return {"wq_a": (d, qr), "wq_b": (qr, h * (nope + rd)),
            "wkv_a": (d, kr + rd), "wkv_b": (kr, h * (nope + vd)),
            "wo": (h * vd, d)}


def _ffn(d, f):
    return {"w1": (d, f), "w3": (d, f), "w2": (f, d)}


def _layer(cfg, kind):
    """(base matmul maps, adapted maps, expected routed pairs a token)."""
    d = cfg["hidden_size"]
    base = dict(_mla_proj(cfg))
    adapted = dict(_mla_proj(cfg))
    if kind == "dense":
        f = _ffn(d, cfg["intermediate_size"])
        base.update({"mlp." + k: v for k, v in f.items()})
        adapted.update({"mlp." + k: v for k, v in f.items()})
        return base, adapted, 0.0
    f = _ffn(d, cfg["moe_intermediate_size"] * cfg["n_shared_experts"])
    base.update({"shared." + k: v for k, v in f.items()})
    adapted.update({"shared." + k: v for k, v in f.items()})
    base["router"] = (d, cfg["router_experts"])
    pairs = cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["router_experts"]
    return base, adapted, pairs


def _attn_core(cfg, seq):
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2 * seq * seq * h * (qk + cfg["v_head_dim"])


def _expert(cfg):
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops(cfg, seq: int, rank: int) -> float:
    """One example's forward pass (embedding lookups, norms, rope,
    softmax and routing's elementwise work not counted)."""
    total = 2 * cfg["hidden_size"] * cfg["n_classes"]
    for kind in layer_kinds(cfg):
        base, adapted, pairs = _layer(cfg, kind)
        total += 2 * seq * sum(i * o for i, o in base.values())
        total += _attn_core(cfg, seq)
        total += 2 * seq * rank * sum(i + o for i, o in adapted.values())
        total += seq * pairs * _expert(cfg)
    return total


def train_flops(cfg, seq: int, rank: int) -> float:
    """One example's forward and backward pass as the algorithm needs it:
    activation gradients through the frozen base (none below layer 0's
    input, none into the router's frozen weights beyond its input
    gradient), the adapters' weight gradients, no base weight gradients, no
    recomputation."""
    d = cfg["hidden_size"]
    fwd = forward_flops(cfg, seq, rank)
    bwd = 2 * d * cfg["n_classes"]
    for i, kind in enumerate(layer_kinds(cfg)):
        base, adapted, pairs = _layer(cfg, kind)
        first_in = ("wq_a", "wkv_a") if i == 0 else ()
        bwd += 2 * seq * sum(a * b for k, (a, b) in base.items()
                             if k not in first_in)
        bwd += 2 * _attn_core(cfg, seq)                 # dP, dV, dQ, dK
        bwd += seq * pairs * _expert(cfg)
        ad_out = sum(2 * seq * rank * o for o in (v[1] for v in
                                                  adapted.values()))
        ad_in = sum(2 * seq * rank * i for k, (i, _) in adapted.items()
                    if k not in first_in)
        bwd += ad_out + ad_in                           # through adapters
        bwd += sum(2 * seq * rank * (i + o)
                   for i, o in adapted.values())        # dA, dB
    return fwd + bwd
