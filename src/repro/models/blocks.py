"""Transformer blocks: (local/global) attention+MLP, attention+MoE, Mamba2,
shared-attention (zamba2), encoder and decoder (cross-attn) variants.
Self-attention is multi-head latent attention (models/mla.py) where the
config sets ``kv_lora_rank``; a MoE model's dense blocks take ``d_ff_dense``.

Block kinds:
  attn        pre-norm GQA attention + FFN
  local       same, sliding-window attention
  moe         GQA attention + top-k MoE FFN
  local_moe   sliding-window attention + MoE FFN
  mamba       Mamba2 SSD block (single residual branch)
  shared_attn attention+FFN whose params are shared across occurrences
  enc         bidirectional attention + FFN (encoder)
  dec         causal self-attn + cross-attn + FFN (decoder)
"""

from __future__ import annotations

from repro.core import adapters as AD
from repro.models import attention as ATT
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import mlp as MLP
from repro.models import moe as MOE
from repro.models import ssm as SSM

LORA_KINDS = (AD.BEA, AD.LORA, AD.FFA)


def is_moe(kind: str) -> bool:
    return kind in ("moe", "local_moe")


def merge_stats(a: dict, b: dict) -> dict:
    """Sum two dicts of per-block statistics key by key."""
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in {**a, **b}}


def block_meta(cfg, kind: str) -> dict:
    if kind == "mamba":
        return {"ln1": L.norm_meta(cfg), "ssm": SSM.ssm_meta(cfg)}
    m = {"ln1": L.norm_meta(cfg),
         "attn": MLA.mla_meta(cfg) if cfg.mla else ATT.attn_meta(cfg),
         "ln2": L.norm_meta(cfg)}
    if kind == "dec":
        m["lnx"] = L.norm_meta(cfg)
        m["xattn"] = ATT.attn_meta(cfg, cross=True)
    if is_moe(kind):
        m["moe"] = MOE.moe_meta(cfg)
    else:
        m["mlp"] = MLP.mlp_meta(cfg, cfg.d_ff_dense)
    if cfg.post_block_norm:
        m["pn1"] = L.norm_meta(cfg)
        m["pn2"] = L.norm_meta(cfg)
    return m


def block_adapter_meta(cfg, kind: str, peft: str) -> dict:
    """Trainable-tree structure for one block under a PEFT strategy."""
    if peft in ("none", "fft"):
        return {}
    if peft in ("adapter_h", "adapter_p"):
        size = cfg.adapter_rank * 2        # bottleneck sized ~2r (paper §V)
        out = {"post_mlp": AD.bottleneck_meta(cfg.d_model, size)}
        if peft == "adapter_h" and kind != "mamba":
            out["post_attn"] = AD.bottleneck_meta(cfg.d_model, size)
        return out
    assert peft in LORA_KINDS, peft
    if kind == "mamba":
        return {"ssm": SSM.ssm_adapter_meta(cfg, peft)}
    out = {"attn": MLA.mla_adapter_meta(cfg, peft) if cfg.mla
           else ATT.attn_adapter_meta(cfg, peft)}
    if kind == "dec":
        out["xattn"] = ATT.attn_adapter_meta(cfg, peft)
    if is_moe(kind):
        out["moe"] = MOE.moe_adapter_meta(cfg, peft)
    else:
        out["mlp"] = MLP.mlp_adapter_meta(cfg, peft, cfg.d_ff_dense)
    return {k: v for k, v in out.items() if v}


def block_cache_meta(cfg, kind: str, batch: int, seq: int,
                     src_len: int = 0) -> dict | None:
    if kind in ("enc",):
        return None
    if kind == "mamba":
        return {"ssm_cache": SSM.ssm_cache_meta(cfg, batch)}
    if cfg.mla:
        return {"attn_cache": MLA.cache_meta(cfg, batch, seq)}
    window = cfg.sliding_window if (
        kind.startswith("local")
        or (kind == "shared_attn" and cfg.sliding_window)) else 0
    out = {"attn_cache": ATT.cache_meta(cfg, batch, seq, window)}
    if kind == "dec":
        out["xattn_cache"] = ATT.cross_cache_meta(cfg, batch, src_len)
    return out


def block_apply(p: dict, x, cfg, kind: str, *, mode: str = "train",
                ad=None, masks=None, cache=None, ctx=None, enc_out=None):
    """Returns (x, stats, new_cache): ``stats`` holds the softmax router's
    load-balance loss (``balance``) or the held experts' counts."""
    ad = ad or {}
    masks = masks or {}
    cache = cache or {}
    stats = {}
    new_cache = {}

    if kind == "mamba":
        h, nc = SSM.ssm_apply(p["ssm"], L.norm_apply(p["ln1"], x, cfg), cfg,
                              mode=mode, ad=ad.get("ssm"),
                              masks=masks.get("ssm"),
                              cache=cache.get("ssm_cache"), ctx=ctx)
        if nc is not None:
            new_cache["ssm_cache"] = nc
        x = x + h
        if "post_mlp" in ad:
            x = AD.apply_bottleneck(x, ad["post_mlp"])
        return x, stats, (new_cache or None)

    window = cfg.sliding_window if kind.startswith("local") else 0
    causal = (kind != "enc") and cfg.causal
    if cfg.mla:
        h, nc = MLA.mla(p["attn"], L.norm_apply(p["ln1"], x, cfg), cfg,
                        mode=mode, ad=ad.get("attn"), masks=masks.get("attn"),
                        cache=cache.get("attn_cache"), causal=causal, ctx=ctx)
    else:
        h, nc = ATT.attention(p["attn"], L.norm_apply(p["ln1"], x, cfg), cfg,
                              mode=mode, ad=ad.get("attn"),
                              masks=masks.get("attn"), window=window,
                              cache=cache.get("attn_cache"), causal=causal,
                              ctx=ctx)
    if nc is not None:
        new_cache["attn_cache"] = nc
    if "pn1" in p:
        h = L.norm_apply(p["pn1"], h, cfg)
    if "post_attn" in ad:
        h = AD.apply_bottleneck(h, ad["post_attn"])
    x = x + h

    if kind == "dec" and (enc_out is not None or cache.get("xattn_cache") is not None):
        h, ncx = ATT.attention(p["xattn"], L.norm_apply(p["lnx"], x, cfg), cfg,
                               mode=mode, ad=ad.get("xattn"),
                               masks=masks.get("xattn"), kv_x=enc_out,
                               cross=True,
                               cache=cache.get("xattn_cache"), ctx=ctx)
        if ncx is not None:
            new_cache["xattn_cache"] = ncx
        x = x + h

    h2 = L.norm_apply(p["ln2"], x, cfg)
    if is_moe(kind) and cfg.router_score == "sigmoid":
        h2, stats = MOE.held_apply(p["moe"], h2, cfg, ad=ad.get("moe"),
                                   masks=masks.get("moe"))
    elif is_moe(kind):
        h2, aux = MOE.moe_apply(p["moe"], h2, cfg, ctx, ad=ad.get("moe"),
                                masks=masks.get("moe"))
        stats = {"balance": aux}
    else:
        h2 = MLP.mlp_apply(p["mlp"], h2, cfg, ad=ad.get("mlp"),
                           masks=masks.get("mlp"))
    if "pn2" in p:
        h2 = L.norm_apply(p["pn2"], h2, cfg)
    if "post_mlp" in ad:
        h2 = AD.apply_bottleneck(h2, ad["post_mlp"])
    x = x + h2
    return x, stats, (new_cache or None)
