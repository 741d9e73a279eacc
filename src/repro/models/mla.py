"""Multi-head latent attention (MLA: DeepSeek-V3, Kimi K2), with YaRN RoPE.

    c_q            = RMSNorm(x W_qa)                       (q_lora_rank)
    [q_nope|q_rope] = c_q W_qb                              per head
    [c_kv|k_rope]  = x W_kva                               k_rope shared by the heads
    [k_nope|v]     = RMSNorm(c_kv) W_kvb                   per head
    scores         = (q_nope·k_nope + q_rope·k_rope) · (nope+rope)^-½ · m²
    out            = concat_h(softmax(scores) v) W_o

RoPE (YaRN frequencies, ``layers.rope_freqs``) turns q_rope and k_rope only;
``m = 0.1·mscale_all_dim·ln(factor) + 1`` is YaRN's attention temperature.
BEA adapters sit on the five projections (W_qb and W_kvb adapt the fused
per-head map).  The cache keeps what the layer's keys and values are made
of, the normalised latent c_kv and the turned k_rope, and expands them
through W_kvb (with its adapter) at every decode step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import adapters as AD
from repro.models import attention as ATT
from repro.models import layers as L
from repro.pytree import ParamMeta


def _dims(cfg) -> dict[str, tuple[int, int]]:
    h, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    return {"wq_a": (cfg.d_model, cfg.q_lora_rank),
            "wq_b": (cfg.q_lora_rank, h * (nope + rope)),
            "wkv_a": (cfg.d_model, cfg.kv_lora_rank + rope),
            "wkv_b": (cfg.kv_lora_rank, h * (nope + cfg.v_head_dim)),
            "wo": (h * cfg.v_head_dim, cfg.d_model)}


def mla_meta(cfg) -> dict:
    h, d = cfg.n_heads, cfg.d_model
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    vd = cfg.v_head_dim
    return {
        "wq_a": L.dense_meta(cfg, d, qr, axes=("embed_fsdp", None)),
        "q_norm": L.norm_meta(cfg, qr),
        "wq_b": {"w": ParamMeta((qr, h, qk), cfg.pdtype, (None, "heads", None),
                                init="normal", fan_in=qr)},
        "wkv_a": L.dense_meta(cfg, d, kr + cfg.qk_rope_head_dim,
                              axes=("embed_fsdp", None)),
        "kv_norm": L.norm_meta(cfg, kr),
        "wkv_b": {"w": ParamMeta((kr, h, cfg.qk_nope_head_dim + vd),
                                 cfg.pdtype, (None, "heads", None),
                                 init="normal", fan_in=kr)},
        "wo": {"w": ParamMeta((h, vd, d), cfg.pdtype,
                              ("heads", None, "embed_fsdp"), init="normal",
                              scale=0.05, fan_in=h * vd)},
    }


def mla_adapter_meta(cfg, kind: str) -> dict:
    out = {}
    for name, (di, do) in _dims(cfg).items():
        if name in cfg.adapter_targets:
            ad = AD.adapter_meta(kind, di, do, cfg.adapter_rank)
            if ad is not None:
                out[name] = ad
    return out


def cache_meta(cfg, batch: int, seq: int) -> dict:
    return {
        "ckv": ParamMeta((batch, seq, cfg.kv_lora_rank), cfg.cdtype,
                         ("batch", "kv_seq", None), init="zeros"),
        "kpe": ParamMeta((batch, seq, cfg.qk_rope_head_dim), cfg.cdtype,
                         ("batch", "kv_seq", None), init="zeros"),
        "pos": ParamMeta((), jnp.int32, (), init="zeros"),
    }


def softmax_scale(cfg) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        s *= L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return s


def _turn(x, positions, cfg):
    """RoPE with YaRN frequencies and YaRN's cos/sin scale."""
    y = L.rope(x, positions, cfg.rope_theta,
               L.rope_freqs(cfg, cfg.qk_rope_head_dim))
    if cfg.yarn_factor:
        m = (L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
        if m != 1.0:
            y = y * jnp.asarray(m, y.dtype)
    return y


def mla(p: dict, x: jax.Array, cfg, *, mode: str = "train", ad=None,
        masks=None, cache=None, causal: bool = True,
        ctx=None) -> tuple[jax.Array, dict | None]:
    """Self-attention op, mode ∈ {train, prefill, decode}.  Returns
    (out, cache')."""
    del ctx
    scaling = cfg.adapter_alpha / max(cfg.adapter_rank, 1)
    ad, masks = ad or {}, masks or {}
    h, nope = cfg.n_heads, cfg.qk_nope_head_dim
    kr, rope_d = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    b, sq, _ = x.shape

    c_q = L.norm_apply(p["q_norm"], L.dense_apply(
        p["wq_a"], x, ad.get("wq_a"), masks.get("wq_a"), scaling), cfg)
    q = ATT._proj(p["wq_b"], c_q, ad.get("wq_b"), masks.get("wq_b"),
                  scaling)                                  # (b,sq,h,qk)
    kv_a = L.dense_apply(p["wkv_a"], x, ad.get("wkv_a"), masks.get("wkv_a"),
                         scaling)                           # (b,sq,kr+rope)
    c_kv = L.norm_apply(p["kv_norm"], kv_a[..., :kr], cfg)
    pos0 = cache["pos"] if mode == "decode" else 0
    positions = pos0 + jnp.arange(sq)[None, :]
    q = jnp.concatenate([q[..., :nope], _turn(q[..., nope:], positions, cfg)],
                        axis=-1)
    k_pe = _turn(kv_a[..., None, kr:], positions, cfg)[:, :, 0]

    new_cache = cache
    qpos = pos0 + jnp.arange(sq)
    if mode == "decode":
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], c_kv.astype(cache["ckv"].dtype), pos0, axis=1)
        kpe = jax.lax.dynamic_update_slice_in_dim(
            cache["kpe"], k_pe.astype(cache["kpe"].dtype), pos0, axis=1)
        new_cache = {"ckv": ckv, "kpe": kpe, "pos": pos0 + sq}
        c_kv, k_pe = ckv.astype(x.dtype), kpe.astype(x.dtype)
        kpos = jnp.arange(c_kv.shape[1])
    else:
        if mode == "prefill" and cache is not None:
            new_cache = {
                "ckv": jnp.zeros_like(cache["ckv"]).at[:, :sq].set(
                    c_kv.astype(cache["ckv"].dtype)),
                "kpe": jnp.zeros_like(cache["kpe"]).at[:, :sq].set(
                    k_pe.astype(cache["kpe"].dtype)),
                "pos": jnp.int32(sq)}
        kpos = qpos
    mask = kpos[None, :] <= qpos[:, None] if causal or mode == "decode" \
        else jnp.ones((sq, kpos.shape[0]), bool)

    kv = ATT._proj(p["wkv_b"], c_kv, ad.get("wkv_b"), masks.get("wkv_b"),
                   scaling)                                 # (b,sk,h,nope+v)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, :, None], kv.shape[:-1] + (rope_d,))],
        axis=-1)
    v = kv[..., nope:]
    scale = softmax_scale(cfg)
    qg = q[:, :, :, None]                                   # (b,sq,h,1,qk)
    if sq <= 2048 or mode == "decode":
        o = ATT._direct(qg, k, v, mask[None, None, None], scale,
                        cfg.attn_softcap)
    else:
        cq, ckv_ = ATT.chunks_for(sq)
        o = ATT._chunked(qg, k, v, scale, cfg.attn_softcap, 0, cq, ckv_,
                         causal=causal)
    o = o.reshape(b, sq, h, cfg.v_head_dim)
    return ATT._out_proj(p["wo"], o, ad.get("wo"), masks.get("wo"),
                         scaling), new_cache
