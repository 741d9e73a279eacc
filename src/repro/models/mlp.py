"""Feed-forward blocks: gated (SwiGLU) and plain (GELU), with adapters on
f1 (= w1/w3, the up projections) and f2 (= w2, the down projection)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import adapters as AD
from repro.models import layers as L


def mlp_meta(cfg, width: int = 0) -> dict:
    """``width`` overrides ``cfg.d_ff`` (a dense layer of a MoE model, a
    shared expert)."""
    f = width or cfg.d_ff
    m = {"w1": L.dense_meta(cfg, cfg.d_model, f, axes=("embed_fsdp", "mlp"))}
    if cfg.glu:
        m["w3"] = L.dense_meta(cfg, cfg.d_model, f,
                               axes=("embed_fsdp", "mlp"))
    m["w2"] = L.dense_meta(cfg, f, cfg.d_model,
                           axes=("mlp", "embed_fsdp"), out_scale=0.05)
    return m


def mlp_adapter_meta(cfg, kind: str, width: int = 0) -> dict:
    f = width or cfg.d_ff
    out = {}
    for name, (di, do) in (("w1", (cfg.d_model, f)),
                           ("w3", (cfg.d_model, f)),
                           ("w2", (f, cfg.d_model))):
        if name == "w3" and not cfg.glu:
            continue
        if name in cfg.adapter_targets:
            ad = AD.adapter_meta(kind, di, do, cfg.adapter_rank)
            if ad is not None:
                out[name] = ad
    return out


def _act(x: jax.Array, act: str) -> jax.Array:
    return jax.nn.silu(x) if act == "silu" else jax.nn.gelu(x)


def mlp_apply(p: dict, x: jax.Array, cfg, ad=None, masks=None) -> jax.Array:
    ad = ad or {}
    masks = masks or {}
    scaling = cfg.adapter_alpha / max(cfg.adapter_rank, 1)
    h = L.dense_apply(p["w1"], x, ad.get("w1"), masks.get("w1"), scaling)
    h = _act(h, cfg.act)
    if cfg.glu:
        g = L.dense_apply(p["w3"], x, ad.get("w3"), masks.get("w3"), scaling)
        h = h * g
    return L.dense_apply(p["w2"], h, ad.get("w2"), masks.get("w2"), scaling)
