"""Shared layer primitives: norms, embeddings, RoPE, adapted dense."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adapters as A
from repro.pytree import ParamMeta


# ---------------------------------------------------------------- norms ----

def norm_meta(cfg, dim: int | None = None) -> dict:
    d = dim or cfg.d_model
    m = {"scale": ParamMeta((d,), jnp.float32, (None,),
                            init="zeros" if cfg.rms_offset else "ones")}
    if cfg.norm == "layernorm":
        m["bias"] = ParamMeta((d,), jnp.float32, (None,), init="zeros")
    return m


def norm_apply(p: dict, x: jax.Array, cfg) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        y = y * p["scale"] + p["bias"]
    else:
        y = xf * jax.lax.rsqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6)
        scale = (1.0 + p["scale"]) if cfg.rms_offset else p["scale"]
        y = y * scale
    return y.astype(x.dtype)


# ----------------------------------------------------------- embeddings ----

def embed_meta(cfg) -> dict:
    # std 0.25: with pre-norm blocks and small (0.05/√fan) residual-out
    # projections, the embedding signal dominates the random frozen base's
    # residual stream (SNR ≈ 2 after ~10 sublayers) — the emulation stand-in
    # for "pretrained features are useful" (DESIGN.md §6).
    m = {"tok": ParamMeta((cfg.vocab_size, cfg.d_model), cfg.pdtype,
                          ("vocab", "embed_fsdp"), init="scaled_normal",
                          scale=0.25)}
    if cfg.pos_emb == "learned":
        m["pos"] = ParamMeta((min(cfg.max_position, 1 << 16), cfg.d_model),
                             cfg.pdtype, (None, None), init="scaled_normal",
                             scale=0.02)
    return m


def embed_apply(p: dict, tokens: jax.Array, cfg,
                position_offset: jax.Array | int = 0) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0).astype(cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cfg.cdtype)
    if cfg.pos_emb == "learned":
        pos = position_offset + jnp.arange(tokens.shape[-1])
        x = x + jnp.take(p["pos"], pos, axis=0).astype(cfg.cdtype)
    elif cfg.pos_emb == "sinusoidal":
        pos = position_offset + jnp.arange(tokens.shape[-1])
        x = x + sinusoidal(pos, cfg.d_model).astype(cfg.cdtype)
    return x


def sinusoidal(pos: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freqs = np.exp(-np.log(10_000.0) * np.arange(half) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ------------------------------------------------------------------ rope ----

def rope_freqs(cfg, dim: int) -> np.ndarray:
    """(dim // 2,) inverse frequencies: θ^(-2i/dim), or YaRN's blend of them
    and their 1/factor interpolation (DeepSeek-V3's form: rotations above
    ``beta_fast`` keep θ's frequency, those below ``beta_slow`` are divided
    by the factor, a linear ramp between)."""
    extra = 1.0 / cfg.rope_theta ** (np.arange(0, dim, 2) / dim)
    if not cfg.yarn_factor:
        return extra

    def corr_dim(rotations):
        return (dim * np.log(cfg.yarn_original_max
                             / (rotations * 2 * np.pi))
                / (2 * np.log(cfg.rope_theta)))

    lo = max(int(np.floor(corr_dim(cfg.yarn_beta_fast))), 0)
    hi = min(int(np.ceil(corr_dim(cfg.yarn_beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return extra / cfg.yarn_factor * ramp + extra * (1 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * np.log(factor) + 1.0 if factor > 1 else 1.0


def rope(x: jax.Array, positions: jax.Array, theta: float,
         freqs: np.ndarray | None = None) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).  Pairs dim
    i with i + hd/2; ``freqs`` (hd/2,) overrides θ's."""
    hd = x.shape[-1]
    half = hd // 2
    if freqs is None:
        freqs = jnp.exp(
            -np.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        freqs, jnp.float32)                                     # (..., S, half)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:2 * half].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if 2 * half != hd:                       # odd head_dim tail passes through
        out = jnp.concatenate([out, x[..., 2 * half:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- dense ----

def dense_meta(cfg, d_in: int, d_out: int, *, axes=(None, None),
               bias: bool = False, n_experts: int = 0,
               out_scale: float = 1.0) -> dict:
    """An (optionally adapted) linear.  The base weight is frozen under PEFT;
    the adapter (if any) lives in the *trainable* tree at the same path.
    ``out_scale < 1`` marks residual-writing projections (GPT-2-style small
    init) so a random frozen base keeps the embedding signal in the residual
    stream — emulating the paper's pretrained base."""
    lead = (n_experts,) if n_experts else ()
    lead_ax = ("experts",) if n_experts else ()
    m = {"w": ParamMeta(lead + (d_in, d_out), cfg.pdtype, lead_ax + tuple(axes),
                        init="normal", scale=out_scale)}
    if bias:
        bias_ax = axes[1] if axes[1] not in ("embed_fsdp",) else None
        m["b"] = ParamMeta(lead + (d_out,), cfg.pdtype, lead_ax + (bias_ax,),
                           init="zeros")
    return m


def dense_apply(p: dict, x: jax.Array, ad: dict | None = None,
                mask: jax.Array | None = None, scaling: float = 1.0) -> jax.Array:
    cd = x.dtype
    w = p["w"].astype(cd)
    if w.ndim == 2:
        y = jnp.einsum("...i,io->...o", x, w)
    else:                                     # per-expert (E, d_in, d_out)
        y = jnp.einsum("e...i,eio->e...o", x, w)
    if "b" in p:
        y = y + p["b"].astype(cd)
    return A.apply_adapter(y, x, ad, mask, scaling)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)
