"""GQA attention: training/prefill (chunked online-softmax), decode (cached),
sliding-window local variant, logit soft-capping, optional QKV bias, and
cross-attention for the encoder-decoder architectures.

Memory-efficient path: a scan over query chunks with an inner scan over KV
chunks carrying (m, l, acc) — a pure-JAX flash attention.  Sliding-window
layers slice only the in-window KV span per query chunk, making local
attention O(S·w) instead of O(S²).

Long-context decode: the KV cache is annotated with the "kv_seq" logical axis;
under the long_500k rules it shards the cache over the mesh, and XLA lowers
the softmax reductions into the cross-shard all-reduce combine (flash-decoding
via GSPMD partial reductions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adapters as AD
from repro.models import layers as L
from repro.pytree import ParamMeta

NEG_INF = -2.3819763e38          # bf16-safe large negative


# ------------------------------------------------------------------ meta ----

def attn_meta(cfg, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    m = {
        "wq": {"w": ParamMeta((d, h, hd), cfg.pdtype, ("embed_fsdp", "heads", None), init="normal", fan_in=d)},
        "wk": {"w": ParamMeta((d, kv, hd), cfg.pdtype, ("embed_fsdp", "kv_heads", None), init="normal", fan_in=d)},
        "wv": {"w": ParamMeta((d, kv, hd), cfg.pdtype, ("embed_fsdp", "kv_heads", None), init="normal", fan_in=d)},
        "wo": {"w": ParamMeta((h, hd, d), cfg.pdtype, ("heads", None, "embed_fsdp"), init="normal", scale=0.05, fan_in=h * hd)},
    }
    if cfg.qkv_bias and not cross:
        m["wq"]["b"] = ParamMeta((h, hd), cfg.pdtype, ("heads", None), init="zeros")
        m["wk"]["b"] = ParamMeta((kv, hd), cfg.pdtype, ("kv_heads", None), init="zeros")
        m["wv"]["b"] = ParamMeta((kv, hd), cfg.pdtype, ("kv_heads", None), init="zeros")
    return m


def attn_adapter_meta(cfg, kind: str) -> dict:
    """Adapters for q/k/v/o as 2D maps over the fused head dims."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dims = {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d)}
    out = {}
    for name, (di, do) in dims.items():
        if name in cfg.adapter_targets:
            ad = AD.adapter_meta(kind, di, do, cfg.adapter_rank)
            if ad is not None:
                out[name] = ad
    return out


# ------------------------------------------------------------- projection ---

def _proj(p: dict, x: jax.Array, ad, mask, scaling) -> jax.Array:
    """x (..., d) @ w (d, H, hd) -> (..., H, hd), adapter on the fused map."""
    w = p["w"]
    _, h, hd = w.shape
    y = jnp.einsum("...d,dhk->...hk", x, w.astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    if ad is not None:
        flat = AD.apply_adapter(jnp.zeros(x.shape[:-1] + (h * hd,), x.dtype),
                                x, ad, mask, scaling)
        y = y + flat.reshape(y.shape)
    return y


def _out_proj(p: dict, o: jax.Array, ad, mask, scaling) -> jax.Array:
    """o (..., H, hd) @ wo (H, hd, d) -> (..., d)."""
    w = p["w"]
    y = jnp.einsum("...hk,hkd->...d", o, w.astype(o.dtype))
    if ad is not None:
        h, hd, _ = w.shape
        y = AD.apply_adapter(y, o.reshape(o.shape[:-2] + (h * hd,)), ad, mask,
                             scaling)
    return y


# ----------------------------------------------------------- core softmax ---

def _scores(q, k, scale, softcap):
    # q: (B, Sq, KV, G, hd), k: (B, Sk, KV, hd) -> (B, KV, G, Sq, Sk)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
    return L.softcap(s, softcap)


def _direct(q, k, v, mask, scale, softcap):
    s = _scores(q, k, scale, softcap)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", p, v)


def chunks_for(sq: int, window: int = 0) -> tuple[int, int]:
    """Chunk sizes used by the flash path — also consumed by the roofline
    correction in launch/analysis.py (scan interiors are cost-counted once)."""
    cq = 512 if sq % 512 == 0 else sq
    ckv = 1024 if sq % 1024 == 0 else sq
    return cq, ckv


def _chunked(q, k, v, scale, softcap, window, chunk_q, chunk_kv,
             causal=True):
    """Online-softmax attention, O(chunk²) live memory.

    q: (B, Sq, KV, G, hd); k: (B, Sk, KV, hd); v: (B, Sk, KV, hv), Sq == Sk
    (train/prefill).
    """
    b, sq, kv, g, hd = q.shape
    hv = v.shape[-1]
    sk = k.shape[1]
    nq = sq // chunk_q
    qs = q.reshape(b, nq, chunk_q, kv, g, hd)

    if window:
        # Local attention: each q chunk sees at most chunk_q + window keys.
        span = int(np.ceil((chunk_q + window) / chunk_kv)) * chunk_kv
        span = min(span, sk)
        pad = span
        kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))

        def per_chunk(args):
            i, qc = args                            # qc: (b, cq, kv, g, hd)
            q_start = i * chunk_q
            start = jnp.clip(q_start - window + pad, 0, sk + pad - span)
            kc = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
            qpos = q_start + jnp.arange(chunk_q)
            kpos = start - pad + jnp.arange(span)
            m = (kpos[None, :] <= qpos[:, None]) \
                & (kpos[None, :] > qpos[:, None] - window) \
                & (kpos[None, :] >= 0)
            return _direct(qc, kc, vc, m[None, None, None], scale, softcap)

        outs = jax.lax.map(per_chunk, (jnp.arange(nq), qs.swapaxes(0, 1)))
        return outs.swapaxes(0, 1).reshape(b, sq, kv, g, hv)

    nk = sk // chunk_kv
    ks = k.reshape(b, nk, chunk_kv, kv, hd)
    vs = v.reshape(b, nk, chunk_kv, kv, hv)

    def q_body(args):
        i, qc = args
        qpos = i * chunk_q + jnp.arange(chunk_q)

        def kv_body(carry, j):
            m_run, l_run, acc = carry
            kc, vc = ks[:, j], vs[:, j]
            kpos = j * chunk_kv + jnp.arange(chunk_kv)
            s = _scores(qc, kc, scale, softcap)             # (b,kv,g,cq,ck)
            if causal:
                msk = kpos[None, :] <= qpos[:, None]
                s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(vc.dtype), vc).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, kv, g, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, chunk_q), jnp.float32)
        a0 = jnp.zeros((b, kv, g, chunk_q, hv), jnp.float32)
        (_, l_f, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), jnp.arange(nk))
        o = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return o.transpose(0, 3, 1, 2, 4).astype(q.dtype)   # (b,cq,kv,g,hd)

    outs = jax.lax.map(q_body, (jnp.arange(nq), qs.swapaxes(0, 1)))
    return outs.swapaxes(0, 1).reshape(b, sq, kv, g, hv)


# ------------------------------------------------------------- public ops ---

def attention(p: dict, x: jax.Array, cfg, *, mode: str = "train", ad=None,
              masks=None, window: int = 0, cache=None, kv_x=None,
              causal: bool = True, cross: bool = False,
              ctx=None) -> tuple[jax.Array, dict | None]:
    """Attention op.  mode ∈ {train, prefill, decode}.  Returns (out, cache').

    RoPE'd keys are stored in the cache, so decode only rotates the new key.
    Local (windowed) layers use a ring-buffer cache of length ``window``.
    """
    scaling = cfg.adapter_alpha / max(cfg.adapter_rank, 1)
    masks = masks or {}
    ad = ad or {}
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    scale = 1.0 / np.sqrt(hd)
    b, sq, _ = x.shape
    cross = cross or (kv_x is not None)
    use_rope = cfg.pos_emb == "rope" and not cross

    q = _proj(p["wq"], x, ad.get("wq"), masks.get("wq"), scaling)  # (b,sq,h,hd)
    new_cache = cache

    if cross:                                                # cross-attention
        if mode == "decode" and cache is not None:
            k, v = cache["k"].astype(x.dtype), cache["v"].astype(x.dtype)
        else:
            k = _proj(p["wk"], kv_x, ad.get("wk"), masks.get("wk"), scaling)
            v = _proj(p["wv"], kv_x, ad.get("wv"), masks.get("wv"), scaling)
            if cache is not None:
                new_cache = {"k": k.astype(cache["k"].dtype),
                             "v": v.astype(cache["v"].dtype)}
        qg = q.reshape(b, sq, kv, g, hd)
        sk = k.shape[1]
        if sq <= 2048 and sk <= 4096:
            m = jnp.ones((1, 1, 1, sq, sk), bool)
            o = _direct(qg, k, v, m, scale, cfg.attn_softcap)
        else:
            cq, _ = chunks_for(sq)
            _, ckv = chunks_for(sk)
            o = _chunked(qg, k, v, scale, cfg.attn_softcap, 0, cq, ckv,
                         causal=False)

    elif mode == "decode":
        pos = cache["pos"]                                    # scalar int32
        positions = jnp.broadcast_to(pos, (b, sq))
        if use_rope:
            q = L.rope(q, positions, cfg.rope_theta)
        k_new = _proj(p["wk"], x, ad.get("wk"), masks.get("wk"), scaling)
        v_new = _proj(p["wv"], x, ad.get("wv"), masks.get("wv"), scaling)
        if use_rope:
            k_new = L.rope(k_new, positions, cfg.rope_theta)
        T = cache["k"].shape[1]
        ring = bool(window) and T <= window
        slot = pos % T if ring else pos
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
        new_cache = {"k": ck, "v": cv, "pos": pos + sq}
        if ctx is not None and ctx.mesh is not None:
            from repro import sharding as SH
            ck = SH.constrain(ck, ("batch", "kv_seq", "kv_heads", None),
                              ctx.mesh, ctx.rules)
            cv = SH.constrain(cv, ("batch", "kv_seq", "kv_heads", None),
                              ctx.mesh, ctx.rules)
        kpos = jnp.arange(T)
        if ring:
            valid = ((slot - kpos) % T) < jnp.minimum(pos + 1, T)
        else:
            valid = kpos <= pos
            if window:
                valid &= kpos > pos - window
        qg = q.reshape(b, sq, kv, g, hd)
        o = _direct(qg, ck.astype(x.dtype), cv.astype(x.dtype),
                    valid[None, None, None, None, :], scale, cfg.attn_softcap)

    else:                                                    # train / prefill
        positions = jnp.arange(sq)[None, :]
        k = _proj(p["wk"], x, ad.get("wk"), masks.get("wk"), scaling)
        v = _proj(p["wv"], x, ad.get("wv"), masks.get("wv"), scaling)
        if use_rope:
            q = L.rope(q, positions, cfg.rope_theta)
            k = L.rope(k, positions, cfg.rope_theta)
        qg = q.reshape(b, sq, kv, g, hd)
        use_flash = (ctx is not None and (ctx.rules or {}).get("flash_kernel")
                     and sq % 128 == 0)
        if use_flash:
            # Pallas flash kernel (kernels/flash_attention.py): VMEM-resident
            # score tiles — the TPU-native memory-roofline fix (§Perf).
            from repro.kernels.flash_attention import mha_flash
            o = mha_flash(q.reshape(b, sq, h, hd), k, v, causal=causal,
                          window=window if causal else 0,
                          softcap=cfg.attn_softcap,
                          block_q=min(512, sq), block_k=min(512, sq))
            o = o.reshape(b, sq, kv, g, hd)
        elif sq <= 2048:
            qpos = jnp.arange(sq)
            if causal:
                m = qpos[None, :] <= qpos[:, None]
                if window:
                    m &= qpos[None, :] > qpos[:, None] - window
                m = m[None, None, None]
            else:
                m = jnp.ones((1, 1, 1, sq, sq), bool)
            o = _direct(qg, k, v, m, scale, cfg.attn_softcap)
        else:
            cq, ckv = chunks_for(sq, window)
            o = _chunked(qg, k, v, scale, cfg.attn_softcap,
                         window if causal else 0, cq, ckv, causal=causal)
        if mode == "prefill" and cache is not None:
            T = cache["k"].shape[1]
            if bool(window) and T <= window and sq >= T:
                # ring alignment: absolute position p lives at slot p % T
                kk = jnp.roll(k[:, -T:], sq % T, axis=1)
                vv = jnp.roll(v[:, -T:], sq % T, axis=1)
                new_cache = {"k": kk.astype(cache["k"].dtype),
                             "v": vv.astype(cache["v"].dtype),
                             "pos": jnp.int32(sq)}
            else:
                ck = jnp.zeros_like(cache["k"]).at[:, :sq].set(
                    k.astype(cache["k"].dtype))
                cv = jnp.zeros_like(cache["v"]).at[:, :sq].set(
                    v.astype(cache["v"].dtype))
                new_cache = {"k": ck, "v": cv, "pos": jnp.int32(sq)}

    o = o.reshape(b, sq, h, hd)
    out = _out_proj(p["wo"], o, ad.get("wo"), masks.get("wo"), scaling)
    return out, new_cache


def cache_meta(cfg, batch: int, seq: int, window: int = 0) -> dict:
    t = min(seq, window) if window else seq
    kvd = cfg.cdtype                     # bf16 in production, f32 in smokes
    return {
        "k": ParamMeta((batch, t, cfg.n_kv_heads, cfg.head_dim), kvd,
                       ("batch", "kv_seq", "kv_heads", None), init="zeros"),
        "v": ParamMeta((batch, t, cfg.n_kv_heads, cfg.head_dim), kvd,
                       ("batch", "kv_seq", "kv_heads", None), init="zeros"),
        "pos": ParamMeta((), jnp.int32, (), init="zeros"),
    }


def cross_cache_meta(cfg, batch: int, src_len: int) -> dict:
    kvd = cfg.cdtype
    return {
        "k": ParamMeta((batch, src_len, cfg.n_kv_heads, cfg.head_dim),
                       kvd, ("batch", "kv_seq", "kv_heads", None),
                       init="zeros"),
        "v": ParamMeta((batch, src_len, cfg.n_kv_heads, cfg.head_dim),
                       kvd, ("batch", "kv_seq", "kv_heads", None),
                       init="zeros"),
    }
