"""CommPru (paper §IV-B3): mask-pruned parameter transmission + byte-exact
accounting.

A rank's triplet for a module with dims (d_in, d_out) costs
``d_in + d_out (+1 for E)`` parameters (× n_experts for per-expert adapters).
Masks travel as booleans (1 bit each) and are negligible, but are counted.
Pack/unpack provide an actual wire format (used by the round-trip property
tests); the federated simulator uses ``prune_tree`` (zero masked ranks —
semantics-preserving because masked ranks are frozen and contribute nothing).

CommPru decides *which* parameters travel; ``repro.fedsim.transport`` layers
the *how* on top of this wire format — pluggable codecs (blockwise int8,
top-k) with error feedback, plus bandwidth/latency links.  ``pack_int8``
below stays as the simple per-tensor variant the paper's §VIII table quotes;
simulation runs should prefer ``fedsim.transport.Int8Block`` (per-block
scales + residual memory).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import masks as MK


def _is_module(x) -> bool:
    return isinstance(x, dict) and "A" in x and "B" in x


def _iter_modules(adapters: Any, masks: Any, path=""):
    if _is_module(adapters):
        yield path, adapters, masks
        return
    if isinstance(adapters, dict):
        for k, v in adapters.items():
            sub = masks.get(k) if isinstance(masks, dict) else None
            yield from _iter_modules(v, sub, f"{path}.{k}" if path else k)


def module_rank_params(mod: dict) -> int:
    """Parameters per surviving (layer, rank) unit: (d_in + d_out [+1])·E."""
    a_shape, b_shape = mod["A"].shape, mod["B"].shape
    return a_shape[-1] + b_shape[-2] + (1 if "E" in mod else 0)


def count_params(adapters: Any, masks: Any | None = None) -> int:
    """Total parameters that CommPru would transmit."""
    total = 0
    for _, mod, msk in _iter_modules(adapters, masks or {}):
        a_shape = mod["A"].shape
        r = a_shape[-2]
        lead_all = int(np.prod(a_shape[:-2])) if len(a_shape) > 2 else 1
        per = module_rank_params(mod)
        if msk is None:
            total += per * lead_all * r
            continue
        m = np.asarray(msk, bool)
        layers = int(np.prod(m.shape[:-1])) if m.ndim > 1 else 1
        experts = max(lead_all // layers, 1)
        total += int(per * experts * m.sum())
    return total


def bytes_down(adapters: Any, masks: Any | None, dtype_bytes: int = 4,
               extra_params: int = 0) -> int:
    """Server → client: pruned adapters + the global mask."""
    n = count_params(adapters, masks) + extra_params
    mask_bits = MK.total_ranks(masks) if masks else 0
    return n * dtype_bytes + (mask_bits + 7) // 8


def bytes_up(adapters: Any, masks: Any | None, dtype_bytes: int = 4,
             extra_params: int = 0) -> int:
    """Client → server: pruned adapters + the local mask."""
    return bytes_down(adapters, masks, dtype_bytes, extra_params)


def prune_tree(adapters: Any, masks: Any | None):
    """Zero all masked-out ranks (transmission-equivalent state).  Branches
    on shapes alone, so it traces with the masks as arguments
    (``federated.server.round_programs``)."""
    if masks is None:
        return adapters

    def prune_module(mod, msk):
        # a select, not a multiply by 0/1: compiled and eager agree to the
        # bit (XLA rewrites a multiply by a converted mask into a select,
        # which writes +0 where the multiply kept a negative's sign)
        m = jnp.asarray(msk, bool)
        out = dict(mod)
        # broadcast mask over expert axis if the adapter is per-expert
        am = m
        if mod["A"].ndim == m.ndim + 2:            # (E, r, d) vs (r,)
            am = m[..., None, :] if m.ndim else m
        out["A"] = jnp.where(am[..., :, None], mod["A"], 0) \
            if mod["A"].ndim >= 2 else mod["A"]
        bm = m
        if mod["B"].ndim == m.ndim + 2:
            bm = m[..., None, :] if m.ndim else m
        out["B"] = jnp.where(bm[..., None, :], mod["B"], 0)
        if "E" in mod:
            em = m
            if mod["E"].ndim == m.ndim + 1:        # (E, r) vs (r,)
                em = m[..., None, :] if m.ndim else m
            out["E"] = jnp.where(em, mod["E"], 0)
        return out

    def walk(ad, msk):
        if _is_module(ad):
            return prune_module(ad, msk) if msk is not None else ad
        if isinstance(ad, dict):
            return {k: walk(v, msk.get(k) if isinstance(msk, dict) else None)
                    for k, v in ad.items()}
        return ad

    return walk(adapters, masks)


def sq_sum(adapters: Any, masks: Any | None):
    """Sum of squares of what ``pack`` transmits, in float32, as traceable
    jax: every rank module's live ranks (``prune_tree`` zeroes the rest)."""
    ad = prune_tree(adapters, masks)
    return sum(jnp.sum(jnp.square(jnp.asarray(mod[k], jnp.float32)))
               for _, mod, _ in _iter_modules(ad, None)
               for k in ("A", "B", "E") if k in mod)


def pack_int8(adapters: Any, masks: Any | None) -> tuple[np.ndarray, float]:
    """Quantized wire format (QLoRA-adjacent, paper §VIII): symmetric int8
    per-tensor quantization of the surviving-rank payload — 4× fewer bytes
    than f32 CommPru.  Returns (int8 payload, scale)."""
    wire = pack(adapters, masks)
    if wire.size == 0:
        return wire.astype(np.int8), 1.0
    scale = float(np.abs(wire).max()) / 127.0 or 1.0
    q = np.clip(np.round(wire / scale), -127, 127).astype(np.int8)
    return q, scale


def unpack_int8(q: np.ndarray, scale: float, adapters_like: Any,
                masks: Any | None) -> Any:
    return unpack(q.astype(np.float32) * scale, adapters_like, masks)


def pack(adapters: Any, masks: Any | None) -> np.ndarray:
    """Wire format: concat of surviving-rank slices, deterministic order."""
    parts = []
    for path, mod, msk in _iter_modules(adapters, masks or {}):
        a = np.asarray(jax.device_get(mod["A"]), np.float32)
        b = np.asarray(jax.device_get(mod["B"]), np.float32)
        e = (np.asarray(jax.device_get(mod["E"]), np.float32)
             if "E" in mod else None)
        r = a.shape[-2]
        if msk is None:
            sel = np.ones(a.shape[:-2][-1:] + (r,), bool) if a.ndim > 2 \
                else np.ones((r,), bool)
            sel = np.ones((r,), bool)
        else:
            sel = np.asarray(msk, bool)
        flat_sel = sel.reshape(-1, r)
        a2 = a.reshape(-1, r, a.shape[-1]) if a.ndim > 2 else a[None]
        b2 = b.reshape(-1, b.shape[-2], r) if b.ndim > 2 else b[None]
        # align layer-stacked masks with (possibly expert-leading) params
        rep_a = a2.shape[0] // flat_sel.shape[0]
        for li in range(flat_sel.shape[0]):
            keep = flat_sel[li]
            for ri in np.nonzero(keep)[0]:
                for g in range(rep_a):
                    parts.append(a2[li * rep_a + g, ri])
        rep_b = b2.shape[0] // flat_sel.shape[0]
        for li in range(flat_sel.shape[0]):
            keep = flat_sel[li]
            for ri in np.nonzero(keep)[0]:
                for g in range(rep_b):
                    parts.append(b2[li * rep_b + g, :, ri])
        if e is not None:
            e2 = e.reshape(-1, r)
            rep_e = e2.shape[0] // flat_sel.shape[0]
            for li in range(flat_sel.shape[0]):
                keep = flat_sel[li]
                for ri in np.nonzero(keep)[0]:
                    for g in range(rep_e):
                        parts.append(e2[li * rep_e + g, ri:ri + 1])
    if not parts:
        return np.zeros((0,), np.float32)
    return np.concatenate([p.reshape(-1) for p in parts])


def unpack(wire: np.ndarray, adapters_like: Any, masks: Any | None) -> Any:
    """Inverse of pack: masked ranks reconstructed as zeros."""
    off = [0]

    def take(n):
        v = wire[off[0]:off[0] + n]
        off[0] += n
        return v

    def walk(ad, msk):
        if _is_module(ad):
            a = np.zeros(ad["A"].shape, np.float32)
            b = np.zeros(ad["B"].shape, np.float32)
            e = np.zeros(ad["E"].shape, np.float32) if "E" in ad else None
            r = a.shape[-2]
            sel = (np.ones((r,), bool) if msk is None
                   else np.asarray(msk, bool))
            flat_sel = sel.reshape(-1, r)
            a2 = a.reshape(-1, r, a.shape[-1])
            b2 = b.reshape(-1, b.shape[-2], r)
            rep_a = a2.shape[0] // flat_sel.shape[0]
            for li in range(flat_sel.shape[0]):
                for ri in np.nonzero(flat_sel[li])[0]:
                    for g in range(rep_a):
                        a2[li * rep_a + g, ri] = take(a.shape[-1])
            rep_b = b2.shape[0] // flat_sel.shape[0]
            for li in range(flat_sel.shape[0]):
                for ri in np.nonzero(flat_sel[li])[0]:
                    for g in range(rep_b):
                        b2[li * rep_b + g, :, ri] = take(b.shape[-2])
            out = {"A": a2.reshape(a.shape), "B": b2.reshape(b.shape)}
            if e is not None:
                e2 = e.reshape(-1, r)
                rep_e = e2.shape[0] // flat_sel.shape[0]
                for li in range(flat_sel.shape[0]):
                    for ri in np.nonzero(flat_sel[li])[0]:
                        for g in range(rep_e):
                            e2[li * rep_e + g, ri] = take(1)[0]
                out["E"] = e2.reshape(e.shape)
            return out
        if isinstance(ad, dict):
            return {k: walk(v, msk.get(k) if isinstance(msk, dict) else None)
                    for k, v in ad.items()}
        return ad

    return walk(adapters_like, masks)
