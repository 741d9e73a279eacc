"""RankDet / rank-based module pruning (paper §IV-C).

Monitors per-module surviving rank counts each round; when a module's rank
hits zero the whole SVD module becomes non-trainable.  Two mechanisms:

- ``trainable_gate``: a 0/1 pytree multiplied into optimizer updates —
  cheap, no recompilation, works for scan-stacked modules (per-layer gating).
- ``prune_structurally``: removes fully-dead *unstacked* modules from the
  trainable tree entirely (JAX analogue of dropping them from the optimizer;
  triggers re-jit at the round boundary — measured in benchmarks).

Both preserve semantics: dead ranks are masked in the forward pass and get
zero gradients anyway; pruning only removes wasted compute/memory.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _is_module(x) -> bool:
    return isinstance(x, dict) and "A" in x and "B" in x


def trainable_gate(adapters: Any, masks: Any) -> Any:
    """Pytree of float gates aligned with ``adapters`` leaves.

    For a module whose mask is all-False (per stacked layer), every leaf of
    that module gets gate 0 for that layer — the optimizer stops updating it.
    Traceable in the masks: the round loop builds it inside a compiled
    program (``federated.server.round_programs``).
    """
    def walk(ad, msk):
        if _is_module(ad):
            if msk is None:
                return jax.tree.map(lambda x: jnp.ones((), x.dtype), ad)
            alive = jnp.any(jnp.asarray(msk, bool), -1).astype(
                jnp.float32)                                    # (lead...,)
            out = {}
            for k, v in ad.items():
                extra = v.ndim - alive.ndim
                g = alive.reshape(alive.shape + (1,) * extra) \
                    if extra >= 0 else jnp.ones((), jnp.float32)
                out[k] = jnp.broadcast_to(g, v.shape) if extra >= 0 else g
            return out
        if isinstance(ad, dict):
            return {k: walk(v, msk.get(k) if isinstance(msk, dict) else None)
                    for k, v in ad.items()}
        return jnp.ones((), jnp.float32)

    return walk(adapters, masks)


def dead_modules(masks: Any) -> list[str]:
    """Paths of modules whose every rank (every stacked layer) is pruned."""
    out = []

    def walk(msk, path):
        if isinstance(msk, dict):
            for k, v in msk.items():
                walk(v, f"{path}.{k}" if path else k)
            return
        if not np.asarray(msk, bool).any():
            out.append(path)

    walk(masks, "")
    return out


def module_rank_summary(masks: Any) -> dict[str, dict[str, int]]:
    """Per-module live/total rank counts: ``{"a.b.c": {"live", "total"}}``.

    Paths follow :func:`dead_modules`'s dotted convention; for stacked
    modules the counts sum over the stacked layers, so ``live == 0`` iff
    the module is in ``dead_modules(masks)``.  This is the payload the
    trace recorder stamps on ``rank_alloc`` events (the paper's rank
    trajectory, reconstructable offline)."""
    out: dict[str, dict[str, int]] = {}

    def walk(msk, path):
        if isinstance(msk, dict):
            for k, v in msk.items():
                walk(v, f"{path}.{k}" if path else k)
            return
        m = np.asarray(msk, bool)
        out[path] = {"live": int(m.sum()), "total": int(m.size)}

    walk(masks, "")
    return out


def prune_structurally(trainable: Any, masks: Any) -> Any:
    """Remove fully-dead unstacked adapter modules from the trainable tree."""
    def walk(tr, msk):
        if _is_module(tr):
            if msk is not None:
                m = np.asarray(msk, bool)
                if m.ndim == 1 and not m.any():
                    return None                      # dead → drop module
            return tr
        if isinstance(tr, dict):
            out = {}
            for k, v in tr.items():
                r = walk(v, msk.get(k) if isinstance(msk, dict) else None)
                if r is None or (isinstance(r, dict) and not r):
                    continue
                out[k] = r
            return out
        # bare mask leaf (pruning a mask tree alongside its adapters)
        if msk is tr and np.asarray(tr).ndim == 1 \
                and not np.asarray(tr, bool).any():
            return None
        return tr

    return walk(trainable, masks)


def count_trainable(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def adapter_flops_per_token(adapters: Any, masks: Any | None) -> int:
    """Forward FLOPs/token of live adapter math (2·r_live·(d_in+d_out))."""
    from repro.core.comm import _iter_modules
    total = 0
    for _, mod, msk in _iter_modules(adapters, masks or {}):
        a_shape, b_shape = mod["A"].shape, mod["B"].shape
        d_in, d_out = a_shape[-1], b_shape[-2]
        r = a_shape[-2]
        lead = int(np.prod(a_shape[:-2])) if len(a_shape) > 2 else 1
        if msk is None:
            live = r * (int(np.prod(np.asarray(msk).shape[:-1]))
                        if msk is not None else 1)
            total += 2 * (d_in + d_out) * r * lead
        else:
            m = np.asarray(msk, bool)
            layers = int(np.prod(m.shape[:-1])) if m.ndim > 1 else 1
            experts = max(lead // layers, 1)
            total += int(2 * (d_in + d_out) * experts * m.sum())
    return total
