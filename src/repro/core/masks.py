"""MaskGen (paper §IV-B1): local rank masks from triplet importance.

Each client sorts *all* triplets across modules and marks the global top-b(t)
as True.  Masks mirror the adapter tree at the module level, leaf shape
(lead..., r) bool — exactly the structure `Model.init_masks()` produces.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from repro.core import importance as IMP


def generate_local_masks(scores: Any, budget: int) -> Any:
    """Top-``budget`` triplets across the whole model → boolean mask tree."""
    return top_budget(*IMP.flat_concat(scores), budget)


def top_budget(flat: np.ndarray, layout: list, budget: int) -> Any:
    """``generate_local_masks`` of scores already flat, in
    ``IMP.flat_concat``'s order and ``layout``."""
    n = flat.size
    if n == 0:
        return {}
    k = int(np.clip(budget, 0, n))
    mask = np.zeros(n, dtype=bool)
    if k > 0:
        idx = np.argpartition(-flat, k - 1)[:k]
        mask[idx] = True
    return IMP.unflatten(mask, layout)


def vote_fractions(local_masks: list) -> dict[str, float]:
    """Per-module mean voted-rank fraction across a cohort's local masks
    (``{"a.b.c": frac}``, dotted paths as in ``pruning.dead_modules``) —
    the importance attribution the trace recorder stamps on ``rank_alloc``
    events alongside the arbitrated live/total counts."""
    acc: dict[str, list[float]] = {}

    def walk(msk, path):
        if isinstance(msk, dict):
            for k, v in msk.items():
                walk(v, f"{path}.{k}" if path else k)
            return
        m = np.asarray(msk, bool)
        acc.setdefault(path, []).append(float(m.mean()) if m.size else 0.0)

    for lm in local_masks:
        if lm:
            walk(lm, "")
    return {p: float(np.mean(v)) for p, v in acc.items()}


def mask_and(a: Any, b: Any) -> Any:
    """Elementwise AND of two mask trees (monotone pruning)."""
    if isinstance(a, dict):
        return {k: mask_and(a[k], b[k]) for k in a}
    return np.logical_and(np.asarray(a), np.asarray(b))


def count_true(masks: Any) -> int:
    flat, _ = IMP.flat_concat(jax_to_np(masks))
    return int(flat.sum())


def jax_to_np(tree: Any) -> Any:
    if isinstance(tree, dict):
        return {k: jax_to_np(v) for k, v in tree.items()}
    return np.asarray(tree)


def total_ranks(masks: Any) -> int:
    flat, _ = IMP.flat_concat(jax_to_np(masks))
    return int(flat.size)
