"""Triplet importance scores (paper Eq. 14, Table I).

For module n, rank i the triplet is (E_i, B[:,i], A[i,:]) and

    I_{n,i} = I(E_i) + mean_j I(B_{j,i}) + mean_j I(A_{i,j})

with four leaf scores:
    Mag          I(w) = |w|                       (the paper's default)
    Grad         I(w) = |∂ℓ/∂w|
    Mixed        I(w) = |w · ∂ℓ/∂w|
    Sensitivity  AdaLoRA-style EMA of |w·g| (≈1.3× compute, Table I)

Scores are computed per client over the (tiny) adapter tree: on the host
(``score_tree``) by the sequential and async runners, and for the cohort's
slots on the device (``score_flat``, in the cohort runner's upload program,
federated/server.py ``round_programs``); both use one definition of the
maths.  Per-expert adapters average over the expert axis because the rank
mask belongs to the insertion position (layer, component), not to
individual experts.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.pytree import flatten_with_paths

MAG, GRAD, MIXED, SENSITIVITY = "mag", "grad", "mixed", "sensitivity"


def _np(x) -> np.ndarray:
    return np.asarray(jax.device_get(x), dtype=np.float32)


def _is_module(x) -> bool:
    return isinstance(x, dict) and "A" in x and "B" in x


def reads_grads(method: str) -> bool:
    return method != MAG


def _leaf_score(w, g, method: str):
    if method == MAG:
        return abs(w)
    if method == GRAD:
        return abs(g)
    if method in (MIXED, SENSITIVITY):
        return abs(w * g)
    raise ValueError(method)


def _module_score(mod: dict, grads: dict | None, method: str,
                  n_experts: int):
    """Returns (lead..., r) float32 score — per-expert axis averaged away.
    numpy leaves give numpy, jax leaves (traced) give jax."""
    xp = np if isinstance(mod["A"], np.ndarray) else jnp

    def leaf(k):
        w = xp.asarray(mod[k], xp.float32)
        g = xp.asarray(grads[k], xp.float32) if grads else xp.zeros_like(w)
        return _leaf_score(w, g, method)

    score = leaf("A").mean(-1) + leaf("B").mean(-2)     # (lead..., r)
    if "E" in mod:
        score = score + leaf("E")
    # average the expert axis into the (layer, component) mask granularity
    if n_experts and score.ndim >= 2 and score.shape[-2] == n_experts:
        score = score.mean(-2)
    return score


def _walk(ad, gr, prev, score):
    """Mask-structured tree of ``score(module, its grads, prev)`` over the
    adapters' rank modules, ``gr`` and ``prev`` walked alongside
    (bottleneck adapters have no ranks)."""
    if _is_module(ad):
        return score(ad, gr, prev)
    if isinstance(ad, dict):
        out = {}
        for k, v in ad.items():
            if isinstance(v, dict) and "down" in v:
                continue
            r = _walk(v, gr.get(k) if isinstance(gr, dict) else None,
                      prev.get(k) if isinstance(prev, dict) else None, score)
            if r is not None:
                out[k] = r
        return out or None
    return None


def score_tree(adapters: Any, grads: Any | None, method: str = MAG,
               n_experts: int = 0, ema_state: Any | None = None,
               ema_beta: float = 0.85):
    """Mask-structured tree of importance scores, on the host.

    Returns (scores, new_ema_state).  ``ema_state`` is used only by the
    Sensitivity method (AdaLoRA's smoothed sensitivity).
    """
    def score(mod, gr, prev):
        s = _module_score({k: _np(v) for k, v in mod.items()},
                          {k: _np(v) for k, v in gr.items()} if gr else None,
                          method, n_experts)
        if method == SENSITIVITY:
            prev = prev if isinstance(prev, np.ndarray) else np.zeros_like(s)
            s = ema_beta * prev + (1 - ema_beta) * s
        return s

    scores = _walk(adapters, grads, ema_state, score) or {}
    if method == SENSITIVITY:
        # the smoothed scores are the next client's EMA state
        return scores, scores
    return scores, ema_state


def score_flat(adapters: Any, grads: Any | None, method: str = MAG,
               n_experts: int = 0) -> jax.Array:
    """``flat_concat(score_tree(...)[0])[0]`` as traceable jax: one client's
    scores in ``score_layout``'s order.  Every method but Sensitivity,
    whose EMA runs across clients on the host."""
    if method == SENSITIVITY:
        raise ValueError("sensitivity scores carry an EMA across clients: "
                         "score_tree computes them on the host")
    leaves = jax.tree.leaves(_walk(
        adapters, grads, None,
        lambda mod, gr, _: _module_score(mod, gr, method, n_experts)))
    if not leaves:
        return jnp.zeros((0,), jnp.float32)
    return jnp.concatenate([s.reshape(-1) for s in leaves])


def score_layout(adapters: Any, n_experts: int = 0
                 ) -> list[tuple[str, tuple]]:
    """``flat_concat``'s layout of the adapters' scores (arrays or
    shapes): where each module's ranks sit in ``score_flat``'s vector."""
    tree = jax.eval_shape(lambda ad: _walk(
        ad, None, None,
        lambda mod, gr, _: _module_score(mod, gr, MAG, n_experts)) or {},
        adapters)
    return [(p, s.shape) for p, s in flatten_with_paths(tree)]


def flat_concat(score_tree_: Any) -> tuple[np.ndarray, list[tuple[str, tuple]]]:
    """Flatten a mask-structured tree → (flat vector, [(path, shape)])."""
    items = flatten_with_paths(score_tree_,
                               is_leaf=lambda x: isinstance(x, np.ndarray))
    vecs, layout = [], []
    for path, leaf in items:
        arr = np.asarray(leaf)
        vecs.append(arr.reshape(-1))
        layout.append((path, arr.shape))
    if not vecs:
        return np.zeros((0,), np.float32), []
    return np.concatenate(vecs), layout


def unflatten(flat: np.ndarray, layout: list[tuple[str, tuple]]) -> dict:
    """Inverse of flat_concat (returns nested dict keyed by path parts)."""
    out: dict = {}
    off = 0
    for path, shape in layout:
        n = int(np.prod(shape)) if shape else 1
        val = flat[off:off + n].reshape(shape)
        off += n
        node = out
        parts = path.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return out
