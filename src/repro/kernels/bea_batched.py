"""Rank-heterogeneous *batched* masked-BEA matmul — the multi-tenant serving
hot-spot: every row of ``x`` attaches its own FedARA adapter to one frozen
linear in a single fused pass:

    y[i] = x[i]·W + s · ((x[i]·A_{g_i}ᵀ) ⊙ (e_{g_i}⊙m_{g_i})) · B_{g_i}ᵀ

where ``g_i = idx[i]`` selects one of G adapters stacked at a common bucket
rank r (shorter adapters are zero-padded with their masks extended by False —
per CommPru semantics a masked rank is exactly free, so padding is free too).

TPU mapping (extends ``bea_fused.py``):
  grid = (M/bm, N/bn, K/bk), k fastest.  The wrapper flattens the adapter
  stacks to A (G·r, K) and Bᵀ (G·r, N), and folds the per-row adapter choice
  and the masked diagonal into one flat selector S (M, G·r):
  S[i, g·r + j] = [g == g_i]·e_g[j]·m_g[j].  Per k step the rank accumulator
  u = x·Aᵀ (bm, G·r) takes one MXU dot against the whole flat stack; at the
  last k step the epilogue is u ⊙ S (elementwise) and one (bm, G·r)·(G·r, bn)
  MXU dot — rows of u outside the row's adapter are zeroed by S, so the
  per-row select costs no gather/scatter and no in-kernel reshape, only the
  G× wider rank accumulator.  For serving-sized G·r (≤ a few hundred) that
  stays well inside VMEM:
  footprint ≈ bm·bk + bk·bn + bm·bn·4 + G·r·(bk+bn) + bm·G·r·8.

Degenerate buckets: G == 0 or r == 0 (fully-pruned bucket) short-circuit to
the plain matmul — rank-0 tenants cost exactly a dense forward.

Compiled by Mosaic on a TPU backend, interpreted elsewhere
(``bea_fused.interpret_mode``); validated against
kernels/ref.py:bea_batched_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.bea_fused import _pad_to, interpret_mode


def _kernel(x_ref, w_ref, a_ref, bt_ref, sel_ref, out_ref, acc_ref, u_ref,
            *, scaling: float, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    xb = x_ref[...]
    acc_ref[...] += jnp.dot(xb, w_ref[...],
                            preferred_element_type=jnp.float32)
    # one dot against the whole flat stack A (G·r, bk)
    u_ref[...] += jnp.dot(xb, a_ref[...].T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        t = u_ref[...] * sel_ref[...]                  # (bm, G·r)
        delta = jnp.dot(t.astype(bt_ref.dtype), bt_ref[...],
                        preferred_element_type=jnp.float32)
        out_ref[...] = (acc_ref[...] + scaling * delta).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scaling", "block_m", "block_n",
                                             "block_k", "interpret"))
def _bea_batched_call(x, w, a, bt, sel, scaling, block_m, block_n, block_k,
                      interpret):
    m0, k0 = x.shape
    n0 = w.shape[1]
    gr = a.shape[0]
    bm, bn, bk = (min(block_m, max(m0, 8)), min(block_n, max(n0, 8)),
                  min(block_k, max(k0, 8)))

    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    ap = _pad_to(a, bk, 1)
    btp = _pad_to(bt, bn, 1)
    selp = _pad_to(sel, bm, 0)            # padded rows select no adapter

    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, scaling=scaling, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((gr, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((gr, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, gr), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, gr), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(xp, wp, ap, btp, selp)
    return out[:m0, :n0]


def bea_batched(x, w, a_stack, b_stack, e_stack, m_stack, idx,
                scaling: float = 1.0, block_m: int = 128, block_n: int = 256,
                block_k: int = 512, interpret: bool | None = None):
    """Fused y[i] = x[i]@W + s·((x[i] A_gᵀ)⊙(e_g⊙m_g))B_gᵀ, g = idx[i].

    x: (M, K); w: (K, N); a_stack: (G, r, K); b_stack: (G, N, r);
    e_stack/m_stack: (G, r); idx: (M,) int32 in [0, G).
    Shapes are padded to block multiples; the result is sliced back.
    """
    g = a_stack.shape[0]
    r = a_stack.shape[1] if g else 0
    if g == 0 or r == 0:                    # fully-pruned bucket: dense only
        return jnp.dot(x, w.astype(x.dtype))
    em = (e_stack * m_stack.astype(e_stack.dtype)).astype(jnp.float32)
    onehot = (idx[:, None] == jnp.arange(g)[None, :]).astype(jnp.float32)
    sel = (onehot[:, :, None] * em[None]).reshape(-1, g * r)    # (M, G·r)
    a = a_stack.reshape(g * r, -1)                              # (G·r, K)
    bt = jnp.swapaxes(b_stack, 1, 2).reshape(g * r, -1)         # (G·r, N)
    return _bea_batched_call(x, w, a, bt, sel, scaling, block_m, block_n,
                             block_k, interpret)
