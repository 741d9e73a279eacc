"""Fused masked-BEA adapter matmul — the compute hot-spot FedARA adds to
every frozen linear:

    y = x·W + (α/r) · ((x·Aᵀ) ⊙ (e⊙m)) · Bᵀ

TPU mapping (HBM→VMEM→MXU):
  grid = (M/bm, N/bn, K/bk), k fastest.  The main accumulator (bm, bn) and
  the rank accumulator u = x·Aᵀ (bm, r) live in VMEM scratch across the k
  loop; at the last k step the adapter epilogue (u ⊙ (e⊙m)) · Bᵀ is applied
  on the MXU and the tile is written once.  The adapter thus costs zero
  extra HBM round-trips (vs 3 for the unfused form: u write, u read, y
  read-modify-write) — rank masking is a VMEM-resident multiply, so a pruned
  rank is free, matching CommPru semantics.

  bm/bn default to 256/256 (MXU-aligned multiples of 128); bk 512.  VMEM
  footprint ≈ bm·bk + bk·bn + bm·bn·4 + r·(bk+bn) ≈ 1.1 MB at defaults —
  comfortably inside the ~16 MB v5e VMEM with double buffering.

On a TPU backend the kernel is compiled by Mosaic; elsewhere (the CPU test
suite) it runs in Pallas interpret mode — see ``interpret_mode``.  Validated
against kernels/ref.py in both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, w_ref, a_ref, b_ref, em_ref, out_ref, acc_ref, u_ref, *,
            scaling: float, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    xb = x_ref[...]
    acc_ref[...] += jnp.dot(xb, w_ref[...],
                            preferred_element_type=jnp.float32)
    u_ref[...] += jnp.dot(xb, a_ref[...].T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        u = u_ref[...] * em_ref[0]                      # (bm, r) ⊙ (r,)
        delta = jnp.dot(u.astype(b_ref.dtype), b_ref[...].T,
                        preferred_element_type=jnp.float32)
        out_ref[...] = (acc_ref[...] + scaling * delta).astype(out_ref.dtype)


def interpret_mode(interpret: bool | None = None) -> bool:
    """The one place that decides whether a Pallas call interprets.

    On a TPU backend a kernel never interprets.  Elsewhere it interprets
    unless the caller passes ``interpret=False``, which lowers it through
    Mosaic — how a test compiles it for a described, unattached TPU."""
    return jax.default_backend() != "tpu" and interpret is not False


def _pad_to(arr, mult, axis):
    size = arr.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths)


@functools.partial(jax.jit, static_argnames=("scaling", "block_m", "block_n",
                                             "block_k", "interpret"))
def bea_dense(x, w, a, b, e, mask, scaling: float = 1.0,
              block_m: int = 256, block_n: int = 256, block_k: int = 512,
              interpret: bool | None = None):
    """Fused y = x@W + scaling·((x Aᵀ)⊙(e⊙m))Bᵀ.

    x: (M, K); w: (K, N); a: (r, K); b: (N, r); e/mask: (r,).
    Shapes are padded to block multiples; the result is sliced back.
    """
    m0, k0 = x.shape
    n0 = w.shape[1]
    r = a.shape[0]
    bm, bn, bk = (min(block_m, max(m0, 8)), min(block_n, max(n0, 8)),
                  min(block_k, max(k0, 8)))

    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    ap = _pad_to(a, bk, 1)
    bp = _pad_to(b, bn, 0)
    em = (e * mask.astype(e.dtype)).astype(jnp.float32)[None, :]   # (1, r)

    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, scaling=scaling, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((r, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((bn, r), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, r), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(xp, wp, ap, bp, em)
    return out[:m0, :n0]
