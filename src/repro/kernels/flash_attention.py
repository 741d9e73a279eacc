"""Flash attention for TPU (Pallas): causal / sliding-window / soft-capped,
GQA-aware without materializing repeated KV heads.

Why it exists here: the §Roofline baseline shows every train/prefill shape is
memory-bound, dominated by the O(S²) f32 score traffic of the jnp
online-softmax path (XLA materializes the per-chunk score tensors to HBM).
This kernel keeps the (bq × bk) score tile, the running max/denominator and
the output accumulator in VMEM across the KV sweep — HBM traffic drops to
the q/k/v/o operands (O(S·d) per head), the TPU-native adaptation of the
paper's training step (DESIGN.md §3).

Layout: q (BH, S, hd); k/v (BH_kv, S, hd).  grid = (BH, nq, nk), kv
innermost; the kv-head index_map folds GQA (h → h // group) so grouped
queries read the same KV tile without a copy.  Fully-masked causal tiles are
skipped with pl.when.

Compiled by Mosaic on a TPU backend, interpreted elsewhere
(``bea_fused.interpret_mode``); validated against kernels/ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.bea_fused import interpret_mode

NEG_INF = -2.3819763e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, nk: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * bq
    k_start = j * bk
    # tile-level skip: fully in the causal future, or fully behind the window
    live = jnp.bool_(True)
    if causal:
        live = k_start <= q_start + bq - 1
    if window:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _tile():
        q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_ref[...] = l_ref[...] * corr + p.sum(1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _out():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "group", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    group: int = 1, block_q: int = 512, block_k: int = 512,
                    interpret: bool | None = None):
    """q: (BH, Sq, hd); k/v: (BH // group, Sk, hd) → (BH, Sq, hd).

    ``group`` = GQA group size; kv tiles are indexed via h // group.
    """
    bh, sq, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq ({sq},{sk}) must divide blocks ({bq},{bk})")
    nq, nk = sq // bq, sk // bk

    grid = (bh, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          softcap=softcap, bq=bq, bk=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return out


def mha_flash(q, k, v, *, causal=True, window=0, softcap=0.0,
              interpret=None, block_q=512, block_k=512):
    """(B, S, H, hd) MHA/GQA wrapper around the kernel."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, v.shape[1], hd)
    of = flash_attention(qf, kf, vf, causal=causal, window=window,
                         softcap=softcap, group=g, interpret=interpret,
                         block_q=block_q, block_k=block_k)
    return of.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
