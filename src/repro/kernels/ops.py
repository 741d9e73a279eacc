"""jit'd dispatch wrappers around the Pallas kernels.

The kernels lower through Mosaic on a TPU backend and run in Pallas
interpret mode elsewhere (``bea_fused.interpret_mode``).  The
model layers call ``adapted_dense`` which routes to the fused kernel when
enabled, otherwise the unfused jnp path (the dry-run default, so the HLO is
analyzable op-by-op; §Perf swaps the kernel in and accounts the fusion win).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bea_batched import bea_batched
from repro.kernels.bea_fused import bea_dense


def adapted_dense(x, w, a, b, e, mask, scaling: float,
                  use_kernel: bool = False):
    """x: (..., K) @ w (K, N) with fused masked-BEA epilogue.

    Leading dims are flattened into M for the kernel.
    """
    if not use_kernel:
        y = jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
        u = jnp.einsum("...k,rk->...r", x, a.astype(x.dtype))
        u = u * (e * mask.astype(e.dtype)).astype(x.dtype)
        return y + scaling * jnp.einsum("...r,nr->...n", u, b.astype(x.dtype))
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    ym = bea_dense(xm, w, a, b, e, mask, scaling=scaling)
    return ym.reshape(lead + (w.shape[1],))


def adapted_dense_multi(x, w, a_stack, b_stack, e_stack, m_stack, idx,
                        scaling: float, use_kernel: bool = False):
    """Multi-tenant x: (M, K) @ w (K, N) — row i uses adapter ``idx[i]``.

    a_stack: (G, r, K); b_stack: (G, N, r); e_stack/m_stack: (G, r).
    The unfused jnp path is the analyzable oracle form; ``use_kernel=True``
    dispatches the fused rank-bucketed Pallas kernel (interpret on CPU).
    The serving engine currently mirrors these semantics via vmap over
    ``Model.decode_step``; wiring this dispatch into the decode hot path on
    TPU is a ROADMAP follow-on.
    """
    if use_kernel:
        return bea_batched(x, w, a_stack, b_stack, e_stack, m_stack, idx,
                           scaling=scaling)
    g = a_stack.shape[0]
    if g == 0 or a_stack.shape[1] == 0:
        return jnp.dot(x, w.astype(x.dtype))
    cd = x.dtype
    y = jnp.dot(x, w.astype(cd))
    onehot = (idx[:, None] == jnp.arange(g)[None, :]).astype(cd)
    u = jnp.einsum("mk,grk->mgr", x, a_stack.astype(cd))
    em = (e_stack * m_stack.astype(e_stack.dtype)).astype(cd)
    t = u * em[None] * onehot[:, :, None]
    return y + scaling * jnp.einsum("mgr,gnr->mn", t, b_stack.astype(cd))
