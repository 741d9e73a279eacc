"""Fused masked-BEA kernel: correctness delta vs oracle, measured wall time
of the unfused XLA path on the device JAX finds, and the analytic
HBM-traffic saving of the fused Pallas kernel (the fusion removes 3 HBM
round-trips of the adapter intermediates)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.kernels.bea_fused import bea_dense
from repro.kernels.ref import bea_dense_ref


def main(quick: bool = False):
    rows = []
    m, k, n, r = (512, 512, 512, 8) if not quick else (128, 128, 128, 4)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), jnp.float32)
    a = jnp.asarray(rng.normal(size=(r, k)) / np.sqrt(k), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n, r)), jnp.float32)
    e = jnp.asarray(rng.normal(size=(r,)), jnp.float32)
    msk = jnp.ones((r,), jnp.float32)

    ref = jax.jit(lambda *t: bea_dense_ref(*t, scaling=2.0))
    out = ref(x, w, a, b, e, msk)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(20):
        jax.block_until_ready(ref(x, w, a, b, e, msk))
    t_ref = (time.time() - t0) / 20

    got = bea_dense(x, w, a, b, e, msk, scaling=2.0, block_m=128,
                    block_n=128, block_k=128)
    err = float(jnp.abs(got - out).max())

    dt = 4
    hbm_unfused = dt * (m * k + k * n + m * n            # main matmul
                        + m * k + r * k + m * r          # u = x Aᵀ
                        + m * r + n * r + m * n          # u Bᵀ
                        + 2 * m * n)                     # y += Δ
    hbm_fused = dt * (m * k + k * n + r * k + n * r + m * n)
    rows = [
        C.row("kernel/unfused_xla_us", f"{t_ref * 1e6:.0f}",
              shape=f"{m}x{k}x{n}_r{r}"),
        C.row("kernel/allclose_maxerr", f"{err:.2e}"),
        C.row("kernel/hbm_bytes_unfused", hbm_unfused),
        C.row("kernel/hbm_bytes_fused", hbm_fused,
              saving_pct=f"{100 * (1 - hbm_fused / hbm_unfused):.1f}"),
    ]
    C.emit(rows)
    return rows


if __name__ == "__main__":
    main()
