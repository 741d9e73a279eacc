"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracle
(interpret=True executes the kernel body on CPU; tests/test_tpu_compile.py
compiles the kernels for the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels.bea_fused import bea_dense
from repro.kernels.ops import adapted_dense
from repro.kernels.ref import bea_dense_ref


def _inputs(m, k, n, r, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), dtype)
    a = jnp.asarray(rng.normal(size=(r, k)) / np.sqrt(k), dtype)
    b = jnp.asarray(rng.normal(size=(n, r)), dtype)
    e = jnp.asarray(rng.normal(size=(r,)), jnp.float32)
    msk = jnp.asarray(rng.integers(0, 2, (r,)), jnp.float32)
    return x, w, a, b, e, msk


SHAPES = [(8, 16, 8, 2), (64, 64, 64, 4), (100, 96, 80, 8),
          (256, 512, 128, 16), (33, 48, 65, 3)]


@pytest.mark.parametrize("m,k,n,r", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bea_dense_matches_ref(m, k, n, r, dtype):
    x, w, a, b, e, msk = _inputs(m, k, n, r, dtype)
    got = bea_dense(x, w, a, b, e, msk, scaling=1.5,
                    block_m=32, block_n=32, block_k=32)
    # reference computed in f32 for a stable target
    f32 = [t.astype(jnp.float32) for t in (x, w, a, b)]
    want = bea_dense_ref(f32[0], f32[1], f32[2], f32[3], e, msk, 1.5)
    tol = 5e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               rtol=tol, atol=tol * np.abs(want).max())


@given(m=st.integers(1, 70), k=st.integers(1, 70), n=st.integers(1, 70),
       r=st.integers(1, 12))
@settings(max_examples=15, deadline=None)
def test_bea_dense_arbitrary_shapes(m, k, n, r):
    x, w, a, b, e, msk = _inputs(m, k, n, r, jnp.float32, seed=m * 71 + n)
    got = bea_dense(x, w, a, b, e, msk, scaling=2.0,
                    block_m=32, block_n=32, block_k=32)
    want = bea_dense_ref(x, w, a, b, e, msk, 2.0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_masked_rank_exactly_free():
    """A fully-masked adapter must equal the plain matmul (CommPru)."""
    x, w, a, b, e, msk = _inputs(32, 32, 32, 4, jnp.float32)
    got = bea_dense(x, w, a, b, e, jnp.zeros(4), scaling=3.0,
                    block_m=32, block_n=32, block_k=32)
    np.testing.assert_allclose(got, x @ w, rtol=1e-5, atol=1e-5)


def test_adapted_dense_wrapper_paths_agree():
    x, w, a, b, e, msk = _inputs(16, 24, 20, 4, jnp.float32)
    x3 = x.reshape(2, 8, 24)
    unfused = adapted_dense(x3, w, a, b, e, msk, 1.3, use_kernel=False)
    fused = adapted_dense(x3, w, a, b, e, msk, 1.3, use_kernel=True)
    np.testing.assert_allclose(unfused, fused, rtol=1e-4, atol=1e-4)
