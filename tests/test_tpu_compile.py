"""The main path's Pallas kernels compile for a TPU v5e at the widths the
chip runs them: each is lowered through Mosaic (``interpret=False``) for a
described, unattached ``v5e:2x2`` topology and must emit a
``tpu_custom_call``.  Interpret mode accepts kernels that the chip's
compiler refuses (unaligned slices, unsupported in-kernel reshapes), so these
compiles are what guard the kernels between chip runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker imports
this file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bea_batched import bea_batched
from repro.kernels.bea_fused import bea_dense
from repro.kernels.flash_attention import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# DistilBERT projections: attention (768→768) and FFN up (768→3072), r=12
@pytest.mark.parametrize("n", [768, 3072])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bea_dense_compiles_for_v5e(one_chip, n, dtype):
    m, k, r = 1024, 768, 12
    dt = jnp.dtype(dtype)
    shapes = [_spec(s, d, one_chip) for s, d in (
        ((m, k), dt), ((k, n), dt), ((r, k), dt), ((n, r), dt),
        ((r,), jnp.float32), ((r,), jnp.float32))]
    text = _compiled_text(
        lambda x, w, a, b, e, msk: bea_dense(x, w, a, b, e, msk, scaling=1.3,
                                             interpret=False), shapes)
    assert "tpu_custom_call" in text


# Qwen2-0.5B attention: 14 query heads over 2 KV heads, head_dim 64
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_compiles_for_v5e(one_chip, dtype):
    b, s, h, kv, hd = 2, 512, 14, 2, 64
    dt = jnp.dtype(dtype)
    shapes = [_spec((b * h, s, hd), dt, one_chip),
              _spec((b * kv, s, hd), dt, one_chip),
              _spec((b * kv, s, hd), dt, one_chip)]
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, group=h // kv,
                                        interpret=False), shapes)
    assert "tpu_custom_call" in text


# Qwen2-0.5B width, four tenants at bucket rank 8
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bea_batched_compiles_for_v5e(one_chip, dtype):
    m, k, n, g, r = 64, 896, 896, 4, 8
    dt = jnp.dtype(dtype)
    shapes = [_spec(s, d, one_chip) for s, d in (
        ((m, k), dt), ((k, n), dt), ((g, r, k), dt), ((g, n, r), dt),
        ((g, r), jnp.float32), ((g, r), jnp.float32), ((m,), jnp.int32))]
    text = _compiled_text(
        lambda x, w, a, b, e, msk, idx: bea_batched(
            x, w, a, b, e, msk, idx, scaling=2.0, interpret=False), shapes)
    assert "tpu_custom_call" in text
