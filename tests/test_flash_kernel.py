"""Flash-attention Pallas kernel: shape/dtype/feature sweeps vs the jnp
oracle (interpret mode on CPU; tests/test_tpu_compile.py compiles the kernel
for the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import mha_flash
from repro.kernels.ref import flash_attention_ref

CASES = [
    # B, S, H, KV, hd, causal, window, softcap
    (2, 128, 4, 4, 32, True, 0, 0.0),
    (2, 128, 4, 2, 32, True, 0, 0.0),        # GQA 2:1
    (1, 256, 4, 1, 64, True, 32, 0.0),       # sliding window, MQA
    (2, 128, 4, 4, 32, False, 0, 0.0),       # bidirectional (encoder)
    (2, 128, 8, 2, 32, True, 0, 50.0),       # gemma-style softcap
    (1, 384, 6, 3, 16, True, 128, 30.0),     # window + softcap + odd dims
]


@pytest.mark.parametrize("b,s,h,kv,hd,causal,window,cap", CASES)
def test_flash_matches_oracle(b, s, h, kv, hd, causal, window, cap):
    rng = np.random.default_rng(b * 100 + s)
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, hd)), jnp.float32)
    got = mha_flash(q, k, v, causal=causal, window=window, softcap=cap,
                    block_q=64, block_k=64)
    g = h // kv
    want = flash_attention_ref(q, jnp.repeat(k, g, 2), jnp.repeat(v, g, 2),
                               causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.bfloat16)
    got = mha_flash(q, k, v, block_q=64, block_k=64)
    want = flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32))
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0.05,
                               atol=0.05)


def test_model_path_with_flash_flag():
    """attention() with ctx.rules['flash_kernel'] must match the default."""
    from repro.configs import get_config
    from repro.models import Ctx, Model
    cfg = get_config("qwen2_0p5b", smoke=True)
    model = Model(cfg, peft="bea")
    base, tr = model.init(jax.random.key(0))
    masks = model.init_masks()
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 128)))}
    ref, _, _ = model.forward(base, tr, masks, batch, mode="train",
                              remat=False)
    ctx = Ctx(mesh=None, rules={"flash_kernel": True})
    got, _, _ = model.forward(base, tr, masks, batch, mode="train", ctx=ctx,
                              remat=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)
