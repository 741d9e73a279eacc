"""Optimizer + schedule unit tests, and quantized optimizer state on a MINI
federated run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim as OPT
from repro.configs.distilbert import MINI
from repro.data.synthetic import make_classification
from repro.federated.baselines import all_strategies
from repro.federated.partition import iid_partition
from repro.federated.server import FedConfig, run_federated
from repro.models import Model


def test_adam_converges_quadratic():
    opt = OPT.adam(0.1)
    params = {"x": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
        upd, state = opt.update(g, state, params)
        params = OPT.apply_updates(params, upd)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_trainable_mask_freezes():
    opt = OPT.sgd(0.5)
    params = {"a": jnp.ones(3), "b": jnp.ones(3)}
    state = opt.init(params)
    g = {"a": jnp.ones(3), "b": jnp.ones(3)}
    upd, state = opt.update(g, state, params)
    out = OPT.apply_updates(params, upd,
                            {"a": jnp.zeros(()), "b": jnp.ones(())})
    np.testing.assert_allclose(out["a"], 1.0)
    np.testing.assert_allclose(out["b"], 0.5)


def test_linear_decay_schedule():
    f = OPT.linear_decay(1.0, 100)
    assert float(f(0)) == 1.0
    assert abs(float(f(50)) - 0.5) < 1e-6
    assert float(f(100)) == 0.0
    assert float(f(150)) == 0.0


def test_wsd_schedule_phases():
    f = OPT.wsd(1.0, 1000, warmup_frac=0.1, decay_frac=0.2, floor_frac=0.1)
    assert float(f(0)) < 0.02                      # warmup start
    assert abs(float(f(500)) - 1.0) < 1e-6         # stable
    assert float(f(999)) < 0.2                     # decayed
    # monotone within warmup
    assert float(f(10)) < float(f(50)) <= 1.0


def test_adam_quantized_state_converges_quadratic():
    """bf16/int8 moment storage must still drive the quadratic to ~0 —
    quantization noise changes the path, not whether adam works."""
    for dtype, tol in (("bfloat16", 1e-2), ("int8", 5e-2)):
        opt = OPT.adam(0.1, state_dtype=dtype)
        params = {"x": jnp.asarray([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(200):
            g = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
            upd, state = opt.update(g, state, params)
            params = OPT.apply_updates(params, upd)
        assert float(jnp.abs(params["x"]).max()) < tol, dtype


def test_adam_state_dtype_packs_bytes():
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
    n = 64 * 32 + 32
    sizes = {d: OPT.state_nbytes(OPT.adam(0.1, state_dtype=d).init(params))
             for d in ("float32", "bfloat16", "int8")}
    assert sizes["float32"] == 4 + 2 * 4 * n       # step + f32 mu + f32 nu
    assert sizes["bfloat16"] == 4 + 2 * 2 * n      # exactly halved moments
    # int8: mu as int8 q + f32 scale per tensor, nu stays bf16
    assert sizes["int8"] == 4 + (n + 2 * 4) + 2 * n
    import pytest
    with pytest.raises(ValueError):
        OPT.adam(0.1, state_dtype="fp8")


def test_adam_weight_decay():
    opt = OPT.adamw(0.1, weight_decay=0.5)
    params = {"x": jnp.asarray([1.0])}
    state = opt.init(params)
    g = {"x": jnp.asarray([0.0])}
    upd, state = opt.update(g, state, params)
    assert float(upd["x"][0]) < 0                  # pure decay pulls down


# ---------------------------------------------------------------------------
# quantized optimizer state
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = MINI.with_(n_layers=2, layer_pattern=("attn",) * 2)
    train = make_classification(800, 20, cfg.vocab_size, 32, seed=1)
    test = make_classification(200, 20, cfg.vocab_size, 32, seed=2)
    parts = iid_partition(train.labels, 12, seed=0)
    return cfg, train, test, parts


def _run(setup, rounds, **fc_kw):
    """A MINI FedLoRA cohort run."""
    cfg, train, test, parts = setup
    strat = all_strategies(rounds=rounds)["fedlora"]
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=rounds, clients_per_round=4, batch_size=16,
                   max_local_batches=2, eval_every=4, lr=3e-3,
                   runner="cohort", **fc_kw)
    return run_federated(model, strat, parts, train, test, fc)


def test_quantized_opt_state_bytes_on_mini(setup):
    """bf16 moments halve adam's per-client state on the MINI adapter tree;
    int8 (mu int8 + nu bf16) cuts it further.  The step counter is the only
    non-moment leaf, so 'halved' is exact up to its 4 bytes."""
    cfg, *_ = setup
    model = Model(cfg, peft=all_strategies()["fedlora"].peft, unroll=True)
    _, trainable = model.init(jax.random.key(0))
    n_par = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(trainable))
    sizes = {d: OPT.state_nbytes(OPT.adam(1e-3, state_dtype=d)
                                 .init(trainable))
             for d in ("float32", "bfloat16", "int8")}
    assert sizes["float32"] == 4 + 2 * 4 * n_par
    assert sizes["bfloat16"] == 4 + 2 * 2 * n_par
    assert sizes["int8"] < sizes["bfloat16"] < sizes["float32"]
    assert sizes["bfloat16"] <= sizes["float32"] / 2 + 4


def test_quantized_opt_state_converges(setup):
    """A MINI cohort run with bf16 (and int8) moment storage tracks the f32
    loss trajectory within tolerance — quantization noise must not change
    whether training works, only the state footprint."""
    h32 = _run(setup, rounds=4)
    for dtype, rtol in (("bfloat16", 0.05), ("int8", 0.15)):
        hq = _run(setup, rounds=4, opt_state_dtype=dtype)
        for a, b in zip(h32["rounds"], hq["rounds"]):
            assert np.isfinite(b.loss)
            np.testing.assert_allclose(b.loss, a.loss, rtol=rtol)
        # byte/clock accounting is storage-independent
        assert hq["comm_gb"] == h32["comm_gb"]
        assert hq["sim_time_s"] == h32["sim_time_s"]
