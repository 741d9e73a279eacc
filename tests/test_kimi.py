"""Kimi K2 (MLA with YaRN, a dense layer, sigmoid-routed held experts beside a
shared expert) against the benchmark's plain reference
(``bench/configs/kimi_k2.py``), on seeded random weights at smoke widths,
float32 on the CPU."""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.kimi_k2_1t_a32b import CHIP, CHIP_SMOKE, SMOKE
from repro.data.synthetic import make_classification
from repro.federated.baselines import all_strategies
from repro.federated.partition import dirichlet_partition
from repro.federated.server import FedConfig, run_federated
from repro.models import Model
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.pytree import materialize

ROOT = Path(__file__).resolve().parents[1]
B, S = 2, 16


def _reference():
    path = ROOT / "bench" / "configs" / "kimi_k2.py"
    spec = importlib.util.spec_from_file_location("kimi_k2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _spec(**kw):
    """The configuration file at its smoke widths (the reference's cfg)."""
    spec = json.loads((ROOT / "bench" / "configs" / "kimi_k2.json")
                      .read_text())
    return {**spec, **spec["smoke"], **kw}


def _live(tree, key, scale=0.3):
    """Adapters with E ≠ 0, so that every adapter contributes."""
    return jax.tree.map(
        lambda x: x + scale * jax.random.normal(key, x.shape, x.dtype), tree)


def _masks(meta):
    """Rank masks with the last rank of every module off."""
    return {k: jnp.arange(v["A"].shape[-2]) < v["A"].shape[-2] - 1
            for k, v in meta.items()}


def _close(a, b, rtol=2e-5, atol=2e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                                   atol=atol)


def test_yarn_frequencies_follow_the_published_form():
    from repro.models import layers as L
    f = L.rope_freqs(CHIP, CHIP.qk_rope_head_dim)
    base = 1.0 / 50_000.0 ** (np.arange(0, 64, 2) / 64)
    # β_fast = β_slow = 1 over 4,096 positions: dims below the correction
    # dimension keep θ's frequency, those above it are divided by 32
    corr = 64 * np.log(4096 / (2 * np.pi)) / (2 * np.log(50_000.0))
    lo = int(np.floor(corr))
    np.testing.assert_allclose(f[:lo + 1], base[:lo + 1])
    np.testing.assert_allclose(f[lo + 1:], base[lo + 1:] / 32)
    np.testing.assert_allclose(f, REF.rope_freqs(_spec(**{
        "qk_rope_head_dim": 64, "rope_theta": 50_000,
        "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096}})),
        rtol=1e-6)
    assert MLA.softmax_scale(CHIP) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)


def test_mla_forward_and_adapter_gradients_match_reference():
    cfg, spec = SMOKE, _spec()
    p = materialize(MLA.mla_meta(cfg), jax.random.key(0))
    ad_meta = MLA.mla_adapter_meta(cfg, "bea")
    ad = _live(materialize(ad_meta, jax.random.key(1)), jax.random.key(2))
    m = _masks(ad_meta)
    x = jax.random.normal(jax.random.key(3), (B, S, cfg.d_model))
    r = jax.random.normal(jax.random.key(4), (B, S, cfg.d_model))

    def prog(ad):
        return MLA.mla(p, x, cfg, ad=ad, masks=m)[0]

    def ref(ad):
        return REF.mla(p, ad, m, x, spec, "ref")

    _close(prog(ad), ref(ad))
    # gradients of order 10: float32 summation order, well below 1e-5 of it
    _close(jax.grad(lambda a: (prog(a) * r).sum())(ad),
           jax.grad(lambda a: (ref(a) * r).sum())(ad), rtol=1e-4, atol=1e-4)


def _moe_params(cfg, key, bias_scale=0.1):
    p = materialize(MOE.held_meta(cfg), key)
    b = p["router"]["b"]
    p["router"]["b"] = bias_scale * jax.random.normal(key, b.shape)
    return p


def _moe_ref(p, ad, m, x, cfg, spec):
    return REF.moe(p, ad, m, x, {**spec, "num_experts_per_tok": cfg.top_k,
                                 "router_experts": cfg.n_experts,
                                 "experts_held": cfg.held[1],
                                 "expert_shard": cfg.expert_shard}, "ref")


def test_moe_layer_matches_reference_with_selection_and_shared_expert():
    cfg, spec = SMOKE.with_(n_experts=8, top_k=3, experts_held=4,
                            expert_shard=1), _spec()
    p = _moe_params(cfg, jax.random.key(0), bias_scale=0.5)
    ad_meta = MOE.moe_adapter_meta(cfg, "bea")
    assert set(ad_meta) == {"shared"}          # routed experts: frozen
    ad = _live(materialize(ad_meta, jax.random.key(1)), jax.random.key(2))
    m = {"shared": _masks(ad_meta["shared"])}
    x = jax.random.normal(jax.random.key(3), (B, S, cfg.d_model))
    y, counts = MOE.held_apply(p, x, cfg, ad=ad, masks=m)
    _close(y, _moe_ref(p, ad, m, x, cfg, spec), rtol=1e-4, atol=1e-5)
    # the pairs on experts 4–7 of 8, three a token
    ids, w = MOE.route(x.reshape(-1, cfg.d_model), p["router"], cfg)
    held = int(((ids >= 4) & (ids < 8)).sum())
    assert counts["moe_routed"] == held > 0
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scale, rtol=1e-5)
    # the bias picks the experts, the sigmoid scores weigh them
    s = jax.nn.sigmoid(x.reshape(-1, cfg.d_model) @ p["router"]["w"])
    top = np.argsort(-(s + p["router"]["b"]), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(top, -1))
    np.testing.assert_allclose(
        w, np.take_along_axis(s, ids, -1)
        / np.take_along_axis(s, ids, -1).sum(-1, keepdims=True)
        * cfg.routed_scale, rtol=1e-5)


def test_moe_drops_no_token_when_the_router_sends_every_token_to_one():
    """Every token picks held expert 0 (and one more held expert): twice as
    many pairs as tokens, all computed."""
    cfg, spec = SMOKE.with_(n_experts=4, top_k=2, experts_held=2), _spec()
    p = _moe_params(cfg, jax.random.key(0))
    p["router"]["b"] = jnp.array([50.0, 25.0, 0.0, 0.0])
    x = jax.random.normal(jax.random.key(3), (B, S, cfg.d_model))
    y, counts = MOE.held_apply(p, x, cfg)
    assert counts["moe_routed"] == B * S * 2
    ref = _moe_ref(p, {}, {}, x, cfg, spec)
    _close(y, ref, rtol=1e-4, atol=1e-5)
    # and its gradient through the experts and the routing weights
    r = jax.random.normal(jax.random.key(4), x.shape)
    _close(jax.grad(lambda x: (MOE.held_apply(p, x, cfg)[0] * r).sum())(x),
           jax.grad(lambda x: (_moe_ref(p, {}, {}, x, cfg, spec) * r).sum())(
               x), rtol=1e-4, atol=1e-5)


def test_unwritten_rows_of_the_grouped_products_reach_nothing(monkeypatch):
    """The TPU's grouped product leaves rows past the groups unwritten, in
    its output and in its transpose's: fill them with NaN, and the layer
    and its gradients (into the tokens and the routing weights) stay the
    reference's."""
    cfg, spec = SMOKE.with_(n_experts=4, top_k=2, experts_held=2), _spec()
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def ragged(x, w, gs):
        out = real(x, w, gs)
        return jnp.where((jnp.arange(x.shape[0]) < gs.sum())[:, None], out,
                         jnp.nan)

    def fwd(x, w, gs):
        return ragged(x, w, gs), (w, gs)

    def bwd(res, ct):
        w, gs = res
        dx = jax.vjp(lambda x: real(x, w, gs), jnp.zeros(
            ct.shape[:1] + w.shape[1:2], ct.dtype))[1](ct)[0]
        dx = jnp.where((jnp.arange(ct.shape[0]) < gs.sum())[:, None], dx,
                       jnp.nan)
        return dx, None, None

    ragged.defvjp(fwd, bwd)
    monkeypatch.setattr(MOE.jax.lax, "ragged_dot",
                        lambda x, w, gs: ragged(x, w, gs))
    p = _moe_params(cfg, jax.random.key(0), bias_scale=0.3)
    x = jax.random.normal(jax.random.key(3), (B, S, cfg.d_model))
    r = jax.random.normal(jax.random.key(4), x.shape)
    y, _ = MOE.held_apply(p, x, cfg)
    _close(y, _moe_ref(p, {}, {}, x, cfg, spec), rtol=1e-4, atol=1e-5)
    _close(jax.grad(lambda x: (MOE.held_apply(p, x, cfg)[0] * r).sum())(x),
           jax.grad(lambda x: (_moe_ref(p, {}, {}, x, cfg, spec) * r).sum())(
               x), rtol=1e-4, atol=1e-5)


def test_four_expert_shares_add_up_to_the_uncut_layer():
    """Four chips holding 4 of 16 experts each: their partial outputs, the
    shared expert counted once, are the whole layer."""
    whole = SMOKE.with_(n_experts=16, top_k=4, experts_held=0)
    p = _moe_params(whole, jax.random.key(0), bias_scale=0.3)
    x = jax.random.normal(jax.random.key(3), (B, S, whole.d_model))
    y_all, _ = MOE.held_apply(p, x, whole)
    shared = MOE.MLP.mlp_apply(p["shared"], x, whole)
    parts = []
    for sh in range(4):
        cfg = whole.with_(experts_held=4, expert_shard=sh)
        ps = dict(p, **{k: {"w": p[k]["w"][4 * sh:4 * sh + 4]}
                        for k in ("w1", "w3", "w2")})
        parts.append(MOE.held_apply(ps, x, cfg)[0] - shared)
    _close(sum(parts) + shared, y_all, rtol=1e-4, atol=1e-5)
    _close(y_all, _moe_ref(p, {}, {}, x, whole, _spec()), rtol=1e-4,
           atol=1e-5)


def test_held_count_must_divide_the_experts():
    with pytest.raises(ValueError, match="divide"):
        CHIP.with_(experts_held=7)
    with pytest.raises(ValueError, match="divide"):
        CHIP.with_(expert_shard=48)
    assert CHIP.held == (0, 8) and CHIP.n_experts == 384


def _smoke_model():
    model = Model(CHIP_SMOKE, peft="bea", unroll=True)
    base, tr = model.init(jax.random.key(0))
    base = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.1 * jax.random.normal(jax.random.key(5), x.shape)
        if str(getattr(path[-1], "key", "")) == "b" else x, base)
    tr = dict(tr, adapters=_live(tr["adapters"], jax.random.key(1), 0.1))
    masks = jax.tree.map(lambda m: m.at[-1].set(False), model.init_masks())
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, CHIP_SMOKE.vocab_size, (4, S)))
    labels = jnp.asarray(rng.integers(0, 20, 4))
    return model, base, tr, masks, toks, labels


def test_smoke_model_loss_and_adapter_gradients_match_reference():
    model, base, tr, masks, toks, labels = _smoke_model()
    spec = _spec()

    def prog(tr):
        return model.cls_loss(base, tr, masks,
                              {"tokens": toks, "labels": labels},
                              remat=True)[1][0]

    def ref(tr):
        return REF.cls_loss(tr, base, masks, toks, labels,
                            jnp.ones(4, jnp.float32), spec)

    np.testing.assert_allclose(prog(tr), ref(tr), rtol=1e-5)
    _close(jax.grad(prog)(tr), jax.grad(ref)(tr), rtol=1e-3, atol=1e-6)
    counts = model.cls_loss(base, tr, masks,
                            {"tokens": toks, "labels": labels})[1][2]
    assert set(counts) == {"moe_routed", "moe_rows"}
    assert 0 < counts["moe_routed"] <= counts["moe_rows"]


def test_reference_refuses_a_width_the_file_does_not_state():
    model, base, tr, masks, toks, labels = _smoke_model()
    attn = base["dec"]["tail"]["t1"]["attn"]
    bad = jax.tree.map(lambda x: x, base)
    bad["dec"]["tail"]["t1"]["attn"] = dict(
        attn, wkv_b={"w": attn["wkv_b"]["w"][:, :, 1:]})
    with pytest.raises(ValueError, match=r"dec\.tail\.t1\.attn\.wkv_b\.w"):
        REF.check_widths(bad, tr, _spec())
    with pytest.raises(ValueError, match="experts_held"):
        REF.check_widths(base, tr, _spec(n_routed_experts=4))


def test_cohort_program_matches_sequential_loop_on_the_moe_model():
    cfg = CHIP_SMOKE
    train = make_classification(240, 20, cfg.vocab_size, S, seed=1)
    test = make_classification(40, 20, cfg.vocab_size, S, seed=2)
    parts = dirichlet_partition(train.labels, 8, alpha=0.1, seed=0)

    def run(runner):
        strat = all_strategies(rounds=3)["fedara"]
        strat.total_rounds, strat.warmup_rounds = 3, 1
        strat.final_rounds_frac = 0.34
        fc = FedConfig(rounds=3, clients_per_round=3, max_local_batches=2,
                       batch_size=8, eval_every=3, lr=3e-3, runner=runner)
        return run_federated(Model(cfg, peft=strat.peft, unroll=True), strat,
                             parts, train, test, fc)

    h_seq, h_coh = run("seq"), run("cohort")
    for a, b in zip(h_seq["rounds"], h_coh["rounds"]):
        assert (a.down_bytes, a.up_bytes, a.live_ranks) == \
            (b.down_bytes, b.up_bytes, b.live_ranks)
        np.testing.assert_allclose(a.loss, b.loss, rtol=2e-4, atol=2e-4)
    for x, y in zip(jax.tree.leaves(h_seq["masks"]),
                    jax.tree.leaves(h_coh["masks"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_cohort_round_counts_the_held_experts_pairs(monkeypatch):
    from repro import obs
    cfg = CHIP_SMOKE
    train = make_classification(160, 20, cfg.vocab_size, S, seed=1)
    parts = dirichlet_partition(train.labels, 6, alpha=0.5, seed=0)
    strat = all_strategies(rounds=2)["fedlora"]
    fc = FedConfig(rounds=2, clients_per_round=3, max_local_batches=2,
                   batch_size=8, eval_every=2, lr=3e-3, runner="cohort")
    tr = obs.configure(None, health=False, profile=False)
    try:
        run_federated(Model(cfg, peft=strat.peft, unroll=True), strat, parts,
                      train, train, fc)
        spans = [e for e in tr.events() if e.get("type") == "span"]
    finally:
        obs.disable()
    pulls = [e["attrs"] for e in spans if e["name"] == "cohort.pull"]
    slots = [e["attrs"]["slots"] for e in spans
             if e["name"] == "cohort_dispatch"]
    assert len(pulls) == 2
    for a, n in zip(pulls, slots):
        # each lane-step, idle lanes included, routes (batch × seq) tokens,
        # top_k pairs each, and the held experts take some of them
        assert 0 < a["moe_routed"] <= n * 8 * S * cfg.top_k
        assert a["moe_routed"] <= a["moe_rows"]
        assert a["moe_rows"] % MOE.RAGGED_TILE == 0
