"""fedsim: cohort-vs-sequential parity, codec round-trip/error-feedback
properties, seeded-async determinism, and the shard_map path on 8 faked host
devices (subprocess, like test_moe_parallel)."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from tests._hyp import given, settings, st

from repro.configs.distilbert import MINI
from repro.data.synthetic import make_classification
from repro.federated.baselines import all_strategies
from repro.federated.partition import dirichlet_partition
from repro.federated.server import FedConfig, run_federated
from repro.fedsim import transport as T
from repro.fedsim import cohort as CH
from repro.fedsim.cohort import client_batch_rng
from repro.models import Model


@pytest.fixture(scope="module")
def setup():
    cfg = MINI.with_(n_layers=2, layer_pattern=("attn",) * 2)
    train = make_classification(600, 20, cfg.vocab_size, 32, seed=1)
    test = make_classification(200, 20, cfg.vocab_size, 32, seed=2)
    parts = dirichlet_partition(train.labels, 10, alpha=0.1, seed=0)
    return cfg, train, test, parts


def _run(setup, runner, strategy="fedara", **fc_kw):
    cfg, train, test, parts = setup
    rounds = fc_kw.pop("rounds", 3)
    strat = all_strategies(rounds=rounds)[strategy]
    if hasattr(strat, "total_rounds"):
        strat.total_rounds = rounds
        strat.warmup_rounds = 1
        strat.final_rounds_frac = 0.34
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(**{"clients_per_round": 3, "max_local_batches": 3,
                      **fc_kw}, rounds=rounds, batch_size=16,
                   eval_every=rounds, lr=3e-3, runner=runner)
    return run_federated(model, strat, parts, train, test, fc)


# ---------------------------------------------------------------------------
# cohort ↔ sequential parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["fedara", "fedlora", "ffa_lora"])
def test_cohort_matches_sequential_oracle(setup, strategy):
    """A MINI run: same per-round losses (within fp tolerance from
    batched-vs-looped XLA fusion), identical masks and byte accounting.
    FedARA re-prunes every round; FFA-LoRA freezes A through the
    optimizer gate."""
    h_seq = _run(setup, "seq", strategy=strategy)
    h_coh = _run(setup, "cohort", strategy=strategy)
    for a, b in zip(h_seq["rounds"], h_coh["rounds"]):
        assert a.down_bytes == b.down_bytes
        assert a.up_bytes == b.up_bytes
        assert a.live_ranks == b.live_ranks
        assert a.dead_modules == b.dead_modules
        np.testing.assert_allclose(a.loss, b.loss, rtol=2e-4, atol=2e-4)
    for x, y in zip(jax.tree.leaves(h_seq["masks"]),
                    jax.tree.leaves(h_coh["masks"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_allclose(h_seq["final_acc"], h_coh["final_acc"],
                               atol=0.02)
    assert h_coh["sim_time_s"] > 0.0


def test_scheduled_cohort_matches_sequential_oracle(setup):
    """Uneven clients on fewer lanes than slots: the cohort program packs
    the real client-steps (fewer lane-steps than the padded rectangle's
    C × T) and still matches the oracle's losses, masks and bytes."""
    from repro import obs
    kw = dict(clients_per_round=5, max_local_batches=4)
    h_seq = _run(setup, "seq", **kw)
    tr = obs.configure(None, health=False, profile=False)
    try:
        h_coh = _run(setup, "cohort", **kw)
        evs = tr.events()
    finally:
        obs.disable()
    spans = [e for e in evs if e.get("type") == "span"]
    rounds = {e["id"] for e in spans if e["name"] == "round"}
    cnt = {e["parent"]: e["attrs"] for e in spans
           if e["name"] == "cohort_dispatch"}
    assert set(cnt) == rounds and len(rounds) == 3
    parts, fc = setup[3], FedConfig(batch_size=16, **kw)
    lanes = CH.lane_count(parts, fc, 5, 1)
    assert lanes < 5
    by_id = {e["id"]: e for e in spans}

    def round_of(e):
        while e["id"] not in rounds:
            e = by_id[e["parent"]]
        return e["id"]
    for rid, c in cnt.items():
        real = sum(CH.cohort_steps(len(parts[e["attrs"]["cid"]]), fc)
                   for e in spans if e["name"] == "client"
                   and round_of(e) == rid)
        assert c["steps"] == real > 0
        assert c["slots"] % lanes == 0
        assert c["steps"] <= c["slots"] < 5 * 4     # fewer than C × T
    for a, b in zip(h_seq["rounds"], h_coh["rounds"]):
        assert a.down_bytes == b.down_bytes
        assert a.up_bytes == b.up_bytes
        assert a.live_ranks == b.live_ranks
        assert a.dead_modules == b.dead_modules
        np.testing.assert_allclose(a.loss, b.loss, rtol=2e-4, atol=2e-4)
    for x, y in zip(jax.tree.leaves(h_seq["masks"]),
                    jax.tree.leaves(h_coh["masks"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_cohort_outputs_are_freed_before_the_next_dispatch(setup,
                                                         monkeypatch):
    """A round's per-client params, grads and loss stacks live on the
    device until the host has pulled them, and no longer: none of them is
    alive when the next round's cohort program launches (only the on-device
    FedAvg, the next broadcast's source, may be)."""
    import weakref
    from repro.fedsim import cohort as CH
    real = CH.make_cohort_fn
    held = []

    def make(*a, **kw):
        fn = real(*a, **kw)

        def launch(*args):
            held.append(sum(r() is not None for r in refs))
            refs.clear()
            pc, gc, lc, mc, avg, _ = out = fn(*args)
            refs.extend(weakref.ref(x) for x in
                        jax.tree.leaves((pc, gc, lc, mc)))
            return out
        return launch
    refs: list = []
    monkeypatch.setattr(CH, "make_cohort_fn", make)
    _run(setup, "cohort", rounds=3)
    assert len(held) == 3 and held == [0, 0, 0]


def _traced_run(setup, parts, strat, **fc_kw):
    """A traced 3-round cohort run on ``parts``, like ``_run``; returns
    (history, events, metrics, upload-program inputs (pc, bc, masks) and
    cohort of each round)."""
    from repro import obs
    from repro.federated import server as SV
    cfg, train, test, _ = setup
    real_programs, real_build = SV.round_programs, CH.build_cohort
    seen, cohorts = [], []

    def programs(*a, **kw):
        rp = real_programs(*a, **kw)
        if rp.upload is None:
            return rp

        def upload(pc, gc, bc, masks):
            seen.append(jax.device_get((pc, bc, masks)))
            return rp.upload(pc, gc, bc, masks)
        return rp._replace(upload=upload)

    def build(*a, **kw):
        cohorts.append(real_build(*a, **kw))
        return cohorts[-1]

    strat.total_rounds, strat.warmup_rounds = 3, 1
    strat.final_rounds_frac = 0.34
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(**{"clients_per_round": 3, "max_local_batches": 3,
                      **fc_kw}, rounds=3, batch_size=16, eval_every=3,
                   lr=3e-3, runner="cohort")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(SV, "round_programs", programs)
        mp.setattr(CH, "build_cohort", build)
        tr = obs.configure(None, health=False, profile=False)
        try:
            h = run_federated(model, strat, parts, train, test, fc)
            evs = list(tr.events())
        finally:
            obs.disable()
    return h, evs, tr.metrics, seen, cohorts, fc


def _uploads(evs):
    """Per round, in order: (clients, sub-batch clients, upload counters,
    cohort.pull bytes)."""
    spans = [e for e in evs if e.get("type") == "span"]
    rounds = [e["id"] for e in spans if e["name"] == "round"]
    by_id = {e["id"]: e for e in spans}

    def round_of(e):
        while e["parent"] not in rounds:
            e = by_id[e["parent"]]
        return e["parent"]
    out = {r: [0, 0, None, None] for r in rounds}
    for e in spans:
        if e["name"] in ("client", "fallback"):
            out[round_of(e)][e["name"] == "fallback"] += 1
        elif e["name"] == "upload":
            out[round_of(e)][2] = e["attrs"]
        elif e["name"] == "cohort.pull":
            out[round_of(e)][3] = e["attrs"]["nbytes"]
    return [out[r] for r in rounds]


@pytest.fixture(scope="module")
def device_run(setup):
    """FedARA on clients that all fill a batch: identity wire, no
    sub-batch client, so every round aggregates on the device."""
    from repro.federated.partition import iid_partition
    parts = iid_partition(setup[1].labels, 10, seed=0)
    assert min(len(p) for p in parts) >= 16
    return _traced_run(setup, parts, all_strategies(rounds=3)["fedara"])


def test_device_aggregated_rounds_pull_no_slot_tree(device_run):
    """No round builds a delta tree on the host, every client's votes come
    from the upload program, and ``cohort.pull`` copies the loss stacks,
    scores and norms alone."""
    h, evs, _, seen, cohorts, fc = device_run
    n_ranks = sum(np.asarray(m).size for m in jax.tree.leaves(h["masks"]))
    C, T = 3, fc.max_local_batches
    rounds = _uploads(evs)
    assert len(rounds) == 3 and len(seen) == 3
    for n, small, up, nbytes in rounds:
        assert n == 3 and small == 0
        assert up == {"device_scores": 3, "host_deltas": 0}
        assert nbytes == 4 * C * (2 * T + n_ranks + 1)


def test_device_reduced_uploads_account_as_the_host_encode(device_run):
    """The ``encode`` events, ``pipeline.up_bytes`` and ``pipeline.updates``
    of a device-aggregated run are those the host's ``encode`` gives on
    the deltas it no longer builds: same clients, bytes, norms."""
    from repro.fedsim import pipeline as PL
    h, evs, metrics, seen, cohorts, fc = device_run
    pipe = PL.UploadPipeline(fc, all_strategies(rounds=3)["fedara"])
    want = []
    for (pc, bc, masks), cohort in zip(seen, cohorts):
        masks_np = jax.tree.map(np.asarray, masks)
        for cid, i in zip(cohort.cids, cohort.slots):
            delta = PL.delta_tree(CH.slice_client(pc, i), bc)
            enc = pipe.encode(PL.ClientUpdate(cid, delta, 1.0), masks_np)
            want.append((cid, enc.nbytes, enc.norm))
    got = [(e["attrs"]["cid"], e["attrs"]["nbytes"], e["attrs"]["norm"])
           for e in evs if e.get("type") == "event" and e["name"] == "encode"]
    assert [g[:2] for g in got] == [w[:2] for w in want]
    np.testing.assert_allclose([g[2] for g in got], [w[2] for w in want],
                               rtol=1e-5)
    labels = {"codec": "identity", "stage": "stage2"}
    assert metrics.counter("pipeline.up_bytes", **labels).value \
        == sum(w[1] for w in want) == sum(r.up_bytes for r in h["rounds"])
    assert metrics.counter("pipeline.updates", **labels).value == len(want)


def test_sensitivity_scores_on_the_host(setup):
    """Sensitivity's EMA runs across clients, so the upload program scores
    nothing: every vote is the host's, from the pulled params and grads."""
    strat = all_strategies(rounds=3)["fedara"]
    strat.importance = "sensitivity"
    h, evs, *_ = _traced_run(setup, setup[3], strat)
    rounds = _uploads(evs)
    assert len(rounds) == 3
    for n, small, up, _ in rounds:
        assert up == {"device_scores": 0,
                      "host_deltas": n if small else 0}
    assert all(r.live_ranks > 0 for r in h["rounds"])


def test_cohort_simulates_stragglers_and_dropout(setup):
    h = _run(setup, "cohort", strategy="fedlora", dropout=0.3,
             straggler=0.5, event_seed=3)
    h0 = _run(setup, "cohort", strategy="fedlora")
    # stragglers stretch the simulated clock; history stays finite
    assert h["sim_time_s"] > h0["sim_time_s"]
    assert np.isfinite(h["final_acc"])


def test_evaluate_lm_returns_mean_nll(setup):
    """task='lm' evaluate() must return a mean NLL (≈ log V for a random
    base), not the old correct-count/label-count ratio (which sat in
    [0, 1/B] and read as a bogus accuracy)."""
    from repro.federated.server import evaluate
    cfg, train, test, parts = setup
    model = Model(cfg.with_(n_classes=0), peft="bea", unroll=True)
    base, trainable = model.init(jax.random.key(0))
    fc = FedConfig(task="lm", batch_size=8, eval_batches=2)
    nll = evaluate(model, base, trainable, None, test, fc)
    # a random base cannot beat the uniform predictor (NLL = log V); the old
    # bug divided a batch-mean NLL by the label count → a value ≤ ~1
    assert np.isfinite(nll)
    assert nll > 0.9 * np.log(cfg.vocab_size)


def test_evaluate_traces_its_step_once(setup):
    """Every evaluation of a run reuses one jitted eval step: an eval round
    after the first traces and compiles nothing (a benchmark window holds
    eval rounds)."""
    from repro.federated import client as CL
    from repro.federated.server import evaluate
    cfg, train, test, parts = setup
    model = Model(cfg, peft="bea", unroll=True)
    base, trainable = model.init(jax.random.key(0))
    fc = FedConfig(batch_size=8, eval_batches=2)
    accs = [evaluate(model, base, trainable, None, test, fc)
            for _ in range(3)]
    ev = CL.make_eval_step(model, fc.task)
    assert ev is CL.make_eval_step(model, fc.task)
    assert ev._cache_size() == 1
    assert accs[0] == accs[1] == accs[2]


def test_batch_rng_stream_incorporates_seed():
    a = client_batch_rng(0, 2, 3).integers(1 << 30, size=4)
    b = client_batch_rng(1, 2, 3).integers(1 << 30, size=4)
    assert not np.array_equal(a, b)


# ---------------------------------------------------------------------------
# transport codecs
# ---------------------------------------------------------------------------

def _wire(n, seed=0, scale=3.0):
    return (np.random.default_rng(seed).standard_normal(n) * scale
            ).astype(np.float32)


def test_int8_roundtrip_error_bound():
    w = _wire(1000)
    codec = T.Int8Block(block=128)
    payload, nbytes = codec.encode(w)
    dec = codec.decode(payload, w.size)
    # ≤ half a quantization step per element, per block
    for blk in range(0, w.size, 128):
        sl = slice(blk, blk + 128)
        step = np.abs(w[sl]).max() / 127.0
        assert np.abs(dec[sl] - w[sl]).max() <= step / 2 + 1e-7
    assert nbytes < w.size * 4 + T.HEADER_BYTES          # beats f32
    assert nbytes == w.size + 4 * 8 + T.HEADER_BYTES     # int8 + 8 scales


def test_topk_keeps_largest():
    w = _wire(500)
    codec = T.TopK(frac=0.1)
    payload, nbytes = codec.encode(w)
    dec = codec.decode(payload, w.size)
    k = 50
    assert (dec != 0).sum() <= k
    top = np.argsort(-np.abs(w))[:k]
    np.testing.assert_allclose(dec[top], w[top])
    assert nbytes == k * 8 + T.HEADER_BYTES


def test_error_feedback_compensates():
    """Cumulative decoded signal tracks the cumulative true signal with a
    bounded (non-accumulating) error — the EF invariant."""
    ef = T.ErrorFeedback(T.TopK(frac=0.05))
    rng = np.random.default_rng(1)
    tot_true = np.zeros(200, np.float32)
    tot_sent = np.zeros(200, np.float32)
    for _ in range(50):
        w = rng.standard_normal(200).astype(np.float32)
        dec, _ = ef.roundtrip("c", w)
        tot_true += w
        tot_sent += dec
    resid = ef._resid["c"]
    np.testing.assert_allclose(tot_sent + resid, tot_true, atol=1e-3)
    # plain (no-EF) top-k leaves most of the signal behind permanently
    plain = np.zeros(200, np.float32)
    codec = T.TopK(frac=0.05)
    rng = np.random.default_rng(1)
    for _ in range(50):
        w = rng.standard_normal(200).astype(np.float32)
        plain += codec.decode(codec.encode(w)[0], 200)
    assert np.abs(tot_sent - tot_true).mean() < \
        np.abs(plain - tot_true).mean()


def test_codec_registry():
    assert T.make_codec("int8", block=64).block == 64
    assert T.make_codec("signsgd").name == "signsgd"
    assert T.make_codec("powersgd").name == "powersgd"
    with pytest.raises(ValueError):
        T.make_codec("nope")


@given(st.integers(min_value=1, max_value=2048),
       st.integers(min_value=0, max_value=1 << 30))
@settings(max_examples=20, deadline=None)
def test_int8_roundtrip_property(n, seed):
    w = _wire(n, seed=seed % (1 << 16))
    codec = T.Int8Block(block=256)
    dec = codec.decode(codec.encode(w)[0], n)
    step = max(np.abs(w).max() / 127.0, 1e-12)
    assert np.abs(dec - w).max() <= step / 2 + 1e-7
    assert dec.shape == w.shape


@given(st.integers(min_value=1, max_value=512),
       st.floats(min_value=0.01, max_value=1.0))
@settings(max_examples=20, deadline=None)
def test_topk_roundtrip_property(n, frac):
    w = _wire(n, seed=n)
    codec = T.TopK(frac=frac)
    payload, _ = codec.encode(w)
    dec = codec.decode(payload, n)
    nz = dec != 0
    np.testing.assert_allclose(dec[nz], w[nz])
    # every transmitted magnitude ≥ every dropped magnitude
    if nz.any() and (~nz).any():
        assert np.abs(w[nz]).min() >= np.abs(w[~nz]).max() - 1e-6


def test_flatten_update_roundtrip(setup):
    cfg, *_ = setup
    model = Model(cfg, peft="bea", unroll=True)
    _, trainable = model.init(jax.random.key(0))
    masks_np = jax.tree.map(np.asarray, model.init_masks())
    wire = T.flatten_update(trainable, masks_np)
    back = T.unflatten_update(wire, trainable, masks_np)
    for a, b in zip(jax.tree.leaves(trainable), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=1e-6)


def test_seq_oracle_prices_time_from_encoded_bytes(setup):
    """The sequential oracle's simulated clock must go through the
    per-device-class transport Link on *encoded* bytes — `--codec int8`
    shrinks simulated time, not just byte counts (it used to be priced off
    the flat 1 MB/s federated/devices.py constant, codec-blind)."""
    h_f32 = _run(setup, "seq", strategy="fedlora", rounds=2)
    h_int8 = _run(setup, "seq", strategy="fedlora", rounds=2, codec="int8")
    assert h_int8["comm_gb"] < h_f32["comm_gb"] / 3      # ≈4× fewer bytes
    assert 0 < h_int8["sim_time_s"] < h_f32["sim_time_s"]
    # compute time is identical, so the whole gap is transfer seconds of
    # the byte delta across the three Link classes — bounded by the slowest
    # (rpi5) and fastest (agx_orin) links end to end
    d_bytes = (h_f32["comm_gb"] - h_int8["comm_gb"]) * 1e9
    d_time = h_f32["sim_time_s"] - h_int8["sim_time_s"]
    assert d_time <= d_bytes / T.DEVICE_LINKS["rpi5"].bandwidth_bps + 1e-6


@given(st.integers(min_value=0, max_value=2048),
       st.integers(min_value=0, max_value=1 << 16))
@settings(max_examples=25, deadline=None)
def test_int8block_byte_formula_and_error_bound(n, seed):
    """Int8Block contract: bytes == n + 4·⌈n/block⌉ + header and per-block
    error ≤ absmax/254; empty and singleton wires must not crash."""
    w = _wire(n, seed=seed) if n else np.zeros((0,), np.float32)
    codec = T.Int8Block(block=128)
    payload, nbytes = codec.encode(w)
    nb = -(-n // 128)
    assert nbytes == (n + 4 * nb + T.HEADER_BYTES if n else T.HEADER_BYTES)
    dec = codec.decode(payload, n)
    assert dec.shape == w.shape
    for b0 in range(0, n, 128):
        sl = slice(b0, min(b0 + 128, n))
        bound = np.abs(w[sl]).max() / 254.0     # scale/2 = absmax/254
        assert np.abs(dec[sl] - w[sl]).max() <= bound + 1e-7


@given(st.integers(min_value=0, max_value=40))
@settings(max_examples=15, deadline=None)
def test_pack_int8_consistent_with_blockwise_codec(n):
    """core/comm.pack_int8 (per-tensor scale, the paper's §VIII variant) and
    fedsim.transport.Int8Block (per-block scales) agree on the contract:
    both reconstruct the CommPru wire within their documented half-step
    bounds, and pack_int8's payload is exactly wire_size bytes (4× f32)."""
    import jax
    from repro.core import adapters as AD
    from repro.pytree import materialize
    rng = np.random.default_rng(n)
    r = int(rng.integers(1, 6))
    tree = {"m": materialize(AD.adapter_meta(AD.BEA, int(rng.integers(1, 9)),
                                             int(rng.integers(1, 9)), r),
                             jax.random.key(n))}
    tree["m"]["E"] = rng.normal(size=r).astype(np.float32)
    masks = {"m": rng.random(r) > 0.4}         # may be empty or singleton
    from repro.core import comm as COMM
    wire = COMM.pack(tree, masks)
    q, scale = COMM.pack_int8(tree, masks)
    assert q.nbytes == wire.size               # 1 byte/param vs 4
    if wire.size:
        # per-tensor bound: global absmax/254 ≥ the per-block bound
        per_tensor = np.abs(q.astype(np.float32) * scale - wire).max()
        assert per_tensor <= np.abs(wire).max() / 254.0 + 1e-7
        blk = T.Int8Block(block=64)
        dec = blk.decode(blk.encode(wire)[0], wire.size)
        # per-block scales are ≤ the per-tensor scale, same half-step bound
        assert np.abs(dec - wire).max() <= np.abs(wire).max() / 254.0 + 1e-7
    back = COMM.unpack_int8(q, scale, tree, masks)
    for part in tree["m"]:                     # shapes survive the roundtrip
        assert np.asarray(back["m"][part]).shape == tree["m"][part].shape


def test_quantized_run_cuts_bytes(setup):
    h_f32 = _run(setup, "cohort", strategy="fedlora", rounds=2)
    h_int8 = _run(setup, "cohort", strategy="fedlora", rounds=2,
                  codec="int8")
    assert h_int8["comm_gb"] < h_f32["comm_gb"] / 3      # ≈4× smaller
    assert np.isfinite(h_int8["rounds"][-1].loss)


# ---------------------------------------------------------------------------
# async runner
# ---------------------------------------------------------------------------

def test_async_seeded_determinism(setup):
    kw = dict(strategy="fedlora", buffer_k=2, straggler=0.3, event_seed=7)
    h1 = _run(setup, "async", **kw)
    h2 = _run(setup, "async", **kw)
    assert h1["events"] == h2["events"]
    assert [l.loss for l in h1["rounds"]] == [l.loss for l in h2["rounds"]]
    assert h1["sim_time_s"] == h2["sim_time_s"]
    # a different event seed reshuffles straggler draws → different history
    h3 = _run(setup, "async", strategy="fedlora", buffer_k=2,
              straggler=0.3, event_seed=8)
    assert h1["events"] != h3["events"]


def test_async_staleness_is_tracked(setup):
    h = _run(setup, "async", strategy="fedlora", buffer_k=2)
    assert len(h["rounds"]) == 3
    # concurrency 2K keeps some clients a version behind
    assert any(l.staleness > 0 for l in h["rounds"])
    assert all(np.isfinite(l.loss) for l in h["rounds"])
    assert h["comm_gb"] > 0


# ---------------------------------------------------------------------------
# shard_map cohort axis on faked multi-device CPU
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from repro.configs.distilbert import MINI
    from repro.data.synthetic import make_classification
    from repro.federated.baselines import all_strategies
    from repro.federated.partition import iid_partition
    from repro.federated.server import FedConfig, run_federated
    from repro.models import Model

    cfg = MINI.with_(n_layers=1, layer_pattern=("attn",))
    train = make_classification(400, 10, cfg.vocab_size, 16, seed=1)
    test = make_classification(100, 10, cfg.vocab_size, 16, seed=2)
    parts = iid_partition(train.labels, 8, seed=0)

    def go(runner, clients_per_round=4, max_local_batches=2):
        strat = all_strategies(rounds=2)["fedlora"]
        model = Model(cfg, peft=strat.peft, unroll=True)
        fc = FedConfig(rounds=2, clients_per_round=clients_per_round,
                       batch_size=16, max_local_batches=max_local_batches,
                       eval_every=4, lr=3e-3, runner=runner)
        return run_federated(model, strat, parts, train, test, fc)

    import jax
    assert len(jax.devices()) == 8
    h_seq, h_coh = go("seq"), go("cohort")
    for a, b in zip(h_seq["rounds"], h_coh["rounds"]):
        np.testing.assert_allclose(a.loss, b.loss, rtol=2e-4, atol=2e-4)
        assert a.down_bytes == b.down_bytes

    # uneven clients, 16 a round: 8 lanes for 16 slots, one per device,
    # and the slots ordered so the devices' step sums balance
    from repro.fedsim import cohort as CH
    train = make_classification(600, 10, cfg.vocab_size, 16, seed=3)
    sizes = [64, 16, 16, 32, 16, 48, 16, 16, 32, 5,
             64, 16, 16, 16, 32, 16, 16, 48, 16, 16]
    order = np.random.default_rng(0).permutation(len(train.labels))
    parts = np.split(order[:sum(sizes)], np.cumsum(sizes)[:-1])
    fc = FedConfig(clients_per_round=16, batch_size=16, max_local_batches=4)
    assert CH.lane_count(parts, fc, 16, 8) == 8
    kw = dict(clients_per_round=16, max_local_batches=4)
    h_seq, h_coh = go("seq", **kw), go("cohort", **kw)
    for a, b in zip(h_seq["rounds"], h_coh["rounds"]):
        np.testing.assert_allclose(a.loss, b.loss, rtol=2e-4, atol=2e-4)
        assert a.down_bytes == b.down_bytes
        assert a.up_bytes == b.up_bytes
    print("SHARDED_COHORT_OK")
""")


def test_cohort_shard_map_8dev():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=".",
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "SHARDED_COHORT_OK" in r.stdout
