"""Fused multi-round cohort training (fedsim/fused.py).

Parity is the tentpole contract: on the fast path (identity codec, no
privacy, no ragged clients) the fused K-round scan must reproduce the eager
cohort runner's history *bit for bit* — same RNG streams, same float-order
byte/sim accounting, same eval cadence — because fusion only moves where
the same ops run, not what they compute.  The ISSUE's acceptance tolerance
is rtol 1e-3 on losses with exact bytes/ranks; these tests pin the stronger
property where it holds and the required tolerance everywhere.

Compile flatness is the perf contract: one XLA program per run.  Blocks are
padded to exactly K rounds, so every dispatch shares one shape signature
and the accounting in obs.profile must show a single backend compile across
all of them, none attributed to rounds ≥ 1.

Tracing is process-global; tests that enable it restore the null tracer in
a ``finally`` (same discipline as tests/test_obs.py).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro import optim as OPT
from repro.configs.distilbert import MINI
from repro.data.synthetic import make_classification
from repro.federated.baselines import all_strategies
from repro.federated.partition import iid_partition
from repro.federated.server import FedConfig, run_federated
from repro.fedsim import fused as FU
from repro.fedsim.cohort import build_cohort
from repro.models import Model
from repro.obs import export as E
from repro.obs import profile as P


@pytest.fixture(scope="module")
def setup():
    cfg = MINI.with_(n_layers=2, layer_pattern=("attn",) * 2)
    train = make_classification(800, 20, cfg.vocab_size, 32, seed=1)
    test = make_classification(200, 20, cfg.vocab_size, 32, seed=2)
    # IID so every client holds ≥ batch_size samples (the fast path's
    # no-ragged-clients precondition)
    parts = iid_partition(train.labels, 12, seed=0)
    return cfg, train, test, parts


def _run(setup, strategy="fedlora", rounds=8, **fc_kw):
    cfg, train, test, parts = setup
    strat = all_strategies(rounds=rounds)[strategy]
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=rounds, clients_per_round=4, batch_size=16,
                   max_local_batches=fc_kw.pop("max_local_batches", 2),
                   eval_every=4, lr=3e-3, runner="cohort", **fc_kw)
    return run_federated(model, strat, parts, train, test, fc)


def _eq_or_nan(a, b):
    return a == b or (a != a and b != b)


def _assert_history_parity(h_e, h_f):
    """Eager-vs-fused history: key-for-key equal dicts, exact byte/rank/sim
    accounting, bit-exact per-round losses (the fused program is the same
    float program, so the ISSUE's rtol 1e-3 is pinned at rtol 0)."""
    assert set(h_e.keys()) == set(h_f.keys())
    assert len(h_e["rounds"]) == len(h_f["rounds"])
    for a, b in zip(h_e["rounds"], h_f["rounds"]):
        assert a.rnd == b.rnd
        assert a.down_bytes == b.down_bytes
        assert a.up_bytes == b.up_bytes
        assert a.live_ranks == b.live_ranks
        assert a.dead_modules == b.dead_modules
        assert a.trainable_params == b.trainable_params
        assert a.sim_time_s == b.sim_time_s
        assert _eq_or_nan(a.loss, b.loss)
        assert _eq_or_nan(a.acc, b.acc)
    assert h_e["comm_gb"] == h_f["comm_gb"]
    assert h_e["sim_time_s"] == h_f["sim_time_s"]
    assert [r for r, _ in h_e["acc"]] == [r for r, _ in h_f["acc"]]


# ---------------------------------------------------------------------------
# fused ↔ eager parity
# ---------------------------------------------------------------------------

def test_fused_matches_eager_bit_exact(setup):
    """K=4 fused blocks replay the eager cohort run exactly: the on-device
    psum FedAvg is the same float program as the eager weighted tensordot,
    selection RNG draws are consumed in the same order, and shape-only byte
    accounting replays identically."""
    h_e = _run(setup, fuse_rounds=1)
    h_f = _run(setup, fuse_rounds=4)
    _assert_history_parity(h_e, h_f)
    np.testing.assert_allclose(h_e["final_acc"], h_f["final_acc"], rtol=0)
    for x, y in zip(jax.tree.leaves(h_e["trainable"]),
                    jax.tree.leaves(h_f["trainable"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fused_parity_under_dropout_and_stragglers(setup):
    """Dropout/straggler draws come from the same host ``ev_rng`` stream in
    the same order, so heterogeneity (including all-dropped NaN rounds
    passing the carry through the psum guard) stays bit-exact."""
    kw = dict(rounds=8, dropout=0.5, straggler=0.3, event_seed=3)
    h_e = _run(setup, fuse_rounds=1, **kw)
    h_f = _run(setup, fuse_rounds=4, **kw)
    _assert_history_parity(h_e, h_f)
    assert h_f["sim_time_s"] > 0


def test_fused_parity_with_optimizer_gate(setup):
    """FFA-LoRA freezes A via the optimizer gate — a per-leaf 0/1 scalar
    tree threaded through the fused scan unchanged."""
    h_e = _run(setup, strategy="ffa_lora", rounds=4, fuse_rounds=1)
    h_f = _run(setup, strategy="ffa_lora", rounds=4, fuse_rounds=4)
    _assert_history_parity(h_e, h_f)


def test_fused_blocks_never_cross_eval_boundary():
    fc = FedConfig(rounds=10, eval_every=4)
    assert FU._block_rounds(0, 16, fc) == [0, 1, 2, 3]
    assert FU._block_rounds(4, 2, fc) == [4, 5]
    assert FU._block_rounds(6, 16, fc) == [6, 7]
    assert FU._block_rounds(8, 16, fc) == [8, 9]       # run end caps it
    fc1 = FedConfig(rounds=3, eval_every=10 ** 6)
    assert FU._block_rounds(0, 16, fc1) == [0, 1, 2]


# ---------------------------------------------------------------------------
# eligibility + fallback
# ---------------------------------------------------------------------------

def test_eligible_gates_every_host_work_source(setup):
    _, train, _, parts = setup
    strats = all_strategies(rounds=8)
    ok_fc = FedConfig(rounds=8, batch_size=16)
    ok, why = FU.eligible(ok_fc, strats["fedlora"], parts)
    assert ok and why == ""

    cases = [
        (FedConfig(codec="int8", batch_size=16), "fedlora", "codec"),
        (FedConfig(secagg="mask", batch_size=16), "fedlora", "secagg"),
        (FedConfig(dp_clip=1.0, dp_noise_multiplier=0.5, batch_size=16),
         "fedlora", "DP"),
        (ok_fc, "fedara", "mask"),                  # re-prunes every round
        (ok_fc, "slora", "stage-1"),
        (FedConfig(rebucket=True, batch_size=16), "fedlora", "bucket"),
    ]
    for fc, sname, frag in cases:
        ok, why = FU.eligible(fc, strats[sname], parts)
        assert not ok and frag.lower() in why.lower(), (sname, why)

    # ragged clients: any partition smaller than one batch
    ragged = [p[:8] if i == 0 else p for i, p in enumerate(parts)]
    ok, why = FU.eligible(ok_fc, strats["fedlora"], ragged)
    assert not ok and "sub-batch" in why


def test_ineligible_config_falls_back_to_eager(setup, tmp_path):
    """fuse_rounds > 1 with a codec must run the eager path (identical
    history) and trace the reason — never silently change results."""
    kw = dict(rounds=4, codec="int8")
    h_e = _run(setup, fuse_rounds=1, **kw)
    path = str(tmp_path / "fallback.jsonl")
    try:
        obs.configure(path, meta=obs.provenance({"cmd": "test"}))
        h_f = _run(setup, fuse_rounds=4, **kw)
        obs.close()
    finally:
        obs.disable()
    for a, b in zip(h_e["rounds"], h_f["rounds"]):
        assert a.loss == b.loss and a.up_bytes == b.up_bytes
    assert h_e["comm_gb"] == h_f["comm_gb"]
    events = E.read_jsonl(path)
    (fb,) = [e for e in events if e.get("type") == "event"
             and e.get("name") == "fused_fallback"]
    assert "codec" in fb["attrs"]["reason"]


# ---------------------------------------------------------------------------
# compile flatness: one XLA program per run
# ---------------------------------------------------------------------------

def test_fused_compiles_once_across_blocks(setup, tmp_path):
    """12 rounds at K=4 → 3 block dispatches sharing ONE shape signature
    (dead-round padding keeps every block (K, C, ...)-shaped) and exactly
    one backend compile for it; nothing compiles in rounds ≥ 1.  This is
    the 'compile count flat in round count' acceptance."""
    path = str(tmp_path / "fused.jsonl")
    cfg, train, test, parts = setup
    strat = all_strategies(rounds=12)["fedlora"]
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=12, clients_per_round=4, batch_size=16,
                   max_local_batches=2, eval_every=4, lr=3e-3,
                   runner="cohort", fuse_rounds=4)
    try:
        obs.configure(path, meta=obs.provenance({"cmd": "test"}))
        run_federated(model, strat, parts, train, test, fc)
        obs.close()
    finally:
        obs.disable()
    events = E.read_jsonl(path)
    dispatches = [e for e in events if e.get("type") == "span"
                  and e.get("kind") == "dispatch"]
    assert len(dispatches) == 3
    sigs = {(e.get("attrs") or {}).get("sig") for e in dispatches}
    assert len(sigs) == 1                          # same rectangle every block
    cs = P.compile_stats(events)
    assert cs["after_first_round"] == 0, cs["by_round"]
    assert cs["by_round"] == {}, cs["by_round"]    # blocks compile as setup
    (sig,) = sigs
    assert cs["by_signature"].get(sig) == 1        # ...exactly once
    assert cs["n"] >= 1 and cs["eval"] >= 1


# ---------------------------------------------------------------------------
# pow-2 re-bucketing
# ---------------------------------------------------------------------------

def test_rebucket_shrinks_step_axis_pow2(setup):
    cfg, train, _, parts = setup
    fc = FedConfig(rounds=1, clients_per_round=4, batch_size=16,
                   max_local_batches=7)
    sel = [0, 1, 2, 3]
    full = build_cohort(train, parts, sel, fc, 0, 4)
    snug = build_cohort(train, parts, sel, fc, 0, 4, bucket=True)
    T_full = full.step_mask.shape[1]
    T_snug = snug.step_mask.shape[1]
    # 12-way IID split of 800 → ~66/client → 4 full batches < 7 requested
    assert T_full == 7
    assert T_snug == 4 and T_snug & (T_snug - 1) == 0   # next pow-2 of max
    np.testing.assert_array_equal(full.n_steps, snug.n_steps)
    np.testing.assert_array_equal(full.weights, snug.weights)
    # the kept prefix is the same work
    np.testing.assert_array_equal(full.step_mask[:, :T_snug], snug.step_mask)
    assert not full.step_mask[:, T_snug:].any()          # only padding dropped


def test_rebucket_run_parity(setup):
    """Dropping all-masked padding steps is a no-op on the trajectory: the
    scan's keep-carry masking means masked steps never touch params."""
    kw = dict(rounds=4, max_local_batches=7)
    h_full = _run(setup, fuse_rounds=1, **kw)
    h_snug = _run(setup, fuse_rounds=1, rebucket=True, **kw)
    for a, b in zip(h_full["rounds"], h_snug["rounds"]):
        assert a.loss == b.loss
        assert a.up_bytes == b.up_bytes
    assert h_full["final_acc"] == h_snug["final_acc"]


# ---------------------------------------------------------------------------
# quantized optimizer state
# ---------------------------------------------------------------------------

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
    h32 = _run(setup, rounds=4, fuse_rounds=4)
    for dtype, rtol in (("bfloat16", 0.05), ("int8", 0.15)):
        hq = _run(setup, rounds=4, fuse_rounds=4, opt_state_dtype=dtype)
        for a, b in zip(h32["rounds"], hq["rounds"]):
            assert np.isfinite(b.loss)
            np.testing.assert_allclose(b.loss, a.loss, rtol=rtol)
        # byte/clock accounting is storage-independent
        assert hq["comm_gb"] == h32["comm_gb"]
        assert hq["sim_time_s"] == h32["sim_time_s"]


# ---------------------------------------------------------------------------
# persistent compilation cache across processes
# ---------------------------------------------------------------------------

_CACHE_SCRIPT = textwrap.dedent("""
    import os, sys
    from repro.compat import enable_compilation_cache
    assert enable_compilation_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
    from repro import obs
    from repro.configs.distilbert import MINI
    from repro.data.synthetic import make_classification
    from repro.federated.baselines import all_strategies
    from repro.federated.partition import iid_partition
    from repro.federated.server import FedConfig, run_federated
    from repro.models import Model

    cfg = MINI.with_(n_layers=1, layer_pattern=("attn",))
    train = make_classification(400, 10, cfg.vocab_size, 16, seed=1)
    test = make_classification(100, 10, cfg.vocab_size, 16, seed=2)
    parts = iid_partition(train.labels, 6, seed=0)
    strat = all_strategies(rounds=4)["fedlora"]
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=4, clients_per_round=3, batch_size=16,
                   max_local_batches=2, eval_every=4, lr=3e-3,
                   runner="cohort", fuse_rounds=4)
    obs.configure(sys.argv[1], meta=obs.provenance({"cmd": "cache-test"}))
    h = run_federated(model, strat, parts, train, test, fc)
    obs.close()
    print("CACHE_RUN_OK", h["final_acc"])
""")


def test_compilation_cache_across_processes(tmp_path):
    """Two identical fused runs in separate processes sharing one cache dir:
    run 1 populates it, run 2 must be compile-free — asserted from the
    traces as cache_misses == 0 (a warm cache still fires backend_compile
    durations for retrieval, so miss events are the ground truth)."""
    cache = str(tmp_path / "xla-cache")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = cache
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    stats = []
    for i in (1, 2):
        trace = str(tmp_path / f"run{i}.jsonl")
        r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, trace],
                           env=env, cwd=".", capture_output=True,
                           text=True, timeout=420)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
        assert "CACHE_RUN_OK" in r.stdout
        stats.append(P.compile_stats(E.read_jsonl(trace)))
    assert stats[0]["cache_misses"] > 0          # run 1 populated the cache
    assert stats[1]["cache_misses"] == 0, stats[1]
    assert stats[1]["cache_hits"] > 0


def test_compilation_cache_default_dir_is_in_checkout(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the cache lands at the one fixed
    path inside the checkout; with it, JAX's own setting is left alone."""
    import jax
    from repro.compat import DEFAULT_CACHE_DIR, enable_compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compilation_cache()
        assert path == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert DEFAULT_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
        assert enable_compilation_cache() == path      # fixed, not per run
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(Path(__file__).resolve().parents[1]
                               / ".jax_cache"))
        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
