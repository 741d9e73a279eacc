"""The round and run bookkeeping the three round loops share
(federated/server.py): the barrier sim clock, the round close-out (eval
cadence, ``end_round``, ``on_round`` last) and the run close-out, driven
through the sequential reference, the cohort runner and async alike."""

from collections import namedtuple

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.distilbert import MINI
from repro.core import masks as MK
from repro.data.synthetic import make_classification
from repro.federated import devices as DV
from repro.federated import server as SV
from repro.federated.baselines import all_strategies
from repro.federated.partition import iid_partition
from repro.federated.server import FedConfig, run_federated
from repro.fedsim import pipeline as PL
from repro.models import Model


@pytest.fixture(scope="module")
def setup():
    cfg = MINI.with_(n_layers=2, layer_pattern=("attn",) * 2)
    train = make_classification(800, 20, cfg.vocab_size, 32, seed=1)
    test = make_classification(200, 20, cfg.vocab_size, 32, seed=2)
    # IID so every client holds ≥ batch_size samples
    parts = iid_partition(train.labels, 12, seed=0)
    return cfg, train, test, parts


def _run(setup, strategy="fedlora", rounds=8, runner="cohort",
         on_round=None, **fc_kw):
    cfg, train, test, parts = setup
    strat = all_strategies(rounds=rounds)[strategy]
    if hasattr(strat, "total_rounds"):
        strat.total_rounds = rounds
        strat.warmup_rounds = 1
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(**{"rounds": rounds, "clients_per_round": 4,
                      "batch_size": 16, "max_local_batches": 2,
                      "eval_every": 4, "lr": 3e-3, "runner": runner,
                      **fc_kw})
    return run_federated(model, strat, parts, train, test, fc, on_round)


def _eq_or_nan(a, b):
    return a == b or (a != a and b != b)


def _assert_history_parity(h_a, h_b):
    """Key-for-key equal dicts, exact byte/rank/sim accounting, bit-exact
    per-round losses and accuracies."""
    assert set(h_a.keys()) == set(h_b.keys())
    assert len(h_a["rounds"]) == len(h_b["rounds"])
    for a, b in zip(h_a["rounds"], h_b["rounds"]):
        assert a.rnd == b.rnd
        assert a.down_bytes == b.down_bytes
        assert a.up_bytes == b.up_bytes
        assert a.live_ranks == b.live_ranks
        assert a.dead_modules == b.dead_modules
        assert a.trainable_params == b.trainable_params
        assert a.sim_time_s == b.sim_time_s
        assert _eq_or_nan(a.loss, b.loss)
        assert _eq_or_nan(a.acc, b.acc)
    assert h_a["comm_gb"] == h_b["comm_gb"]
    assert h_a["sim_time_s"] == h_b["sim_time_s"]
    assert [r for r, _ in h_a["acc"]] == [r for r, _ in h_b["acc"]]


def test_cohort_run_is_deterministic_under_dropout_and_stragglers(setup):
    """Dropout and straggler draws come from the seeded event stream, so
    one (seed, event_seed) pair replays the same history bit for bit,
    all-dropped rounds included."""
    kw = dict(rounds=8, dropout=0.5, straggler=0.3, event_seed=3)
    h_a = _run(setup, **kw)
    h_b = _run(setup, **kw)
    _assert_history_parity(h_a, h_b)
    assert _eq_or_nan(h_a["final_acc"], h_b["final_acc"])
    for x, y in zip(jax.tree.leaves(h_a["trainable"]),
                    jax.tree.leaves(h_b["trainable"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert h_a["sim_time_s"] > 0


_SHARED_KEYS = {"rounds", "acc", "comm_gb", "sim_time_s", "final_acc",
                "wall_s", "base", "trainable", "masks"}
_RUNNER_KEYS = {"seq": {"secagg_rounds", "dp_eps"},
                "cohort": {"secagg_rounds", "dp_eps"},
                "async": {"events"}}


@pytest.mark.parametrize("runner", ["seq", "cohort", "async"])
def test_round_close_out(setup, runner):
    """Every loop evaluates at ``(r + 1) % eval_every == 0`` and at the last
    round, hands ``on_round`` the logged round after its eval, and closes
    the run with ``final_acc`` = the last logged accuracy and the same
    history keys."""
    rounds, every = 5, 2
    seen = []
    h = _run(setup, strategy="fedara", rounds=rounds, runner=runner,
             eval_every=every,
             on_round=lambda rnd, log: seen.append((rnd, log, log.acc)))
    want = [r for r in range(rounds)
            if (r + 1) % every == 0 or r == rounds - 1]
    assert [r for r, _ in h["acc"]] == want == [1, 3, 4]
    logs = h["rounds"]
    assert [log.rnd for log in logs] == list(range(rounds))
    for log in logs:
        assert (log.acc == log.acc) == (log.rnd in want)
    assert dict(h["acc"]) == {log.rnd: log.acc for log in logs
                              if log.rnd in want}
    # on_round saw each round once, in order, with its eval already done
    assert [r for r, _, _ in seen] == list(range(rounds))
    assert all(log is logs[r] for r, log, _ in seen)
    assert all(_eq_or_nan(acc, logs[r].acc) for r, _, acc in seen)
    assert h["final_acc"] == logs[-1].acc
    assert set(h) == _SHARED_KEYS | _RUNNER_KEYS[runner]
    assert h["sim_time_s"] == logs[-1].sim_time_s > 0
    assert logs[-1].live_ranks == MK.count_true(h["masks"])
    assert h["wall_s"] > 0


Enc = namedtuple("Enc", "cid nbytes n_steps")


@pytest.mark.parametrize("drops", [[0, 0, 0, 0], [1, 0, 1, 0], [1, 1, 1, 1]],
                         ids=["no_drops", "some_drops", "all_dropped"])
def test_barrier_clock(drops):
    """The barrier round lasts as long as its slowest surviving client,
    plus the protocol's time; the round span carries the surviving costs'
    max and median."""
    fc = FedConfig(device_profile="distilbert")
    pipe = PL.UploadPipeline(fc)
    sel = np.asarray([3, 7, 1, 10])
    slows = np.asarray([1.0, 4.0, 1.0, 1.0])
    drops = np.asarray(drops, bool)
    encoded = [Enc(int(c), 1000 * (k + 1), 2 + k)
               for k, c in enumerate(sel) if not drops[k]]
    down_per, protocol_s = 5000, 0.25
    costs = [pipe.client_time(int(c), down_per, 1000 * (k + 1),
                              DV.compute_s(int(c), fc.device_profile, 2 + k,
                                           slows[k]))
             for k, c in enumerate(sel) if not drops[k]]
    tr = obs.configure(None, health=False, profile=False)
    try:
        history = obs.RunRecorder("cohort", fc)
        rsp = history.begin_round(0)
        SV._barrier_clock(history, rsp, pipe, fc, sel, encoded, down_per,
                          protocol_s, drops, slows)
        rsp.end()
        events = tr.events()
    finally:
        obs.disable()
    (rnd,) = [e for e in events if e.get("name") == "round"]
    assert history["sim_time_s"] == (max(costs) if costs else 0.0) \
        + protocol_s
    if costs:
        assert rnd["attrs"]["cost_max"] == max(costs)
        assert rnd["attrs"]["cost_med"] == sorted(costs)[len(costs) // 2]
    else:
        assert "cost_max" not in rnd["attrs"]
        assert history["sim_time_s"] == protocol_s


def test_stage1_rounds_default_is_zero():
    """Only SLoRA runs a stage before the adapter rounds; every other
    strategy inherits the base's zero."""
    for name, strat in all_strategies(rounds=20).items():
        want = 2 if name == "slora" else 0
        assert strat.stage1_rounds(20) == want, name
