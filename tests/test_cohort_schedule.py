"""The cohort program's client-step schedule (host only, no model).

``schedule`` packs each cohort slot's real local steps onto W lanes;
``lane_count`` picks W from the partition; ``shard_order`` balances the
per-device step sums; ``build_cohort`` applies that order to its slots.
"""

import numpy as np
import pytest

from repro.data.synthetic import Dataset
from repro.federated.server import FedConfig
from repro.fedsim import cohort as CH

# (n_steps per slot, T, lanes, ndev)
CASES = {
    "skewed": ([8, 1, 3, 8, 2, 0, 5, 7, 0, 4, 6, 1, 8, 2, 3, 0], 8, 11, 1),
    "one_long": ([8, 1, 1, 1, 0, 0], 8, 3, 1),
    "uniform": ([4] * 8, 4, 8, 1),
    "all_dead_but_one": ([3, 0, 0, 0], 4, 2, 1),
    "one_lane": ([2, 3, 1], 4, 1, 1),
    "four_devices": ([8, 1, 3, 8, 2, 0, 5, 7, 0, 4, 6, 1, 8, 2, 3, 0],
                     8, 8, 4),
    "random": (list(np.random.default_rng(5).integers(0, 9, 24)), 8, 10, 2),
}


def _plan(name):
    n, T, lanes, ndev = CASES[name]
    return np.asarray(n), T, lanes, ndev, CH.schedule(np.asarray(n), T,
                                                      lanes, ndev)


def _runs(n, lanes, ndev, plan):
    """{global slot: [(iteration, step), ...]} of every live lane entry,
    and the per-device iteration sets of each slot."""
    per, w = len(n) // ndev, lanes // ndev
    out: dict[int, list] = {}
    for t in range(plan.client.shape[0]):
        for lane in range(lanes):
            c = int(plan.client[t, lane])
            if c == per:
                continue
            assert 0 <= c < per
            out.setdefault((lane // w) * per + c, []).append(
                (t, int(plan.step[t, lane])))
    return out


@pytest.mark.parametrize("name", sorted(CASES))
def test_each_step_runs_once_in_order(name):
    n, T, lanes, ndev, plan = _plan(name)
    runs = _runs(n, lanes, ndev, plan)
    for slot, k in enumerate(n):
        got = runs.get(slot, [])
        assert [s for _, s in got] == list(range(k)), slot
        assert [t for t, _ in got] == sorted(t for t, _ in got)


@pytest.mark.parametrize("name", sorted(CASES))
def test_no_client_twice_in_an_iteration(name):
    n, T, lanes, ndev, plan = _plan(name)
    per, w = len(n) // ndev, lanes // ndev
    for t in range(plan.client.shape[0]):
        for d in range(ndev):
            live = [int(c) for c in plan.client[t, d * w:(d + 1) * w]
                    if c != per]
            assert len(live) == len(set(live)), (t, d)


@pytest.mark.parametrize("name", sorted(CASES))
def test_iteration_count_is_the_lower_bound(name):
    n, T, lanes, ndev, plan = _plan(name)
    per, w = len(n) // ndev, lanes // ndev
    want = max(max(int(n[d * per:(d + 1) * per].max()),
                   -(-int(n[d * per:(d + 1) * per].sum()) // w))
               for d in range(ndev))
    assert int(plan.n_iter) == want
    assert plan.client.shape == (-(-len(n) * T // lanes), lanes)


@pytest.mark.parametrize("name", sorted(CASES))
def test_dead_slots_and_padded_steps_never_run(name):
    n, T, lanes, ndev, plan = _plan(name)
    runs = _runs(n, lanes, ndev, plan)
    assert all(n[slot] > 0 for slot in runs)
    assert all(s < n[slot] for slot, r in runs.items() for _, s in r)
    per = len(n) // ndev
    assert (plan.client[int(plan.n_iter):] == per).all()
    # lane-steps computed = real steps + idle lanes, and no more
    assert sum(len(r) for r in runs.values()) == int(n.sum())


@pytest.mark.parametrize("n", [
    [8, 8, 8, 8, 1, 1, 1, 1, 0, 0, 0, 0, 5, 5, 5, 5],
    [8, 1, 1, 1, 1, 1, 1, 1],
    [8, 7, 6, 5, 4, 3, 2, 1, 0, 0, 2, 2, 3, 3, 8, 8],
    list(np.random.default_rng(11).integers(0, 9, 32)),
])
def test_four_device_shards_balance(n):
    n = np.asarray(n)
    order = CH.shard_order(n, 4)
    assert sorted(order) == list(range(len(n)))
    sums = n[order].reshape(4, -1).sum(axis=1)
    assert sums.max() - sums.min() <= n.max()
    # the balanced order never needs more iterations than the given one
    lanes = len(n) // 2
    T = int(n.max())
    assert int(CH.schedule(n[order], T, lanes, 4).n_iter) \
        <= int(CH.schedule(n, T, lanes, 4).n_iter)


def test_one_device_keeps_the_order():
    n = np.asarray([3, 0, 8, 1])
    np.testing.assert_array_equal(CH.shard_order(n, 1), np.arange(4))


def _fc(**kw):
    return FedConfig(**{"batch_size": 16, "max_local_batches": 8,
                        "clients_per_round": 4, **kw})


@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_uniform_population_runs_the_rectangle(ndev):
    fc = _fc(max_local_batches=4)
    parts = [np.arange(70)] * 12                 # 4 batches each: full T
    c_pad = -(-fc.clients_per_round // ndev) * ndev
    lanes = CH.lane_count(parts, fc, c_pad, ndev)
    assert lanes == c_pad
    plan = CH.schedule(np.full(c_pad, 4), 4, lanes, ndev)
    assert int(plan.n_iter) == 4


# steps per client: 8, 8, 8, 8, 0 (sub-batch), 1, 2, 3, 5, 7 — sum 50,
# mean 5 over 10 clients; W = ⌈cpr · 5 / 8⌉, rounded up to ndev, ≤ c_pad
SIZES = [200, 128, 130, 143, 9, 16, 32, 63, 80, 127]


@pytest.mark.parametrize("cpr,ndev,want", [
    (4, 1, 3), (4, 2, 4), (4, 4, 4), (8, 1, 5), (8, 4, 8), (16, 1, 10),
])
def test_lane_count_from_client_sizes(cpr, ndev, want):
    fc = _fc(clients_per_round=cpr)
    parts = [np.arange(s) for s in SIZES] * 2    # 20 clients, same mean
    assert [CH.cohort_steps(s, fc) for s in SIZES] \
        == [8, 8, 8, 8, 0, 1, 2, 3, 5, 7]
    c_pad = -(-cpr // ndev) * ndev
    assert CH.lane_count(parts, fc, c_pad, ndev) == want


def test_build_cohort_slots_follow_the_balanced_order():
    fc = _fc(max_local_batches=4, clients_per_round=6)
    rng = np.random.default_rng(0)
    train = Dataset(rng.integers(0, 50, (600, 8)).astype(np.int32),
                    rng.integers(0, 3, 600).astype(np.int32))
    sizes = [64, 16, 48, 5, 32, 64, 16]
    parts = np.split(np.arange(sum(sizes)), np.cumsum(sizes)[:-1])
    sel = [0, 1, 2, 3, 4, 5, 6]
    one = CH.build_cohort(train, parts, sel, fc, 0, 8)
    four = CH.build_cohort(train, parts, sel, fc, 0, 8, ndev=4)
    assert one.fallback == four.fallback == [3]
    assert one.slots == list(range(6))
    for cid, i, j in zip(one.cids, one.slots, four.slots):
        assert four.cids[four.slots.index(j)] == cid
        assert four.weights[j] == one.weights[i] == len(parts[cid])
        assert four.n_steps[j] == one.n_steps[i]
        np.testing.assert_array_equal(four.step_mask[j], one.step_mask[i])
        for k in one.batches:
            np.testing.assert_array_equal(four.batches[k][j],
                                          one.batches[k][i])
    sums = four.n_steps.reshape(4, -1).sum(axis=1)
    assert sums.max() - sums.min() <= four.n_steps.max()
