"""Obs spans written as profiler annotations.

Every enabled span is also a ``jax.profiler.TraceAnnotation``: traced on
the CPU, it lands on the ``/host:CPU`` plane with its name, its interval
and its ``begin`` attributes and ``count`` counters as event stats.  A
disabled tracer writes nothing there.  A traced cohort run emits its round
stages in order, so the device trace can name what the host was doing in
each idle gap.
"""

import glob
import os
import pathlib
import sys
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro import obs  # noqa: E402
from repro.obs.trace import NULL_SPAN  # noqa: E402

TOP_STAGES = ("prepare", "cohort_dispatch", "cohort.pull", "upload",
              "aggregate", "arbitrate", "evaluate")


def _host_events(log_dir, names=None):
    """[(name, start_ns, end_ns, stats)] of the host plane, by start."""
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if names is None or ev.name in names:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def _traced(log_dir, fn):
    jax.profiler.start_trace(str(log_dir))
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def test_span_lands_on_host_plane_with_attrs_and_counters(tmp_path):
    def body():
        tr = obs.configure(None, health=False, profile=False)
        try:
            outer = tr.begin("t.outer", kind="round", rnd=3, tag="x",
                             skip={"not": "scalar"})
            inner = tr.begin("t.inner", k=1.5)
            time.sleep(0.01)
            inner.count(nbytes=12345)
            inner.set(only_jsonl=1)
            time.sleep(0.005)
            inner.end(at_end=7)
            outer.end()
            return tr.events()
        finally:
            obs.disable()

    evs = _traced(tmp_path, body)
    host = {n: (s, e, st) for n, s, e, st in
            _host_events(tmp_path, {"t.outer", "t.inner"})}
    o_s, o_e, o_st = host["t.outer"]
    i_s, i_e, i_st = host["t.inner"]
    assert o_s <= i_s < i_e <= o_e
    assert i_e - i_s >= 15e6                 # the two sleeps, in ns
    assert o_st == {"rnd": 3, "tag": "x"}    # scalars only
    assert i_st == {"k": 1.5, "nbytes": 12345}
    # the JSONL keeps every attribute and the parent link
    spans = {e["name"]: e for e in evs if e["type"] == "span"}
    assert spans["t.inner"]["parent"] == spans["t.outer"]["id"]
    assert spans["t.inner"]["attrs"] == {"k": 1.5, "nbytes": 12345,
                                         "only_jsonl": 1, "at_end": 7}


def test_spans_ended_out_of_order_keep_both_intervals(tmp_path):
    def body():
        tr = obs.configure(None, health=False, profile=False)
        try:
            a = tr.begin("t.a")
            b = tr.begin("t.b")
            time.sleep(0.005)
            a.end()                          # the parent first
            time.sleep(0.005)
            b.end()
            b.end()                          # idempotent
            later = tr.begin("t.later")
            later.end()
            return tr.events()
        finally:
            obs.disable()

    evs = _traced(tmp_path, body)
    host = _host_events(tmp_path, {"t.a", "t.b", "t.later"})
    assert [n for n, *_ in host] == ["t.a", "t.b", "t.later"]
    (_, a_s, a_e, _), (_, b_s, b_e, _), (_, l_s, _, _) = host
    assert a_s <= b_s < a_e < b_e <= l_s
    later = next(e for e in evs if e.get("name") == "t.later")
    assert later["parent"] is None


def test_disabled_tracer_writes_nothing_to_the_profiler(tmp_path):
    def body():
        obs.disable()
        tr = obs.get_tracer()
        sp = tr.begin("t.ghost", kind="stage", rnd=1)
        assert sp is NULL_SPAN
        assert sp.count(nbytes=1) is NULL_SPAN
        time.sleep(0.002)
        sp.end()
        jax.numpy.ones(3).block_until_ready()

    _traced(tmp_path, body)
    assert _host_events(tmp_path, {"t.ghost"}) == []


@pytest.mark.parametrize("kw,readers", [
    ({"health": False, "profile": False}, False),
    ({"health": True, "profile": False}, True),
    ({"health": False, "profile": True}, True),
    ({"path": "trace.jsonl", "health": False, "profile": False}, True),
])
def test_has_readers(tmp_path, kw, readers):
    kw = dict(kw)
    if "path" in kw:
        kw["path"] = str(tmp_path / kw["path"])
    try:
        tr = obs.configure(kw.pop("path", None), **kw)
        assert tr.has_readers is readers
    finally:
        obs.disable()


@pytest.mark.parametrize("health", [False, True])
def test_drift_is_computed_only_for_a_reader(health):
    from repro.fedsim import pipeline as PL
    rng = np.random.default_rng(0)
    enc = [PL.EncodedUpdate(cid=i, wire=rng.normal(size=64).astype(
        np.float32), delta=None, nbytes=0, weight=1.0) for i in range(3)]
    try:
        tr = obs.configure(None, health=health, profile=False)
        PL.UploadPipeline.__new__(PL.UploadPipeline)._emit_drift(enc, rnd=0)
        drift = [e for e in tr.events() if e.get("name") == "drift"]
    finally:
        obs.disable()
    assert len(drift) == (1 if health else 0)


def test_programs_carry_stable_names():
    from repro.configs.distilbert import MINI
    from repro.federated import client as CL
    from repro.fedsim import cohort as CH
    from repro.models import Model
    from repro import optim as OPT
    model = Model(MINI.with_(n_layers=1, layer_pattern=("attn",)))
    opt = OPT.adam(1e-3)
    names = {CH.make_cohort_fn(model, opt).__name__,
             CL.make_train_step(model, opt).__name__,
             CL.make_eval_step(model).__name__,
             CL.make_base_update_step(opt).__name__}
    assert names == {"cohort_round", "client_step", "eval_step",
                     "base_update_step"}


# ---------------------------------------------------------------------------
# a traced cohort run: the round's stages, in order, on the host plane
# ---------------------------------------------------------------------------

def _cohort_cfg():
    from repro.configs.distilbert import MINI
    return MINI.with_(n_layers=2, layer_pattern=("attn",) * 2)


@pytest.fixture(scope="module")
def cohort_trace(tmp_path_factory):
    from repro.data.synthetic import make_classification
    from repro.federated.baselines import all_strategies
    from repro.federated.server import FedConfig, run_federated
    from repro.models import Model
    cfg = _cohort_cfg()
    train = make_classification(500, 20, cfg.vocab_size, 32, seed=1)
    test = make_classification(64, 20, cfg.vocab_size, 32, seed=2)
    order = np.random.default_rng(0).permutation(len(train.labels))
    # client 7 holds fewer rows than a batch: the sub-batch fallback trains
    # it, in rounds 0 and 2 of selection seed 7
    sizes = [60] * 7 + [5]
    parts = np.split(order[:sum(sizes)], np.cumsum(sizes)[:-1])
    rounds = 4
    strat = all_strategies(rounds=rounds)["fedara"]
    strat.total_rounds, strat.warmup_rounds = rounds, 1
    strat.final_rounds_frac = 0.34
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=rounds, clients_per_round=3, batch_size=16,
                   max_local_batches=2, eval_every=rounds, lr=3e-3,
                   runner="cohort", seed=7)
    log_dir = tmp_path_factory.mktemp("cohort_trace")

    def body():
        tr = obs.configure(None, health=False, profile=False)
        try:
            run_federated(model, strat, parts, train, test, fc)
            return tr.events()
        finally:
            obs.disable()

    evs = _traced(log_dir, body)
    names = set(TOP_STAGES) | {"round", "client", "importance", "encode",
                               "fallback"}
    return evs, _host_events(log_dir, names), parts, fc


def _by_round(host):
    rounds = [(s, e, st["rnd"]) for n, s, e, st in host if n == "round"]
    out = {}
    for r_s, r_e, rnd in rounds:
        out[rnd] = [ev for ev in host if ev[0] != "round"
                    and r_s <= ev[1] and ev[2] <= r_e]
    return out


def test_cohort_round_stages_in_order(cohort_trace):
    evs, host, parts, fc = cohort_trace
    rounds = _by_round(host)
    assert sorted(rounds) == list(range(fc.rounds))
    kinds = set()
    for rnd, inside in rounds.items():
        small = [st["cid"] for n, _, _, st in inside if n == "client"
                 and len(parts[st["cid"]]) < fc.batch_size]
        kinds.add(bool(small))
        want = ["prepare", "cohort_dispatch", "cohort.pull", "upload"]
        want += ["aggregate"] if small else []
        want += ["arbitrate"]
        want += ["evaluate"] if rnd == fc.rounds - 1 else []
        assert [n for n, *_ in inside if n in TOP_STAGES] == want, rnd
        falls = [(s, e, st) for n, s, e, st in inside if n == "fallback"]
        assert sorted(st["cid"] for *_, st in falls) == sorted(small)
        (up_s, up_e) = next((s, e) for n, s, e, _ in inside
                            if n == "upload")
        for s, e, st in falls:
            assert up_s <= s and e <= up_e
            assert st["steps"] == 1 and st["rows"] == 5
        n_clients = sum(1 for n, *_ in inside if n == "client")
        for nested in ("importance", "encode"):
            assert sum(1 for n, *_ in inside if n == nested) == n_clients
    assert kinds == {True, False}        # rounds with and without fallback


def test_cohort_stage_counters(cohort_trace):
    evs, host, parts, fc = cohort_trace
    pulls = [st for n, _, _, st in host if n == "cohort.pull"]
    assert len(pulls) == fc.rounds and all(st["nbytes"] > 0 for st in pulls)
    arbs = [st for n, _, _, st in host if n == "arbitrate"]
    assert len(arbs) == fc.rounds
    assert all(st["pruned"] >= 0 for st in arbs)
    # the JSONL parents every stage under its round
    spans = [e for e in evs if e["type"] == "span"]
    round_ids = {e["id"] for e in spans if e["name"] == "round"}
    for e in spans:
        if e["name"] in TOP_STAGES:
            assert e["parent"] in round_ids, e["name"]
    # nothing reads this tracer but the profiler: no reader-only events
    assert not any(e.get("name") in ("rank_alloc", "memory", "drift")
                   for e in evs)
    assert all("sig" not in e["attrs"] for e in spans
               if e["name"] == "cohort_dispatch")


def test_cohort_dispatch_counts_steps_and_slots(cohort_trace):
    """``steps`` is the round's real client-steps, ``slots`` the lane-steps
    the cohort program ran: a whole number of iterations on every lane."""
    from repro.fedsim import cohort as CH
    evs, host, parts, fc = cohort_trace
    lanes = CH.lane_count(parts, fc, fc.clients_per_round, 1)
    rounds = _by_round(host)
    for rnd, inside in rounds.items():
        (st,) = [st for n, _, _, st in inside if n == "cohort_dispatch"]
        real = sum(CH.cohort_steps(len(parts[st_c["cid"]]), fc)
                   for n, _, _, st_c in inside if n == "client")
        assert st["steps"] == real > 0, rnd
        assert st["slots"] % lanes == 0
        assert st["steps"] <= st["slots"]
        assert st["slots"] <= -(-fc.clients_per_round
                                * fc.max_local_batches // lanes) * lanes


def test_upload_counts_where_votes_and_deltas_are_made(cohort_trace):
    """Every cohort client's votes come from the upload program.  A round
    with no sub-batch client builds no delta tree on the host and pulls
    no slot tree, only the loss stacks, scores and norms; a round with one
    aggregates on the host from every client's delta tree, and pulls the
    slots' params and the broadcast, never the grads."""
    from repro.models import Model
    evs, host, parts, fc = cohort_trace
    model = Model(_cohort_cfg(), peft="bea", unroll=True)
    _, t_shapes = jax.eval_shape(model.init, jax.random.key(0))
    tree_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(t_shapes))
    n_ranks = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init_masks)))
    C, T = fc.clients_per_round, fc.max_local_batches * fc.local_epochs
    small_bytes = 4 * C * (2 * T + n_ranks + 1)     # lc, mc, scores, norms
    kinds = set()
    for rnd, inside in _by_round(host).items():
        n = sum(1 for name, *_ in inside if name == "client")
        small = sum(1 for name, *_ in inside if name == "fallback")
        (up,) = [st for name, _, _, st in inside if name == "upload"]
        (pull,) = [st for name, _, _, st in inside if name == "cohort.pull"]
        assert up["device_scores"] == n - small, rnd
        assert up["host_deltas"] == (n if small else 0), rnd
        slot_trees = (C + 1) * tree_bytes if small else 0
        assert pull["nbytes"] == small_bytes + slot_trees, rnd
        kinds.add(bool(small))
    assert kinds == {True, False}
