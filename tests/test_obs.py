"""repro.obs: span tracing, labeled metrics, JSONL export, and the
trace-parity acceptance contract.

The parity tests pin the tentpole guarantee: ``summarize`` reconstructs the
runners' ``history``-level accounting (``comm_gb``, ``sim_time_s``, secagg
per-phase bytes) from the JSONL trace alone, to EXACT equality — because
the recorder emits one round span per history round with the same integer
byte counts, in the same order, so the summary replays the identical float
fold.

Tracing is process-global state; every test that enables it restores the
null tracer in a ``finally`` so ordering can't leak spans across tests.
"""

import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro import obs  # noqa: E402
from repro.obs import export as E  # noqa: E402
from repro.obs.__main__ import main as obs_main  # noqa: E402
from repro.obs.metrics import NULL_METRICS, Metrics, SAMPLE_CAP  # noqa: E402
from repro.obs.trace import NULL_SPAN, NULL_TRACER  # noqa: E402


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_trace_schema_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    try:
        tr = obs.configure(path, meta={"cmd": "unit"})
        with tr.span("run", kind="run", runner="seq"):
            rsp = tr.begin("round", kind="round", rnd=0)
            with tr.span("client", kind="client", cid=3):
                pass
            tr.event("dispatch", sim_t=1.5, cid=3)
            rsp.end(down_bytes=10, up_bytes=20, sim_time_s=2.0)
        tr.metrics.counter("pipeline.up_bytes", codec="signsgd").inc(20)
        obs.close()
    finally:
        obs.disable()

    events = E.read_jsonl(path)
    assert E.check(events, require_kinds=["run", "round", "client"]) == []
    assert events[0]["type"] == "meta"
    assert events[0]["meta"]["cmd"] == "unit"
    spans = {e["name"]: e for e in events if e["type"] == "span"}
    # nesting: client under round under run
    assert spans["client"]["parent"] == spans["round"]["id"]
    assert spans["round"]["parent"] == spans["run"]["id"]
    assert spans["run"]["parent"] is None
    assert spans["round"]["attrs"]["down_bytes"] == 10
    ev = next(e for e in events if e["type"] == "event")
    assert ev["name"] == "dispatch" and ev["sim_t"] == 1.5
    met = next(e for e in events if e["type"] == "metric")
    assert met["metric"] == "counter" and met["value"] == 20
    assert met["labels"] == {"codec": "signsgd"}


def test_out_of_order_span_end_keeps_stack_sane():
    try:
        tr = obs.configure(None)
        outer = tr.begin("outer")
        inner = tr.begin("inner")
        outer.end()                       # parent closed before child
        inner.end()
        child = tr.begin("later")         # must not re-parent under a ghost
        child.end()
        evs = tr.events()
    finally:
        obs.disable()
    later = next(e for e in evs if e.get("name") == "later")
    assert later["parent"] is None
    # double-end is idempotent
    assert sum(1 for e in evs if e.get("name") == "outer") == 1


def test_disabled_tracer_is_shared_noop():
    obs.disable()
    tr = obs.get_tracer()
    assert tr is NULL_TRACER and not tr.enabled
    # every hot-path call returns shared singletons — no allocation
    assert tr.begin("x", kind="round", rnd=1) is NULL_SPAN
    assert tr.span("y") is NULL_SPAN
    assert NULL_SPAN.set(a=1) is NULL_SPAN
    assert NULL_SPAN.lazy("k", object()) is NULL_SPAN
    assert tr.event("e", sim_t=0.0) is None
    assert tr.events() == [] and tr.close() == []
    assert tr.metrics is NULL_METRICS
    c = tr.metrics.counter("n", codec="int8")
    assert c is tr.metrics.counter("other")      # shared per-kind singleton
    c.inc(5)
    assert c.value == 0 and tr.metrics.snapshot() == {}
    # null instruments carry their kind's value/summary() SHAPE (satellite:
    # disabled-tracing code paths must not branch differently on shape)
    g = tr.metrics.gauge("m")
    h = tr.metrics.histogram("h")
    assert g is tr.metrics.gauge("m2") and h is tr.metrics.histogram("h2")
    assert c.kind == "counter" and isinstance(c.value, int)
    assert g.kind == "gauge" and isinstance(g.value, float)
    g.set(3.3)
    assert g.value == 0.0
    h.observe(1.0)
    assert h.kind == "histogram"
    assert h.value == h.summary() == {"count": 0, "sum": 0.0,
                                      "min": None, "max": None}
    assert h.quantile(0.5) is None and h.count == 0
    # counters on the null span are shared no-ops too
    assert NULL_SPAN.count(nbytes=1) is NULL_SPAN
    assert not tr.has_readers


def test_lazy_attrs_resolve_in_one_batch(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    path = str(tmp_path / "lazy.jsonl")
    try:
        tr = obs.configure(path)
        sp = tr.begin("round", kind="round", rnd=0)
        sp.lazy("loss", jnp.float32(0.25))
        sp.end(down_bytes=0, up_bytes=0, sim_time_s=0.0)
        assert tr.resolve_pending() == 1
        assert sp.attrs["loss"].resolved and sp.attrs["loss"].value == 0.25
        assert tr.resolve_pending() == 0          # drained
        obs.close()
    finally:
        obs.disable()
    # jax compile spans may land alongside (obs.profile's listener is
    # installed by configure) — select the round span, don't assume one
    (rnd,) = [e for e in E.read_jsonl(path)
              if e.get("type") == "span" and e.get("kind") == "round"]
    assert rnd["attrs"]["loss"] == 0.25           # serialized resolved


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_label_identity_and_aggregation():
    m = Metrics()
    a = m.counter("up_bytes", codec="signsgd", stage="stage2")
    b = m.counter("up_bytes", stage="stage2", codec="signsgd")
    assert a is b                         # label order is irrelevant
    a.inc(3)
    b.inc(4)
    assert a.value == 7
    other = m.counter("up_bytes", codec="int8", stage="stage2")
    assert other is not a and other.value == 0
    m.gauge("eps").set(1.25)
    h = m.histogram("resid")
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        h.observe(v)
    snap = m.snapshot()
    assert snap["up_bytes{codec=signsgd,stage=stage2}"] == 7
    assert snap["eps"] == 1.25
    assert snap["resid"]["count"] == 5 and snap["resid"]["sum"] == 15.0
    assert snap["resid"]["min"] == 1.0 and snap["resid"]["max"] == 5.0
    # sketch-backed quantile: exact to the documented relative-error bound
    assert snap["resid"]["p50"] == pytest.approx(3.0, rel=0.01)


def test_histogram_quantiles():
    m = Metrics()
    h = m.histogram("lat")
    for i in range(1, 102):                   # 1..101: known rank quantiles
        h.observe(float(i))
    # whole-stream sketch quantiles: within the documented rel-error bound
    assert h.quantile(0.5) == pytest.approx(51.0, rel=0.01)
    assert h.quantile(0.95) == pytest.approx(96.0, rel=0.01)
    assert h.quantile(0.99) == pytest.approx(100.0, rel=0.01)
    s = h.summary()
    assert s["p50"] == pytest.approx(51.0, rel=0.01)
    assert s["p95"] == pytest.approx(96.0, rel=0.01)
    assert s["p99"] == pytest.approx(100.0, rel=0.01)
    # summary keys stay pinned across the sketch-backend swap
    assert set(s) == {"count", "sum", "min", "max",
                      "p50", "p90", "p95", "p99"}
    # snapshot mirrors the summary keys (satellite: tail latency surfaces
    # through export.summarize and serving stats alike)
    snap = m.snapshot()
    assert snap["lat"]["p99"] == pytest.approx(100.0, rel=0.01)


def test_metrics_kind_mismatch_raises():
    m = Metrics()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")


def test_histogram_sample_buffer_is_bounded():
    m = Metrics()
    h = m.histogram("big")
    for i in range(SAMPLE_CAP + 100):
        h.observe(float(i))
    assert h.count == SAMPLE_CAP + 100    # exact count survives the cap
    # reservoir holds a bounded uniform sample of the WHOLE stream (Vitter's
    # R, seeded): late observations can displace early ones — the old
    # first-N buffer froze on warmup and could never contain the tail
    assert len(h.reservoir.items) == SAMPLE_CAP
    assert h.reservoir.n == SAMPLE_CAP + 100
    assert any(v >= SAMPLE_CAP for v in h.reservoir.items)
    assert h.vmax == float(SAMPLE_CAP + 99)


def test_histogram_quantiles_reflect_whole_stream_not_warmup():
    # regression for the first-N bias: a stream whose distribution shifts
    # after SAMPLE_CAP observations must move the quantiles
    m = Metrics()
    h = m.histogram("shift")
    for _ in range(SAMPLE_CAP):
        h.observe(1.0)
    for _ in range(9 * SAMPLE_CAP):
        h.observe(100.0)
    # true p50 of the full stream is 100.0; the old buffer said 1.0
    assert h.quantile(0.5) == pytest.approx(100.0, rel=0.01)


def test_label_cardinality_cap():
    from repro.obs.metrics import LABEL_CARD_CAP, OVERFLOW_LABEL
    m = Metrics()
    n = LABEL_CARD_CAP + 50
    for i in range(n):
        m.counter("per_client", client=str(i)).inc()
    snap = m.snapshot()
    series = [k for k in snap if k.startswith("per_client{")]
    # bounded registry: CAP distinct values + one __overflow__ bucket
    assert len(series) == LABEL_CARD_CAP + 1
    assert f"per_client{{client={OVERFLOW_LABEL}}}" in snap
    # aggregate stays exact: every increment landed somewhere
    assert sum(snap[k] for k in series) == n
    assert snap[f"per_client{{client={OVERFLOW_LABEL}}}"] == \
        n - LABEL_CARD_CAP
    # an already-tracked value keeps resolving to its own series
    m.counter("per_client", client="3").inc()
    assert m.snapshot()["per_client{client=3}"] == 2


def test_metric_events_serialize_for_trace():
    m = Metrics()
    m.counter("n", phase="masked").inc(2)
    (ev,) = m.events()
    assert ev == {"type": "metric", "metric": "counter", "name": "n",
                  "labels": {"phase": "masked"}, "value": 2}


# ---------------------------------------------------------------------------
# export: summarize / diff / check goldens
# ---------------------------------------------------------------------------

def _golden_events():
    return [
        {"type": "meta", "schema": 1, "t_epoch": 0.0, "meta": {}},
        {"type": "span", "id": 0, "parent": None, "name": "run",
         "kind": "run", "t0": 0.0, "dur": 1.0, "sim_t0": 0.0, "sim_dur": 3.0,
         "attrs": {"runner": "seq", "final_acc": 0.5, "wall_s": 1.0}},
        {"type": "span", "id": 1, "parent": 0, "name": "round",
         "kind": "round", "t0": 0.0, "dur": 0.4, "sim_t0": 0.0,
         "sim_dur": 1.5,
         "attrs": {"rnd": 0, "down_bytes": 10, "up_bytes": 20,
                   "sim_time_s": 1.5}},
        {"type": "span", "id": 2, "parent": 0, "name": "round",
         "kind": "round", "t0": 0.4, "dur": 0.4, "sim_t0": 1.5,
         "sim_dur": 1.5,
         "attrs": {"rnd": 1, "down_bytes": 30, "up_bytes": 40,
                   "sim_time_s": 3.0}},
        {"type": "span", "id": 3, "parent": 1, "name": "advertise",
         "kind": "secagg-phase", "t0": 0.0, "dur": 0.0, "sim_t0": 0.0,
         "sim_dur": 0.0, "attrs": {"down": 5, "up": 7, "time_s": 0.1}},
        {"type": "span", "id": 4, "parent": 1, "name": "secagg",
         "kind": "secagg", "t0": 0.0, "dur": 0.1, "sim_t0": 0.0,
         "sim_dur": 0.0,
         "attrs": {"rnd": 0, "recovery_bytes": 64, "n_dropped": 1}},
        {"type": "event", "name": "inflight_comm", "t": 0.9, "sim_t": 3.0,
         "attrs": {"down_bytes": 100, "up_bytes": 0}},
        {"type": "metric", "metric": "counter", "name": "sched.admits",
         "labels": {}, "value": 4},
    ]


def test_summarize_golden():
    s = E.summarize(_golden_events())
    assert s["n_rounds"] == 2
    assert s["down_bytes"] == 40 and s["up_bytes"] == 60
    # event-order float fold incl. the trailing inflight event
    assert s["comm_gb"] == ((10 + 20) / 1e9 + (30 + 40) / 1e9
                            + (100 + 0) / 1e9)
    assert s["sim_time_s"] == 3.0
    assert s["runner"] == "seq" and s["final_acc"] == 0.5
    assert s["secagg"] == {"rounds": 1,
                           "phase_bytes": {"advertise": {"down": 5, "up": 7}},
                           "recovery_bytes": 64, "n_dropped": 1}
    assert s["metrics"]["sched.admits"] == 4
    assert s["spans"]["round"] == 2


def test_check_golden_and_corruptions():
    evs = _golden_events()
    assert E.check(evs, require_kinds=["run", "round", "secagg"]) == []
    assert E.check(evs, require_kinds=["pipeline"]) \
        == ["required span kind 'pipeline' absent"]
    assert E.check([]) == ["empty trace"]
    bad = [dict(e) for e in evs]
    bad[1] = dict(bad[1], id=2)                    # duplicate id
    assert any("duplicate id" in p for p in E.check(bad))
    bad = [dict(e) for e in evs]
    bad[2] = dict(bad[2], attrs={"down_bytes": 1.5, "up_bytes": 0,
                                 "sim_time_s": 0.0})
    assert any("bad down_bytes" in p for p in E.check(bad))
    assert any("not a meta record" in p for p in E.check(evs[1:]))
    orphan = evs + [dict(evs[2], id=99, parent=98)]
    assert any("dangling parent" in p for p in E.check(orphan))


def test_diff_golden():
    a = {"comm_gb": 1.0, "n_rounds": 2, "only_a": 5}
    b = {"comm_gb": 1.1, "n_rounds": 2, "only_b": 7}
    d = E.diff(a, b)
    assert d["comm_gb"]["delta"] == pytest.approx(0.1)
    assert d["comm_gb"]["rel"] == pytest.approx(0.1)
    assert d["n_rounds"]["delta"] == 0
    assert d["only_a"]["b"] is None and d["only_b"]["a"] is None


def test_chrome_trace_golden():
    ct = E.chrome_trace(_golden_events())
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 5
    rnd = next(e for e in xs if e["name"] == "round")
    assert rnd["ts"] == 0.0 and rnd["dur"] == pytest.approx(0.4e6)
    assert any(e["ph"] == "i" and e["name"] == "inflight_comm"
               for e in ct["traceEvents"])


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_summarize_check_diff_chrome(tmp_path, capsys):
    p1 = str(tmp_path / "a.jsonl")
    E.write_jsonl(p1, _golden_events())

    assert obs_main(["check", p1, "--require-kinds", "run,round"]) == 0
    assert "ok:" in capsys.readouterr().out
    assert obs_main(["check", p1, "--require-kinds", "pipeline"]) == 1
    assert "PROBLEM" in capsys.readouterr().err

    assert obs_main(["summarize", p1, "--format", "json"]) == 0
    s = json.loads(capsys.readouterr().out)
    assert s["n_rounds"] == 2

    evs2 = _golden_events()
    evs2[2]["attrs"]["up_bytes"] = 400          # 10x regression in round 1
    p2 = str(tmp_path / "b.jsonl")
    E.write_jsonl(p2, evs2)
    assert obs_main(["diff", p1, p2]) == 0      # no tolerance → report only
    capsys.readouterr()
    assert obs_main(["diff", p1, p2, "--rel-tol", "0.5"]) == 1
    assert "FAIL" in capsys.readouterr().err

    out = str(tmp_path / "c.json")
    assert obs_main(["chrome", p1, "-o", out]) == 0
    assert json.load(open(out))["traceEvents"]


def test_cli_check_unreadable(tmp_path, capsys):
    p = tmp_path / "garbage.jsonl"
    p.write_text("not json\n")
    assert obs_main(["check", str(p)]) == 1
    assert "unreadable" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# trace-parity acceptance: history == summarize(trace), exactly
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    from repro.configs.distilbert import MINI
    from repro.data.synthetic import make_classification
    from repro.federated.partition import dirichlet_partition
    cfg = MINI.with_(n_layers=1, layer_pattern=("attn",))
    train = make_classification(400, 10, cfg.vocab_size, 24, seed=1)
    test = make_classification(120, 10, cfg.vocab_size, 24, seed=2)
    parts = dirichlet_partition(train.labels, 6, alpha=0.3, seed=0)
    return cfg, train, test, parts


def _traced_run(setup, path, **fc_kw):
    from repro.federated.baselines import all_strategies
    from repro.federated.server import FedConfig, run_federated
    from repro.models import Model
    cfg, train, test, parts = setup
    rounds = fc_kw.pop("rounds", 2)
    strat = all_strategies(rounds=rounds)[fc_kw.pop("strategy", "fedlora")]
    for k, v in fc_kw.pop("strategy_params", {}).items():
        setattr(strat, k, v)
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=rounds, clients_per_round=3, batch_size=16,
                   max_local_batches=2, lr=3e-3,
                   eval_every=fc_kw.pop("eval_every", rounds), **fc_kw)
    try:
        obs.configure(path, meta=obs.provenance({"cmd": "test"}))
        h = run_federated(model, strat, parts, train, test, fc)
        obs.close()
    finally:
        obs.disable()
    return h


def _assert_parity(h, s):
    # EXACT float equality, not allclose: the summary replays the runner's
    # own accumulation (this is the ISSUE's acceptance criterion)
    assert s["comm_gb"] == h["comm_gb"]
    assert s["sim_time_s"] == h["sim_time_s"]
    assert s["n_rounds"] == len(h["rounds"])
    assert s["down_bytes"] == sum(l.down_bytes for l in h["rounds"])
    assert s["up_bytes"] == sum(l.up_bytes for l in h["rounds"])
    if h.get("final_acc") == h.get("final_acc"):       # non-NaN
        assert s["final_acc"] == h["final_acc"]


def test_traced_secagg_signsgd_run_parity(setup, tmp_path):
    """The issue's acceptance run: --secagg mask --codec signsgd with
    dropout, traced; summarize reconstructs history exactly."""
    path = str(tmp_path / "fed.jsonl")
    h = _traced_run(setup, path, runner="cohort", secagg="mask",
                    codec="signsgd", dropout=0.3, event_seed=3,
                    secagg_threshold=0.5)
    events = E.read_jsonl(path)
    assert E.check(events, require_kinds=[
        "run", "round", "client", "pipeline", "secagg", "secagg-phase"]) == []
    s = E.summarize(events)
    _assert_parity(h, s)
    # per-phase secagg bytes: trace sums == history sums, int-exact
    want = {}
    for r in h["secagg_rounds"]:
        for name, pc in r["phases"].items():
            w = want.setdefault(name, {"down": 0, "up": 0})
            w["down"] += pc["down"]
            w["up"] += pc["up"]
    assert s["secagg"]["phase_bytes"] == want
    assert s["secagg"]["rounds"] == len(h["secagg_rounds"])
    assert s["secagg"]["recovery_bytes"] == \
        sum(r["recovery_bytes"] for r in h["secagg_rounds"])
    # byte provenance metrics carry codec+stage labels
    assert any(k.startswith("pipeline.up_bytes{") and "codec=signsgd" in k
               for k in s.get("metrics", {}))


def test_traced_async_run_parity(setup, tmp_path):
    """Async: round spans + trailing inflight_comm event reproduce comm_gb
    exactly; dict-normalized events survive the JSONL round-trip."""
    path = str(tmp_path / "async.jsonl")
    h = _traced_run(setup, path, runner="async", buffer_k=3,
                    straggler=0.25, rounds=2)
    events = E.read_jsonl(path)
    assert E.check(events, require_kinds=["run", "round"]) == []
    s = E.summarize(events)
    _assert_parity(h, s)
    assert all(ev["type"] == "event" and "sim_t" in ev
               for ev in h["events"])      # satellite: normalized schema
    # every history event is mirrored into the trace
    traced = [e for e in events if e.get("type") == "event"
              and e.get("name") in ("dispatch", "update")]
    assert len(traced) == len(h["events"])


def test_untraced_run_history_identical(setup):
    """With tracing disabled the recorder is just a dict — same keys, same
    values, no trace side channel."""
    from repro.federated.baselines import all_strategies
    from repro.federated.server import FedConfig, run_federated
    from repro.models import Model
    cfg, train, test, parts = setup
    obs.disable()
    strat = all_strategies(rounds=2)["fedlora"]
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=2, clients_per_round=3, batch_size=16,
                   max_local_batches=2, eval_every=2, lr=3e-3)
    h = run_federated(model, strat, parts, train, test, fc)
    assert isinstance(h, dict)
    assert np.isfinite(h["rounds"][-1].loss)
    assert h["comm_gb"] > 0 and len(h["rounds"]) == 2
    assert obs.get_tracer().events() == []


def test_zero_round_run_guard(setup):
    """rounds=0: both sync runners must report final_acc=NaN, not crash."""
    from repro.federated.baselines import all_strategies
    from repro.federated.server import FedConfig, run_federated
    from repro.models import Model
    cfg, train, test, parts = setup
    for runner in ("seq", "cohort"):
        strat = all_strategies(rounds=1)["fedlora"]
        model = Model(cfg, peft=strat.peft, unroll=True)
        fc = FedConfig(rounds=0, clients_per_round=3, batch_size=16,
                       max_local_batches=2, eval_every=1, lr=3e-3,
                       runner=runner)
        h = run_federated(model, strat, parts, train, test, fc)
        assert h["rounds"] == [] and h["comm_gb"] == 0.0
        assert h["final_acc"] != h["final_acc"]        # NaN


# ---------------------------------------------------------------------------
# forensics: rank trajectory, compile flatness, no-alert golden
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fedara_trace(setup, tmp_path_factory):
    """One traced 3-round fedara cohort run shared by the forensics tests."""
    path = str(tmp_path_factory.mktemp("fedara") / "fedara.jsonl")
    h = _traced_run(setup, path, runner="cohort", strategy="fedara",
                    rounds=3)
    return h, E.read_jsonl(path)


@pytest.fixture(scope="module")
def fedara_masks_trace(setup, tmp_path_factory):
    """A traced 4-round fedara cohort run with no warm-up, so the rank
    budget falls from round 0 and the masks change value every round."""
    path = str(tmp_path_factory.mktemp("fedara_masks") / "fedara.jsonl")
    h = _traced_run(setup, path, runner="cohort", strategy="fedara",
                    rounds=4, strategy_params={"warmup_rounds": 0})
    return h, E.read_jsonl(path)


@pytest.fixture(scope="module")
def fedlora_eval_trace(setup, tmp_path_factory):
    """A traced 5-round FedLoRA cohort run that evaluates after rounds 1, 3
    and 4: eval boundaries inside the round loop, not only at its end."""
    path = str(tmp_path_factory.mktemp("fedlora_eval") / "fedlora.jsonl")
    h = _traced_run(setup, path, runner="cohort", strategy="fedlora",
                    rounds=5, eval_every=2)
    return h, E.read_jsonl(path)


@pytest.mark.parametrize("trace", ["fedara_trace", "fedara_masks_trace",
                                   "fedlora_eval_trace"])
def test_compile_flat_after_first_round(trace, request):
    """Compile-span accounting on a traced cohort run
    shows zero new compilations after round 1.  Rounds are 0-indexed in
    the trace, so 'after round 1' == no backend compile under a round span
    with rnd >= 1 (the eval span buckets separately — evaluating at the end
    legitimately compiles the eval step once).  The round loop's prune and
    placement take the masks as arguments: masks that change value between
    rounds compile nothing either."""
    from repro.obs import profile as P
    h, events = request.getfixturevalue(trace)
    if trace == "fedara_masks_trace":
        live = [log.live_ranks for log in h["rounds"]]
        assert live[0] > live[1] > live[2], live
    if trace == "fedlora_eval_trace":
        assert [r for r, _ in h["acc"]] == [1, 3, 4]
    cs = P.compile_stats(events)
    assert cs["after_first_round"] == 0, cs["by_round"]
    assert all(rnd == 0 for rnd in cs["by_round"]), cs["by_round"]
    # the accounting is live, not vacuous: this run's fresh jit closures
    # compiled *somewhere*, and eval's compile is attributed to its own
    # bucket rather than inflating a round
    assert cs["n"] >= 1
    assert cs["eval"] >= 1


def test_rank_trajectory_reconstructs_history(fedara_trace):
    """The per-round live-rank counts — the paper's allocation decision —
    reconstruct from the JSONL alone and match the runner's history."""
    h, events = fedara_trace
    traj = E.rank_trajectory(events)
    want = {log.rnd: log.live_ranks for log in h["rounds"]}
    assert traj["live"] == want
    assert traj["total"] == h["rounds"][0].live_ranks \
        or traj["total"] >= max(want.values())
    # every module appears with per-round live counts
    assert traj["modules"]
    for mod, per_round in traj["modules"].items():
        assert set(per_round) <= set(traj["rounds"])
    s = E.summarize(events)
    assert s["ranks"]["rounds"] == len(h["rounds"])
    assert s["ranks"]["final_live"] == h["rounds"][-1].live_ranks


def test_clean_run_emits_no_alerts(fedara_trace):
    """No-alert golden: a healthy short run must stay silent — both the
    live monitor (embedded alert events) and the offline scan."""
    from repro.obs import health as H
    _, events = fedara_trace
    assert H.embedded_alerts(events) == []
    assert H.scan(events) == []
    s = E.summarize(events)
    assert s["alerts"] == {"n": 0, "by_type": {}}


def test_memory_watermark_events_present(fedara_trace):
    """Round boundaries sample device memory; on backends with no memory
    stats (CPU) the sampler degrades to silence rather than erroring."""
    _, events = fedara_trace
    mems = [e for e in events if e.get("type") == "event"
            and e.get("name") == "memory"]
    import jax
    if jax.devices()[0].memory_stats():
        assert mems
    else:
        assert mems == []


# ---------------------------------------------------------------------------
# serving instrumentation
# ---------------------------------------------------------------------------

def test_scheduler_stats_and_bounded_retention():
    from repro.serving.scheduler import Scheduler
    sch = Scheduler(n_slots=2, max_seq=16, max_retained=3)
    for _ in range(5):
        sch.submit("t", np.arange(4), 0)       # invalid → rejected
    ok = sch.submit("t", np.arange(4), 4)
    sch.admit()
    sch.reject(ok, "unknown adapter", kind="unknown_adapter")
    st = sch.stats()
    assert st["submitted"] == 6
    assert st["rejects"] == {"invalid": 5, "unknown_adapter": 1}
    assert st["admits"] == 1
    assert len(sch.rejected) == 3              # bounded triage window


# ---------------------------------------------------------------------------
# cohort-scale trace sampling (head-sample + tail-keep + rollups)
# ---------------------------------------------------------------------------

class _StubLog:
    """RoundLog stand-in: just what end_round reads."""

    def __init__(self, loss, acc):
        self.loss, self.acc = loss, acc


def _synthetic_round(rec, rnd, n_clients, alert_cid=None):
    """One stubbed cohort round through the recorder: n_clients client
    spans with deterministic losses/bytes, optionally one alert event
    implicating ``alert_cid`` (tail-keep trigger)."""
    rsp = rec.begin_round(rnd)
    down = up = 0
    for cid in range(n_clients):
        csp = rec.begin_client(cid)
        ub = 1000 + cid
        up += ub
        down += 2000
        if cid == alert_cid:
            obs.get_tracer().event("alert", alert="ef_blowup", cid=cid,
                                   rnd=rnd)
        csp.end(n_steps=4, up_bytes=ub, loss=1.0 + cid * 1e-3)
    rec.add_sim(12.5)
    rec.end_round(rsp, _StubLog(1.5, 0.5), down, up)
    return down, up


def _run_synthetic(tmp_path, name, n_clients, rounds, client_sample,
                   alert_cid=None):
    path = str(tmp_path / name)
    try:
        obs.configure(path, health=False, profile=False,
                      client_sample=client_sample, sample_seed=0)
        rec = obs.RunRecorder("cohort")
        for rnd in range(rounds):
            _synthetic_round(rec, rnd, n_clients, alert_cid=alert_cid)
        rec.finish()
        return rec, obs.close()
    finally:
        obs.disable()


def test_sampled_1000_client_round_acceptance(tmp_path):
    """The ISSUE's acceptance bar: a traced 1000-client synthetic round
    emits ≤ 5% of the unsampled events, summarize/check reconstruct
    comm_gb/sim_time_s exactly, and rollup sketch quantiles stay within
    the documented relative-error bound of the exact per-client values."""
    from repro.obs.sketch import DEFAULT_REL_ERR
    n, rounds = 1000, 2
    rec_full, ev_full = _run_synthetic(tmp_path, "full.jsonl", n, rounds,
                                       client_sample=None)
    rec_smp, ev_smp = _run_synthetic(tmp_path, "sampled.jsonl", n, rounds,
                                     client_sample=0.02)
    assert len(ev_smp) <= 0.05 * len(ev_full), (len(ev_smp), len(ev_full))

    # exact counters survive sampling (round spans are never pruned)
    s = E.summarize(ev_smp)
    assert s["comm_gb"] == rec_smp["comm_gb"] == rec_full["comm_gb"]
    assert s["sim_time_s"] == rec_smp["sim_time_s"]
    assert s["down_bytes"] == E.summarize(ev_full)["down_bytes"]
    assert E.check(ev_smp) == []

    # rollup sketches: one per round, full population counted, quantiles
    # within the rel-error bound of the exact nearest-rank values
    ro = s["rollup"]
    assert ro["rounds"] == rounds
    assert ro["n_clients"] == n * rounds
    assert 0 < ro["n_kept"] < n * rounds
    losses = sorted([1.0 + cid * 1e-3 for cid in range(n)] * rounds)
    for q, tag in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
        exact = losses[int(round(q * (len(losses) - 1)))]
        est = ro["dists"]["loss"][tag]
        assert abs(est - exact) <= DEFAULT_REL_ERR * exact * (1 + 1e-6), \
            (tag, est, exact)
    assert ro["dists"]["loss"]["count"] == n * rounds


def test_sampling_is_deterministic_and_head_sampled(tmp_path):
    from repro.obs.trace import client_keep
    _, ev_a = _run_synthetic(tmp_path, "a.jsonl", 300, 1, client_sample=0.1)
    _, ev_b = _run_synthetic(tmp_path, "b.jsonl", 300, 1, client_sample=0.1)
    kept = lambda evs: sorted(  # noqa: E731
        e["attrs"]["cid"] for e in evs
        if e.get("type") == "span" and e.get("kind") == "client")
    assert kept(ev_a) == kept(ev_b)            # same seed → same clients
    # and they are exactly the head-sample decision function's picks
    expect = [c for c in range(300) if client_keep(0, 0, c, 0.1)]
    assert kept(ev_a) == expect


def test_tail_keep_on_alert(tmp_path):
    """A client implicated in an alert keeps its spans even when the head
    sample would have dropped it."""
    from repro.obs.trace import client_keep
    alert_cid = next(c for c in range(200)
                     if not client_keep(0, 0, c, 0.05))
    _, events = _run_synthetic(tmp_path, "alerted.jsonl", 200, 1,
                               client_sample=0.05, alert_cid=alert_cid)
    kept_cids = {e["attrs"]["cid"] for e in events
                 if e.get("type") == "span" and e.get("kind") == "client"}
    assert alert_cid in kept_cids
    # the alert event itself is never pruned
    assert any(e.get("type") == "event" and e.get("name") == "alert"
               and (e.get("attrs") or {}).get("cid") == alert_cid
               for e in events)
    # rollup n_kept counts the tail-kept client too
    (rollup,) = [e for e in events if e.get("type") == "span"
                 and e.get("kind") == "rollup"]
    assert rollup["attrs"]["n_kept"] == len(kept_cids)


def test_unsampled_trace_has_no_rollups(tmp_path):
    _, events = _run_synthetic(tmp_path, "uns.jsonl", 20, 1,
                               client_sample=None)
    assert not [e for e in events if e.get("kind") == "rollup"]
    assert E.summarize(events).get("rollup") is None


def test_check_flags_malformed_rollup():
    events = _golden_events()
    events.append({"type": "span", "id": 99, "parent": None,
                   "name": "cohort_rollup", "kind": "rollup", "t0": 0.0,
                   "dur": 0.0, "sim_t0": 0.0, "sim_dur": 0.0,
                   "attrs": {"n_clients": 5, "n_kept": "two",
                             "sketches": {"loss": {"pos": {}}}}})
    problems = E.check(events)
    assert any("bad n_kept" in p for p in problems)
    assert any("malformed sketch" in p for p in problems)
