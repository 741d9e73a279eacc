"""Health detectors and the forensics report.

Everything here is stdlib-only by design (no jax import): the detectors,
and ``report`` operate on plain dicts read back from JSONL,
so the whole active-observability surface is testable without an accelerator.

The synthetic fixtures below pin the *exact* alert payloads — the alert
schema is an interface (CI greps it, the report renders it), so payload
drift is a breaking change, not an implementation detail.
"""

import json
import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.obs import export as E  # noqa: E402
from repro.obs import health as H  # noqa: E402
from repro.obs import profile as P  # noqa: E402
from repro.obs import report as REP  # noqa: E402
from repro.obs.__main__ import main as obs_main  # noqa: E402


# ---------------------------------------------------------------------------
# synthetic trace builders
# ---------------------------------------------------------------------------

def _meta():
    return {"type": "meta", "schema": 1, "t_epoch": 0.0, "meta": {}}


def _round(rnd, **attrs):
    return {"type": "span", "id": 100 + rnd, "parent": None, "name": "round",
            "kind": "round", "t0": float(rnd), "dur": 1.0,
            "sim_t0": 0.0, "sim_dur": 0.0, "attrs": {"rnd": rnd, **attrs}}


def _secagg(rnd, **attrs):
    return {"type": "span", "id": 200 + rnd, "parent": None, "name": "secagg",
            "kind": "secagg", "t0": float(rnd), "dur": 0.1,
            "sim_t0": 0.0, "sim_dur": 0.0, "attrs": {"rnd": rnd, **attrs}}


def _event(name, **attrs):
    return {"type": "event", "name": name, "t": 0.0, "sim_t": 0.0,
            "attrs": attrs}


def _scan_jsonl(tmp_path, events):
    """Round-trip through JSONL before scanning: the forensics contract is
    that alerts reconstruct from the serialized trace alone."""
    p = str(tmp_path / "trace.jsonl")
    E.write_jsonl(p, [_meta()] + events)
    return H.scan(E.read_jsonl(p))


# ---------------------------------------------------------------------------
# detectors: exact payloads
# ---------------------------------------------------------------------------

def test_nan_loss_alert(tmp_path):
    alerts = _scan_jsonl(tmp_path, [_round(0, loss=1.0),
                                    _round(1, loss=float("nan"))])
    assert len(alerts) == 1
    a = alerts[0]
    assert a["alert"] == "nan_loss" and a["rnd"] == 1
    assert math.isnan(a["loss"])


def test_loss_divergence_alert(tmp_path):
    alerts = _scan_jsonl(tmp_path, [_round(0, loss=1.0), _round(1, loss=0.8),
                                    _round(2, loss=3.0)])
    assert alerts == [{"alert": "loss_divergence", "rnd": 2,
                       "loss": 3.0, "best": 0.8}]


def test_loss_divergence_needs_min_rounds(tmp_path):
    # round 1 already exceeds the factor but only one round is on record
    alerts = _scan_jsonl(tmp_path, [_round(0, loss=1.0),
                                    _round(1, loss=9.0)])
    assert alerts == []


def test_straggler_skew_alert(tmp_path):
    alerts = _scan_jsonl(
        tmp_path, [_round(0, loss=1.0, cost_max=8.0, cost_med=1.0)])
    assert alerts == [{"alert": "straggler_skew", "rnd": 0,
                       "cost_max": 8.0, "cost_med": 1.0, "ratio": 8.0}]


def test_secagg_abort_and_dropout_skew(tmp_path):
    alerts = _scan_jsonl(tmp_path, [
        _secagg(0, participants=4, n_dropped=1),          # healthy
        _secagg(1, participants=4, n_dropped=2),          # skew (frac 0.5)
        _secagg(2, participants=4, n_dropped=3, aborted=True)])
    assert alerts == [
        {"alert": "dropout_skew", "rnd": 1, "n_dropped": 2,
         "participants": 4, "frac": 0.5},
        {"alert": "secagg_abort", "rnd": 2, "n_dropped": 3,
         "participants": 4}]


def test_rank_collapse_fires_once_until_revived(tmp_path):
    mod = "layer0.attn.q"
    alerts = _scan_jsonl(tmp_path, [
        _event("rank_alloc", rnd=0,
               modules={mod: {"live": 4, "total": 12}}),
        _event("rank_alloc", rnd=1,
               modules={mod: {"live": 0, "total": 12}}),
        _event("rank_alloc", rnd=2,                       # still dead: quiet
               modules={mod: {"live": 0, "total": 12}}),
        _event("rank_alloc", rnd=3,                       # revived
               modules={mod: {"live": 2, "total": 12}}),
        _event("rank_alloc", rnd=4,                       # re-collapse fires
               modules={mod: {"live": 0, "total": 12}})])
    assert alerts == [
        {"alert": "rank_collapse", "rnd": 1, "module": mod, "total": 12},
        {"alert": "rank_collapse", "rnd": 4, "module": mod, "total": 12}]


def test_ef_blowup_alert_once_per_client(tmp_path):
    warm = [_event("encode", cid=c, ef_norm=1.0) for c in range(8)]
    alerts = _scan_jsonl(tmp_path, warm + [
        _event("encode", cid=5, ef_norm=20.0),
        _event("encode", cid=5, ef_norm=30.0),            # same cid: quiet
        _event("encode", cid=6, ef_norm=0.9)])            # healthy
    assert alerts == [{"alert": "ef_blowup", "cid": 5, "ef_norm": 20.0,
                       "baseline": 1.0}]


def test_client_drift_alert(tmp_path):
    alerts = _scan_jsonl(tmp_path, [
        _event("drift", n=4, mean_cos=0.5, dispersion=0.5),
        _event("drift", n=4, mean_cos=0.02, dispersion=0.98)])
    assert alerts == [{"alert": "client_drift", "rnd": None,
                       "dispersion": 0.98, "n": 4}]


def test_scan_skips_embedded_alerts(tmp_path):
    """Scanning a live-monitored trace must not double-count its alerts."""
    evs = [_round(0, loss=float("nan")),
           _event("alert", alert="nan_loss", rnd=0, loss=None)]
    alerts = _scan_jsonl(tmp_path, evs)
    assert len(alerts) == 1 and alerts[0]["alert"] == "nan_loss"
    p = str(tmp_path / "emb.jsonl")
    E.write_jsonl(p, [_meta()] + evs)
    emb = H.embedded_alerts(E.read_jsonl(p))
    assert emb == [{"alert": "nan_loss", "rnd": 0, "loss": None}]


def test_live_attach_mirrors_scan():
    """attach() writes the same payloads into the trace that scan() returns."""
    from repro import obs
    try:
        tr = obs.configure(None, health=True, profile=False)
        rsp = tr.begin("round", kind="round", rnd=0)
        rsp.end(loss=float("inf"), down_bytes=0, up_bytes=0, sim_time_s=0.0)
        evs = tr.events()
    finally:
        obs.disable()
    emb = H.embedded_alerts(evs)
    assert len(emb) == 1 and emb[0]["alert"] == "nan_loss"
    assert H.scan(evs) == emb


# ---------------------------------------------------------------------------
# report: forensics from the JSONL alone
# ---------------------------------------------------------------------------

def _report_events():
    mod_a, mod_b = "layer0.attn.q", "layer0.attn.v"
    return [
        _round(0, loss=1.0, down_bytes=10, up_bytes=20, sim_time_s=1.0),
        _round(1, loss=float("nan"), down_bytes=10, up_bytes=20,
               sim_time_s=1.0),
        _event("rank_alloc", rnd=0, live=10, total=24,
               modules={mod_a: {"live": 6, "total": 12},
                        mod_b: {"live": 4, "total": 12}}),
        _event("rank_alloc", rnd=1, live=6, total=24,
               modules={mod_a: {"live": 6, "total": 12},
                        mod_b: {"live": 0, "total": 12}}),
        _event("module_pruned", rnd=1, module=mod_b),
        {"type": "span", "id": 300, "parent": None, "name": "backend_compile",
         "kind": "compile", "t0": 0.0, "dur": 1.5, "sim_t0": 0.0,
         "sim_dur": 0.0, "attrs": {}},
        {"type": "metric", "metric": "counter", "name": "pipeline.up_bytes",
         "labels": {"codec": "topk", "stage": "stage2"}, "value": 1234},
    ]


def test_report_build_and_render(tmp_path):
    p = str(tmp_path / "rep.jsonl")
    E.write_jsonl(p, [_meta()] + _report_events())
    rep = REP.build_report(E.read_jsonl(p))
    assert rep["trajectory"]["rounds"] == [0, 1]
    assert rep["trajectory"]["pruned"] == [{"rnd": 1,
                                            "module": "layer0.attn.v"}]
    assert any(b["codec"] == "topk" and b["up"] == 1234
               for b in rep["bytes_by"])
    assert any(a["alert"] == "nan_loss" for a in rep["alerts"])
    assert rep["compiles"]["n"] == 1

    txt = REP.render_text(rep)
    assert "layer0.attn.v" in txt and "×" in txt      # pruned cell marker
    assert "nan_loss" in txt and "topk" in txt

    html = REP.render_html(rep)
    assert html.lstrip().lower().startswith("<!doctype html>")
    assert "layer0.attn.q" in html and "nan_loss" in html


def test_self_times_attribution(tmp_path):
    # round(10s) > dispatch(6s) > compile(2s): self-time subtracts only
    # *direct* children, compile time is carved out on the span that paid it.
    events = [
        {"type": "span", "id": 1, "parent": None, "name": "round",
         "kind": "round", "t0": 0.0, "dur": 10.0, "sim_t0": 0.0,
         "sim_dur": 0.0, "attrs": {"rnd": 0}},
        {"type": "span", "id": 2, "parent": 1, "name": "cohort_step",
         "kind": "dispatch", "t0": 1.0, "dur": 6.0, "sim_t0": 0.0,
         "sim_dur": 0.0, "attrs": {}},
        {"type": "span", "id": 3, "parent": 2, "name": "backend_compile",
         "kind": "compile", "t0": 1.5, "dur": 2.0, "sim_t0": 0.0,
         "sim_dur": 0.0, "attrs": {}},
    ]
    p = str(tmp_path / "st.jsonl")
    E.write_jsonl(p, [_meta()] + events)
    st = P.self_times(E.read_jsonl(p))
    assert "compile/backend_compile" not in st   # compiles are not rows
    rnd = st["round/round"]
    assert rnd == {"n": 1, "total_s": 10.0, "self_s": 4.0, "compile_s": 0.0}
    dsp = st["dispatch/cohort_step"]
    assert dsp == {"n": 1, "total_s": 6.0, "self_s": 4.0, "compile_s": 2.0}

    rep = REP.build_report(E.read_jsonl(p))
    assert rep["self_times"] == st
    assert "device time by span" in REP.render_text(rep)
    assert "Device time by span" in REP.render_html(rep)


def test_report_cli_writes_html(tmp_path, capsys):
    p = str(tmp_path / "rep.jsonl")
    E.write_jsonl(p, [_meta()] + _report_events())
    out = str(tmp_path / "rep.html")
    assert obs_main(["report", p, "-o", out]) == 0
    assert open(out).read().lstrip().lower().startswith("<!doctype html>")
    capsys.readouterr()
    assert obs_main(["report", p]) == 0               # terminal mode
    assert "layer0.attn.q" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# graceful degradation: empty / span-less traces (satellite)
# ---------------------------------------------------------------------------

def test_cli_graceful_on_empty_and_spanless_traces(tmp_path, capsys):
    empty = str(tmp_path / "empty.jsonl")
    E.write_jsonl(empty, [_meta()])
    spanless = str(tmp_path / "spanless.jsonl")
    E.write_jsonl(spanless, [_meta(), _event("dispatch", cid=0)])

    for p in (empty, spanless):
        assert obs_main(["summarize", p, "--format", "json"]) == 0
        s = json.loads(capsys.readouterr().out)
        assert s["n_rounds"] == 0
        assert obs_main(["report", p]) == 0
        capsys.readouterr()
        assert obs_main(["chrome", p,
                         "-o", str(tmp_path / "ct.json")]) == 0
        capsys.readouterr()
    # check still *reports* the span-less shape (strictness lives there)
    assert obs_main(["check", spanless, "--require-kinds", "round"]) == 1
    capsys.readouterr()


def test_check_require_metrics(tmp_path, capsys):
    p = str(tmp_path / "m.jsonl")
    E.write_jsonl(p, [_meta(), _round(0, loss=1.0, down_bytes=0, up_bytes=0,
                                      sim_time_s=0.0),
                      {"type": "metric", "metric": "counter",
                       "name": "pipeline.up_bytes",
                       "labels": {"codec": "topk"}, "value": 7}])
    assert obs_main(["check", p, "--require-metrics", "pipeline.up_bytes"]) \
        == 0
    capsys.readouterr()
    assert obs_main(["check", p, "--require-metrics",
                     "pipeline.up_bytes,serve.step_s"]) == 1
    assert "serve.step_s" in capsys.readouterr().err
