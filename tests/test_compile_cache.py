"""Persistent compilation cache (repro.compat): a cohort run in a second
process compiles nothing once a first one has filled the cache, and the
cache's default directory is the one fixed path inside the checkout."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.obs import export as E
from repro.obs import profile as P

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
                   runner="cohort")
    obs.configure(sys.argv[1], meta=obs.provenance({"cmd": "cache-test"}))
    h = run_federated(model, strat, parts, train, test, fc)
    obs.close()
    print("CACHE_RUN_OK", h["final_acc"])
""")


def test_compilation_cache_across_processes(tmp_path):
    """Two identical cohort runs in separate processes sharing one cache
    dir: run 1 populates it, run 2 must be compile-free — asserted from the
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
