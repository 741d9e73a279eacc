"""Serving-path benchmark: multi-tenant engine throughput/latency vs the
number of distinct adapters and the rank spread, plus batched-kernel step
timing vs the sequential per-request reference.

Emits the usual CSV rows through benchmarks/common.py AND a JSON record list
(BENCH_serving.json, override with BENCH_SERVING_JSON) so the perf
trajectory starts tracking the serving path.

  PYTHONPATH=src BENCH_ONLY=serving python -m benchmarks.run
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.configs import get_config
from repro.kernels.bea_batched import bea_batched
from repro.kernels.ref import bea_batched_ref
from repro.launch.serve import build_engine

JSON_PATH = os.environ.get("BENCH_SERVING_JSON", "BENCH_serving.json")


def _serve_once(cfg, n_req, n_tenants, ranks, gen, prompt_len, n_slots):
    engine = build_engine(cfg, n_slots=n_slots, max_seq=prompt_len + gen,
                          n_tenants=n_tenants, ranks=ranks)
    rng = np.random.default_rng(0)
    tenant_ids = engine.registry.ids()
    reqs = [engine.submit(tenant_ids[i % len(tenant_ids)],
                          rng.integers(0, cfg.vocab_size, prompt_len), gen)
            for i in range(n_req)]
    t0 = time.time()
    engine.run()
    wall = time.time() - t0
    n_tok = sum(len(r.out) for r in reqs)
    lat = [r.finish_step - r.submit_step for r in reqs]
    return {"tok_per_s": n_tok / max(wall, 1e-9), "wall_s": wall,
            "mean_latency_steps": float(np.mean(lat)),
            "max_latency_steps": float(np.max(lat)),
            "decode_calls": engine.decode_calls, "steps": engine.steps}


def _kernel_step(m, k, n, g, r, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), jnp.float32)
    a = jnp.asarray(rng.normal(size=(g, r, k)) / np.sqrt(k), jnp.float32)
    b = jnp.asarray(rng.normal(size=(g, n, r)), jnp.float32)
    e = jnp.asarray(rng.normal(size=(g, r)), jnp.float32)
    msk = jnp.ones((g, r), jnp.float32)
    idx = jnp.asarray(rng.integers(0, g, (m,)), jnp.int32)

    # untimed warmup: exclude trace/compile from both paths
    jax.block_until_ready(bea_batched(x, w, a, b, e, msk, idx, scaling=1.0,
                                      block_m=32, block_n=64, block_k=64))
    jax.block_until_ready(bea_batched_ref(x, w, a, b, e, msk, idx, 1.0))

    t0 = time.time()
    out = bea_batched(x, w, a, b, e, msk, idx, scaling=1.0,
                      block_m=32, block_n=64, block_k=64)
    jax.block_until_ready(out)
    t_batched = time.time() - t0

    t0 = time.time()
    ref = bea_batched_ref(x, w, a, b, e, msk, idx, 1.0)
    jax.block_until_ready(ref)
    t_seq = time.time() - t0
    return t_batched, t_seq


def main(quick: bool = False):
    cfg = get_config("qwen2_0p5b", smoke=True)
    gen = 4 if quick else 6
    prompt_len = 12
    n_req = 8 if quick else 16
    records = []

    # throughput vs number of distinct adapters (homogeneous rank 8)
    for n_ad in ([1, 4] if quick else [1, 2, 4, 8]):
        res = _serve_once(cfg, n_req, n_ad, [8], gen, prompt_len, n_slots=8)
        rec = dict(name="serving/adapters", n_adapters=n_ad, rank_spread="r8",
                   n_requests=n_req, **res)
        records.append(rec)
        C.emit([C.row(f"serving/tok_per_s/adapters{n_ad}",
                      f"{res['tok_per_s']:.2f}",
                      latency=f"{res['mean_latency_steps']:.1f}",
                      decode_calls=res["decode_calls"])])

    # throughput vs rank spread (4 adapters)
    spreads = {"uniform8": [8], "spread": [2, 4, 8, 16]}
    for label, ranks in spreads.items():
        res = _serve_once(cfg, n_req, 4, ranks, gen, prompt_len, n_slots=8)
        rec = dict(name="serving/rank_spread", n_adapters=4,
                   rank_spread=label, n_requests=n_req, **res)
        records.append(rec)
        C.emit([C.row(f"serving/tok_per_s/{label}", f"{res['tok_per_s']:.2f}",
                      latency=f"{res['mean_latency_steps']:.1f}",
                      decode_calls=res["decode_calls"])])

    # batched kernel vs sequential per-request reference (Mosaic on a TPU,
    # interpret mode elsewhere — there a relative trend only)
    for g in ([2] if quick else [2, 4, 8]):
        t_b, t_s = _kernel_step(16, 64, 64, g, 8)
        rec = dict(name="serving/kernel", n_adapters=g, batched_s=t_b,
                   sequential_s=t_s, speedup=t_s / max(t_b, 1e-9))
        records.append(rec)
        C.emit([C.row(f"serving/kernel_step_s/g{g}", f"{t_b:.4f}",
                      sequential=f"{t_s:.4f}")])

    with open(JSON_PATH, "w") as f:
        json.dump(records, f, indent=1)
    C.emit([C.row("serving/json", JSON_PATH, records=len(records))])


if __name__ == "__main__":
    main(quick=C.QUICK)
