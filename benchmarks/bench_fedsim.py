"""fedsim benchmark: cohort-vs-sequential round throughput, delta-codec
byte ratios + convergence-vs-bytes curves (identity / int8 / topk /
signsgd / powersgd through the shared upload pipeline), and async event
throughput.

The throughput comparison runs in this process on the devices JAX finds
(the shard_map cohort axis spans all of them; on a CPU host, fake several
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on the command
line) and measures *steady-state* seconds/round from per-round
``perf_counter`` marks (one ``on_round`` callback per round), dropping the
warmup intervals where jit compile time lands and taking the median of the
rest — see benchmarks/common.py ``steady_state``.  Clients are
IID-partitioned so every cohort slot carries real work (dirichlet skew creates sub-batch clients that fall back to the sequential
path and padded slots that waste cohort compute — that regime is the
round-robin fallback's job, not this benchmark's).

Emits CSV rows through benchmarks/common.py and BENCH_fedsim.json
(override with BENCH_FEDSIM_JSON).

  PYTHONPATH=src BENCH_ONLY=fedsim python -m benchmarks.run
"""

from __future__ import annotations

import json
import os
import time

import jax

from benchmarks import common as C
from benchmarks.common import steady_state
from repro.configs.distilbert import MINI
from repro.data.synthetic import make_classification
from repro.federated.baselines import all_strategies
from repro.federated.partition import iid_partition
from repro.federated.server import FedConfig, run_federated
from repro.models import Model

JSON_PATH = os.environ.get("BENCH_FEDSIM_JSON", "BENCH_fedsim.json")


def _measure(quick: bool) -> dict:
    """Time the runners on the devices this process has; returns the
    JSON record ``main`` turns into CSV rows."""
    cfg = MINI.with_(n_layers=2, layer_pattern=("attn",) * 2)
    train = make_classification(1600, 20, cfg.vocab_size, 32, seed=1)
    test = make_classification(200, 20, cfg.vocab_size, 32, seed=2)
    parts = iid_partition(train.labels, 20, seed=0)

    def timed(runner, rounds, cpr, codec="identity"):
        # steady-state s/round: perf_counter marks at run start and after
        # every round; the first interval (jit compile) is dropped and the
        # remaining intervals' median is the measurement.  run_federated
        # fences with block_until_ready before its final timestamp.
        strat = all_strategies(rounds=rounds)["fedlora"]
        model = Model(cfg, peft=strat.peft, unroll=True)
        fc = FedConfig(rounds=rounds, clients_per_round=cpr, batch_size=16,
                       max_local_batches=4, eval_every=10**6, lr=3e-3,
                       runner=runner, codec=codec)
        marks = [time.perf_counter()]
        h = run_federated(model, strat, parts, train, test, fc,
                          on_round=lambda r, log:
                          marks.append(time.perf_counter()))
        round_s, n = steady_state(marks, warmup=1)
        return round_s, n, h

    out = {"ndev": len(jax.devices()), "rows": []}
    r_bench = 3 if quick else 6
    for cpr in ([4] if quick else [2, 4, 8]):
        rec = {"cpr": cpr}
        for runner in ("seq", "cohort"):
            rs, n, _ = timed(runner, r_bench, cpr)
            rec[runner + "_round_s"] = rs
            rec[runner + "_samples"] = n
        # noisy only when no steady-state samples survive the warmup drop
        noisy = (rec["seq_samples"] == 0 or rec["cohort_samples"] == 0
                 or not rec["seq_round_s"] > 0
                 or not rec["cohort_round_s"] > 0)
        rec["noisy"] = noisy
        rec["speedup"] = (float("nan") if noisy
                          else rec["seq_round_s"] / rec["cohort_round_s"])
        out["rows"].append(rec)

    # transport: bytes per round + convergence-vs-bytes under each codec
    # (cohort runner, same seeds → same client draws across codecs)
    out["codec"], out["convergence"] = {}, {}
    r_conv = 2 if quick else r_bench
    for codec in ("identity", "int8", "topk", "signsgd", "powersgd"):
        _, _, h = timed("cohort", r_conv, 4, codec)
        out["codec"][codec] = h["comm_gb"] * 1e9 / r_conv
        cum = 0
        curve = []
        for l in h["rounds"]:
            cum += l.down_bytes + l.up_bytes
            curve.append([cum, l.loss])
        out["convergence"][codec] = curve

    # async: simulated time + events per aggregation round
    strat = all_strategies(rounds=r_bench)["fedlora"]
    model = Model(cfg, peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=r_bench, clients_per_round=4, batch_size=16,
                   max_local_batches=4, eval_every=10**6, lr=3e-3,
                   runner="async", buffer_k=4, straggler=0.25)
    t0 = time.perf_counter()
    h = run_federated(model, strat, parts, train, test, fc)
    out["async"] = {"wall_s": time.perf_counter() - t0,
                    "sim_time_s": h["sim_time_s"],
                    "events": len(h["events"]),
                    "mean_staleness": sum(l.staleness for l in h["rounds"])
                    / max(len(h["rounds"]), 1)}
    return out


def main(quick: bool = False) -> None:
    out = _measure(bool(quick or C.QUICK))

    rows = []
    for rec in out["rows"]:
        rows.append(C.row(f"fedsim/cohort_speedup_cpr{rec['cpr']}",
                          f"{rec['speedup']:.3f}",
                          seq_s=f"{rec['seq_round_s']:.3f}",
                          cohort_s=f"{rec['cohort_round_s']:.3f}",
                          ndev=out["ndev"], noisy=int(rec["noisy"])))
    ident = out["codec"]["identity"]
    for name, b in out["codec"].items():
        final_loss = out["convergence"][name][-1][1]
        rows.append(C.row(f"fedsim/codec_{name}_bytes_per_round",
                          int(b), ratio=f"{ident / max(b, 1):.2f}",
                          final_loss=f"{final_loss:.4f}"))
    a = out["async"]
    rows.append(C.row("fedsim/async_sim_time_s", f"{a['sim_time_s']:.1f}",
                      events=a["events"],
                      mean_staleness=f"{a['mean_staleness']:.2f}"))
    from repro.obs import provenance
    out["provenance"] = provenance({"bench_quick": bool(quick or C.QUICK)})
    with open(JSON_PATH, "w") as f:
        json.dump(out, f, indent=1)
    rows.append(C.row("fedsim/json", JSON_PATH, ndev=out["ndev"]))
    C.emit(rows)


if __name__ == "__main__":
    main()
