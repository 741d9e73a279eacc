"""Federated fine-tuning driver — the paper's end-to-end scenario.

Usage:
  PYTHONPATH=src python -m repro.launch.fed_train --strategy fedara \
      --rounds 20 --clients 20 --alpha 0.1

The fedsim engine is selected with ``--runner``: ``seq`` is the sequential
oracle, ``cohort`` runs each round's local phase as one vmap+scan+shard_map
dispatch over all devices, ``async`` runs FedBuff-style buffered aggregation
on a simulated event clock.  ``--codec`` picks the delta-space transport
codec (int8 blockwise / top-k sparsification / 1-bit signsgd / low-rank
powersgd, all with error feedback on the client→server *delta* wire) and
``--straggler`` / ``--dropout`` inject client heterogeneity.  ``--secagg``
composes with field-exact codecs (``--codec signsgd``).

Compiled programs persist in the directory ``JAX_COMPILATION_CACHE_DIR``
names, or else in ``<checkout>/.jax_cache`` (``repro.compat``).
"""

from __future__ import annotations

import argparse

import numpy as np

from repro import obs
from repro.compat import enable_compilation_cache
from repro.configs.distilbert import MINI
from repro.data.synthetic import make_classification
from repro.federated.baselines import all_strategies
from repro.federated.partition import (dirichlet_partition,
                                       pathological_partition)
from repro.federated.server import FedConfig, run_federated
from repro.models import Model


def main(argv=None):
    ap = argparse.ArgumentParser(
        epilog="Contributions to the federated wire path are gated by the "
               "repro.lint static-analysis pass (rng hygiene, host-sync/"
               "retrace hazards, privacy pipeline invariants): "
               "`python -m repro.lint src/ --baseline lint_baseline.json`; "
               "`--list-rules` documents the rule registry.")
    ap.add_argument("--strategy", default="fedara",
                    choices=list(all_strategies()))
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--clients-per-round", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=0.1,
                    help="Dirichlet α; 0 → pathological split")
    ap.add_argument("--rank", type=int, default=12)
    ap.add_argument("--n-classes", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--runner", default="seq",
                    choices=["seq", "cohort", "async"])
    ap.add_argument("--opt-state-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"],
                    help="adam moment storage (bf16 halves per-client "
                         "optimizer state; int8 quarters it)")
    ap.add_argument("--codec", default="identity",
                    choices=["identity", "int8", "topk", "signsgd",
                             "powersgd"])
    ap.add_argument("--powersgd-rank", type=int, default=2,
                    help="q for --codec powersgd (q·(m+k) floats per wire)")
    ap.add_argument("--straggler", type=float, default=0.0,
                    help="P(client is a straggler); slowdown ×4")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="P(selected client never reports)")
    ap.add_argument("--buffer-k", type=int, default=0,
                    help="async: aggregate every K arrivals")
    ap.add_argument("--event-seed", type=int, default=0)
    ap.add_argument("--secagg", default="off", choices=["off", "mask"],
                    help="simulated secure aggregation (repro.secagg)")
    ap.add_argument("--secagg-threshold", type=float, default=2.0 / 3.0,
                    help="Shamir threshold as a fraction of the cohort")
    ap.add_argument("--secagg-bits", type=int, default=32,
                    help="field modulus 2^bits for the masked sum")
    ap.add_argument("--dp-clip", type=float, default=0.0,
                    help="client-level DP: per-client delta L2 clip")
    ap.add_argument("--dp-noise-multiplier", type=float, default=0.0,
                    help="client-level DP: z (server noise = z·clip on sum)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a repro.obs JSONL trace (spans + metrics) "
                         "here; inspect with `python -m repro.obs summarize`")
    ap.add_argument("--trace-sample-clients", type=float, default=None,
                    metavar="RATE",
                    help="head-sample per-client spans at this rate "
                         "(deterministic by (seed, round, client); clients "
                         "with health alerts always kept; cohort rollup "
                         "sketches preserve the dropped distributions)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve live telemetry on this port: /metrics "
                         "(Prometheus text), /healthz, /snapshot (tail with "
                         "`python -m repro.obs top URL`); implies tracing "
                         "(in-memory only unless --trace)")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    live = None
    if args.trace or args.metrics_port is not None:
        obs.configure(args.trace, meta=obs.provenance(
            {"cmd": "fed_train", "strategy": args.strategy,
             "runner": args.runner, "codec": args.codec,
             "secagg": args.secagg}),
            client_sample=args.trace_sample_clients,
            sample_seed=args.seed)
        if args.metrics_port is not None:
            live = obs.serve_live(port=args.metrics_port)
            print(f"live telemetry at {live.url}/metrics "
                  f"(/healthz, /snapshot)", flush=True)

    cfg = MINI.with_(n_classes=args.n_classes, adapter_rank=args.rank)
    train = make_classification(1500, args.n_classes, cfg.vocab_size, 32,
                                seed=1)
    test = make_classification(300, args.n_classes, cfg.vocab_size, 32,
                               seed=2)
    if args.alpha <= 0:
        parts = pathological_partition(train.labels, args.clients, 2,
                                       args.seed)
    else:
        parts = dirichlet_partition(train.labels, args.clients, args.alpha,
                                    args.seed)

    strat = all_strategies(rounds=args.rounds)[args.strategy]
    if hasattr(strat, "total_rounds"):
        strat.total_rounds = args.rounds
        strat.warmup_rounds = max(1, args.rounds // 10)
    model = Model(cfg.with_(adapter_rank=strat.init_rank(cfg)),
                  peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=args.rounds,
                   clients_per_round=args.clients_per_round, seed=args.seed,
                   runner=args.runner, codec=args.codec,
                   opt_state_dtype=args.opt_state_dtype,
                   powersgd_rank=args.powersgd_rank,
                   straggler=args.straggler, dropout=args.dropout,
                   buffer_k=args.buffer_k, event_seed=args.event_seed,
                   secagg=args.secagg,
                   secagg_threshold=args.secagg_threshold,
                   secagg_bits=args.secagg_bits,
                   dp_clip=args.dp_clip,
                   dp_noise_multiplier=args.dp_noise_multiplier)

    def on_round(rnd, log):
        print(f"round {rnd:3d}  loss {log.loss:.4f}  "
              f"acc {log.acc if log.acc == log.acc else float('nan'):.4f}  "
              f"comm {(log.down_bytes + log.up_bytes) / 1e6:.2f} MB  "
              f"live_ranks {log.live_ranks}  dead_modules {log.dead_modules}"
              + (f"  sim {log.sim_time_s:.1f}s" if log.sim_time_s else "")
              + (f"  stale {log.staleness:.1f}" if log.staleness else ""),
              flush=True)

    h = run_federated(model, strat, parts, train, test, fc,
                      on_round=on_round)
    sim = (f"  sim_time {h['sim_time_s']:.0f}s"
           if h.get("sim_time_s") else "")
    print(f"final acc {h['final_acc']:.4f}  total comm "
          f"{h['comm_gb'] * 1e3:.1f} MB  wall {h['wall_s']:.0f}s{sim}")
    if h.get("secagg_rounds"):
        sr = h["secagg_rounds"]
        extra = sum(sum(p["down"] + p["up"] for p in r["phases"].values())
                    for r in sr)
        rec = sum(r["recovery_bytes"] for r in sr)
        print(f"secagg: {len(sr)} rounds  protocol bytes {extra / 1e6:.2f} MB"
              f"  recovery {rec / 1e3:.1f} kB")
    if h.get("dp"):
        print(f"DP: ε={h['dp']['epsilon']:.3f} @ δ={h['dp']['delta']:g}  "
              f"(z={h['dp']['noise_multiplier']}, clip={h['dp']['clip']})")
    if h.get("stage1"):
        s1 = h["stage1"]
        print(f"stage1: {s1['rounds']} rounds  up {s1['up_bytes'] / 1e6:.2f}"
              f" MB  clipped {s1['n_clipped']}")
    if args.trace or args.metrics_port is not None:
        obs.close()
        if live is not None:
            live.stop()
        if args.trace:
            print(f"trace written to {args.trace}  "
                  f"(python -m repro.obs summarize {args.trace})")


if __name__ == "__main__":
    main()
