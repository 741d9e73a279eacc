#!/usr/bin/env python3
"""Chip smoke: drive the system's main paths once on a TPU, at the published
widths of the models it supports, with random weights made from ``--seed``.

  python chip_smoke.py                # one chip: every phase below
  python chip_smoke.py --four-chips   # four chips: the cohort axis on a mesh

One chip, one process (a chip belongs to one process at a time):

  kernels        bea_dense (DistilBERT 768→768 / 768→3072, r=12),
                 flash_attention (Qwen2-0.5B: 14 q / 2 kv heads, S=512,
                 hd 64) and bea_batched (Qwen2 width 896, 4 tenants, r=8),
                 compiled by Mosaic and compared with kernels/ref.py.
  fedara_cohort  FedARA rounds through ``run_federated(runner="cohort")`` at
                 DistilBERT width on a Dirichlet α=0.1 split.
  fused          FedLoRA with ``fuse_rounds=2`` (donated carry, one program
                 per two rounds) against the eager cohort loop, same seed.
  serving        ``build_engine`` + ``serve_requests`` at Qwen2-0.5B width:
                 requests over tenants at mixed ranks, batched outputs equal
                 to unbatched ones.

``--four-chips`` runs only the cross-chip path: FedLoRA cohort rounds with
8 clients per round whose client axis is ``shard_map``-ped over 4 chips
against the sequential reference, plus one fused block.

Every phase prints one ``phase`` line (compile seconds, run seconds, device
memory).  These are smoke timings, not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed; the
script exits non-zero, printing no such line, when a phase fails or JAX finds
no TPU.  The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.compat import enable_compilation_cache  # noqa: E402
from repro.configs import distilbert, qwen2_0p5b  # noqa: E402
from repro.data.synthetic import make_classification  # noqa: E402
from repro.federated.baselines import all_strategies  # noqa: E402
from repro.federated.partition import (dirichlet_partition,  # noqa: E402
                                       iid_partition)
from repro.federated.server import FedConfig, run_federated  # noqa: E402
from repro.fedsim import fused as FU  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.bea_batched import bea_batched  # noqa: E402
from repro.kernels.bea_fused import bea_dense  # noqa: E402
from repro.kernels.flash_attention import mha_flash  # noqa: E402
from repro.launch.serve import build_engine, serve_requests  # noqa: E402
from repro.models import Model  # noqa: E402

# Loss tolerances between two programs that train the same clients.  On TPU
# XLA computes float32 dots with bfloat16 passes by default (8-bit mantissa,
# ~4e-3 relative rounding per product), and two programs that fuse and order
# their reductions differently round differently; a mean loss over a few
# local steps then agrees to well under 1%.  Bytes are compared exactly.
LOSS_RTOL = 1e-2
# Kernel vs reference: |got - want| <= tol · max|want|, with the reference
# computed in float32 at the highest matmul precision.  bfloat16 operands
# and outputs carry 8 mantissa bits.
KERNEL_TOL = {"float32": 1e-2, "bfloat16": 3e-2}

SEQ_LEN = 128            # DistilBERT tokens per example (max_position 512)
N_CLIENTS = 20


class CompileClock:
    """Compile seconds and persistent-cache outcomes, from JAX's own
    monitoring events (one listener pair per process)."""

    def __init__(self):
        self.backend_s = 0.0           # XLA compile, incl. cache retrieval
        self.trace_lower_s = 0.0       # jaxpr tracing + lowering to MLIR
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, dur, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_s += dur
        elif name in ("/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace_lower_s += dur

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.backend_s, self.trace_lower_s, self.hits, self.misses)


def _memory() -> dict:
    out = {}
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        out[d.id] = (int(ms.get("bytes_in_use", 0)),
                     int(ms.get("peak_bytes_in_use", 0)))
    return out


def run_phase(name: str, fn, clock: CompileClock, failed: list) -> None:
    """Run one phase; print its line, or its traceback and mark it failed.
    A failure never stops the later phases, and always fails the run."""
    b0, t0_, h0, m0 = clock.snapshot()
    t0 = time.perf_counter()
    try:
        info = fn() or {}
        status = "ok"
    except Exception:  # noqa: BLE001 — reported, and fails the run below
        traceback.print_exc()
        failed.append(name)
        info, status = {}, "FAILED"
    wall = time.perf_counter() - t0
    b1, t1_, h1, m1 = clock.snapshot()
    compile_s = (b1 - b0) + (t1_ - t0_)
    mem = _memory()
    mem_s = " ".join(f"dev{d}_bytes_in_use={u} dev{d}_peak_bytes_in_use={p}"
                     for d, (u, p) in sorted(mem.items()))
    extra = " ".join(f"{k}={v}" for k, v in info.items())
    print(f"phase {name} {status} wall_s={wall:.3f} "
          f"compile_s={compile_s:.3f} (xla={b1 - b0:.3f} "
          f"trace_lower={t1_ - t0_:.3f}) run_s={max(wall - compile_s, 0):.3f} "
          f"cache_hits={h1 - h0} cache_misses={m1 - m0} {mem_s} {extra}",
          flush=True)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _check(label, got, want, dtype) -> float:
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise AssertionError(f"{label}: shape {got.shape} vs {want.shape} "
                             f"or non-finite output")
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
    if err > KERNEL_TOL[dtype]:
        raise AssertionError(f"{label}: max err {err:.3e} of max|ref| > "
                             f"{KERNEL_TOL[dtype]}")
    return err


def _timed(label, fn, args) -> tuple[object, float, float]:
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    c = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))             # warm
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    r = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{label}: no Mosaic kernel in the program")
    return out, c, r


def phase_kernels(seed: int, dense=((1024, 768, 768, 12),
                                    (1024, 768, 3072, 12)),
                  flash=(2, 512, 14, 2, 64), batched=(64, 896, 896, 4, 8)):
    rng = np.random.default_rng(seed)
    hi = jax.default_matmul_precision("highest")
    for dtype in ("float32", "bfloat16"):
        dt = jnp.dtype(dtype)
        for m, k, n, r in dense:
            x = rng.normal(size=(m, k))
            w = rng.normal(size=(k, n)) / np.sqrt(k)
            a = rng.normal(size=(r, k)) / np.sqrt(k)
            b = rng.normal(size=(n, r))
            e = jnp.asarray(rng.normal(size=(r,)), jnp.float32)
            msk = jnp.asarray(rng.integers(0, 2, (r,)), jnp.float32)
            args = [jnp.asarray(t, dt) for t in (x, w, a, b)] + [e, msk]
            out, c, t = _timed(
                "bea_dense", lambda *t: bea_dense(*t, scaling=1.3), args)
            with hi:
                want = ref.bea_dense_ref(
                    *[jnp.asarray(v, jnp.float32) for v in args[:4]],
                    e, msk, 1.3)
            err = _check("bea_dense", out, want, dtype)
            print(f"  kernel bea_dense {dtype} {m}x{k}->{n} r={r} "
                  f"compile_s={c:.3f} run_s={t:.6f} max_rel_err={err:.3e}",
                  flush=True)

        bsz, s, h, kv, hd = flash
        q, kk, v = (jnp.asarray(rng.normal(size=(bsz, s, nh, hd)), dt)
                    for nh in (h, kv, kv))
        out, c, t = _timed("flash_attention",
                           lambda q, k, v: mha_flash(q, k, v, causal=True),
                           [q, kk, v])
        with hi:
            f32 = [jnp.asarray(z, jnp.float32) for z in (q, kk, v)]
            want = ref.flash_attention_ref(
                f32[0], jnp.repeat(f32[1], h // kv, 2),
                jnp.repeat(f32[2], h // kv, 2), causal=True)
        err = _check("flash_attention", out, want, dtype)
        print(f"  kernel flash_attention {dtype} B={bsz} S={s} H={h} KV={kv} "
              f"hd={hd} compile_s={c:.3f} run_s={t:.6f} "
              f"max_rel_err={err:.3e}", flush=True)

        m, k, n, g, r = batched
        x = jnp.asarray(rng.normal(size=(m, k)), dt)
        w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), dt)
        a = jnp.asarray(rng.normal(size=(g, r, k)) / np.sqrt(k), dt)
        b = jnp.asarray(rng.normal(size=(g, n, r)), dt)
        e = jnp.asarray(rng.normal(size=(g, r)), jnp.float32)
        msk = jnp.asarray(rng.integers(0, 2, (g, r)), jnp.float32)
        msk = msk.at[1].set(0.0)                   # one fully-pruned tenant
        idx = jnp.asarray(rng.integers(0, g, (m,)), jnp.int32)
        out, c, t = _timed("bea_batched",
                           lambda *t: bea_batched(*t, scaling=2.0),
                           [x, w, a, b, e, msk, idx])
        with hi:
            want = ref.bea_batched_ref(
                *[jnp.asarray(z, jnp.float32) for z in (x, w, a, b)],
                e, msk, idx, 2.0)
        err = _check("bea_batched", out, want, dtype)
        print(f"  kernel bea_batched {dtype} M={m} {k}->{n} G={g} r={r} "
              f"compile_s={c:.3f} run_s={t:.6f} max_rel_err={err:.3e}",
              flush=True)
    return {}


# ---------------------------------------------------------------------------
# federated rounds
# ---------------------------------------------------------------------------

def _data(cfg, seed: int, n_train: int, n_test: int):
    train = make_classification(n_train, cfg.n_classes, cfg.vocab_size,
                                SEQ_LEN, seed=seed)
    test = make_classification(n_test, cfg.n_classes, cfg.vocab_size,
                               SEQ_LEN, seed=seed + 1)
    return train, test


def _fed_run(cfg, strategy: str, parts, train, test, **fc_kw):
    """One ``run_federated`` the way ``launch.fed_train`` builds it."""
    rounds = fc_kw["rounds"]
    strat = all_strategies(rounds=rounds)[strategy]
    if hasattr(strat, "total_rounds"):
        strat.total_rounds = rounds
        strat.warmup_rounds = max(1, rounds // 10)
    model = Model(cfg.with_(adapter_rank=strat.init_rank(cfg)),
                  peft=strat.peft, unroll=True)
    fc = FedConfig(**fc_kw)
    return run_federated(model, strat, parts, train, test, fc), strat, fc


def _round_lines(tag, h):
    for log in h["rounds"]:
        print(f"  {tag} round {log.rnd} loss={log.loss:.6f} "
              f"live_ranks={log.live_ranks} down_bytes={log.down_bytes} "
              f"up_bytes={log.up_bytes}", flush=True)


def _compare(tag, h_ref, h, rtol=LOSS_RTOL) -> float:
    """Exact bytes, losses within ``rtol``; returns the largest relative
    loss difference seen."""
    if len(h_ref["rounds"]) != len(h["rounds"]):
        raise AssertionError(f"{tag}: round counts differ")
    worst = 0.0
    for a, b in zip(h_ref["rounds"], h["rounds"]):
        if (a.down_bytes, a.up_bytes) != (b.down_bytes, b.up_bytes):
            raise AssertionError(
                f"{tag} round {a.rnd}: bytes {(a.down_bytes, a.up_bytes)} "
                f"!= {(b.down_bytes, b.up_bytes)}")
        if not (np.isfinite(a.loss) and np.isfinite(b.loss)):
            raise AssertionError(f"{tag} round {a.rnd}: non-finite loss")
        rel = abs(a.loss - b.loss) / max(abs(a.loss), 1e-12)
        worst = max(worst, rel)
        if rel > rtol:
            raise AssertionError(f"{tag} round {a.rnd}: loss {b.loss} vs "
                                 f"{a.loss} (rel {rel:.3e} > {rtol})")
    if h_ref["comm_gb"] != h["comm_gb"]:
        raise AssertionError(f"{tag}: comm_gb {h['comm_gb']} != "
                             f"{h_ref['comm_gb']}")
    return worst


def phase_fedara(cfg, seed: int, rounds=3, cpr=4, n_train=1600, n_test=256):
    train, test = _data(cfg, seed, n_train, n_test)
    parts = dirichlet_partition(train.labels, N_CLIENTS, 0.1, seed)
    h, _, _ = _fed_run(cfg, "fedara", parts, train, test, rounds=rounds,
                       clients_per_round=cpr, seed=seed, runner="cohort",
                       max_local_batches=4)
    _round_lines("fedara_cohort", h)
    for log in h["rounds"]:
        if not np.isfinite(log.loss):
            raise AssertionError(f"round {log.rnd}: loss {log.loss}")
        if not (isinstance(log.live_ranks, int) and log.live_ranks > 0):
            raise AssertionError(f"round {log.rnd}: live_ranks "
                                 f"{log.live_ranks!r}")
    if len(h["rounds"]) != rounds or not h["comm_gb"] > 0:
        raise AssertionError(f"{len(h['rounds'])} rounds, comm_gb "
                             f"{h['comm_gb']}")
    return {"rounds": rounds, "clients_per_round": cpr,
            "comm_gb": f"{h['comm_gb']:.6f}",
            "final_acc": f"{h['final_acc']:.4f}",
            "loop_wall_s": f"{h['wall_s']:.3f}"}


def phase_fused(cfg, seed: int, rounds=4, cpr=4, k=2, n_train=1600,
                n_test=256):
    train, test = _data(cfg, seed, n_train, n_test)
    parts = iid_partition(train.labels, N_CLIENTS, seed)
    kw = dict(rounds=rounds, clients_per_round=cpr, seed=seed,
              runner="cohort", max_local_batches=4)
    h_eager, strat, _ = _fed_run(cfg, "fedlora", parts, train, test, **kw)
    ok, why = FU.eligible(FedConfig(**kw, fuse_rounds=k), strat, parts)
    if not ok:
        raise AssertionError(f"fused path not eligible: {why}")
    h_fused, _, _ = _fed_run(cfg, "fedlora", parts, train, test, **kw,
                             fuse_rounds=k)
    _round_lines("eager", h_eager)
    _round_lines(f"fused_K{k}", h_fused)
    worst = _compare("fused vs eager", h_eager, h_fused)
    return {"rounds": rounds, "fuse_rounds": k,
            "max_rel_loss_diff": f"{worst:.3e}", "loss_rtol": LOSS_RTOL,
            "eager_loop_wall_s": f"{h_eager['wall_s']:.3f}",
            "fused_loop_wall_s": f"{h_fused['wall_s']:.3f}"}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def phase_serving(cfg, seed: int, n_req=8, ranks=(4, 8, 4, 8), prompt=64,
                  gen=16, n_slots=8):
    max_seq = prompt + gen
    engine = build_engine(cfg, n_slots=n_slots, max_seq=max_seq,
                          n_tenants=len(ranks), ranks=list(ranks), seed=seed)
    rng = np.random.default_rng(seed)
    ids = engine.registry.ids()
    prompts = [rng.integers(0, cfg.vocab_size, prompt) for _ in range(n_req)]
    aids = [ids[i % len(ids)] for i in range(n_req)]
    t0 = time.perf_counter()
    reqs = serve_requests(engine, prompts, aids, gen)
    serve_s = time.perf_counter() - t0
    bad = [r.rid for r in reqs if r.state != "finished" or len(r.out) != gen]
    if bad:
        raise AssertionError(f"requests {bad} did not finish with {gen} "
                             f"tokens")
    # batched must equal unbatched: two requests at different ranks, each
    # alone through a one-slot engine over the same base and tenants
    solo = build_engine(cfg, n_slots=1, max_seq=max_seq,
                        n_tenants=len(ranks), ranks=list(ranks), seed=seed)
    for i in (0, 1):
        alone = serve_requests(solo, [prompts[i]], [aids[i]], gen)[0]
        if alone.out != reqs[i].out:
            raise AssertionError(f"request {i} ({aids[i]}): batched "
                                 f"{reqs[i].out} != unbatched {alone.out}")
    n_tok = sum(len(r.out) for r in reqs)
    print(f"  serving first request tokens {reqs[0].out}", flush=True)
    return {"requests": n_req, "tenants": len(ranks),
            "ranks": ",".join(map(str, ranks)), "prompt": prompt,
            "gen": gen, "tokens": n_tok, "serve_wall_s": f"{serve_s:.3f}",
            "engine_steps": engine.steps,
            "decode_calls": engine.decode_calls}


# ---------------------------------------------------------------------------
# four chips: the cohort axis on a real mesh
# ---------------------------------------------------------------------------

def _cohort_split(cfg, seed: int, parts, train, cpr: int) -> str:
    """One cohort dispatch built as ``runner.run_cohort`` builds round 0's;
    returns where each client-stacked output shard lives."""
    from repro.federated import server as SV
    from repro.fedsim import cohort as CH
    strat = all_strategies(rounds=1)["fedlora"]
    model = Model(cfg.with_(adapter_rank=strat.init_rank(cfg)),
                  peft=strat.peft, unroll=True)
    fc = FedConfig(rounds=1, clients_per_round=cpr, seed=seed,
                   runner="cohort", max_local_batches=2)
    base, trainable, masks, _, _, opt, rng = SV._init_run(model, strat, fc)
    mesh = CH.cohort_mesh()
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    ndev = len(jax.devices())
    sel = rng.choice(len(parts), size=cpr, replace=False)
    cohort = CH.build_cohort(train, parts, [int(c) for c in sel], fc, 0,
                             -(-cpr // ndev) * ndev)
    base, _ = SV.pin_params(base, sharding=rep)
    bc, masks = SV.pin_params(trainable, masks, sharding=rep)
    fn = CH.make_cohort_fn(model, opt, fc.task, mesh=mesh)
    pc, _, lc, _, _ = fn(base, CH.stack_params(bc, len(cohort.weights)),
                         masks, strat.optimizer_gate(bc, None),
                         cohort.batches, cohort.step_mask, cohort.weights)
    leaf = jax.tree.leaves(pc)[0]
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in leaf.addressable_shards)
    if len({d for d, _ in shards}) != ndev \
            or any(n != len(cohort.weights) // ndev for _, n in shards):
        raise AssertionError(f"client axis not split over {ndev} devices: "
                             f"{shards}")
    if not np.isfinite(np.asarray(lc)[cohort.step_mask]).all():
        raise AssertionError("non-finite cohort losses")
    return ";".join(f"dev{d}:{n}clients" for d, n in shards)


def phase_four_chips(cfg, seed: int, rounds=2, cpr=8, n_clients=16,
                     n_train=1280, n_test=128):
    ndev = len(jax.devices())
    if ndev != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found {ndev}")
    train, test = _data(cfg, seed, n_train, n_test)
    parts = iid_partition(train.labels, n_clients, seed)
    split = _cohort_split(cfg, seed, parts, train, cpr)
    print(f"  cohort client shards {split}", flush=True)
    kw = dict(rounds=rounds, clients_per_round=cpr, seed=seed,
              max_local_batches=2, eval_batches=4)
    h_seq, _, _ = _fed_run(cfg, "fedlora", parts, train, test, runner="seq",
                           **kw)
    h_coh, _, _ = _fed_run(cfg, "fedlora", parts, train, test,
                           runner="cohort", **kw)
    h_fus, _, _ = _fed_run(cfg, "fedlora", parts, train, test,
                           runner="cohort", fuse_rounds=rounds, **kw)
    _round_lines("seq", h_seq)
    _round_lines("cohort_4chip", h_coh)
    _round_lines(f"fused_K{rounds}_4chip", h_fus)
    w_coh = _compare("cohort vs seq", h_seq, h_coh)
    w_fus = _compare("fused vs cohort", h_coh, h_fus)
    return {"devices": ndev, "rounds": rounds, "clients_per_round": cpr,
            "client_shards": split,
            "cohort_vs_seq_max_rel_loss_diff": f"{w_coh:.3e}",
            "fused_vs_cohort_max_rel_loss_diff": f"{w_fus:.3e}",
            "loss_rtol": LOSS_RTOL}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cohort-on-a-4-chip-mesh phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    cache = enable_compilation_cache()
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}  "
          f"jax {jax.__version__}  compile cache {cache}", flush=True)
    clock = CompileClock()
    failed: list[str] = []
    if args.four_chips:
        run_phase("four_chips_cohort",
                  lambda: phase_four_chips(distilbert.CONFIG, args.seed),
                  clock, failed)
    else:
        run_phase("kernels", lambda: phase_kernels(args.seed), clock, failed)
        run_phase("fedara_cohort",
                  lambda: phase_fedara(distilbert.CONFIG, args.seed),
                  clock, failed)
        run_phase("fused", lambda: phase_fused(distilbert.CONFIG, args.seed),
                  clock, failed)
        run_phase("serving",
                  lambda: phase_serving(qwen2_0p5b.CONFIG, args.seed),
                  clock, failed)
    print(f"compile cache totals: hits={clock.hits} misses={clock.misses}",
          flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
