"""Serving CLI — a thin driver over the multi-tenant serving engine
(``repro.serving``): continuous batching, per-request adapters at
heterogeneous ranks, greedy decode against the KV/SSM cache.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3_1b --smoke \
      --batch 8 --tenants 2 --prompt-len 32 --gen 16

Encoder-decoder and vision architectures fall back to the legacy
static-batch loop (engine v1 is decoder-only text; see ROADMAP).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compat import enable_compilation_cache
from repro.configs import ARCH_IDS, PAPER_IDS, get_config
from repro.models import Model
from repro.pytree import materialize


def make_tenants(model, cfg, n_tenants: int, ranks=None, seed: int = 0):
    """Simulated post-federated tenants: one BEA adapter tree per tenant at
    its own rank (round-robin over ``ranks``), E bumped off its zero init so
    the adapters actually steer generation, plus a pruned top rank."""
    ranks = list(ranks or [max(cfg.adapter_rank // 2, 1), cfg.adapter_rank])
    rng = np.random.default_rng(seed)
    tenants = {}
    for i in range(n_tenants):
        r = ranks[i % len(ranks)]
        m_t = Model(cfg.with_(adapter_rank=r), peft="bea")
        _, tr = m_t.init(jax.random.key(seed))

        def bump(tree):
            if isinstance(tree, dict):
                return {k: jnp.asarray(rng.normal(size=v.shape) * 0.05,
                                       v.dtype) if k == "E" else bump(v)
                        for k, v in tree.items()}
            return tree

        masks = m_t.init_masks()
        if r > 1:                       # CommPru'd top rank
            masks = jax.tree.map(lambda m: m.at[..., -1].set(False), masks)
        tenants[f"client{i}"] = dict(trainable=bump(tr), masks=masks, rank=r)
    return tenants


def build_engine(cfg, *, n_slots: int, max_seq: int, n_tenants: int = 1,
                 ranks=None, seed: int = 0):
    """Model + frozen base + engine with ``n_tenants`` registered adapters."""
    from repro.serving import ServingEngine

    model = Model(cfg, peft="bea")
    base, _ = model.init(jax.random.key(seed))
    engine = ServingEngine(model, base, n_slots=n_slots, max_seq=max_seq)
    for tid, spec in make_tenants(model, cfg, n_tenants, ranks, seed).items():
        engine.register_adapter(tid, spec["trainable"], spec["masks"],
                                rank=spec["rank"], alpha=cfg.adapter_alpha)
    return engine


def serve_requests(engine, prompts, adapter_ids, gen: int):
    """Submit (prompt, adapter) pairs, run to completion, return requests.

    Raises if any request was rejected at submit time — a silent drop would
    masquerade as an empty generation.
    """
    reqs = [engine.submit(aid, p, gen) for p, aid in zip(prompts, adapter_ids)]
    bad = [r for r in reqs if r.state == "rejected"]
    if bad:
        raise ValueError(
            f"{len(bad)}/{len(reqs)} requests rejected, first: {bad[0].error}")
    engine.run()
    return reqs


def legacy_static_batch(cfg, args):
    """Original static-batch loop — kept for enc-dec/vision architectures."""
    model = Model(cfg, peft="bea")
    base, trainable = model.init(jax.random.key(0))
    masks = model.init_masks()
    rng = np.random.default_rng(0)

    total = args.prompt_len + args.gen
    src_len = args.prompt_len * 2 if cfg.is_encoder_decoder else 0
    cache = materialize(model.cache_meta(args.batch, total, src_len=src_len),
                        jax.random.key(1))
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (args.batch, args.prompt_len)))
    batch = {"tokens": prompts}
    if cfg.is_encoder_decoder:
        if cfg.modality == "audio":
            batch["frames"] = jnp.asarray(
                rng.normal(size=(args.batch, src_len, cfg.d_model)) * 0.1,
                cfg.cdtype)
        else:
            batch["enc_tokens"] = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (args.batch, src_len)))
    if cfg.modality == "vision":
        p = cfg.n_prefix_embeds
        batch["prefix_embeds"] = jnp.asarray(
            rng.normal(size=(args.batch, p, cfg.d_model)) * 0.1, cfg.cdtype)

    prefill = jax.jit(lambda b, t, m, bt, c: model.prefill(b, t, m, bt, c))
    decode = jax.jit(lambda b, t, m, tok, c: model.decode_step(b, t, m, tok, c))

    t0 = time.time()
    logits, cache = prefill(base, trainable, masks, batch, cache)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [tok]
    t_prefill = time.time() - t0
    for _ in range(args.gen - 1):
        logits, cache = decode(base, trainable, masks, tok, cache)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_total = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} [legacy static batch] batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen}")
    print(f"prefill {t_prefill * 1e3:.1f} ms, "
          f"decode {(t_total - t_prefill) / max(args.gen - 1, 1) * 1e3:.1f} "
          f"ms/token")
    print("generated token ids (first request):", gen[0].tolist())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0p5b",
                    choices=ARCH_IDS + PAPER_IDS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to serve")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--tenants", type=int, default=2,
                    help="distinct adapters (round-robin across requests)")
    ap.add_argument("--slots", type=int, default=0,
                    help="engine cache slots (0 → min(batch, 8))")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a repro.obs JSONL trace (engine steps, "
                         "scheduler metrics, token counters) here")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve live telemetry on this port: /metrics "
                         "(Prometheus text), /healthz, /snapshot; implies "
                         "tracing (in-memory only unless --trace)")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    if args.batch < 1:
        ap.error("--batch must be >= 1")
    if args.tenants < 1:
        ap.error("--tenants must be >= 1")
    if args.gen < 1:
        ap.error("--gen must be >= 1")
    if args.prompt_len < 1:
        ap.error("--prompt-len must be >= 1")

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.is_encoder_decoder or cfg.modality == "vision":
        legacy_static_batch(cfg, args)
        return

    live = None
    if args.trace or args.metrics_port is not None:
        obs.configure(args.trace, meta=obs.provenance(
            {"cmd": "serve", "arch": args.arch, "tenants": args.tenants,
             "slots": args.slots, "gen": args.gen}))
        if args.metrics_port is not None:
            live = obs.serve_live(port=args.metrics_port)
            print(f"live telemetry at {live.url}/metrics "
                  f"(/healthz, /snapshot)", flush=True)

    n_slots = args.slots or min(args.batch, 8)
    max_seq = args.prompt_len + args.gen
    engine = build_engine(cfg, n_slots=n_slots, max_seq=max_seq,
                          n_tenants=args.tenants)
    rng = np.random.default_rng(0)
    tenant_ids = engine.registry.ids()
    prompts = [rng.integers(0, cfg.vocab_size, args.prompt_len)
               for _ in range(args.batch)]
    adapter_ids = [tenant_ids[i % len(tenant_ids)]
                   for i in range(args.batch)]

    t0 = time.time()
    reqs = serve_requests(engine, prompts, adapter_ids, args.gen)
    wall = time.time() - t0
    n_tok = sum(len(r.out) for r in reqs)
    print(f"arch={cfg.name} requests={args.batch} tenants={args.tenants} "
          f"slots={n_slots} prompt={args.prompt_len} gen={args.gen}")
    print(f"{n_tok} tokens in {wall:.2f}s ({n_tok / wall:.1f} tok/s), "
          f"{engine.steps} engine steps, "
          f"{engine.decode_calls} decode calls")
    print("generated token ids (first request):", reqs[0].out)
    if args.trace or args.metrics_port is not None:
        obs.get_metrics().gauge("serve.tokens_per_s").set(n_tok / wall)
        obs.close()
        if live is not None:
            live.stop()
        if args.trace:
            print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
