"""Centralized LM fine-tuning driver (PEFT on a frozen base).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0p5b --smoke \
      --steps 50 --batch 4 --seq 128
On the production mesh this is the same train_step the dry-run lowers; on
CPU use --smoke for the reduced config.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim as OPT
from repro.compat import enable_compilation_cache
from repro.configs import ARCH_IDS, PAPER_IDS, get_config
from repro.data.synthetic import make_lm_stream
from repro.launch import steps as ST
from repro.models import Ctx, Model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0p5b",
                    choices=ARCH_IDS + PAPER_IDS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--peft", default="bea",
                    choices=["bea", "lora", "ffa", "none"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--schedule", default="linear",
                    choices=["linear", "cosine", "wsd", "constant"])
    args = ap.parse_args(argv)
    enable_compilation_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = Model(cfg, peft=args.peft)
    base, trainable = model.init(jax.random.key(0))
    masks = model.init_masks()

    sched = {"linear": OPT.linear_decay(args.lr, args.steps),
             "cosine": OPT.cosine(args.lr, args.steps, warmup=args.steps // 10),
             "wsd": OPT.wsd(args.lr, args.steps),
             "constant": OPT.constant(args.lr)}[args.schedule]
    opt = OPT.adam(sched)
    opt_state = opt.init(trainable)
    step = jax.jit(ST.make_train_step(model, opt, Ctx(), task="lm"))

    data = make_lm_stream(args.steps * args.batch, cfg.vocab_size, args.seq,
                          seed=0)
    t0 = time.time()
    for i in range(args.steps):
        sl = slice(i * args.batch, (i + 1) * args.batch)
        batch = {"tokens": jnp.asarray(data["tokens"][sl]),
                 "targets": jnp.asarray(data["targets"][sl])}
        if cfg.modality == "vision":
            p = cfg.n_prefix_embeds
            batch["prefix_embeds"] = jnp.zeros((args.batch, p, cfg.d_model),
                                               cfg.cdtype)
        if cfg.is_encoder_decoder:
            if cfg.modality == "audio":
                batch["frames"] = jnp.zeros((args.batch, args.seq,
                                             cfg.d_model), cfg.cdtype)
            else:
                batch["enc_tokens"] = batch["tokens"]
        trainable, opt_state, metrics = step(base, trainable, opt_state,
                                             masks, batch)
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            # deliberate sync point: progress log every 10% of steps
            print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "  # lint: disable=RL2
                  f"({time.time() - t0:.1f}s)", flush=True)
    print(f"done: {args.steps} steps in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
