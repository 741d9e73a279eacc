"""Client-side local training.  One jitted step is compiled per (model,
strategy-structure) and shared across all clients — the emulation pattern the
paper uses on a single GPU, here on whatever jax.devices() offers."""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim as OPT


def make_train_step(model, opt: OPT.Optimizer, task: str = "cls",
                    train_base: bool = False):
    loss_fn = model.cls_loss if task == "cls" else model.lm_loss
    remat = model.cfg.train_remat

    @jax.jit
    def client_step(base, params, opt_state, masks, gate, batch):
        if train_base:
            def f(both):
                return loss_fn(both["base"], both["trainable"], masks, batch,
                               remat=remat)
            both = {"base": base, "trainable": params}
            (_, (loss, metric, counts)), grads = jax.value_and_grad(
                f, has_aux=True)(both)
            g = grads["trainable"]
            gb = grads["base"]
        else:
            def f(tr):
                return loss_fn(base, tr, masks, batch, remat=remat)
            (_, (loss, metric, counts)), g = jax.value_and_grad(
                f, has_aux=True)(params)
            gb = None
        updates, opt_state = opt.update(g, opt_state, params)
        if gate is not None:
            updates = jax.tree.map(
                lambda u, gt: u * jnp.asarray(gt, u.dtype), updates, gate)
        params = jax.tree.map(lambda p, u: (p + u.astype(p.dtype)),
                              params, updates)
        return params, opt_state, g, gb, loss, metric, counts

    return client_step


def make_base_update_step(opt: OPT.Optimizer):
    """Sparse full-FT update of the base (SLoRA stage 1)."""
    @jax.jit
    def base_update_step(base, opt_state, grads, gate):
        updates, opt_state = opt.update(grads, opt_state, base)
        if gate is not None:
            updates = jax.tree.map(
                lambda u, gt: u * jnp.asarray(gt, u.dtype), updates, gate)
        base = jax.tree.map(lambda p, u: p + u.astype(p.dtype), base, updates)
        return base, opt_state
    return base_update_step


@functools.lru_cache(maxsize=8)
def make_eval_step(model, task: str = "cls"):
    """One jitted eval step per (model, task), shared by every evaluation of
    a run: an eval round after the first traces and compiles nothing."""
    @jax.jit
    def eval_step(base, params, masks, batch):
        logits, _, _ = model.forward(base, params, masks, batch,
                                     mode="train", remat=False)
        if task == "cls":
            pred = logits.argmax(-1)
            return (pred == batch["labels"]).astype(jnp.float32).sum()
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                                   -1)[..., 0]
        return nll.mean()
    return eval_step


def local_train(step_fn, base, trainable, masks, gate, opt, data_batches
                ) -> tuple[Any, Any, dict]:
    """Run local epochs.  Returns (trainable', last_grads, metrics); the
    metrics carry the steps' summed ``counts`` (the model's, e.g. the held
    experts' ``moe_routed`` and ``moe_rows``)."""
    opt_state = opt.init(trainable)
    params = trainable
    losses, metrics, counts = [], [], []
    grads = None
    for batch in data_batches:
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, grads, _, loss, metric, c = step_fn(
            base, params, opt_state, masks, gate, jb)
        losses.append(loss)
        metrics.append(metric)
        counts.append(c)
    # one device→host transfer after the loop keeps dispatch async
    losses, metrics, counts = jax.device_get((losses, metrics, counts))
    return params, grads, {
        "loss": float(np.mean(losses)) if losses else float("nan"),
        "metric": float(np.mean(metrics)) if metrics else float("nan"),
        "n_batches": len(losses),
        "counts": {k: float(sum(c[k] for c in counts))
                   for k in (counts[0] if counts else {})}}
