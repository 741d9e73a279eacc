"""jit-able production steps (train / prefill / decode) + sharding assembly.

The same factories serve the real trainer (examples/, launch/train.py) and
the multi-pod dry-run (.lower().compile() on ShapeDtypeStructs).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim as OPT
from repro import sharding as SH
from repro.models import Ctx
from repro.pytree import ParamMeta, abstractify


def mask_abstract(model):
    return jax.tree.map(
        lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype), model.mask_meta(),
        is_leaf=lambda x: isinstance(x, ParamMeta))


def make_train_step(model, opt: OPT.Optimizer, ctx: Ctx, task: str = "lm"):
    """(base, trainable, opt_state, masks, batch) -> (trainable', opt_state',
    metrics).  Gradients only w.r.t. the PEFT trainables; base is frozen."""
    loss_fn = model.cls_loss if task == "cls" else model.lm_loss

    def train_step(base, trainable, opt_state, masks, batch):
        def f(tr):
            return loss_fn(base, tr, masks, batch, ctx=ctx)
        (_, (loss, metric, _)), grads = jax.value_and_grad(f, has_aux=True)(
            trainable)
        updates, opt_state = opt.update(grads, opt_state, trainable)
        trainable = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                 trainable, updates)
        return trainable, opt_state, {"loss": loss, "metric": metric}

    return train_step


def make_prefill_step(model, ctx: Ctx):
    def prefill(base, trainable, masks, batch, cache):
        return model.prefill(base, trainable, masks, batch, cache, ctx=ctx)
    return prefill


def make_decode_step(model, ctx: Ctx):
    def decode(base, trainable, masks, token, cache):
        logits, new_cache = model.decode_step(base, trainable, masks,
                                              token["tokens"], cache, ctx=ctx)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, new_cache
    return decode


# ---------------------------------------------------------------- shardings -

def tree_shardings(meta_tree, mesh, rules):
    return SH.sharding_tree(meta_tree, mesh, rules)


def batch_shardings(batch_specs: dict, logical_axes: dict, mesh, rules):
    out = {}
    for k, sds in batch_specs.items():
        axes = logical_axes.get(k, ("batch",) + (None,) * (len(sds.shape) - 1))
        spec = SH.spec_for_axes(axes, rules, mesh)
        spec = SH._divisible(sds.shape, spec, mesh)
        out[k] = NamedSharding(mesh, spec)
    return out


def abstract_opt_state(opt: OPT.Optimizer, trainable_abstract):
    return jax.eval_shape(opt.init, trainable_abstract)


def sharding_like(abstract_tree, template_shardings, mesh):
    """Shardings for derived trees (opt state mirrors trainable; scalars
    replicated)."""
    rep = NamedSharding(mesh, P())

    def pick(x):
        return rep if not hasattr(x, "shape") or x.ndim == 0 else None

    # opt state: mu/nu mirror params; step scalar replicated
    def walk(abs_node, tmpl):
        if isinstance(abs_node, dict):
            if set(abs_node) == {"step", "mu", "nu"}:
                return {"step": rep,
                        "mu": tmpl, "nu": tmpl}
            return {k: walk(v, tmpl) for k, v in abs_node.items()}
        return rep
    return walk(abstract_tree, template_shardings)
