"""Unified language model covering all assigned architectures.

One `Model` object builds, from an ArchConfig:
  - the frozen base meta tree (decoder-only, optionally encoder-decoder),
  - the trainable tree for a PEFT strategy (BEA/LoRA/FFA adapters, bottleneck
    adapters, or full fine-tuning),
  - rank-mask trees (the paper's dynamic rank allocation state),
  - KV/SSM cache metas for serving,
and exposes pure functions: forward, train loss, prefill, decode.

Layer execution follows the Plan (models/plan.py): repeated patterns are
`lax.scan`-ned over stacked params with `jax.checkpoint` on the body.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import adapters as AD
from repro.models import blocks as BK
from repro.models import layers as L
from repro.models.plan import Plan, build_plan, stack_meta
from repro.pytree import ParamMeta, abstractify, materialize


@dataclasses.dataclass
class Ctx:
    """Execution context threaded through apply fns."""
    mesh: Any = None
    rules: dict | None = None


def _get(tree, key):
    return tree.get(key) if tree else None


# --------------------------------------------------------------------------
# Plan-level meta builders
# --------------------------------------------------------------------------

def _plan_meta(cfg, plan: Plan, build_fn, share_skip: bool = True) -> dict:
    """Build {body: {p<j>: stacked}, shared: ..., tail: {t<i>: ...}}.

    build_fn(kind) -> block-level meta (params, adapters, or cache); may
    return {} / None for blocks with nothing (filtered out).
    ``share_skip``: shared_attn positions share params/adapters (one "shared"
    entry) — but per-position state (KV caches) must NOT be shared, so cache
    trees are built with share_skip=False.
    """
    out: dict = {}
    if plan.repeats:
        body = {}
        for j, kind in enumerate(plan.period):
            if kind == "shared_attn" and share_skip:
                continue
            m = build_fn(kind)
            if m:
                body[f"p{j}"] = stack_meta(m, plan.repeats)
        out["body"] = body
    if share_skip and ("shared_attn" in plan.period
                       or "shared_attn" in plan.tail):
        m = build_fn("attn")
        if m:
            out["shared"] = m
    tail = {}
    for i, kind in enumerate(plan.tail):
        if kind == "shared_attn" and share_skip:
            continue
        m = build_fn(kind)
        if m:
            tail[f"t{i}"] = m
    if tail:
        out["tail"] = tail
    return out


def _maybe_remat(fn, remat, mode, ctx):
    """Per-layer activation checkpointing; ctx.rules['remat_policy'] picks
    the XLA saveable set ('dots' saves matmul outputs → fewer recompute
    passes at higher live memory — a §Perf knob)."""
    if not (remat and mode == "train"):
        return fn
    pol = None
    if ctx is not None and ctx.rules:
        pol = ctx.rules.get("remat_policy")
    if pol == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if pol == "nothing":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(fn)


def _run_plan(plan: Plan, params, x, cfg, *, mode, ad, masks, caches, ctx,
              enc_out=None, remat=True, unroll=False):
    """Execute a plan segment.  Returns (x, stats, new_caches)."""
    ad = ad or {}
    masks = masks or {}
    caches = caches or {}
    shared_p = _get(params, "shared")
    shared_ad = _get(ad, "shared")
    shared_m = _get(masks, "shared")
    aux = {}

    def one_block(pj, x, kind, adj, mj, cj):
        real_kind = kind
        if kind == "shared_attn":
            # zamba2: the shared block serves with a sliding window if set
            real_kind = "local" if cfg.sliding_window else "attn"
        return BK.block_apply(pj, x, cfg, real_kind, mode=mode, ad=adj,
                              masks=mj, cache=cj, ctx=ctx, enc_out=enc_out)

    if plan.repeats:
        body_p = params["body"]
        body_ad = _get(ad, "body") or {}
        body_m = _get(masks, "body") or {}
        body_c = _get(caches, "body")

        def body_fn(x, xs):
            lp, lad, lm, lc = xs
            new_c = {}
            a_tot = {}
            for j, kind in enumerate(plan.period):
                if kind == "shared_attn":
                    pj, adj, mj = shared_p, shared_ad, shared_m
                else:
                    pj = lp[f"p{j}"]
                    adj, mj = _get(lad, f"p{j}"), _get(lm, f"p{j}")
                cj = _get(lc, f"p{j}") if lc else None
                x, a, ncj = one_block(pj, x, kind, adj, mj, cj)
                a_tot = BK.merge_stats(a_tot, a)
                if ncj:
                    new_c[f"p{j}"] = ncj
            return x, (a_tot, new_c or None)

        fn = _maybe_remat(body_fn, remat, mode, ctx)
        x, (a_steps, new_body_c) = jax.lax.scan(
            fn, x, (body_p, body_ad, body_m, body_c),
            unroll=plan.repeats if unroll else 1)
        aux = BK.merge_stats(aux, {k: v.sum() for k, v in a_steps.items()})
    else:
        new_body_c = None

    new_tail_c = {}
    tail_p = _get(params, "tail") or {}
    tail_ad = _get(ad, "tail") or {}
    tail_m = _get(masks, "tail") or {}
    tail_c = _get(caches, "tail") or {}
    for i, kind in enumerate(plan.tail):
        if kind == "shared_attn":
            pj, adj, mj = shared_p, shared_ad, shared_m
        else:
            pj = tail_p[f"t{i}"]
            adj, mj = _get(tail_ad, f"t{i}"), _get(tail_m, f"t{i}")
        cj = _get(tail_c, f"t{i}")
        blk = functools.partial(one_block, kind=kind, adj=adj, mj=mj, cj=cj)
        wrapped = _maybe_remat(lambda p, y: blk(p, y), remat, mode, ctx)
        x, a, ncj = wrapped(pj, x)
        aux = BK.merge_stats(aux, a)
        if ncj:
            new_tail_c[f"t{i}"] = ncj

    new_caches = {}
    if new_body_c is not None:
        new_caches["body"] = new_body_c
    if new_tail_c:
        new_caches["tail"] = new_tail_c
    return x, aux, (new_caches or None)


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------

class Model:
    def __init__(self, cfg, peft: str = AD.BEA, unroll: bool = False):
        self.cfg = cfg
        self.peft = peft
        # unroll=True: no lax.scan over layers — used by the dry-run so
        # cost_analysis/collective parsing see per-layer ops (scan bodies are
        # counted once), and by structurally-pruning federated runs.
        self.unroll = unroll
        dec_pattern = cfg.layer_pattern
        if cfg.is_encoder_decoder:
            dec_pattern = tuple(            # decoder blocks get cross-attn
                "dec" if k == "attn" else k for k in dec_pattern)
        if unroll:
            # flat per-layer params, python loop: needed by the federated
            # runtime (structural pruning / per-module SVD init) and the
            # dry-run calibration programs
            self.plan = Plan((), 0, tuple(dec_pattern))
            self.enc_plan = (Plan((), 0, ("enc",) * cfg.n_encoder_layers)
                             if cfg.is_encoder_decoder else None)
        else:
            self.plan = build_plan(dec_pattern)
            self.enc_plan = (build_plan(("enc",) * cfg.n_encoder_layers)
                             if cfg.is_encoder_decoder else None)

    # ---- metas ------------------------------------------------------------

    def base_meta(self) -> dict:
        cfg = self.cfg
        m: dict = {"embed": L.embed_meta(cfg)}
        if cfg.is_encoder_decoder:
            m["enc"] = _plan_meta(cfg, self.enc_plan,
                                  lambda k: BK.block_meta(cfg, k))
            m["enc_norm"] = L.norm_meta(cfg)
        m["dec"] = _plan_meta(cfg, self.plan,
                              lambda k: BK.block_meta(cfg, k))
        m["final_norm"] = L.norm_meta(cfg)
        if not cfg.tie_embeddings and not cfg.n_classes:   # a classifier
            m["head"] = ParamMeta((cfg.d_model, cfg.vocab_size), cfg.pdtype,
                                  ("embed_fsdp", "vocab"), init="normal")
        return m

    def adapter_meta(self) -> dict:
        cfg, peft = self.cfg, self.peft
        out: dict = {}
        if peft in ("none",):
            return out
        if cfg.is_encoder_decoder:
            enc = _plan_meta(cfg, self.enc_plan,
                             lambda k: BK.block_adapter_meta(cfg, k, peft))
            if enc:
                out["enc"] = enc
        dec = _plan_meta(cfg, self.plan,
                         lambda k: BK.block_adapter_meta(cfg, k, peft))
        if dec:
            out["dec"] = dec
        return out

    def trainable_meta(self) -> dict:
        out = {"adapters": self.adapter_meta()}
        if self.cfg.n_classes:
            out["head"] = {
                "w": ParamMeta((self.cfg.d_model, self.cfg.n_classes),
                               jnp.float32, (None, None), init="normal"),
                "b": ParamMeta((self.cfg.n_classes,), jnp.float32, (None,),
                               init="zeros")}
        return out

    def mask_meta(self) -> dict:
        """One boolean (r,) per adapter module (stacked where scanned).

        A *module* is one insertion position; its mask leaf matches the
        leading (stacking/expert-free) dims of the module's "A" tensor.
        """
        def to_mask(ad_module):
            a = ad_module["A"]
            # strip the expert axis if present: mask is per-(layer,component)
            lead = a.shape[:-2]
            if len(lead) >= 1 and self.cfg.n_experts and \
                    lead[-1] == self.cfg.n_experts:
                lead = lead[:-1]
            r = a.shape[-2]
            return ParamMeta(lead + (r,), jnp.bool_,
                             (None,) * len(lead) + ("rank",), init="ones")

        def walk(tree):
            if isinstance(tree, dict) and "A" in tree and "B" in tree:
                return to_mask(tree)
            if isinstance(tree, dict):
                out = {k: walk(v) for k, v in tree.items()
                       if not (isinstance(v, dict) and "down" in v)}
                return {k: v for k, v in out.items() if v}
            return None

        return walk(self.adapter_meta()) or {}

    def cache_meta(self, batch: int, seq: int, src_len: int = 0) -> dict:
        cfg = self.cfg
        out = {"dec": _plan_meta(
            cfg, self.plan,
            lambda k: BK.block_cache_meta(cfg, k, batch, seq, src_len),
            share_skip=False)}
        return out

    @property
    def count_keys(self) -> tuple[str, ...]:
        """Keys of the ``counts`` the losses return."""
        held = self.cfg.router_score == "sigmoid" and any(
            BK.is_moe(k) for k in self.cfg.layer_pattern)
        return ("moe_routed", "moe_rows") if held else ()

    # ---- materialization ----------------------------------------------------

    def init(self, key) -> tuple[dict, dict]:
        kb, kt = jax.random.split(key)
        return (materialize(self.base_meta(), kb),
                materialize(self.trainable_meta(), kt))

    def init_masks(self) -> dict:
        return jax.tree.map(lambda m: jnp.ones(m.shape, m.dtype),
                            self.mask_meta(),
                            is_leaf=lambda x: isinstance(x, ParamMeta))

    # ---- forward ------------------------------------------------------------

    def forward(self, base, trainable, masks, batch, *, mode="train",
                cache=None, ctx=None, remat=True):
        """Returns (logits, stats, new_cache); ``stats`` sums the blocks'
        (models/blocks.py ``block_apply``).

        batch keys: tokens (B,S) [decoder]; prefix_embeds (B,P,D) [vlm];
        enc_tokens (B,Se) or frames (B,Se,D) [enc-dec]; positions optional.
        """
        cfg = self.cfg
        ctx = ctx or Ctx()
        adapters = (trainable or {}).get("adapters") or {}
        cache = cache or {}
        aux = {}

        enc_out = None
        if cfg.is_encoder_decoder and mode != "decode":
            if "frames" in batch:                 # audio: precomputed embeds
                ex = batch["frames"].astype(cfg.cdtype)
            else:
                ex = L.embed_apply(base["embed"], batch["enc_tokens"], cfg)
            ex, a, _ = _run_plan(self.enc_plan, base["enc"], ex, cfg,
                                 mode="train" if mode == "train" else "prefill",
                                 ad=_get(adapters, "enc"),
                                 masks=_get(masks, "enc"), caches=None,
                                 ctx=ctx, remat=remat, unroll=self.unroll)
            enc_out = L.norm_apply(base["enc_norm"], ex, cfg)
            aux = BK.merge_stats(aux, a)

        tokens = batch["tokens"]
        x = L.embed_apply(base["embed"], tokens, cfg)
        n_prefix = 0
        if "prefix_embeds" in batch:              # vlm: patch embeds prepended
            pe = batch["prefix_embeds"].astype(cfg.cdtype)
            n_prefix = pe.shape[1]
            x = jnp.concatenate([pe, x], axis=1)
        if ctx.mesh is not None:
            from repro import sharding as SH
            x = SH.constrain(x, ("batch", None, None), ctx.mesh, ctx.rules)

        x, a, new_cache = _run_plan(
            self.plan, base["dec"], x, cfg, mode=mode,
            ad=_get(adapters, "dec"), masks=_get(masks, "dec"),
            caches=_get(cache, "dec"), ctx=ctx, enc_out=enc_out, remat=remat,
            unroll=self.unroll)
        aux = BK.merge_stats(aux, a)
        x = L.norm_apply(base["final_norm"], x, cfg)
        if n_prefix:
            x = x[:, n_prefix:]

        if (trainable or {}).get("head") and cfg.n_classes:
            # mean pooling (paper uses CLS on a *pretrained* base; with the
            # emulation's random frozen base, mean pooling carries the signal)
            pooled = x.mean(axis=1).astype(jnp.float32)
            h = trainable["head"]
            logits = pooled @ h["w"] + h["b"]
        else:
            if cfg.tie_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", x,
                                    base["embed"]["tok"].astype(x.dtype))
            else:
                logits = jnp.einsum("bsd,dv->bsv", x,
                                    base["head"].astype(x.dtype))
            logits = L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
        return logits, aux, ({"dec": new_cache} if new_cache else None)

    # ---- losses -------------------------------------------------------------
    # Both return (objective, (loss, metric, counts)): ``counts`` are the
    # blocks' statistics other than the load-balance loss (the held
    # experts' ``moe_routed`` and ``moe_rows``; {} for other models).

    def lm_loss(self, base, trainable, masks, batch, ctx=None, remat=True):
        logits, aux, _ = self.forward(base, trainable, masks, batch,
                                      mode="train", ctx=ctx, remat=remat)
        targets = batch["targets"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        valid = (targets >= 0).astype(jnp.float32)
        loss = (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)
        bal = aux.pop("balance", jnp.float32(0.0))
        return loss + self.cfg.router_aux_coef * bal, (loss, bal, aux)

    def cls_loss(self, base, trainable, masks, batch, ctx=None, remat=True):
        logits, aux, _ = self.forward(base, trainable, masks, batch,
                                      mode="train", ctx=ctx, remat=remat)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        acc = (logits.argmax(-1) == labels).astype(jnp.float32).mean()
        bal = aux.pop("balance", jnp.float32(0.0))
        return loss + self.cfg.router_aux_coef * bal, (loss, acc, aux)

    # ---- serving ------------------------------------------------------------

    def prefill(self, base, trainable, masks, batch, cache, ctx=None):
        logits, _, new_cache = self.forward(
            base, trainable, masks, batch, mode="prefill", cache=cache,
            ctx=ctx, remat=False)
        return logits[:, -1], new_cache

    def decode_step(self, base, trainable, masks, token, cache, ctx=None):
        """token: (B, 1) int32.  One step against the cache."""
        logits, _, new_cache = self.forward(
            base, trainable, masks, {"tokens": token}, mode="decode",
            cache=cache, ctx=ctx, remat=False)
        return logits[:, -1], new_cache
