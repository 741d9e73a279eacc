"""Vectorized client cohorts (fedsim pillar 1).

The sequential oracle (federated/server.py) emulates each selected client
with a Python loop over jitted steps — ``clients_per_round × local_batches``
dispatches per round.  Here the whole local-training phase is ONE dispatch:

  - per-client params / optimizer states are stacked on a leading cohort axis,
  - local SGD runs as ``lax.scan`` over local batches inside ``vmap`` over
    clients (uneven client data handled by padding + per-client step masks:
    a padded step computes and then discards, so real steps are bit-identical
    in structure to the oracle's),
  - the cohort axis is ``shard_map``-ped across ``jax.devices()`` with an
    on-device ``psum`` weighted FedAvg, so aggregation needs no host gather.

Clients whose data is smaller than one batch (ragged trailing batch) cannot
join the rectangle; ``build_cohort`` reports them as fallbacks and the runner
routes them through the oracle's per-client path.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.data.synthetic import Dataset, batches as batch_iter


def client_batch_rng(seed: int, rnd: int, cid: int) -> np.random.Generator:
    """The per-(seed, round, client) batch-order stream.  Single source of
    truth shared by the sequential oracle, SLoRA stage 1, and the cohort
    builder — parity across runners is by construction."""
    return np.random.default_rng(seed * 1000 + rnd * 97 + int(cid))


@dataclasses.dataclass
class Cohort:
    """Host-side rectangle of one round's local datasets."""
    batches: dict                 # key -> (C, T, B, ...) np arrays
    step_mask: np.ndarray         # (C, T) bool — False for padded steps
    weights: np.ndarray           # (C,) f32 client data sizes (0 = pad slot)
    cids: list[int]               # real client ids, stacked order
    fallback: list[int]           # too-small clients → sequential path
    n_steps: np.ndarray           # (C,) int — real local steps per client


def build_cohort(train: Dataset, parts: list[np.ndarray], sel, fc, rnd: int,
                 pad_clients_to: int, bucket: bool = False) -> Cohort | None:
    """Materialize selected clients' local batches into a padded rectangle
    using the same RNG streams as the sequential oracle.

    ``bucket=True`` re-buckets the step axis per round: instead of padding
    every client to the global ``max_local_batches × local_epochs`` ceiling,
    the rectangle's T is the next power of two ≥ this cohort's real maximum
    step count — dirichlet-skewed cohorts stop paying for steps nobody runs,
    and the pow-2 snap bounds distinct compiled shapes to log2(T_max).
    """
    T = fc.max_local_batches * fc.local_epochs
    raw, weights, cids, fallback = [], [], [], []
    for cid in sel:
        idx = parts[cid]
        cd = Dataset(train.tokens[idx], train.labels[idx])
        gen = batch_iter(cd, fc.batch_size,
                         client_batch_rng(fc.seed, rnd, cid),
                         epochs=fc.local_epochs)
        bl = list(itertools.islice(gen, T))
        if not bl or any(v.shape[0] != fc.batch_size
                         for b in bl for v in b.values()):
            fallback.append(int(cid))
            continue
        raw.append(bl)
        weights.append(float(len(idx)))
        cids.append(int(cid))
    if not raw:
        return None
    if bucket:
        T = min(T, 1 << (max(len(bl) for bl in raw) - 1).bit_length())
    stacked, smask, nsteps = [], [], []
    for bl in raw:
        m = np.zeros(T, bool)
        m[:len(bl)] = True
        bl = bl + [bl[0]] * (T - len(bl))
        stacked.append({k: np.stack([b[k] for b in bl]) for k in bl[0]})
        smask.append(m)
        nsteps.append(int(m.sum()))
    C = max(pad_clients_to, len(stacked))
    while len(stacked) < C:                     # dead slots: weight 0, no steps
        stacked.append(stacked[0])
        smask.append(np.zeros(T, bool))
        weights.append(0.0)
        nsteps.append(0)
    return Cohort(
        batches={k: np.stack([s[k] for s in stacked]) for k in stacked[0]},
        step_mask=np.stack(smask), weights=np.asarray(weights, np.float32),
        cids=cids, fallback=fallback, n_steps=np.asarray(nsteps))


def cohort_mesh():
    """1-D mesh over every local device; the cohort axis shards across it."""
    return jax.make_mesh((len(jax.devices()),), ("clients",))


def stack_params(trainable: Any, n: int) -> Any:
    """Broadcast the (pruned) global trainable to n per-client copies."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), trainable)


def make_local_phase(model, opt, task: str = "cls"):
    """One client's whole local-training phase as a scan over (padded)
    batches — the shared inner loop of ``make_cohort_fn`` (vmapped per
    round) and ``fused.make_fused_fn`` (vmapped inside a scan over rounds).

    ``local_phase(base, params0, masks, gate, bstack, smask) → (params,
    grads, losses, metrics)``; a False ``smask`` step computes and then
    discards, so real steps are structurally identical to the oracle's.
    """
    loss_fn = model.cls_loss if task == "cls" else model.lm_loss

    def local_phase(base, params0, masks, gate, bstack, smask):
        opt0 = opt.init(params0)
        g0 = jax.tree.map(jnp.zeros_like, params0)

        def step(carry, xs):
            params, opt_state, grads = carry
            batch, live = xs

            def f(tr):
                return loss_fn(base, tr, masks, batch, remat=False)

            (_, (loss, metric)), g = jax.value_and_grad(
                f, has_aux=True)(params)
            updates, new_opt = opt.update(g, opt_state, params)
            if gate is not None:
                updates = jax.tree.map(
                    lambda u, gt: u * jnp.asarray(gt, u.dtype), updates, gate)
            new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                      params, updates)

            def keep(n, o):
                return jnp.where(live, n, o)
            carry = (jax.tree.map(keep, new_params, params),
                     jax.tree.map(keep, new_opt, opt_state),
                     jax.tree.map(keep, g, grads))
            return carry, (loss, metric)

        (params, _, grads), (losses, metrics) = jax.lax.scan(
            step, (params0, opt0, g0), (bstack, smask))
        return params, grads, losses, metrics

    return local_phase


def make_cohort_fn(model, opt, task: str = "cls", mesh=None):
    """Build the one-dispatch cohort round.

    Returns jitted ``fn(base, stacked, masks, gate, bstacks, smasks, weights)
    → (params_c, grads_c, losses_c, metrics_c, avg)`` where the ``_c`` outputs
    carry the cohort axis and ``avg`` is the weight-normalized on-device
    FedAvg of the final per-client params (weight-0 pad slots drop out).
    """
    local_phase = make_local_phase(model, opt, task)
    mesh = mesh if mesh is not None else cohort_mesh()

    def cohort_round(base, stacked, masks, gate, bstacks, smasks, weights):
        params_c, grads_c, losses_c, metrics_c = jax.vmap(
            local_phase, in_axes=(None, 0, None, None, 0, 0))(
            base, stacked, masks, gate, bstacks, smasks)
        part = jax.tree.map(
            lambda p: jnp.tensordot(weights, p.astype(jnp.float32),
                                    axes=(0, 0)), params_c)
        tot = jax.lax.psum(part, "clients")
        wtot = jax.lax.psum(weights.sum(), "clients")
        avg = jax.tree.map(lambda s, p: (s / wtot).astype(p.dtype),
                           tot, params_c)
        return params_c, grads_c, losses_c, metrics_c, avg

    cspec = P("clients")
    fn = jax.shard_map(
        cohort_round, mesh=mesh,
        in_specs=(P(), cspec, P(), P(), cspec, cspec, cspec),
        out_specs=(cspec, cspec, cspec, cspec, P()),
        check_vma=False)
    return jax.jit(fn)


def slice_client(tree_c: Any, i: int) -> Any:
    """Host-side view of one client's slice of a stacked output tree."""
    return jax.tree.map(lambda x: x[i], tree_c)
