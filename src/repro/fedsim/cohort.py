"""Vectorized client cohorts (fedsim pillar 1).

The sequential oracle (federated/server.py) emulates each selected client
with a Python loop over jitted steps — ``clients_per_round × local_batches``
dispatches per round.  Here the whole local-training phase is ONE dispatch:

  - per-client params / optimizer states are stacked on a leading cohort axis,
  - local SGD runs as a loop over client-steps on W ≤ C lanes: the host
    ``schedule`` packs each client's real steps, in order, into a
    (T_cap, W) table, and every iteration gathers the W scheduled clients'
    state and batches, runs the oracle's step ``vmap``-ped over the lanes
    and scatters the results back.  Uneven clients take only their real
    steps, and dead (weight-0) slots none: a padded step is never computed,
  - the cohort axis is ``shard_map``-ped across ``jax.devices()`` with an
    on-device ``psum`` weighted FedAvg, so aggregation needs no host gather.

The lane count W comes from the partition (``lane_count``): enough lanes
for an average cohort's steps in T iterations.  A population whose clients
all fill T steps gets W = C and T iterations, the rectangle's work.

Clients whose data is smaller than one batch (ragged trailing batch) cannot
join the rectangle; ``build_cohort`` reports them as fallbacks and the runner
routes them through the oracle's per-client path.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, NamedTuple

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
    cids: list[int]               # real client ids
    slots: list[int]              # stacked slot of each of ``cids``
    fallback: list[int]           # too-small clients → sequential path
    n_steps: np.ndarray           # (C,) int — real local steps per client


def cohort_steps(n: int, fc) -> int:
    """Local steps a client of ``n`` examples takes in the cohort program
    (0 for a sub-batch client, which takes the sequential fallback)."""
    if n < fc.batch_size:
        return 0
    return min(fc.max_local_batches, n // fc.batch_size) * fc.local_epochs


def lane_count(parts: list, fc, c_pad: int, ndev: int) -> int:
    """Lanes W of the cohort program, fixed for the run: enough for the
    mean cohort's real steps in T iterations,
    ``min(c_pad, ⌈cpr · n̄ / T⌉ rounded up to a multiple of ndev)``, with
    ``n̄`` the mean of ``cohort_steps`` over every client.  A population
    whose clients all fill T steps gets ``c_pad`` lanes."""
    T = fc.max_local_batches * fc.local_epochs
    cpr = min(fc.clients_per_round, len(parts))
    total = sum(cohort_steps(len(p), fc) for p in parts)
    w = max(1, -(-cpr * total // (len(parts) * T)))
    return min(c_pad, -(-w // ndev) * ndev)


def shard_order(n_steps: np.ndarray, ndev: int) -> np.ndarray:
    """Slot order that balances the per-device step sums: the longest
    clients first, each to the device with the fewest steps that still
    has a free slot.  One device keeps the given order."""
    n = len(n_steps)
    if ndev == 1:
        return np.arange(n)
    per = n // ndev
    bins: list[list[int]] = [[] for _ in range(ndev)]
    sums = [0] * ndev
    for i in np.argsort(-np.asarray(n_steps), kind="stable"):
        d = min((d for d in range(ndev) if len(bins[d]) < per),
                key=lambda d: sums[d])
        bins[d].append(int(i))
        sums[d] += int(n_steps[i])
    return np.asarray([i for b in bins for i in b])


class Plan(NamedTuple):
    """Which client-step each lane runs at each iteration of the cohort
    program.  Lanes are split over the devices as the cohort is, so a
    lane's client is an index into its device's shard of the cohort; an
    idle lane holds the shard size, one past its last slot."""
    client: np.ndarray            # (T_cap, W) int32
    step: np.ndarray              # (T_cap, W) int32 — that client's step
    n_iter: np.ndarray            # () int32 — iterations that run


def schedule(n_steps: np.ndarray, T: int, lanes: int, ndev: int = 1
             ) -> Plan:
    """Pack each slot's ``n_steps`` real steps onto ``lanes`` lanes.

    Each device's lanes take its own slots.  At every iteration the clients
    with the most steps left take one step each, so a client's steps run
    in order, never two at once, and the device finishes in
    ``max(max n, ⌈Σ n / lanes per device⌉)`` iterations, the fewest
    possible.  The table has ``⌈C · T / lanes⌉`` rows, so any cohort fits
    one compiled shape; ``n_iter`` is the largest device's count.
    """
    C = len(n_steps)
    per, w = C // ndev, lanes // ndev
    t_cap = -(-C * T // lanes)
    client = np.full((t_cap, lanes), per, np.int32)
    step = np.zeros((t_cap, lanes), np.int32)
    n_iter = 0
    for d in range(ndev):
        left = np.array(n_steps[d * per:(d + 1) * per], np.int64)
        done = np.zeros(per, np.int64)
        t = 0
        while left.any():
            take = np.argsort(-left, kind="stable")[:w]
            take = take[left[take] > 0]
            lo = d * w
            client[t, lo:lo + len(take)] = take
            step[t, lo:lo + len(take)] = done[take]
            done[take] += 1
            left[take] -= 1
            t += 1
        n_iter = max(n_iter, t)
    return Plan(client, step, np.asarray(n_iter, np.int32))


def build_cohort(train: Dataset, parts: list[np.ndarray], sel, fc, rnd: int,
                 pad_clients_to: int, ndev: int = 1) -> Cohort | None:
    """Materialize selected clients' local batches into a padded rectangle
    using the same RNG streams as the sequential oracle.

    ``ndev > 1`` orders the slots by ``shard_order``, so that each device's
    shard of the cohort holds about as many steps as the others.
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
    order = shard_order(np.asarray(nsteps), ndev)
    slot = np.argsort(order)
    return Cohort(
        batches={k: np.stack([stacked[i][k] for i in order])
                 for k in stacked[0]},
        step_mask=np.stack(smask)[order],
        weights=np.asarray(weights, np.float32)[order],
        cids=cids, slots=[int(slot[i]) for i in range(len(cids))],
        fallback=fallback, n_steps=np.asarray(nsteps)[order])


def cohort_mesh():
    """1-D mesh over every local device; the cohort axis shards across it.
    The axis is ``Auto``: values placed on it outside ``shard_map`` (the
    pinned broadcast state) carry no sharding type, which grouped products
    (``jax.lax.ragged_dot``, models/moe.py) have no rule for."""
    return jax.make_mesh((len(jax.devices()),), ("clients",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def stack_params(trainable: Any, n: int) -> Any:
    """Broadcast the (pruned) global trainable to n per-client copies."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), trainable)


def make_step(model, opt, task: str = "cls"):
    """One client's local step, run by the cohort program's lanes:
    ``step(base, masks, gate, params, opt_state, batch) → (params',
    opt_state', grads, loss, metric, counts)``, the oracle's step
    (``federated/client.py``) op for op."""
    loss_fn = model.cls_loss if task == "cls" else model.lm_loss
    remat = model.cfg.train_remat

    def step(base, masks, gate, params, opt_state, batch):
        def f(tr):
            return loss_fn(base, tr, masks, batch, remat=remat)

        (_, (loss, metric, counts)), g = jax.value_and_grad(
            f, has_aux=True)(params)
        updates, new_opt = opt.update(g, opt_state, params)
        if gate is not None:
            updates = jax.tree.map(
                lambda u, gt: u * jnp.asarray(gt, u.dtype), updates, gate)
        new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                  params, updates)
        return new_params, new_opt, g, loss, metric, counts

    return step


def make_cohort_fn(model, opt, task: str = "cls", mesh=None):
    """Build the one-dispatch cohort round.

    Returns jitted ``fn(base, stacked, masks, gate, bstacks, plan, weights)
    → (params_c, grads_c, losses_c, metrics_c, avg, counts)`` where
    ``plan`` is ``schedule``'s table, the ``_c`` outputs carry the cohort
    axis (``losses_c``/``metrics_c`` are (C, T), zero past a client's
    steps), ``avg`` is the weight-normalized on-device FedAvg of the final
    per-client params (weight-0 pad slots drop out) and ``counts`` sums the
    model's step counts (``cls_loss``'s; {} for most models) over every
    lane the round ran, idle lanes included: their work is done all the
    same, and ``steps / slots`` says how much of it was idle.

    Per device, the loop carries its shard's client-major state and runs
    ``plan.n_iter`` iterations (a traced count: one compile for every
    cohort of these shapes).  An iteration gathers each lane's client
    state and batch, steps all lanes at once, and scatters back; an idle
    lane's index is out of range, so its write is dropped.
    """
    lanes = jax.vmap(make_step(model, opt, task),
                     in_axes=(None, None, None, 0, 0, 0))
    mesh = mesh if mesh is not None else cohort_mesh()

    def cohort_round(base, stacked, masks, gate, bstacks, plan, weights):
        C = weights.shape[0]
        T = jax.tree.leaves(bstacks)[0].shape[1]
        state = (stacked, jax.vmap(opt.init)(stacked),
                 jax.tree.map(jnp.zeros_like, stacked))
        zeros = jnp.zeros((C, T), jnp.float32)
        counts0 = {k: jnp.float32(0.0) for k in model.count_keys}

        def body(t, carry):
            state, losses, metrics, counts = carry
            cl, st = plan.client[t], plan.step[t]
            batch = jax.tree.map(lambda b: b.at[cl, st].get(mode="clip"),
                                 bstacks)
            params, opt_state = jax.tree.map(
                lambda x: x.at[cl].get(mode="clip"), state[:2])
            p, o, g, loss, metric, c = lanes(base, masks, gate, params,
                                             opt_state, batch)
            counts = {k: v + c[k].sum() for k, v in counts.items()}
            state = jax.tree.map(lambda x, v: x.at[cl].set(v, mode="drop"),
                                 state, (p, o, g))
            losses = losses.at[cl, st].set(loss.astype(jnp.float32),
                                           mode="drop")
            metrics = metrics.at[cl, st].set(metric.astype(jnp.float32),
                                             mode="drop")
            return state, losses, metrics, counts

        (params_c, _, grads_c), losses_c, metrics_c, counts = \
            jax.lax.fori_loop(0, plan.n_iter, body,
                              (state, zeros, zeros, counts0))
        part = jax.tree.map(
            lambda p: jnp.tensordot(weights, p.astype(jnp.float32),
                                    axes=(0, 0)), params_c)
        tot = jax.lax.psum(part, "clients")
        wtot = jax.lax.psum(weights.sum(), "clients")
        avg = jax.tree.map(lambda s, p: (s / wtot).astype(p.dtype),
                           tot, params_c)
        return (params_c, grads_c, losses_c, metrics_c, avg,
                jax.lax.psum(counts, "clients"))

    cspec = P("clients")
    lspec = P(None, "clients")
    fn = jax.shard_map(
        cohort_round, mesh=mesh,
        in_specs=(P(), cspec, P(), P(), cspec, Plan(lspec, lspec, P()),
                  cspec),
        out_specs=(cspec, cspec, cspec, cspec, P(), P()),
        check_vma=False)
    return jax.jit(fn)


def slice_client(tree_c: Any, i: int) -> Any:
    """Host-side view of one client's slice of a stacked output tree."""
    return jax.tree.map(lambda x: x[i], tree_c)
