"""Federated server loop (paper Algorithm 1), strategy-agnostic.

Implements: client selection → CommPru'd broadcast → parallel local training
→ delta-space aggregation → FedArb mask arbitration → RankDet module gating —
with byte-exact communication accounting per round.

The sequential per-client loop below (``runner="seq"``) is the parity oracle.
``FedConfig.runner`` routes the same run through ``repro.fedsim``:
``"cohort"`` executes each round's local phase as one vmap+scan+shard_map
dispatch, ``"async"`` runs FedBuff-style buffered aggregation on a simulated
event clock (see fedsim/runner.py).

Every upload — seq, cohort, async, and SLoRA stage 1 — is a
``fedsim.pipeline.ClientUpdate`` (delta tree + weight + rank votes) routed
through the shared delta pipeline: flatten → DP clip → codec (identity /
int8 / topk / signsgd / powersgd) → error feedback → byte accounting → link
pricing → aggregate.  Broadcasts ride the same codecs as delta-coded streams
(``DeltaChannel``).

Privacy (``repro.secagg``): ``FedConfig.secagg="mask"`` routes the same
encoded delta wires through simulated Bonawitz secure aggregation — the
server sees only the field aggregate of weighted deltas and the summed
one-hot rank votes (aggregate-only arbitration) — and
``dp_clip``/``dp_noise_multiplier`` add client-level DP-FedAvg with a
per-round ε trajectory in the history.  Field-exact codecs (signsgd)
compose with both.  The oracle's simulated wall clock prices *encoded*
bytes through the per-device-class ``fedsim.transport.Link``s, so lossy
codecs shrink simulated time, not just byte counts.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as OBS
from repro import optim as OPT
from repro.core import comm as COMM
from repro.core import importance as IMP
from repro.core import masks as MK
from repro.core import pruning as PR
from repro.data.synthetic import Dataset, batches
from repro.federated import client as CL
from repro.federated import devices as DV
from repro.fedsim import pipeline as PL
from repro.fedsim import transport as T
from repro.fedsim.cohort import client_batch_rng, stack_params
from repro.secagg import dp as DP
from repro.secagg import protocol as SA


@dataclasses.dataclass
class FedConfig:
    rounds: int = 30
    clients_per_round: int = 5
    local_epochs: int = 1
    batch_size: int = 8
    lr: float = 2e-3
    head_lr: float = 2e-3
    seed: int = 0
    task: str = "cls"
    eval_every: int = 5
    max_local_batches: int = 8          # caps emulation cost per client
    eval_batches: int = 16
    # ---- fedsim (device-parallel simulation / transport / async) ----------
    runner: str = "seq"                 # seq | cohort | async
    opt_state_dtype: str = "float32"    # adam moment storage:
                                        # float32 | bfloat16 | int8
    codec: str = "identity"      # identity | int8 | topk | signsgd | powersgd
    powersgd_rank: int = 2              # q for the powersgd codec
    dropout: float = 0.0                # P(selected client never reports)
    straggler: float = 0.0              # P(client is a straggler this round)
    straggler_slow: float = 4.0         # straggler compute-time multiplier
    buffer_k: int = 0                   # async: aggregate every K arrivals
    async_concurrency: int = 0          # async: in-flight clients (0 → 2K)
    staleness_alpha: float = 0.5        # async: weight = n·(1+s)^-alpha
    event_seed: int = 0                 # dropout/straggler/event-time stream
    device_profile: str = "distilbert"  # federated/devices.py compute profile
    # ---- privacy (repro.secagg: masked aggregation + client-level DP) ------
    secagg: str = "off"                 # off | mask (Bonawitz-style pairwise)
    secagg_threshold: float = 2.0 / 3.0  # Shamir threshold frac of the cohort
    secagg_bits: int = 32               # field modulus 2^bits
    secagg_frac_bits: int = 16          # fixed-point fractional bits
    secagg_clip: float = 8.0            # per-element clip at field encode
    dp_clip: float = 0.0                # client delta L2 clip (0 → DP off)
    dp_noise_multiplier: float = 0.0    # z: server noise std = z·clip on sum
    dp_delta: float = 1e-5              # δ for the RDP accountant's ε(δ)


@dataclasses.dataclass
class RoundLog:
    rnd: int
    down_bytes: int
    up_bytes: int
    live_ranks: int
    dead_modules: int
    trainable_params: int
    loss: float
    acc: float = float("nan")
    sim_time_s: float = 0.0             # simulated wall clock (fedsim runners)
    staleness: float = 0.0              # mean update staleness (async runner)


def fedavg(trees: list[Any], weights: list[float]) -> Any:
    w = np.asarray(weights, np.float64)
    w = (w / w.sum()).astype(np.float32)

    def avg(*leaves):
        acc = leaves[0].astype(jnp.float32) * w[0]
        for wi, leaf in zip(w[1:], leaves[1:]):
            acc = acc + leaf.astype(jnp.float32) * wi
        return acc.astype(leaves[0].dtype)

    return jax.tree.map(avg, *trees)


def evaluate(model, base, trainable, masks, test: Dataset, fc: FedConfig):
    """cls → accuracy over the eval batches; lm → mean per-token NLL (the
    eval step returns a batch-mean NLL for lm; next-token targets are
    derived from the dataset's token stream)."""
    ev = CL.make_eval_step(model, fc.task)
    rng = np.random.default_rng(0)
    total, vals = 0, []
    # eval-kind span: the eval step legitimately jit-compiles on its first
    # use (often during the *final* round), and obs.profile buckets compile
    # spans under an eval ancestor separately from the round-loop flatness
    # accounting — without this wrap, the first eval would read as a
    # round-loop retrace
    esp = OBS.get_tracer().begin("evaluate", kind="eval", task=fc.task)
    for i, batch in enumerate(batches(test, fc.batch_size, rng)):
        if i >= fc.eval_batches:
            break
        if fc.task == "cls":
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            vals.append(ev(base, trainable, masks, jb))
            total += len(batch["labels"])
        else:
            toks = jnp.asarray(batch["tokens"])
            jb = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
            vals.append(ev(base, trainable, masks, jb))
    # device scalars accumulate without blocking dispatch; one transfer here
    vals = [float(v) for v in jax.device_get(vals)]
    esp.end(n_batches=len(vals))
    if fc.task == "cls":
        return sum(vals) / max(total, 1)
    return float(np.mean(vals)) if vals else float("nan")


# ---------------------------------------------------------------------------
# Shared round machinery (used by the oracle below and by fedsim/runner.py)
# ---------------------------------------------------------------------------

def _init_run(model, strategy, fc: FedConfig):
    """Common run state: init params, masks, optimizer, selection stream."""
    key = jax.random.key(fc.seed)
    base, trainable = model.init(key)
    base, trainable = strategy.post_init(model, base, trainable,
                                         jax.random.fold_in(key, 1))
    masks = model.init_masks() if strategy.uses_masks() else None
    masks_np = MK.jax_to_np(masks) if masks else None
    n_rank_units = MK.total_ranks(masks_np) if masks_np else 0
    total_steps = fc.rounds * fc.max_local_batches * fc.local_epochs
    opt = OPT.adam(OPT.linear_decay(fc.lr, total_steps),
                   state_dtype=fc.opt_state_dtype)
    rng = np.random.default_rng(fc.seed)
    return base, trainable, masks, masks_np, n_rank_units, opt, rng


def pin_params(tree, masks=None, sharding=None):
    """Commit state to one canonical placement, once, before a run's loop.

    Fresh params are uncommitted arrays; jit outputs are committed.  A
    program called with both lowers (and compiles) the *identical* jaxpr
    twice — a silent duplicate XLA compile in round 1.  Pinning the initial
    state, and ``round_programs`` placing every round's outputs on the same
    sharding, keep compile counts flat after the first round (asserted in
    tests/test_obs.py via obs.profile.compile_stats).
    """
    dst = sharding if sharding is not None else jax.devices()[0]
    tree = jax.device_put(tree, dst)
    if masks is not None:
        masks = jax.device_put(masks, dst)
    return tree, masks


class RoundPrograms(NamedTuple):
    """The server's per-round work on the adapter tree (``round_programs``)."""
    prune: Callable     # (trainable, masks_np) → (trainable, masks)
    place: Callable     # (bc, masks) → (bc, masks, gate, stacked)
    upload: Callable | None     # (pc, gc, bc, masks) → (scores, norms)


def round_programs(strategy, sharding, slots: int = 0,
                   slot_sharding=None) -> RoundPrograms:
    """One run's round-loop work on the adapter tree as compiled programs,
    each one dispatch where eager code took one per leaf.

    ``prune`` zeroes the trainable adapters' masked-out ranks (CommPru,
    ``COMM.prune_tree``) and returns the masks on the device: before the
    broadcast and after arbitration.  ``place`` puts the broadcast state
    and its masks on ``sharding``, builds the strategy's optimizer gate
    and, for ``slots`` > 0, the cohort program's per-client copies
    (``cohort.stack_params``) split over ``slot_sharding``.

    For ``slots`` > 0, ``upload`` reduces the cohort program's per-slot
    params ``pc`` (and grads ``gc``, where the importance method reads
    them, else None) against the broadcast ``bc`` to what the host's
    upload stage reads, both split over ``slot_sharding``: each slot's
    importance scores, ``(slots, R)`` in ``IMP.score_layout``'s order
    (None where ``strategy.device_importance()`` is), and the L2 norm of
    its identity wire ``pc − bc`` (``transport.wire_norm``).

    Mask values are arguments, so a round whose masks changed compiles
    nothing.  ``prune`` takes the host masks and ``place`` and ``upload``
    device masks (``prune``'s, or pinned ones), each always in one
    placement, and every output lands on the same sharding every round.
    """
    method = strategy.device_importance()

    def prune(trainable, masks_np):
        return dict(trainable, adapters=COMM.prune_tree(
            trainable["adapters"], masks_np)), masks_np

    def place(bc, masks):
        stacked = stack_params(bc, slots) if slots else None
        return bc, masks, strategy.optimizer_gate(bc, masks), stacked

    def upload(pc, gc, bc, masks):
        def one(p, g):
            norm = T.wire_norm(jax.tree.map(
                lambda x, b: x.astype(jnp.float32) - b.astype(jnp.float32),
                p, bc), masks)
            if method is None:
                return None, norm
            return IMP.score_flat(p["adapters"],
                                  g["adapters"] if g is not None else None,
                                  method, strategy.n_experts), norm
        return jax.vmap(one)(pc, gc)

    return RoundPrograms(
        jax.jit(prune, out_shardings=sharding),
        jax.jit(place, out_shardings=(sharding, sharding, sharding,
                                      slot_sharding)),
        jax.jit(upload, out_shardings=slot_sharding) if slots else None)


def _arbitrate(strategy, trainable, local_masks, masks, masks_np, rnd,
               prune):
    """FedArb + RankDet after aggregation → (trainable, masks, masks_np),
    pruned by ``prune`` (``round_programs``).  Traced as an ``arbitrate``
    span counting ``pruned``: ranks switched off this round."""
    if strategy.uses_masks():
        tr = OBS.get_tracer()
        asp = tr.begin("arbitrate", kind="stage", rnd=int(rnd))
        live0 = MK.count_true(masks_np) if tr.enabled else 0
        strategy.last_aggregate = trainable   # FedARA-global ablation hook
        masks_np = strategy.arbitrate(rnd, local_masks, masks_np)
        trainable, masks = prune(trainable, masks_np)
        if tr.enabled:
            asp.count(pruned=live0 - MK.count_true(masks_np))
        asp.end()
    return trainable, masks, masks_np


def _arbitrate_votes(strategy, trainable, vote_sums, n_reporting, masks,
                     masks_np, rnd, prune):
    """Aggregate-only FedArb: the secagg server sees vote *sums*, never a
    client's mask (core.arbitration.arbitrate_from_votes)."""
    if strategy.uses_masks():
        strategy.last_aggregate = trainable
        masks_np = strategy.arbitrate_votes(rnd, vote_sums, n_reporting,
                                            masks_np)
        trainable, masks = prune(trainable, masks_np)
    return trainable, masks, masks_np


def validate_privacy_config(fc: FedConfig) -> None:
    """Fail loudly — and *before* any training — on privacy-knob
    combinations the simulation cannot honor."""
    if fc.secagg not in ("off", "mask"):
        raise ValueError(f"unknown secagg mode {fc.secagg!r} (off|mask)")
    if fc.codec not in T.FIELD_EXACT and (fc.secagg != "off"
                                          or fc.dp_clip > 0
                                          or fc.dp_noise_multiplier > 0):
        raise ValueError(
            "privacy modes need a field-exact codec — one whose decoded "
            "delta never exceeds the DP clip norm and encodes faithfully "
            "into the fixed-point field (signSGD's sign+scale wire "
            "contracts the L2 norm per block; int8/topk/powersgd do not "
            f"qualify).  Use --codec {'|'.join(T.FIELD_EXACT)}")
    if fc.runner == "async" and (fc.secagg != "off" or fc.dp_clip > 0
                                 or fc.dp_noise_multiplier > 0):
        raise ValueError("secagg/DP for the async/FedBuff runner is a "
                         "ROADMAP follow-on; use runner seq|cohort")
    if fc.dp_noise_multiplier > 0 and fc.dp_clip <= 0:
        raise ValueError("--dp-noise-multiplier requires --dp-clip > 0")
    if fc.secagg != "off":
        spec = SA.field_spec(fc)        # raises on bad bits/frac_bits combos
        spec.check_headroom(fc.clients_per_round)
        if fc.secagg_clip < 1.0:
            raise ValueError("secagg_clip must be ≥ 1 (weights and one-hot "
                             "votes encode as field elements of magnitude 1)")
        if fc.dp_clip > fc.secagg_clip:
            raise ValueError("dp_clip must be ≤ secagg_clip: an L2-clipped "
                             "delta element may reach dp_clip and would be "
                             "silently saturated by the field encode")


def _private_round(strategy, bc, encoded, sel, masks, masks_np, fc, rnd,
                   history, accountant, pipe, prune=None):
    """Shared secagg/DP aggregation step (seq oracle, cohort runner, and
    SLoRA stage 1): routes the pipeline's encoded delta wires through
    ``secagg.protocol.aggregate_round``, arbitrates from vote sums (pruned
    by ``prune``, which a strategy with masks needs), and records protocol
    accounting + the ε trajectory in the history."""
    agg = pipe.aggregate_private(bc, encoded, sel, masks_np, rnd)
    trainable, masks, masks_np = _arbitrate_votes(
        strategy, agg.trainable, agg.vote_sums, agg.n_reporting, masks,
        masks_np, rnd, prune)
    if agg.secagg is not None:
        history.record_secagg({
            "rnd": rnd,
            "phases": {k: dataclasses.asdict(v)
                       for k, v in agg.secagg.phases.items()},
            "recovery_bytes": agg.secagg.recovery_bytes,
            "n_dropped": len(agg.secagg.dropped),
            "n_clipped": agg.n_clipped,
            "aborted": agg.aborted})
    if accountant is not None and not agg.aborted:
        # an aborted round never decodes (or noises) an aggregate, so no
        # privacy is spent — ε only grows on actual releases
        accountant.step()
        history.record_eps(rnd, accountant.epsilon(fc.dp_delta))
    return trainable, masks, masks_np, agg


def make_accountant(fc: FedConfig, n_clients: int):
    """Subsampled-Gaussian RDP accountant for the run's (z, q), or None."""
    if fc.dp_noise_multiplier <= 0:
        return None
    q = min(fc.clients_per_round / max(n_clients, 1), 1.0)
    return DP.RDPAccountant(fc.dp_noise_multiplier, q)


def _run_stage1(model, strategy, base, trainable, parts, train, fc, opt, rng,
                history, accountant=None):
    """SLoRA stage 1: sparse full-FT rounds before LoRA (baselines.SLoRA).
    Consumes ``rng`` selections exactly like main rounds, so runners that
    share the selection stream stay aligned with the oracle.

    Uploads ride the shared delta pipeline on the *sparse-gate* wire (the
    gate is server-seeded, so indices never travel): base deltas are
    DP-clipped by the shared clip stage, codec'd with error feedback,
    byte-accounted exactly, and priced through the same per-device links as
    stage 2 — and when privacy is on they flow through secagg/DP like any
    other round (previously stage 1 uploaded raw unclipped deltas in the
    clear, bypassing transport and secagg entirely)."""
    s1_rounds = strategy.stage1_rounds(fc.rounds)
    masks = model.init_masks() if strategy.uses_masks() else None
    base0 = base
    s1_gate = strategy.sparse_gate(base, fc.seed)
    s1_step = CL.make_train_step(model, opt, fc.task, train_base=True)
    s1_update = CL.make_base_update_step(opt)
    pipe = PL.UploadPipeline(
        fc, strategy=None,
        flatten=lambda d, m: PL.flatten_gate(d, s1_gate),
        unflatten=lambda w, like, m: PL.unflatten_gate(w, like, s1_gate),
        stage="stage1")
    private = SA.wants_private(fc)
    s1_stats = history.setdefault(
        "stage1", {"rounds": 0, "up_bytes": 0, "n_clipped": 0})
    for rnd in range(s1_rounds):
        rsp = history.begin_round(rnd, phase="stage1")
        sel = rng.choice(len(parts), size=min(fc.clients_per_round,
                                              len(parts)), replace=False)
        down_per = strategy.stage1_comm_bytes(base)
        down = down_per * len(sel)
        encoded = []
        for cid in sel:
            idx = parts[cid]
            cd = Dataset(train.tokens[idx], train.labels[idx])
            bk, opt_b = base, opt.init(base)
            opt_t, params_k = opt.init(trainable), trainable
            gen = _take(batches(cd, fc.batch_size,
                                client_batch_rng(fc.seed, rnd, cid)),
                        fc.max_local_batches)
            n_b = 0
            for bt in gen:
                jb = {k: jnp.asarray(v) for k, v in bt.items()}
                params_k, opt_t, _, gb, _, _, _ = s1_step(
                    bk, params_k, opt_t, masks, None, jb)
                bk, opt_b = s1_update(bk, opt_b, gb, s1_gate)
                n_b += 1
            upd = PL.ClientUpdate(int(cid), PL.delta_tree(bk, base),
                                  weight=float(len(idx)), n_steps=n_b)
            encoded.append(pipe.encode(upd, None))
        protocol_s = 0.0
        if private:
            base, _, _, agg = _private_round(
                strategy, base, encoded, sel, None, None, fc, rnd, history,
                accountant, pipe)
            up = agg.up_bytes + sum(e.nbytes for e in encoded)
            down += agg.down_bytes
            protocol_s = agg.time_s
        else:
            base = pipe.aggregate(base, encoded, rnd=rnd)
            up = sum(e.nbytes for e in encoded)
        s1_stats["rounds"] += 1
        s1_stats["up_bytes"] += up
        s1_stats["n_clipped"] += sum(int(e.clipped) for e in encoded)
        enc_of = {e.cid: e for e in encoded}
        costs = [pipe.client_time(
            cid, down_per, enc_of[int(cid)].nbytes,
            DV.compute_s(int(cid), fc.device_profile,
                         enc_of[int(cid)].n_steps)) for cid in sel]
        history.add_sim((max(costs) if costs else 0.0) + protocol_s)
        log = RoundLog(rnd, int(down), int(up),
                       live_ranks=0, dead_modules=0,
                       trainable_params=PR.count_trainable(base),
                       loss=float("nan"),
                       sim_time_s=history["sim_time_s"])
        history.end_round(rsp, log, down, up)
    # convert the sparse delta into the LoRA init, reset the base
    trainable = strategy.svd_init_from_delta(model, base0, base, trainable)
    return base0, trainable


# ---------------------------------------------------------------------------
# Round and run bookkeeping shared by the round loops (seq, cohort, async)
# ---------------------------------------------------------------------------

class _Run(NamedTuple):
    """What a run's shared bookkeeping reads, fixed for the run."""
    model: Any
    test: Dataset
    fc: FedConfig
    history: OBS.RunRecorder
    t0: float                   # wall clock at the start of the run
    n_rank_units: int
    accountant: Any             # secagg.dp.RDPAccountant, or None
    on_round: Callable | None


def _start_run(model, strategy, parts, train, test, fc, on_round, runner,
               sharding, extra_keys, accountant=None):
    """Run start: initial state, the recorder (whose ``run`` span opens
    here) and the wall clock, SLoRA stage 1, and the adapter state pinned
    on ``sharding``.  Returns ``(run, base, trainable, masks, masks_np,
    opt, rng, s1_rounds)``."""
    base, trainable, masks, masks_np, n_rank_units, opt, rng = \
        _init_run(model, strategy, fc)
    history = OBS.RunRecorder(runner, fc, extra_keys=extra_keys)
    t0 = time.perf_counter()
    s1_rounds = strategy.stage1_rounds(fc.rounds)
    if s1_rounds:
        base, trainable = _run_stage1(model, strategy, base, trainable,
                                      parts, train, fc, opt, rng, history,
                                      accountant)
    trainable, masks = pin_params(trainable, masks, sharding=sharding)
    run = _Run(model, test, fc, history, t0, n_rank_units, accountant,
               on_round)
    return run, base, trainable, masks, masks_np, opt, rng, s1_rounds


def _record_ranks(history, rnd, masks_np, local_masks):
    """Rank trajectory → trace (FedARA's per-round allocation decision)."""
    if OBS.get_tracer().has_readers and masks_np:
        history.record_ranks(rnd, masks_np,
                             votes=MK.vote_fractions(local_masks))


def _barrier_clock(history, rsp, pipe, fc, sel, encoded, down_per,
                   protocol_s, drops=None, slows=None):
    """Barrier-synchronous simulated clock: the round lasts as long as its
    slowest surviving client (encoded bytes through its device's links plus
    its compute time), plus the secagg protocol's ``protocol_s``.  Clients
    with ``drops[k]`` never report; ``slows[k]`` multiplies compute time."""
    enc_of = {e.cid: e for e in encoded}
    costs = []
    for k, cid in enumerate(sel):
        if drops is not None and drops[k]:
            continue
        enc = enc_of[int(cid)]
        costs.append(pipe.client_time(
            int(cid), down_per, enc.nbytes,
            DV.compute_s(int(cid), fc.device_profile, enc.n_steps,
                         1.0 if slows is None else slows[k])))
    if costs:
        sc = sorted(costs)
        rsp.set(cost_max=float(sc[-1]), cost_med=float(sc[len(sc) // 2]))
    history.add_sim((max(costs) if costs else 0.0) + protocol_s)


def _close_round(run: _Run, rsp, rnd, down, up, losses, base, trainable,
                 masks, masks_np, staleness=0.0) -> None:
    """Round close-out: the round's ``RoundLog``, the eval every
    ``eval_every`` rounds and at the last, the recorder's ``end_round``,
    then ``on_round`` — always the round's last call."""
    fc, history = run.fc, run.history
    live = int(MK.count_true(masks_np)) if masks_np else run.n_rank_units
    n_dead = len(PR.dead_modules(masks_np)) if masks_np else 0
    loss = float(np.mean(losses)) if losses else float("nan")
    log = RoundLog(rnd, int(down), int(up), live, dead_modules=n_dead,
                   trainable_params=PR.count_trainable(trainable),
                   loss=loss, sim_time_s=history["sim_time_s"],
                   staleness=staleness)
    if (rnd + 1) % fc.eval_every == 0 or rnd == fc.rounds - 1:
        log.acc = evaluate(run.model, base, trainable, masks, run.test, fc)
        history["acc"].append((rnd, log.acc))
    history.end_round(rsp, log, down, up)
    if run.on_round:
        run.on_round(rnd, log)


def _finish_run(run: _Run, base, trainable, masks_np) -> dict:
    """Run close-out: final accuracy, the DP guarantee spent (when an
    accountant runs), wall time once the last update has landed, and the
    final state."""
    fc, history = run.fc, run.history
    logs = history["rounds"]
    history["final_acc"] = logs[-1].acc if logs else float("nan")
    if run.accountant is not None:
        history["dp"] = {"epsilon": run.accountant.epsilon(fc.dp_delta),
                         "delta": fc.dp_delta,
                         "noise_multiplier": fc.dp_noise_multiplier,
                         "clip": fc.dp_clip}
    jax.block_until_ready(trainable)            # stop the clock honestly
    history["wall_s"] = time.perf_counter() - run.t0
    history["base"] = base
    history["trainable"] = trainable
    history["masks"] = masks_np
    history.finish()
    return history


def run_federated(model, strategy, parts: list[np.ndarray], train: Dataset,
                  test: Dataset, fc: FedConfig,
                  on_round: Callable | None = None) -> dict:
    """Returns history dict with per-round logs and final accuracy."""
    validate_privacy_config(fc)
    if fc.runner != "seq":
        from repro.fedsim import runner as FR   # lazy: fedsim imports us back
        return FR.run(model, strategy, parts, train, test, fc, on_round)

    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    run, base, trainable, masks, masks_np, opt, rng, s1_rounds = _start_run(
        model, strategy, parts, train, test, fc, on_round, "seq", dev,
        ("secagg_rounds", "dp_eps"), make_accountant(fc, len(parts)))
    history = run.history
    step_fn = CL.make_train_step(model, opt, fc.task)
    rp = round_programs(strategy, dev)
    pipe = PL.UploadPipeline(fc, strategy)
    private = SA.wants_private(fc)

    for rnd in range(s1_rounds, fc.rounds):
        rsp = history.begin_round(rnd)
        sel = rng.choice(len(parts), size=min(fc.clients_per_round,
                                              len(parts)), replace=False)
        # ---- CommPru'd broadcast (delta-coded when a codec is on) --------
        if masks_np is not None:
            trainable, masks = rp.prune(trainable, masks_np)
        bc, down_per = pipe.broadcast(trainable, masks_np)
        bc, masks, gate, _ = rp.place(bc, masks)
        down = down_per * len(sel)

        losses, local_masks, encoded = [], [], []
        for cid in sel:
            csp = history.begin_client(int(cid))
            idx = parts[cid]
            client_data = Dataset(train.tokens[idx], train.labels[idx])
            gen = batches(client_data, fc.batch_size,
                          client_batch_rng(fc.seed, rnd, cid),
                          epochs=fc.local_epochs)
            gen = _take(gen, fc.max_local_batches * fc.local_epochs)
            params_k, grads_k, m = CL.local_train(
                step_fn, base, bc, masks, gate, opt, gen)
            lm = None
            if strategy.uses_masks():
                lm = strategy.local_masks(rnd, params_k["adapters"],
                                          (grads_k or {}).get("adapters"),
                                          run.n_rank_units)
                local_masks.append(lm)
            # upload pruned by the *current* global mask (Alg. 1 line 28),
            # as a delta through the shared pipeline stages
            upd = PL.ClientUpdate(int(cid), PL.delta_tree(params_k, bc),
                                  weight=float(len(idx)), votes=lm,
                                  n_steps=m["n_batches"])
            enc = pipe.encode(upd, masks_np)
            encoded.append(enc)
            losses.append(m["loss"])
            csp.end(n_steps=m["n_batches"], up_bytes=enc.nbytes,
                    loss=m["loss"])

        if private:
            # ---- secagg / DP: the server only sees the field aggregate ---
            trainable, masks, masks_np, agg = _private_round(
                strategy, bc, encoded, sel, masks, masks_np, fc, rnd,
                history, run.accountant, pipe, rp.prune)
            up = agg.up_bytes + sum(e.nbytes for e in encoded)
            down += agg.down_bytes
            protocol_s = agg.time_s
        else:
            # ---- delta-space FedAvg --------------------------------------
            trainable = pipe.aggregate(bc, encoded, rnd=rnd)
            up = sum(e.nbytes for e in encoded)
            # ---- FedArb + RankDet ---------------------------------------
            trainable, masks, masks_np = _arbitrate(
                strategy, trainable, local_masks, masks, masks_np, rnd,
                rp.prune)
            protocol_s = 0.0

        _record_ranks(history, rnd, masks_np, local_masks)
        _barrier_clock(history, rsp, pipe, fc, sel, encoded, down_per,
                       protocol_s)
        _close_round(run, rsp, rnd, down, up, losses, base, trainable,
                     masks, masks_np)

    return _finish_run(run, base, trainable, masks_np)


def _take(gen, n):
    for i, x in enumerate(gen):
        if i >= n:
            return
        yield x
