"""Event-driven federated round runner (fedsim pillar 3).

Two execution modes behind ``FedConfig.runner`` (the sequential oracle stays
in federated/server.py):

  cohort  barrier-synchronous rounds whose local phase is ONE
          shard_map dispatch (fedsim/cohort.py: real client-steps packed
          onto lanes) with on-device psum FedAvg; dropout/straggler
          injection and a simulated wall clock from the per-device-class
          transport links.
  async   FedBuff-style buffered aggregation [Nguyen et al. 2022]: clients
          train against the global version they were dispatched with; the
          server aggregates every K arrivals with size·(1+staleness)^-α
          weights on the accumulated deltas.

Every randomness source is seeded — selection from ``fc.seed`` (the oracle's
stream), event times / dropout / stragglers from ``fc.event_seed`` — so one
(seed, event_seed) pair reproduces the identical history and event log.
Both runners emit ``fedsim.pipeline.ClientUpdate`` deltas through the shared
delta pipeline (flatten → clip → codec → error feedback → byte accounting →
link pricing), the same wire path the sequential oracle uses.  A cohort
round that aggregates on the device (identity wire, no privacy, no
sub-batch client) builds no delta on the host: the upload program
(``server.round_programs``) hands it each slot's importance scores and wire
norm, and ``UploadPipeline.encode_reduced`` accounts for the upload.
"""

from __future__ import annotations

import heapq
from typing import Callable

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs as OBS
from repro.core import importance as IMP
from repro.data.synthetic import Dataset, batches
from repro.federated import client as CL
from repro.federated import devices as DV
from repro.federated import server as SV
from repro.fedsim import cohort as CH
from repro.fedsim import pipeline as PL
from repro.fedsim import transport as T
from repro.secagg import protocol as SA

device_of = DV.device_of          # shared client→device-class assignment


def _event_rng(fc) -> np.random.Generator:
    return np.random.default_rng([fc.event_seed, fc.seed])


def _n_local_batches(n: int, fc) -> int:
    """Exact per-client local step count (mirrors data.synthetic.batches)."""
    per_epoch = n // fc.batch_size if n >= fc.batch_size else 1
    return min(fc.max_local_batches * fc.local_epochs,
               per_epoch * fc.local_epochs)


def run(model, strategy, parts, train, test, fc,
        on_round: Callable | None = None) -> dict:
    if fc.runner == "async":
        return run_async(model, strategy, parts, train, test, fc, on_round)
    if fc.runner == "cohort":
        return run_cohort(model, strategy, parts, train, test, fc, on_round)
    raise ValueError(f"unknown runner {fc.runner!r} (seq|cohort|async)")


# ---------------------------------------------------------------------------
# cohort: barrier-sync rounds, one dispatch per round
# ---------------------------------------------------------------------------

def run_cohort(model, strategy, parts, train, test, fc,
               on_round: Callable | None = None) -> dict:
    mesh = CH.cohort_mesh()
    # broadcast state replicated on the mesh, its per-client copies split
    # over it, the same every round (see SV.round_programs)
    rep = jax.sharding.NamedSharding(mesh, P())
    run, base, trainable, masks, masks_np, opt, rng, s1_rounds = \
        SV._start_run(model, strategy, parts, train, test, fc, on_round,
                      "cohort", rep, ("secagg_rounds", "dp_eps"),
                      SV.make_accountant(fc, len(parts)))
    history = run.history
    step_fn = CL.make_train_step(model, opt, fc.task)     # ragged fallback
    cohort_fn = CH.make_cohort_fn(model, opt, fc.task, mesh=mesh)
    ndev = len(jax.devices())
    cpr = min(fc.clients_per_round, len(parts))
    c_pad = -(-cpr // ndev) * ndev                        # shardable cohort
    lanes = CH.lane_count(parts, fc, c_pad, ndev)
    rp = SV.round_programs(strategy, rep, slots=c_pad,
                           slot_sharding=jax.sharding.NamedSharding(
                               mesh, P("clients")))
    pipe = PL.UploadPipeline(fc, strategy)
    ev_rng = _event_rng(fc)
    private = SA.wants_private(fc)
    # FedARA's votes: the upload program scores the cohort's slots where
    # the importance method allows (``method``); else the host scores them
    method = strategy.device_importance()
    host_scores = strategy.uses_masks() and method is None
    layout = (IMP.score_layout(trainable["adapters"], strategy.n_experts)
              if method else None)

    for rnd in range(s1_rounds, fc.rounds):
        rsp = history.begin_round(rnd)
        tr = OBS.get_tracer()
        # stage spans (siblings under the round, in round order): prepare,
        # cohort_dispatch, cohort.pull, upload, aggregate/arbitrate, evaluate
        psp = tr.begin("prepare", kind="stage")
        sel = rng.choice(len(parts), size=cpr, replace=False)
        # ---- CommPru'd broadcast (delta-coded when a codec is on) --------
        if masks_np is not None:
            trainable, masks = rp.prune(trainable, masks_np)
        bc, down_per = pipe.broadcast(trainable, masks_np)
        # the cohort's C = c_pad slots each start from a copy of bc
        bc, masks, gate, stacked = rp.place(bc, masks)
        down = down_per * len(sel)

        # ---- dropout / straggler draws (fixed order → determinism) ------
        drops = ev_rng.random(len(sel)) < fc.dropout
        slows = np.where(ev_rng.random(len(sel)) < fc.straggler,
                         fc.straggler_slow, 1.0)
        active = [int(c) for c, d in zip(sel, drops) if not d]

        # ---- local phase: one dispatch for the whole cohort --------------
        cohort = CH.build_cohort(train, parts, active, fc, rnd, c_pad,
                                 ndev=ndev)
        pc = gc = lc = mc = avg = scores = None
        cohort_idx = {}
        # the round aggregates on the host — from every client's delta
        # tree — with a codec, privacy or a sub-batch client; otherwise
        # the on-device FedAvg is the aggregate and the upload program's
        # scores and norms are all the host reads of the cohort's slots
        host_agg = (pipe.codec is not None or private or cohort is None
                    or bool(cohort.fallback))
        if cohort is not None:
            plan = CH.schedule(cohort.n_steps, cohort.step_mask.shape[1],
                               lanes, ndev)
        psp.end()
        if cohort is not None:
            # dispatch span keyed by shape signature: any jax compile fired
            # inside parents under this span, so obs.profile attributes the
            # compile to the exact argument shapes that caused the retrace.
            # The span wraps the launch alone (the signature is computed
            # before it opens).
            sig = {}
            if tr.has_readers:
                from repro.obs import profile as PROF
                sig["sig"] = PROF.shape_signature(
                    stacked, cohort.batches, plan, cohort.weights)
            dsp = tr.begin("cohort_dispatch", kind="dispatch", **sig)
            # fill share = steps / slots: real client-steps over lane-steps
            dsp.count(steps=int(cohort.n_steps.sum()),
                      slots=lanes * int(plan.n_iter))
            pc, gc, lc, mc, avg, counts = cohort_fn(
                base, stacked, masks, gate, cohort.batches, plan,
                cohort.weights)
            dsp.end()
            cohort_idx = dict(zip(cohort.cids, cohort.slots))
            scores, norms = rp.upload(
                pc, gc if method and IMP.reads_grads(method) else None, bc,
                masks)
            # ONE batched device→host pull for everything the host path
            # reads: the loss/metric stacks, the upload program's scores
            # and norms and, only where the host needs them, the slots'
            # params, the broadcast and grads (host importance scores,
            # host aggregation); the per-client trees below are host-side
            # slices of these, not per-leaf transfers inside the loop.
            pull = (pc if host_agg or host_scores else None,
                    bc if host_agg else None,
                    gc if host_scores else None,
                    lc, mc, counts, scores, norms)
            ksp = tr.begin("cohort.pull", kind="stage")
            if tr.enabled:
                ksp.count(nbytes=sum(x.nbytes for x in jax.tree.leaves(pull)))
            pc_host, bc_host, gc_host, lc, mc, counts, scores, norms = \
                jax.device_get(pull)
            # the model's counts: held experts' pairs and estimated rows,
            # whose ratio estimates the expert fill share
            ksp.count(**{k: round(float(v)) for k, v in counts.items()})
            ksp.end()
            del pull    # or it holds this round's outputs through the next

        usp = tr.begin("upload", kind="stage")
        if cohort is not None:
            lc, mc = np.asarray(lc, np.float32), np.asarray(mc, np.float32)
            if host_agg:
                dc = jax.tree.map(
                    lambda p, b: np.asarray(p, np.float32)
                    - np.asarray(b, np.float32), pc_host, bc_host)
            else:
                # the identity wire's bytes: shapes and masks alone
                nbytes = strategy.comm_up(bc, masks_np)

        losses, local_masks, encoded = [], [], []
        up = n_dev_scores = n_host_deltas = 0
        for cid in active:
            csp = history.begin_client(cid)
            params_k = grads_k = delta_k = None
            if cid in cohort_idx:
                i = cohort_idx[cid]
                sm = cohort.step_mask[i]
                if host_scores:
                    params_k = CH.slice_client(pc_host, i)
                    grads_k = CH.slice_client(gc_host, i)
                if host_agg:
                    delta_k = CH.slice_client(dc, i)
                m = {"loss": float(np.mean(lc[i][sm])) if sm.any()
                     else float("nan"),
                     "metric": float(np.mean(mc[i][sm])) if sm.any()
                     else float("nan"),
                     "n_batches": int(cohort.n_steps[i])}
                w = float(cohort.weights[i])
            else:                                   # ragged client → oracle
                fsp = tr.begin("fallback", kind="stage", cid=int(cid))
                idx = parts[cid]
                gen = SV._take(
                    batches(Dataset(train.tokens[idx], train.labels[idx]),
                            fc.batch_size,
                            CH.client_batch_rng(fc.seed, rnd, cid),
                            epochs=fc.local_epochs),
                    fc.max_local_batches * fc.local_epochs)
                params_k, grads_k, m = CL.local_train(
                    step_fn, base, bc, masks, gate, opt, gen)
                delta_k = PL.delta_tree(params_k, bc)
                w = float(len(parts[cid]))
                fsp.count(steps=m["n_batches"], rows=len(idx),
                          **{k: round(v) for k, v in m["counts"].items()})
                fsp.end()
            lm = None
            if strategy.uses_masks():
                isp = tr.begin("importance", kind="stage")
                if params_k is None:                # the upload program's
                    lm = strategy.masks_from_scores(
                        rnd, scores[i], layout, run.n_rank_units)
                    n_dev_scores += 1
                else:
                    lm = strategy.local_masks(
                        rnd, params_k["adapters"],
                        (grads_k or {}).get("adapters"), run.n_rank_units)
                isp.end()
                local_masks.append(lm)
            upd = PL.ClientUpdate(int(cid), delta_k,
                                  weight=w, votes=lm,
                                  n_steps=m["n_batches"])
            esp = tr.begin("encode", kind="stage")
            if delta_k is None:
                enc = pipe.encode_reduced(upd, float(norms[i]), nbytes)
            else:
                enc = pipe.encode(upd, masks_np)
                n_host_deltas += 1
            esp.end()
            up += enc.nbytes
            encoded.append(enc)
            losses.append(m["loss"])
            csp.end(n_steps=m["n_batches"], up_bytes=enc.nbytes,
                    loss=m["loss"])
        usp.count(device_scores=n_dev_scores, host_deltas=n_host_deltas)
        usp.end()

        # ---- aggregation: on-device psum unless a side path was taken ----
        protocol_s = 0.0
        if private:
            # secagg / DP: masked field aggregation with dropout *recovery*
            # (dropped clients' pairwise masks are reconstructed from
            # survivor shares, not silently excluded; an all-dropped round
            # still pays — and records — the advertise/share phases)
            trainable, masks, masks_np, agg = SV._private_round(
                strategy, bc, encoded, sel, masks, masks_np, fc, rnd,
                history, run.accountant, pipe, rp.prune)
            up = agg.up_bytes + sum(e.nbytes for e in encoded)
            down += agg.down_bytes
            protocol_s = agg.time_s
        elif encoded:
            if host_agg:
                trainable = pipe.aggregate(bc, encoded, rnd=rnd)
            else:
                # identity wire: the on-device psum FedAvg equals the
                # pipeline's delta-space mean (Σŵ(bc+Δ) = bc + ΣŵΔ)
                trainable = avg
            trainable, masks, masks_np = SV._arbitrate(
                strategy, trainable, local_masks, masks, masks_np, rnd,
                rp.prune)

        SV._record_ranks(history, rnd, masks_np, local_masks)
        SV._barrier_clock(history, rsp, pipe, fc, sel, encoded, down_per,
                          protocol_s, drops, slows)
        SV._close_round(run, rsp, rnd, down, up, losses, base, trainable,
                        masks, masks_np)

    return SV._finish_run(run, base, trainable, masks_np)


# ---------------------------------------------------------------------------
# async: FedBuff-style buffered aggregation on a simulated event clock
# ---------------------------------------------------------------------------

def run_async(model, strategy, parts, train, test, fc,
              on_round: Callable | None = None) -> dict:
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    run, base, trainable, masks, masks_np, opt, rng, s1_rounds = \
        SV._start_run(model, strategy, parts, train, test, fc, on_round,
                      "async", dev, ("events",))
    history = run.history
    step_fn = CL.make_train_step(model, opt, fc.task)
    rp = SV.round_programs(strategy, dev)
    pipe = PL.UploadPipeline(fc, strategy)
    ev_rng = _event_rng(fc)

    buffer_k = fc.buffer_k or min(fc.clients_per_round, len(parts))
    concurrency = fc.async_concurrency or 2 * buffer_k
    version = s1_rounds                   # server model version = agg round
    heap: list = []                       # (finish_t, seq, cid)
    stash: dict = {}                      # seq -> dispatch snapshot
    buffer: list = []                     # pending (delta, params, grads, ...)
    seq_no = 0
    pend_down = pend_up = 0

    def dispatch(now: float):
        nonlocal seq_no, pend_down
        cid = int(rng.integers(len(parts)))
        dropped = bool(ev_rng.random() < fc.dropout)
        slow = (fc.straggler_slow if ev_rng.random() < fc.straggler else 1.0)
        # per-client DeltaChannel: a stale client's broadcast stream is
        # delta-coded against *its own* last reconstruction
        bc, down = pipe.broadcast(trainable, masks_np, endpoint=cid)
        bc, bc_masks, gate, _ = rp.place(bc, masks)
        pend_down += down
        n_b = _n_local_batches(len(parts[cid]), fc)
        link = T.link_for(device_of(cid))
        # upload size is only known post-encode; model it as symmetric
        finish = (now + link.transfer_s(down)
                  + DV.compute_s(cid, fc.device_profile, n_b, slow)
                  + link.transfer_s(down))
        if not dropped:
            stash[seq_no] = (bc, bc_masks, masks_np, gate, version)
        heapq.heappush(heap, (finish, seq_no, cid, dropped))
        history.async_event(now, "dispatch", cid=cid, version=version,
                            dropped=dropped)
        seq_no += 1

    for _ in range(concurrency):
        dispatch(0.0)

    agg = version
    max_events = (fc.rounds - s1_rounds) * buffer_k * 50 + 1000
    n_events = 0
    while agg < fc.rounds and heap and n_events < max_events:
        n_events += 1
        now, sq, cid, dropped = heapq.heappop(heap)
        if dropped:
            dispatch(now)
            continue
        bc, d_masks, d_masks_np, gate, d_version = stash.pop(sq)
        gen = SV._take(
            batches(Dataset(train.tokens[parts[cid]],
                            train.labels[parts[cid]]),
                    fc.batch_size, CH.client_batch_rng(fc.seed, sq, cid),
                    epochs=fc.local_epochs),
            fc.max_local_batches * fc.local_epochs)
        params_k, grads_k, m = CL.local_train(
            step_fn, base, bc, d_masks, gate, opt, gen)
        staleness = version - d_version
        w = len(parts[cid]) * (1.0 + staleness) ** -fc.staleness_alpha
        upd = PL.ClientUpdate(cid, PL.delta_tree(params_k, bc), weight=w,
                              n_steps=m["n_batches"],
                              staleness=float(staleness))
        enc = pipe.encode(upd, d_masks_np)
        pend_up += enc.nbytes
        buffer.append((enc, params_k, grads_k, m))
        history.async_event(now, "update", cid=cid, version=d_version)
        dispatch(now)

        if len(buffer) >= buffer_k:
            # ---- staleness-weighted buffered aggregation -----------------
            # (deltas were encoded against per-dispatch masks; averaging in
            # tree space keeps stale and fresh contributions aligned)
            rsp = history.begin_round(agg)
            trainable = pipe.aggregate(trainable,
                                       [b[0] for b in buffer], rnd=agg)
            local_masks = []
            if strategy.uses_masks():
                for _, pk, gk, *_ in buffer:
                    local_masks.append(strategy.local_masks(
                        agg, pk["adapters"], (gk or {}).get("adapters"),
                        run.n_rank_units))
            trainable, masks, masks_np = SV._arbitrate(
                strategy, trainable, local_masks, masks, masks_np, agg,
                rp.prune)
            SV._record_ranks(history, agg, masks_np, local_masks)
            history.set_sim(now)
            SV._close_round(
                run, rsp, agg, pend_down, pend_up,
                [b[3]["loss"] for b in buffer], base, trainable, masks,
                masks_np,
                staleness=float(np.mean([b[0].staleness for b in buffer])))
            pend_down = pend_up = 0
            buffer.clear()
            version += 1
            agg += 1

    # in-flight broadcasts were transmitted even if never aggregated
    history.inflight_comm(pend_down, pend_up)
    return SV._finish_run(run, base, trainable, masks_np)
