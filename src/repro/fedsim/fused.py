"""Fused multi-round cohort training: one XLA program per K rounds.

The eager cohort runner (fedsim/runner.py) dispatches once per round and
round-trips the whole cohort tree device→host between rounds to feed the
upload pipeline.  On the *fast path* — identity codec, no privacy, no
ragged clients, no per-round mask pruning — none of that host work changes
the params trajectory: the on-device psum FedAvg already equals the
pipeline's delta-space mean, byte accounting is shape-only, and client
selection / dropout / straggler draws are host RNG streams that can be
drawn ahead of time.  So this module fuses the round loop itself:

  - ``lax.scan`` over K rounds wraps the existing vmap×scan local phase
    (cohort.make_local_phase) inside one ``shard_map`` over the cohort
    axis, with the psum FedAvg + broadcast feeding round r+1's clients
    directly on device;
  - client selection and dropout/straggler draws are precomputed host-side
    (consuming ``rng``/``ev_rng`` in exactly the eager order) into stacked
    per-round batch/mask/weight arrays;
  - the params carry is donated (``donate_argnums``), so K rounds of
    training re-materialize nothing on host;
  - per-round per-client loss/metric stacks come back in ONE device_get per
    block and are replayed into ``RunRecorder`` — round/client spans, exact
    ``comm_gb``/``sim_time_s`` float-order accounting, eval cadence, and
    the history dict are key-for-key identical to the eager cohort runner.

Blocks are chunked so they never cross an eval boundary (eval needs the
carry on host) and every block is padded to exactly K rounds with dead
rounds (all weights 0 → the carry passes through the psum guard
unchanged), so the fused program compiles ONCE regardless of round count.

``run_cohort`` routes here when ``fc.fuse_rounds > 1`` and ``eligible``
says the config has no per-round host work; otherwise it falls back to the
eager path and traces the reason (``fused_fallback`` event).
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs as OBS
from repro.core import pruning as PR
from repro.federated import server as SV
from repro.fedsim import cohort as CH
from repro.fedsim import pipeline as PL


def eligible(fc, strategy, parts) -> tuple[bool, str]:
    """Can this config run the fused fast path?  → (ok, reason-if-not).

    Anything that needs host work *between* rounds disqualifies: codecs and
    privacy touch the per-client wire, rank-mask strategies re-prune the
    trainable structure, SLoRA's stage 1 precedes the main loop, ragged
    (sub-batch) clients route through the sequential oracle, and re-bucketing
    intentionally varies the rectangle shape per round.
    """
    if fc.codec != "identity":
        return False, f"codec {fc.codec!r} encodes per-client wires on host"
    if fc.secagg != "off":
        return False, "secagg runs a host-side masked-sum protocol"
    if fc.dp_clip > 0 or fc.dp_noise_multiplier > 0:
        return False, "DP clips/noises per-client wires on host"
    if strategy.uses_masks():
        return False, f"strategy {strategy.name!r} re-prunes rank masks " \
                      "every round"
    if getattr(strategy, "stage1_rounds", None) is not None \
            and strategy.stage1_rounds(fc.rounds) > 0:
        return False, f"strategy {strategy.name!r} runs host-side stage-1 " \
                      "rounds"
    if getattr(fc, "rebucket", False):
        return False, "re-bucketing varies the cohort rectangle per round"
    small = [i for i, p in enumerate(parts) if len(p) < fc.batch_size]
    if small:
        return False, f"{len(small)} sub-batch clients need the " \
                      "sequential fallback"
    return True, ""


def make_fused_fn(model, opt, task: str = "cls", mesh=None):
    """Build the one-dispatch K-round block.

    Returns jitted ``fn(base, trainable, masks, gate, bstacks, smasks,
    weights) → (trainable', losses, metrics)`` where the per-round inputs
    are stacked ``(K, C, ...)`` arrays (client axis sharded over the mesh),
    ``trainable`` is the replicated carry — donated, so the block trains in
    place — and ``losses``/``metrics`` come back ``(K, C, T)``.

    Round structure matches ``cohort.make_cohort_fn`` op for op: vmap of the
    shared local phase, weighted tensordot, psum over the ``"clients"``
    axis.  The only addition is the ``wtot > 0`` guard so an all-dropped or
    block-padding round passes the carry through unchanged.
    """
    local_phase = CH.make_local_phase(model, opt, task)
    mesh = mesh if mesh is not None else CH.cohort_mesh()

    def body(base, trainable, masks, gate, bstacks, smasks, weights):
        def round_body(carry, xs):
            bstack, smask, w = xs
            params_c, _, losses_c, metrics_c = jax.vmap(
                local_phase, in_axes=(None, None, None, None, 0, 0))(
                base, carry, masks, gate, bstack, smask)
            part = jax.tree.map(
                lambda p: jnp.tensordot(w, p.astype(jnp.float32),
                                        axes=(0, 0)), params_c)
            tot = jax.lax.psum(part, "clients")
            wtot = jax.lax.psum(w.sum(), "clients")
            safe = jnp.where(wtot > 0, wtot, 1.0)
            avg = jax.tree.map(
                lambda s, p: jnp.where(wtot > 0, s / safe,
                                       p.astype(jnp.float32)).astype(p.dtype),
                tot, carry)
            return avg, (losses_c, metrics_c)

        final, (losses, metrics) = jax.lax.scan(
            round_body, trainable, (bstacks, smasks, weights))
        return final, losses, metrics

    cspec = P(None, "clients")
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), cspec, cspec, cspec),
        out_specs=(P(), cspec, cspec),
        check_vma=False)
    # the carry is donated: params never re-materialize between rounds (on
    # backends without donation support this is a harmless no-op warning)
    return jax.jit(fn, donate_argnums=(1,))


def _block_rounds(rnd: int, K: int, fc) -> list[int]:
    """Rounds [rnd, ...] of the next block: at most K, never crossing an
    eval boundary (eval round r satisfies (r+1) % eval_every == 0) or the
    end of the run — eval needs the carry back on host."""
    ev_r = fc.eval_every * (-(-(rnd + 1) // fc.eval_every)) - 1
    return list(range(rnd, min(rnd + K - 1, ev_r, fc.rounds - 1) + 1))


def run_fused(model, strategy, parts, train, test, fc,
              on_round: Callable | None = None) -> dict:
    """Fused-block twin of ``runner.run_cohort`` — same RNG streams, same
    history contract, K rounds per dispatch.  Callers must have checked
    ``eligible`` first (no codec/privacy/mask/ragged host work exists)."""
    from repro.fedsim.runner import _compute_s, _event_rng

    base, trainable, masks, masks_np, n_rank_units, opt, rng = \
        SV._init_run(model, strategy, fc)
    mesh = CH.cohort_mesh()
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    ndev = len(jax.devices())
    cpr = min(fc.clients_per_round, len(parts))
    c_pad = -(-cpr // ndev) * ndev
    K = max(1, int(fc.fuse_rounds))
    fused_fn = make_fused_fn(model, opt, fc.task, mesh=mesh)

    pipe = PL.UploadPipeline(fc, strategy)
    ev_rng = _event_rng(fc)
    history = OBS.RunRecorder("cohort", fc,
                              extra_keys=("secagg_rounds", "dp_eps"))
    logs: list[SV.RoundLog] = history["rounds"]
    t0 = time.perf_counter()

    gate = strategy.optimizer_gate(trainable, masks_np)
    # shape-only byte accounting (identity codec): constant across rounds
    up_per = strategy.comm_up(trainable, masks_np)
    base, _ = SV.pin_params(base, sharding=rep)
    trainable, masks = SV.pin_params(trainable, masks, sharding=rep)

    rnd = 0
    while rnd < fc.rounds:
        block = _block_rounds(rnd, K, fc)

        # ---- host precompute: selection + event draws in eager RNG order --
        sels, dropss, slowss, cohorts = [], [], [], []
        for r in block:
            sel = rng.choice(len(parts), size=cpr, replace=False)
            drops = ev_rng.random(len(sel)) < fc.dropout
            slows = np.where(ev_rng.random(len(sel)) < fc.straggler,
                             fc.straggler_slow, 1.0)
            active = [int(c) for c, d in zip(sel, drops) if not d]
            sels.append(sel)
            dropss.append(drops)
            slowss.append(slows)
            cohorts.append(CH.build_cohort(train, parts, active, fc, r,
                                           c_pad))

        tmpl = next((c for c in cohorts if c is not None), None)
        if tmpl is not None:
            # stack block rounds + pad to exactly K dead rounds so every
            # block dispatch lowers against the same (K, C, ...) shapes.
            # Dead/pad rounds reuse the template's batch arrays: all-False
            # step masks keep the per-client carry and weight 0 drops the
            # slot from the psum, so content never matters (and stays
            # finite, unlike zeros → NaN-free by construction).
            dead_m = np.zeros_like(tmpl.step_mask)
            dead_w = np.zeros_like(tmpl.weights)
            rows = [(c.batches, c.step_mask, c.weights) if c is not None
                    else (tmpl.batches, dead_m, dead_w) for c in cohorts]
            rows += [(tmpl.batches, dead_m, dead_w)] * (K - len(rows))
            bstacks = {k: np.stack([b[k] for b, _, _ in rows])
                       for k in tmpl.batches}
            smasks = np.stack([m for _, m, _ in rows])
            weights = np.stack([w for _, _, w in rows])

            dsp = OBS.get_tracer().begin("cohort_dispatch", kind="dispatch",
                                         fused=len(block))
            if OBS.get_tracer().enabled:
                from repro.obs import profile as PROF
                dsp.set(sig=PROF.shape_signature(
                    trainable, bstacks, smasks, weights))
            with OBS.annotate("cohort_dispatch"):
                trainable, lc, mc = fused_fn(base, trainable, masks, gate,
                                             bstacks, smasks, weights)
            dsp.end()
            # ONE device→host pull for the whole block's loss/metric stacks
            lc, mc = jax.device_get((lc, mc))
            lc = np.asarray(lc, np.float32)

        # ---- replay the block into the recorder (eager span/float order) --
        for j, r in enumerate(block):
            rsp = history.begin_round(r)
            _, down_per = pipe.broadcast(trainable, masks_np)
            down = down_per * len(sels[j])
            cohort = cohorts[j]
            up = 0
            losses = []
            met = OBS.get_metrics()
            if cohort is not None:
                for i, cid in enumerate(cohort.cids):
                    csp = history.begin_client(cid)
                    sm = cohort.step_mask[i]
                    loss_i = float(np.mean(lc[j][i][sm]))
                    losses.append(loss_i)
                    up += up_per
                    if met.enabled:
                        met.counter("pipeline.up_bytes", codec=fc.codec,
                                    stage="stage2").inc(int(up_per))
                        met.counter("pipeline.updates", codec=fc.codec,
                                    stage="stage2").inc()
                    csp.end(n_steps=int(cohort.n_steps[i]),
                            up_bytes=int(up_per), loss=loss_i)

            costs = []
            if cohort is not None:
                idx_of = {cid: i for i, cid in enumerate(cohort.cids)}
                for k, cid in enumerate(sels[j]):
                    if dropss[j][k]:
                        continue
                    cid = int(cid)
                    costs.append(pipe.client_time(
                        cid, down_per, up_per,
                        _compute_s(cid, fc,
                                   int(cohort.n_steps[idx_of[cid]]),
                                   slowss[j][k])))
            round_s = max(costs) if costs else 0.0
            if costs:
                sc = sorted(costs)
                rsp.set(cost_max=float(sc[-1]),
                        cost_med=float(sc[len(sc) // 2]))
            history.add_sim(round_s)

            loss = float(np.mean(losses)) if losses else float("nan")
            log = SV.RoundLog(r, int(down), int(up), n_rank_units,
                              dead_modules=0,
                              trainable_params=PR.count_trainable(trainable),
                              loss=loss, sim_time_s=history["sim_time_s"])
            if (r + 1) % fc.eval_every == 0 or r == fc.rounds - 1:
                # block boundaries align with eval rounds, so the carry on
                # host here is exactly round r's post-aggregation params
                log.acc = SV.evaluate(model, base, trainable, masks, test,
                                      fc)
                history["acc"].append((r, log.acc))
            history.end_round(rsp, log, down, up)
            if on_round:
                on_round(r, log)

        rnd = block[-1] + 1

    history["final_acc"] = logs[-1].acc if logs else float("nan")
    jax.block_until_ready(trainable)
    history["wall_s"] = time.perf_counter() - t0
    history["base"] = base
    history["trainable"] = trainable
    history["masks"] = masks_np
    history.finish()
    return history
