"""Delta-space upload pipeline — the one wire path for every producer.

FedARA's communication story (§IV-B3 CommPru, the 2.40× efficiency claim) is
about what clients *upload*.  Before this module the repo had four divergent
upload paths: the sequential oracle and cohort runner codec'd the pruned
*params* wire (so error feedback fought the server average), the async
runner codec'd deltas with its own framing, SLoRA stage 1 uploaded raw
unclipped base deltas that bypassed transport and secagg entirely, and
privacy mode rejected every lossy codec.  Now every producer — seq oracle,
vectorized cohort, FedBuff async, SLoRA stage 1 — emits a ``ClientUpdate``
(delta tree + weight + rank votes) and routes it through the same composable
stages:

    flatten → (+EF residual) → DP clip → codec → field snap → (−EF residual)
            → byte accounting → link pricing → aggregate

Stage notes:
  - The DP clip sits *inside* the error-feedback loop: the residual is folded
    in before clipping, so the transmitted signal (not just the fresh delta)
    respects the L2 sensitivity bound.
  - ``field snap``: when secure aggregation is on, the residual is computed
    against the *field-quantized* decode — the exact vector the masked sum
    will aggregate — so EF state never diverges from what the server applies.
  - Downlink broadcasts are delta-coded too (``DeltaChannel``): each endpoint
    holds the receiver's reconstruction and ships ``codec(target − ref)``,
    re-projecting the reference through the current rank masks when CommPru
    pruning shrinks the wire.
  - Aggregation is delta-space weighted FedAvg applied to the broadcast state
    (``aggregate``), or the secagg/DP field path (``aggregate_private`` →
    secagg.protocol.aggregate_round) — both consume the same encoded wires.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as OBS
from repro.federated import devices as DV
from repro.fedsim import transport as T
from repro.secagg import dp as DP


@dataclasses.dataclass
class ClientUpdate:
    """What a producer hands the pipeline: one client's round contribution."""
    cid: int
    delta: Any                      # f32 delta tree (global-state
                                    # structure); None for encode_reduced
    weight: float                   # aggregation weight (data size)
    votes: Any | None = None        # local rank-mask tree (FedArb votes)
    n_steps: int = 0                # local batches run (compute pricing)
    staleness: float = 0.0          # async: server versions behind


@dataclasses.dataclass
class EncodedUpdate:
    """A ClientUpdate after the wire stages: what the server aggregates."""
    cid: int
    wire: np.ndarray | None         # decoded (post-codec, post-snap) wire
    delta: Any                      # the decoded delta *tree* (same content)
                                    # (both None from encode_reduced)
    nbytes: int                     # exact upload bytes (0 under secagg —
                                    # the protocol phases price the upload)
    weight: float
    votes: Any | None = None
    clipped: bool = False           # DP clip engaged for this client
    norm: float = 0.0               # pre-clip L2 of the transmitted signal
    n_steps: int = 0
    staleness: float = 0.0


def delta_tree(params: Any, ref: Any) -> Any:
    """Host-side f32 delta between two structurally-equal trees.  One
    batched device→host pull for both trees, not a pair per leaf."""
    params, ref = jax.device_get((params, ref))
    return jax.tree.map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
        params, ref)


def apply_delta(global_tree: Any, delta: Any) -> Any:
    """global + delta, accumulated in f32, cast back to the global dtypes."""
    return jax.tree.map(
        lambda p, d: (jnp.asarray(p, jnp.float32)
                      + jnp.asarray(d, jnp.float32)).astype(p.dtype),
        global_tree, delta)


def make_fc_codec(fc) -> T.Codec | None:
    """FedConfig → codec instance (None for the identity f32 wire)."""
    if fc.codec == "identity":
        return None
    kw = {"rank": fc.powersgd_rank} if fc.codec == "powersgd" else {}
    return T.make_codec(fc.codec, **kw)


# ---------------------------------------------------------------------------
# SLoRA stage-1 wire: the sparse-gate support, not the whole base
# ---------------------------------------------------------------------------

def flatten_gate(delta: Any, gate: Any) -> np.ndarray:
    """Base-delta tree → f32 wire of the sparse-gate support.  The gate is
    server-seeded, so indices never travel; frozen leaves (scalar-0 gates on
    non-float dtypes) contribute nothing."""
    parts = []
    for d, g in zip(jax.tree.leaves(delta), jax.tree.leaves(gate)):
        g = np.asarray(jax.device_get(g))
        if g.ndim == 0:
            continue
        d = np.asarray(jax.device_get(d), np.float32).reshape(-1)
        parts.append(d[np.asarray(g, bool).reshape(-1)])
    if not parts:
        return np.zeros((0,), np.float32)
    return np.concatenate(parts)


def unflatten_gate(wire: np.ndarray, like: Any, gate: Any) -> Any:
    """Inverse of flatten_gate: zeros off the gate support."""
    leaves, treedef = jax.tree.flatten(like)
    gates = jax.tree.leaves(gate)
    out, off = [], 0
    for leaf, g in zip(leaves, gates):
        g = np.asarray(jax.device_get(g))
        buf = np.zeros(int(np.prod(leaf.shape)), np.float32)
        if g.ndim != 0:
            sel = np.asarray(g, bool).reshape(-1)
            n = int(sel.sum())
            buf[sel] = wire[off:off + n]
            off += n
        out.append(buf.reshape(leaf.shape))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Downlink: delta-coded broadcast channel
# ---------------------------------------------------------------------------

class DeltaChannel:
    """One broadcast endpoint's delta-coded stream state.

    The endpoint holds ``ref`` — the receiver's current reconstruction, as a
    host f32 tree.  ``send(target)`` transmits ``codec(target − ref)`` and
    advances both sides' ``ref`` by the decoded delta.  The reference
    accumulation *is* the error feedback: whatever a lossy codec failed to
    transmit stays in ``target − ref`` and is retried next send (a separate
    EF residual here would count the untransmitted mass twice and diverge).
    When CommPru pruning changes the wire length, the reference *tree* is
    re-flattened through the new masks, so the pruned ranks drop out of both
    sides consistently.  With no codec the channel is a pass-through priced
    by the caller.
    """

    def __init__(self, codec, flatten, unflatten, key):
        self.codec, self.key = codec, key
        self.flatten, self.unflatten = flatten, unflatten
        self._ref: Any | None = None

    def send(self, target: Any, masks_np: Any | None) -> tuple[Any, int]:
        """→ (receiver's reconstruction tree, payload bytes excl. masks)."""
        if self.codec is None:
            return target, 0          # caller prices the f32 wire (CommPru)
        wire_t = self.flatten(target, masks_np)
        ref_w = (self.flatten(self._ref, masks_np)
                 if self._ref is not None else np.zeros_like(wire_t))
        if ref_w.shape != wire_t.shape:       # structure changed: resync
            ref_w = np.zeros_like(wire_t)
        x = wire_t - ref_w
        payload, nbytes = self.codec.encode(x, key=self.key)
        dec = self.codec.decode(payload, x.size)
        new_ref = self.unflatten(ref_w + dec, target, masks_np)
        self._ref = new_ref
        bc = jax.tree.map(lambda d, p: jnp.asarray(d, p.dtype),
                          new_ref, target)
        return bc, nbytes


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------

class UploadPipeline:
    """flatten → clip → codec(+EF) → field snap → bytes → links → aggregate.

    One instance per run (per runner); per-endpoint state (EF residuals,
    PowerSGD warm factors, broadcast channels) is keyed by client id /
    endpoint name, so the sequential oracle and the cohort runner evolve
    byte-identical transport state and stay parity-comparable.

    ``flatten``/``unflatten`` default to the CommPru trainable wire
    (fedsim.transport.flatten_update); SLoRA stage 1 passes the sparse-gate
    pair above so its base deltas ride the same stages.
    """

    def __init__(self, fc, strategy=None, flatten=None, unflatten=None,
                 link_of: Callable[[int], T.Link] | None = None,
                 field_spec=None, stage: str = "stage2"):
        self.fc = fc
        self.strategy = strategy
        self.stage = stage                  # metric label: stage1 | stage2
        self.codec = make_fc_codec(fc)
        self.flatten = flatten or T.flatten_update
        self.unflatten = unflatten or T.unflatten_update
        self.link_of = link_of or (lambda c: T.link_for(DV.device_of(c)))
        self._resid: dict[Any, np.ndarray] = {}
        self._down: dict[Any, DeltaChannel] = {}
        if field_spec is None and getattr(fc, "secagg", "off") != "off":
            from repro.secagg import protocol as SA
            field_spec = SA.field_spec(fc)
        self.field_spec = field_spec

    # ---- downlink ----------------------------------------------------------

    def broadcast(self, trainable: Any, masks_np: Any | None,
                  endpoint: Any = "down") -> tuple[Any, int]:
        """Server→client broadcast through the endpoint's DeltaChannel.
        Returns (what the client reconstructs, per-client down bytes).

        The sync runners use one shared ``"down"`` endpoint: the downlink is
        modeled as a *multicast* delta stream every client follows, so a
        client first selected in round r is assumed caught up on rounds
        0..r−1 for free.  Byte counts are unaffected (every codec's cost
        depends only on the wire length), but a rotating cohort's
        reconstruction fidelity is optimistic; per-client catch-up
        accounting is a ROADMAP follow-on.  The async runner already keys a
        channel per client (its clients genuinely hold stale streams)."""
        psp = OBS.get_tracer().begin("broadcast", kind="pipeline",
                                     endpoint=str(endpoint))
        ch = self._down.get(endpoint)
        if ch is None:
            ch = self._down[endpoint] = DeltaChannel(
                self.codec, self.flatten, self.unflatten, ("down", endpoint))
        bc, nbytes = ch.send(trainable, masks_np)
        if self.codec is None:
            if self.strategy is not None:
                total = self.strategy.comm_down(trainable, masks_np)
            else:
                wire = self.flatten(trainable, masks_np)
                total = wire.size * 4 + T.HEADER_BYTES \
                    + T.mask_wire_bytes(masks_np)
        else:
            total = nbytes + T.mask_wire_bytes(masks_np)
        m = OBS.get_metrics()
        if m.enabled:
            m.counter("pipeline.down_bytes", codec=self.fc.codec,
                      stage=self.stage).inc(int(total))
        psp.end(nbytes=int(total))
        return bc, total

    # ---- uplink ------------------------------------------------------------

    def encode(self, upd: ClientUpdate, masks_np: Any | None
               ) -> EncodedUpdate:
        """Run one ClientUpdate through the wire stages."""
        fc = self.fc
        wire = self.flatten(upd.delta, masks_np)
        x = wire
        r = self._resid.get(upd.cid) if self.codec is not None else None
        if r is not None and r.shape == x.shape:
            x = x + r
        norm = float(np.linalg.norm(x))
        clipped = False
        if getattr(fc, "dp_clip", 0.0) > 0:
            x, norm = DP.clip_to_norm(x, fc.dp_clip)
            clipped = norm > fc.dp_clip
        if self.codec is not None:
            payload, nbytes = self.codec.encode(x, key=upd.cid)
            dec = self.codec.decode(payload, x.size)
            if self.field_spec is not None:
                # residual against the field-quantized decode — exactly what
                # the masked sum aggregates — so EF never fights the field
                dec = self.field_spec.decode_sum(self.field_spec.encode(dec))
            self._resid[upd.cid] = x - dec
            nbytes += T.mask_wire_bytes(masks_np)
        else:
            dec = x
            if self.strategy is not None:
                nbytes = self.strategy.comm_up(upd.delta, masks_np)
            else:
                nbytes = dec.size * 4 + T.HEADER_BYTES \
                    + T.mask_wire_bytes(masks_np)
        if getattr(fc, "secagg", "off") != "off":
            nbytes = 0        # the protocol's masked phase prices the upload
        self._account(upd.cid, nbytes, norm, clipped)
        d_tree = self.unflatten(dec, upd.delta, masks_np)
        return EncodedUpdate(
            cid=upd.cid, wire=dec, delta=d_tree, nbytes=nbytes,
            weight=upd.weight, votes=upd.votes, clipped=clipped, norm=norm,
            n_steps=upd.n_steps, staleness=upd.staleness)

    def encode_reduced(self, upd: ClientUpdate, norm: float, nbytes: int
                       ) -> EncodedUpdate:
        """An identity-wire update whose delta a device program reduced to
        its wire's L2 ``norm`` (``transport.wire_norm``), with ``nbytes``
        as ``encode`` prices its wire (the strategy's ``comm_up``, which
        reads shapes and masks): the counters and ``encode`` event of
        ``encode``, and no wire or delta tree, for a round that aggregates
        on the device."""
        assert self.codec is None and self.field_spec is None \
            and getattr(self.fc, "dp_clip", 0.0) <= 0
        self._account(upd.cid, nbytes, norm, False)
        return EncodedUpdate(
            cid=upd.cid, wire=None, delta=None, nbytes=nbytes,
            weight=upd.weight, votes=upd.votes, norm=norm,
            n_steps=upd.n_steps, staleness=upd.staleness)

    def _account(self, cid, nbytes: int, norm: float, clipped: bool) -> None:
        fc = self.fc
        m = OBS.get_metrics()
        if m.enabled:
            m.counter("pipeline.up_bytes", codec=fc.codec,
                      stage=self.stage).inc(int(nbytes))
            m.counter("pipeline.updates", codec=fc.codec,
                      stage=self.stage).inc()
            if clipped:
                m.counter("dp.clip_events", stage=self.stage).inc()
            ef_norm = 0.0
            if self.codec is not None:
                ef_norm = float(np.linalg.norm(self._resid[cid]))
                m.histogram("pipeline.ef_residual_norm",
                            codec=fc.codec).observe(ef_norm)
            # per-update encode event: the EF-residual stream the health
            # monitor watches for codec blowup (plus clip/byte forensics)
            OBS.get_tracer().event(
                "encode", cid=int(cid), norm=float(norm),
                ef_norm=ef_norm, clipped=bool(clipped),
                nbytes=int(nbytes), stage=self.stage)

    # ---- link pricing ------------------------------------------------------

    def client_time(self, cid: int, down_bytes: int, up_bytes: int,
                    compute_s: float) -> float:
        """One client's simulated round time: compute + a single round-trip
        transfer of the encoded down+up payloads over its device link."""
        return compute_s + self.link_of(int(cid)).transfer_s(
            down_bytes + up_bytes)

    # ---- aggregation -------------------------------------------------------

    def _emit_drift(self, encoded: list[EncodedUpdate],
                    rnd: int | None = None) -> None:
        """Client-drift dispersion of this aggregation's decoded wires:
        ``1 − mean pairwise cosine`` over unit-normalized wires, computed as
        ``(‖Σu‖² − n) / (n(n−1))`` — one O(n·d) pass, no pairwise matrix.
        This is the FeDeRA-style heterogeneity signal; the health monitor
        alerts when dispersion crosses its threshold.  Computed only when a
        reader is attached (``Tracer.has_readers``)."""
        tr = OBS.get_tracer()
        if not tr.has_readers or len(encoded) < 2:
            return
        flat = [np.asarray(e.wire, np.float64).ravel() for e in encoded]
        if len({w.size for w in flat}) != 1:
            return      # async buffers can mix mask vintages → wire lengths
        wires = np.stack(flat)
        nrm = np.linalg.norm(wires, axis=1)
        ok = nrm > 0
        if int(ok.sum()) < 2:
            return
        u = wires[ok] / nrm[ok, None]
        s = u.sum(axis=0)
        n = len(u)
        mean_cos = (float(s @ s) - n) / (n * (n - 1))
        tr.event("drift", rnd=rnd, n=int(n), mean_cos=mean_cos,
                 dispersion=1.0 - mean_cos)
        tr.metrics.histogram("pipeline.drift_dispersion").observe(
            1.0 - mean_cos)

    def aggregate(self, global_tree: Any, encoded: list[EncodedUpdate],
                  rnd: int | None = None) -> Any:
        """Plain weighted delta-space FedAvg applied to the broadcast state.
        With the identity codec this equals param-space FedAvg exactly:
        Σŵ·(bc+Δᵢ) = bc + Σŵ·Δᵢ."""
        if not encoded:
            return global_tree
        psp = OBS.get_tracer().begin("aggregate", kind="pipeline",
                                     n_updates=len(encoded))
        self._emit_drift(encoded, rnd)
        w = np.asarray([e.weight for e in encoded], np.float64)
        w = (w / w.sum()).astype(np.float32)

        def avg(*leaves):
            acc = np.asarray(leaves[0], np.float32) * w[0]
            for wi, leaf in zip(w[1:], leaves[1:]):
                acc = acc + np.asarray(leaf, np.float32) * wi
            return acc

        davg = jax.tree.map(avg, *[e.delta for e in encoded])
        out = apply_delta(global_tree, davg)
        psp.end()
        return out

    def aggregate_private(self, bc: Any, encoded: list[EncodedUpdate],
                          participants, masks_np: Any | None, rnd: int):
        """secagg/DP aggregation of the same encoded wires (field sums,
        dropout recovery, vote sums, noise) — secagg.protocol owns it."""
        from repro.secagg import protocol as SA
        psp = OBS.get_tracer().begin("aggregate_private", kind="pipeline",
                                     n_updates=len(encoded))
        self._emit_drift(encoded, int(rnd))
        out = SA.aggregate_round(bc, encoded, [int(c) for c in participants],
                                 masks_np, self.fc, rnd,
                                 link_of=self.link_of,
                                 unflatten=self.unflatten)
        psp.end(up_bytes=int(out.up_bytes), down_bytes=int(out.down_bytes),
                aborted=out.aborted)
        return out
