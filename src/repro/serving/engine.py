"""Multi-tenant serving engine: one frozen base, many FedARA adapters.

Batching model
--------------
The engine owns a *stacked* cache: ``n_slots`` independent batch-1 KV/SSM
caches (leaves ``(n_slots, 1, ...)``, positions ``(n_slots,)``).  Each step it

  1. admits waiting requests into free slots and prefills each one's largest
     power-of-two prompt chunk (bounding jit retraces to O(log max_seq)
     shapes) into its slot;
  2. groups live requests by their adapter's *rank bucket*, gathers each
     group's cache rows and its registry-normalized adapter stacks (pad-to-
     bucket, masked ranks zeroed — CommPru makes the padding exactly free),
     and drives one ``vmap``-over-slots decode per bucket: every row attaches
     its own adapter tree and advances its own scalar cache position, so the
     batched step is semantically identical to running each request alone —
     this is the model-level mirror of the ``kernels/bea_batched`` Pallas
     epilogue, which fuses the same rank-bucketed stacks on TPU;
  3. feeds each row its next unconsumed prompt token (decode catch-up,
     interleaving prefill with generation) or its last sampled token, records
     greedy samples once the prompt is resident, scatters the gathered cache
     rows back, and retires finished requests — freeing their slots for the
     next admission within the same serving loop.

Decode groups are padded to power-of-two row counts (duplicating the first
row; padded outputs are dropped before the scatter) so jit sees a bounded set
of shapes: (rank buckets) × (log2 n_slots) decode variants in total.
"""

from __future__ import annotations

import time
from collections import defaultdict, deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as OBS
from repro.obs.metrics import Histogram
from repro.pytree import is_meta, tree_bytes
from repro.serving.registry import AdapterRegistry, RegistryFullError
from repro.serving.scheduler import Request, Scheduler


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def _pow2_ceil(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def _zeros(meta_tree):
    return jax.tree.map(lambda m: jnp.zeros(m.shape, m.dtype), meta_tree,
                        is_leaf=is_meta)


class ServingEngine:
    """Continuous-batching multi-tenant serving over one frozen base model."""

    def __init__(self, model, base, *, registry: AdapterRegistry | None = None,
                 n_slots: int = 8, max_seq: int = 128,
                 bucket_sizes: tuple[int, ...] = (4, 8, 16, 32, 64),
                 chunk_prefill: bool = True):
        cfg = model.cfg
        if cfg.is_encoder_decoder or cfg.modality == "vision":
            raise NotImplementedError(
                "engine v1 serves decoder-only text models; use the legacy "
                "static batch path in repro.launch.serve for enc-dec/vision")
        self.model = model
        self.base = base
        self.cfg = cfg
        self.chunk_prefill = chunk_prefill
        self.scaling = cfg.adapter_alpha / max(cfg.adapter_rank, 1)
        if registry is not None and \
                registry.serving_scaling != self.scaling:
            raise ValueError(
                f"registry.serving_scaling={registry.serving_scaling} does "
                f"not match the model's α/r={self.scaling}; adapters would "
                f"apply at the wrong strength")
        self.registry = registry or AdapterRegistry(
            self.scaling, bucket_sizes=bucket_sizes)
        self.scheduler = Scheduler(n_slots, max_seq)
        self.max_seq = max_seq
        self.n_slots = n_slots

        slot_meta = model.cache_meta(1, max_seq)
        self.cache_slot_bytes = tree_bytes(slot_meta)
        self._zero_slot_cache = _zeros(slot_meta)
        # (n_slots, 1, ...) stacked batch-1 caches; scalar pos → (n_slots,)
        self.cache = jax.tree.map(
            lambda m: jnp.zeros((n_slots,) + m.shape, m.dtype), slot_meta,
            is_leaf=is_meta)

        # One jitted prefill/decode pair per Model — shared across engine
        # instances (the audit/tests spin up many engines over one model) so
        # XLA's trace cache is hit instead of recompiling per engine.
        jits = getattr(model, "_serving_jits", None)
        if jits is None:
            prefill_fn = jax.jit(
                lambda base, ad, m, toks, cache: model.prefill(
                    base, {"adapters": ad}, m, {"tokens": toks}, cache))

            def _decode_row(base, ad, m, tok, cache):
                logits, new_cache = model.decode_step(
                    base, {"adapters": ad}, m, tok, cache)
                return logits[0], new_cache

            decode_fn = jax.jit(
                jax.vmap(_decode_row, in_axes=(None, 0, 0, 0, 0)))
            jits = model._serving_jits = (prefill_fn, decode_fn)
        self._prefill_fn, self._decode_fn = jits
        self._stack_cache: dict[tuple, tuple] = {}
        # bounded retention (triage window); scheduler.n_finished holds the
        # lifetime total — a sustained serving loop must not grow per-request
        self.finished: deque[Request] = deque(maxlen=256)
        self.steps = 0
        self._deferred = 0
        self.decode_calls = 0
        self.prefill_calls = 0
        # always-on latency histograms (host wall clock, whole-stream
        # quantile sketches — see obs.metrics.Histogram): stats() surfaces
        # their p50/p95/p99, independent of whether tracing is configured
        self._lat_step = Histogram("serve.step_s", ())
        self._lat_request = Histogram("serve.request_s", ())
        self._t_submit: dict[int, float] = {}

    # ---- tenant management -------------------------------------------------

    def register_adapter(self, adapter_id: str, trainable, masks, *,
                         rank: int | None = None, alpha: float | None = None,
                         scaling: float | None = None, pin: bool = False):
        """Admit one tenant's trained adapters (see AdapterRegistry)."""
        return self.registry.register(adapter_id, trainable, masks, rank=rank,
                                      alpha=alpha, scaling=scaling, pin=pin)

    # ---- request intake ----------------------------------------------------

    def submit(self, adapter_id: str, prompt, max_new_tokens: int,
               eos_id: int | None = None) -> Request:
        req = self.scheduler.submit(adapter_id, prompt, max_new_tokens,
                                    eos_id=eos_id)
        self._t_submit[req.rid] = time.perf_counter()
        return req

    # ---- the serving loop --------------------------------------------------

    def step(self) -> list[Request]:
        """One engine iteration; returns the requests finished this step."""
        t_step = time.perf_counter()
        self.steps += 1
        self.scheduler.step_count = self.steps
        self._deferred = 0
        self._prune_stacks()
        ssp = OBS.get_tracer().begin("engine.step", kind="serving",
                                     step=self.steps)

        to_defer = []
        for req in self.scheduler.admit():
            try:
                req.entry = self.registry.acquire(req.adapter_id)
            except KeyError:
                self.scheduler.reject(
                    req, f"unknown adapter {req.adapter_id!r}",
                    kind="unknown_adapter")
                self._t_submit.pop(req.rid, None)
                continue
            except RegistryFullError:
                to_defer.append(req)                  # retry next step
                continue
            self._prefill(req)
        # defer() prepends — reversed keeps FIFO order across multiple defers
        for req in reversed(to_defer):
            self._deferred += 1
            self.scheduler.defer(req)

        groups: dict[int, list[Request]] = defaultdict(list)
        for req in self.scheduler.running():
            if not req.done:
                groups[req.entry.bucket].append(req)
        for bucket in sorted(groups):
            self._decode_group(groups[bucket])

        done = []
        now = time.perf_counter()
        for req in self.scheduler.running():
            if req.done:
                self.scheduler.finish(req)
                self.registry.release(req.adapter_id)
                req.entry = None
                done.append(req)
                lat = now - self._t_submit.pop(req.rid, now)
                self._lat_request.observe(lat)
                OBS.get_metrics().histogram("serve.request_s").observe(lat)
        self.finished.extend(done)
        step_s = time.perf_counter() - t_step
        self._lat_step.observe(step_s)
        OBS.get_metrics().histogram("serve.step_s").observe(step_s)
        ssp.end(running=self.scheduler.n_running,
                waiting=self.scheduler.n_waiting, finished=len(done),
                deferred=self._deferred)
        tr = OBS.get_tracer()
        if tr.live is not None:
            # live plane refresh at the step boundary, throttled — and free
            # (one attribute check) when tracing is disabled
            tr.live.publish(tr, progress={
                "steps": self.steps, "running": self.scheduler.n_running,
                "waiting": self.scheduler.n_waiting,
                "finished": self.scheduler.n_finished},
                min_interval=0.25)
        return done

    def run(self, max_steps: int | None = None) -> list[Request]:
        """Drive until every submitted request completes."""
        out = []
        while not self.scheduler.idle:
            done = self.step()
            out.extend(done)
            # No finishes, nothing running, and every admission was deferred:
            # the next step would be identical — the registry is wedged.
            if not done and self.scheduler.n_running == 0 and self._deferred:
                raise RegistryFullError(
                    "no request can acquire its adapter (registry wedged by "
                    "pinned entries) and nothing is running — aborting")
            if max_steps is not None and self.steps >= max_steps:
                break
        return out

    # ---- internals ---------------------------------------------------------

    def _prefill(self, req: Request) -> None:
        entry = req.entry
        n = req.prompt_len
        chunk = min(_pow2_floor(n), n) if self.chunk_prefill else n
        toks = jnp.asarray(req.prompt[:chunk], jnp.int32)[None]      # (1, C)
        with OBS.get_tracer().begin("serve.prefill", kind="serving",
                                    tokens=chunk):
            logits, new_cache = self._prefill_fn(
                self.base, entry.adapters, entry.masks, toks,
                self._zero_slot_cache)
        self.prefill_calls += 1
        OBS.get_metrics().counter("serve.prefill_tokens").inc(chunk)
        self.cache = jax.tree.map(
            lambda g, c: g.at[req.slot].set(c), self.cache, new_cache)
        req.n_cached = chunk
        if chunk >= n:                  # whole prompt resident → first sample
            req.out.append(int(jnp.argmax(logits[0])))

    def _stacked(self, reqs: list[Request]):
        key = tuple(r.entry.serial for r in reqs)
        hit = self._stack_cache.get(key)
        if hit is not None:
            return hit
        ad = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[r.entry.adapters for r in reqs])
        msk = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[r.entry.masks for r in reqs])
        if len(self._stack_cache) > 256:
            self._stack_cache.clear()
        self._stack_cache[key] = (ad, msk)
        return ad, msk

    def _prune_stacks(self) -> None:
        """Drop stacks referencing evicted/re-registered adapters so cached
        copies don't outlive the registry's memory accounting (runs every
        step — hit-only steady states must not retain evicted tenants)."""
        if not self._stack_cache:
            return
        live = self.registry.live_serials()
        self._stack_cache = {k: v for k, v in self._stack_cache.items()
                             if set(k) <= live}

    def _decode_group(self, reqs: list[Request]) -> None:
        # Canonical order: slot turnover permutes scheduler.running(), and the
        # stack cache keys on the serial tuple — sorting avoids re-stacking
        # (and re-tracing) the same adapter group in a different order.
        reqs = sorted(reqs, key=lambda r: (r.entry.serial, r.slot))
        k = len(reqs)
        k_pad = min(_pow2_ceil(k), self.n_slots)
        padded = reqs + [reqs[0]] * (k_pad - k)       # dup rows are discarded
        rows = jnp.asarray([r.slot for r in padded], jnp.int32)
        toks = jnp.asarray([[r.next_input()] for r in padded],
                           jnp.int32)[:, None]        # (k_pad, 1, 1)
        ad, msk = self._stacked(padded)
        sub = jax.tree.map(lambda v: v[rows], self.cache)
        with OBS.get_tracer().begin("serve.decode", kind="serving", rows=k):
            logits, new_sub = self._decode_fn(self.base, ad, msk, toks, sub)
        self.decode_calls += 1
        OBS.get_metrics().counter("serve.decode_tokens").inc(k)
        sampled = np.asarray(jnp.argmax(logits, axis=-1))  # (k_pad,)
        real = rows[:k]
        self.cache = jax.tree.map(
            lambda g, n_: g.at[real].set(n_[:k]), self.cache, new_sub)
        for r, tok in zip(reqs, sampled[:k]):
            r.observe(int(tok))

    # ---- introspection -----------------------------------------------------

    def stats(self) -> dict:
        s = {"steps": self.steps, "prefill_calls": self.prefill_calls,
             "decode_calls": self.decode_calls,
             "finished": self.scheduler.n_finished,
             "running": self.scheduler.n_running,
             "waiting": self.scheduler.n_waiting,
             "scheduler": self.scheduler.stats(),
             "registry": self.registry.stats(),
             "latency": {"step_s": self._lat_step.summary(),
                         "request_s": self._lat_request.summary()}}
        s["cache"] = self.scheduler.slot_bytes(self.cache_slot_bytes)
        return s
