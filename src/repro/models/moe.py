"""Top-k MoE: capacity-bounded softmax routing with expert parallelism, and
dropless sigmoid routing over the experts this chip holds.

Softmax routing (``moe_apply``, granite): experts are sharded over the
``model`` mesh axis (E_loc = E / |model| per shard); the frozen expert
weights additionally shard their d_model dim over ``data`` (ZeRO-3 storage)
and are all-gathered per layer at use.  Tokens are data-sharded and replicated across
``model``, so dispatch is local: each model shard selects the tokens routed to
its experts with a capacity-bounded gather, runs the expert FFN, scatters the
weighted results and ``psum``s partial outputs over ``model``.

Collective schedule per MoE layer (explicit, for the roofline):
  all-gather(W_experts, data)  +  all-reduce(y, model)

The paper's adapters attach per-expert (A/B/E carry the expert axis) and to
the router; a (layer, component) rank mask is shared by all experts of that
component — mask granularity is the insertion position, as in the paper.

``router_score="sigmoid"`` (DeepSeek-V3, Kimi K2; ``held_apply``): scores
``s = sigmoid(x W_r)`` in float32, the top_k of ``s + b`` (a frozen
correction bias that only picks experts) with weights ``s`` normalised over
the chosen experts and scaled by ``routed_scale``, plus the shared experts.
The layer is told which experts it holds (``cfg.held``, the chip's share
under expert parallelism): it routes over all ``n_experts`` and computes
only its own experts' part, for every token–expert pair routed to them (no
capacity, no dropped token); pairs routed to absent experts add nothing.
The pairs are sorted by expert and computed by grouped products
(``jax.lax.ragged_dot``); under ``vmap`` (the cohort's lanes) the mapped
axis joins the token axis, so one grouped product serves every lane.  The
router, its bias and the routed experts are frozen and carry no adapters;
the shared experts carry the FFN's.  No auxiliary loss.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adapters as AD
from repro.models import layers as L
from repro.models import mlp as MLP
from repro.pytree import ParamMeta

# Row tile assumed for XLA's grouped-product (ragged-dot) kernel on TPU:
# each group is computed in whole tiles of this many rows, a tile shared by
# two groups once for each.  Read once from a v5e program's ragged-dot
# metadata; ``moe_rows`` is an estimate from it, wrong if XLA's tile moves.
RAGGED_TILE = 512


def moe_meta(cfg) -> dict:
    if cfg.router_score == "sigmoid":
        return held_meta(cfg)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    m = {
        "router": {"w": ParamMeta((d, e), jnp.float32, (None, None),
                                  init="normal")},
        "w1": {"w": ParamMeta((e, d, f), cfg.pdtype,
                              ("experts", "embed_fsdp", None), init="normal")},
        "w2": {"w": ParamMeta((e, f, d), cfg.pdtype,
                              ("experts", None, "embed_fsdp"), init="normal",
                              scale=0.05)},
    }
    if cfg.glu:
        m["w3"] = {"w": ParamMeta((e, d, f), cfg.pdtype,
                                  ("experts", "embed_fsdp", None),
                                  init="normal")}
    return m


def moe_adapter_meta(cfg, kind: str) -> dict:
    if cfg.router_score == "sigmoid":
        shared = MLP.mlp_adapter_meta(cfg, kind, shared_width(cfg)) \
            if cfg.n_shared_experts else {}
        return {"shared": shared} if shared else {}
    out = {}
    if "router" in cfg.adapter_targets or "w1" in cfg.adapter_targets:
        r = AD.adapter_meta(kind, cfg.d_model, cfg.n_experts,
                            min(cfg.adapter_rank, cfg.n_experts))
        if r is not None:
            out["router"] = r
    for name, (di, do) in (("w1", (cfg.d_model, cfg.d_ff)),
                           ("w3", (cfg.d_model, cfg.d_ff)),
                           ("w2", (cfg.d_ff, cfg.d_model))):
        if name == "w3" and not cfg.glu:
            continue
        if name in cfg.adapter_targets:
            ad = AD.adapter_meta(kind, di, do, cfg.adapter_rank,
                                 n_experts=cfg.n_experts)
            if ad is not None:
                out[name] = ad
    return out


def _capacity(t_local: int, cfg) -> int:
    c = int(np.ceil(t_local * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)


def _expert_ffn(w, ad, masks, xe, cfg):
    """xe: (E_loc, C, D) -> (E_loc, C, D); per-expert adapters."""
    scaling = cfg.adapter_alpha / max(cfg.adapter_rank, 1)
    masks = masks or {}
    cd = xe.dtype
    h = jnp.einsum("ecd,edf->ecf", xe, w["w1"]["w"].astype(cd))
    h = AD.apply_adapter(h, xe, ad.get("w1"), masks.get("w1"), scaling)
    h = jax.nn.silu(h) if cfg.act == "silu" else jax.nn.gelu(h)
    if cfg.glu:
        g = jnp.einsum("ecd,edf->ecf", xe, w["w3"]["w"].astype(cd))
        g = AD.apply_adapter(g, xe, ad.get("w3"), masks.get("w3"), scaling)
        h = h * g
    y = jnp.einsum("ecf,efd->ecd", h, w["w2"]["w"].astype(cd))
    return AD.apply_adapter(y, h, ad.get("w2"), masks.get("w2"), scaling)


def _route_and_dispatch(xf, w, ad, masks, cfg, e_loc: int, mp_idx):
    """Router + capacity-bounded dispatch to this shard's local experts.

    xf: (T, D).  Returns (xe (E_loc,C,D), gidx, gw, valid, aux)."""
    scaling = cfg.adapter_alpha / max(cfg.adapter_rank, 1)
    t, d = xf.shape
    k = cfg.top_k

    logits = xf @ w["router"]["w"].astype(xf.dtype)
    logits = AD.apply_adapter(logits, xf, ad.get("router"),
                              (masks or {}).get("router"), scaling)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)    # (T, E)
    top_vals, top_ids = jax.lax.top_k(probs, k)                     # (T, k)
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balance auxiliary loss (Switch-style): E · Σ_e f_e · p̄_e.
    counts = jnp.zeros((cfg.n_experts,), jnp.float32).at[top_ids.reshape(-1)].add(1.0)
    frac = counts / (t * k)
    aux = cfg.n_experts * jnp.sum(frac * probs.mean(0))

    c = _capacity(t, cfg)
    flat_ids = top_ids.reshape(-1)                                  # (T*k,)
    flat_w = top_vals.reshape(-1)
    tok_of = jnp.arange(t * k) // k
    local_e = flat_ids - mp_idx * e_loc
    is_local = (local_e >= 0) & (local_e < e_loc)
    oh = jax.nn.one_hot(jnp.where(is_local, local_e, e_loc), e_loc + 1,
                        dtype=jnp.int32)[:, :e_loc]                 # (T*k, E_loc)
    pos = jnp.cumsum(oh, axis=0) - oh                               # slot index
    pos = (pos * oh).sum(-1)
    keep = is_local & (pos < c)
    dump = e_loc * c
    dest = jnp.where(keep, jnp.clip(local_e, 0, e_loc - 1) * c + pos, dump)

    gidx = jnp.zeros((e_loc * c + 1,), jnp.int32).at[dest].set(tok_of)
    gw = jnp.zeros((e_loc * c + 1,), jnp.float32).at[dest].add(
        jnp.where(keep, flat_w, 0.0))
    gidx, gw = gidx[:dump], gw[:dump]
    valid = (gw > 0).astype(xf.dtype)
    xe = xf[gidx].reshape(e_loc, c, d) * valid.reshape(e_loc, c, 1)
    return xe, gidx, gw, valid, aux


def _moe_local(x, w, ad, masks, cfg, e_loc: int, mp_idx, model_ax,
               data_axes) -> tuple[jax.Array, jax.Array]:
    """Per-shard MoE body (ZeRO-3 mode: full weights gathered).  x: (B_loc,
    S, D), full on the model axis."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    xe, gidx, gw, valid, aux = _route_and_dispatch(xf, w, ad, masks, cfg,
                                                   e_loc, mp_idx)
    if data_axes:
        aux = jax.lax.pmean(aux, data_axes)
    ye = _expert_ffn(w, ad, masks, xe, cfg)
    ye = ye.reshape(-1, d) * (gw.astype(x.dtype) * valid)[:, None]
    y = jnp.zeros((b * s, d), x.dtype).at[gidx].add(ye)
    if model_ax is not None:
        y = jax.lax.psum(y, model_ax)
    return y.reshape(b, s, d), aux


def _moe_replicated_tokens(xl, w, ad, masks, cfg, e_loc: int, mp_idx,
                           model_ax, data_axes, data_sizes):
    """Decode-mode MoE: tokens are tiny — replicate them across the data
    axes and contract against the *locally stored* FSDP weight slices with
    activation psums, instead of gathering GBs of expert weights (decode
    under ZeRO-3 gathers is collective-bound).

    Collectives per layer: all-gather(x, ~MBs) + psum(h) + all-gather(y)
    + psum(y, model) — all on activations.
    """
    scaling = cfg.adapter_alpha / max(cfg.adapter_rank, 1)
    b_loc, s, d = xl.shape
    x_all = xl
    for a in reversed(data_axes):                # leading axis = axis order
        x_all = jax.lax.all_gather(x_all, a, axis=0, tiled=True)
    t = x_all.shape[0] * s
    xf = x_all.reshape(t, d)
    xe, gidx, gw, valid, aux = _route_and_dispatch(xf, w, ad, masks, cfg,
                                                   e_loc, mp_idx)
    # linear data index (major-to-minor = data_axes order, matches GSPMD's
    # split of the weight dim over the axis tuple)
    dp_lin = 0
    for a in data_axes:
        dp_lin = dp_lin * data_sizes[a] + jax.lax.axis_index(a)
    n_dp = 1
    for a in data_axes:
        n_dp *= data_sizes[a]

    cd = xe.dtype
    w1 = w["w1"]["w"]                            # (E_loc, d/n_dp, F)
    d_loc = w1.shape[1]
    xe_d = jax.lax.dynamic_slice_in_dim(xe, dp_lin * d_loc, d_loc, axis=-1)
    h = jnp.einsum("ecd,edf->ecf", xe_d, w1.astype(cd))
    if cfg.glu:
        g = jnp.einsum("ecd,edf->ecf", xe_d, w["w3"]["w"].astype(cd))
        h = jax.lax.psum(jnp.stack([h, g]), data_axes)
        h, g = h[0], h[1]
    else:
        h = jax.lax.psum(h, data_axes)
        g = None
    # adapters act on the full-d tokens (replicated) — added after the psum
    h = AD.apply_adapter(h, xe, ad.get("w1"), (masks or {}).get("w1"),
                         scaling)
    h = jax.nn.silu(h) if cfg.act == "silu" else jax.nn.gelu(h)
    if g is not None:
        g = AD.apply_adapter(g, xe, ad.get("w3"), (masks or {}).get("w3"),
                             scaling)
        h = h * g
    w2 = w["w2"]["w"]                            # (E_loc, F, d/n_dp)
    y_p = jnp.einsum("ecf,efd->ecd", h, w2.astype(cd))
    for a in reversed(data_axes):
        y_p = jax.lax.all_gather(y_p, a, axis=-1, tiled=True)
    ye = AD.apply_adapter(y_p, h, ad.get("w2"), (masks or {}).get("w2"),
                          scaling)
    ye = ye.reshape(-1, d) * (gw.astype(cd) * valid)[:, None]
    y = jnp.zeros((t, d), cd).at[gidx].add(ye)
    if model_ax is not None:
        y = jax.lax.psum(y, model_ax)
    # keep only this shard's batch rows
    y = y.reshape(-1, s, d)
    y = jax.lax.dynamic_slice_in_dim(y, dp_lin * b_loc, b_loc, axis=0)
    return y, aux


def moe_apply(p, x, cfg, ctx, ad=None, masks=None):
    """Returns (y, aux_loss)."""
    ad = ad or {}
    mesh = None if ctx is None else ctx.mesh
    if mesh is None or np.prod(list(mesh.shape.values())) == 1:
        return _moe_local(x, p, ad, masks, cfg, cfg.n_experts, 0, None, ())

    from jax.sharding import PartitionSpec as P
    from repro import sharding as SH
    rules = ctx.rules
    data_axes = SH.batch_axes(mesh, rules)
    model_ax = SH.model_axis(mesh, rules)
    e_shards = mesh.shape[model_ax] if model_ax in mesh.axis_names else 1
    if cfg.n_experts % e_shards != 0:
        e_shards = 1
        model_ax = None
    e_loc = cfg.n_experts // e_shards

    # shard_map in/out specs (experts over model, weights FSDP over data,
    # gathered inside).
    dspec = tuple(data_axes) if data_axes else None
    xspec = P(dspec, None, None)
    wspec = {
        "router": {"w": P(None, None)},
        "w1": {"w": P(model_ax, dspec, None)},
        "w2": {"w": P(model_ax, None, dspec)},
    }
    if "w3" in p:
        wspec["w3"] = {"w": P(model_ax, dspec, None)}
    # Per-expert adapters (under w1/w3/w2) carry the expert axis on dim 0;
    # the router adapter and all masks are replicated.
    adspec = {}
    for comp, leaves in ad.items():
        per_expert = comp in ("w1", "w2", "w3")
        adspec[comp] = {k: P(model_ax) if per_expert else P()
                        for k in leaves}
    mspec = jax.tree.map(lambda _: P(), masks) if masks else None

    # Decode steps (seq 1) route through the token-replicated path: the
    # tokens are MBs while the ZeRO-3 expert-weight gathers are GBs.
    replicate = (x.shape[1] == 1 and bool(data_axes)
                 and rules.get("moe_token_replicate", True))
    data_sizes = {a: mesh.shape[a] for a in data_axes}

    def body(xl, wl, adl, ml):
        mp_idx = jax.lax.axis_index(model_ax) if model_ax else 0
        if replicate:
            return _moe_replicated_tokens(xl, wl, adl, ml, cfg, e_loc,
                                          mp_idx, model_ax, data_axes,
                                          data_sizes)
        # ZeRO-3: gather the FSDP dim of the frozen expert weights.
        wg = dict(wl)
        if data_axes:
            def gather(arr, axis):
                for a in data_axes:
                    arr = jax.lax.all_gather(arr, a, axis=axis, tiled=True)
                return arr
            wg["w1"] = {"w": gather(wl["w1"]["w"], 1)}
            wg["w2"] = {"w": gather(wl["w2"]["w"], 2)}
            if "w3" in wl:
                wg["w3"] = {"w": gather(wl["w3"]["w"], 1)}
        return _moe_local(xl, wg, adl, ml, cfg, e_loc, mp_idx, model_ax,
                          data_axes)

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, wspec, adspec, mspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p, ad, masks)
    return y, aux


# ---------------------------------------------------------------------------
# Dropless sigmoid routing over the held experts
# ---------------------------------------------------------------------------

def shared_width(cfg) -> int:
    return cfg.d_ff * cfg.n_shared_experts


def held_meta(cfg) -> dict:
    if not cfg.glu:
        raise ValueError(f"{cfg.name}: sigmoid routing runs gated experts")
    _, n = cfg.held
    d, f = cfg.d_model, cfg.d_ff
    m = {
        "router": {"w": ParamMeta((d, cfg.n_experts), jnp.float32,
                                  (None, None), init="normal"),
                   "b": ParamMeta((cfg.n_experts,), jnp.float32, (None,),
                                  init="zeros")},
        "w1": {"w": ParamMeta((n, d, f), cfg.pdtype,
                              ("experts", "embed_fsdp", None), init="normal")},
        "w3": {"w": ParamMeta((n, d, f), cfg.pdtype,
                              ("experts", "embed_fsdp", None), init="normal")},
        "w2": {"w": ParamMeta((n, f, d), cfg.pdtype,
                              ("experts", None, "embed_fsdp"), init="normal",
                              scale=0.05)},
    }
    if cfg.n_shared_experts:
        m["shared"] = MLP.mlp_meta(cfg, shared_width(cfg))
    return m


def route(xf, router, cfg):
    """xf (T, D) → (expert ids (T, top_k), weights (T, top_k) float32)."""
    s = jax.nn.sigmoid(jnp.dot(xf.astype(jnp.float32), router["w"],
                               precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(s + router["b"], cfg.top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
    return ids, w


def _chunk(x, gate, order, gs, lo, c: int, w1, w3, w2):
    """Rows ``[lo, lo + c)`` of the sorted pairs through their experts:
    (their tokens' part of the layer (T, D), the rows computed)."""
    k = gate.shape[1]
    rows = jax.lax.dynamic_slice_in_dim(order, lo, c)
    tok = rows // k
    end = jnp.cumsum(gs)
    gsi = jnp.clip(end, lo, lo + c) - jnp.clip(end - gs, lo, lo + c)
    # Rows past the chunk's pairs belong to no group: the grouped product
    # leaves them unwritten (on TPU they hold whatever the buffer held, NaN
    # included), in its output and in its transpose's.  Selects, never
    # products, keep them out of the result and out of every cotangent.
    live = (jnp.arange(c) < gsi.sum())[:, None]
    cd = x.dtype
    xs = jnp.where(live, jnp.take(x, tok, axis=0), 0)
    a1 = jax.lax.ragged_dot(xs, w1.astype(cd), gsi)
    a3 = jax.lax.ragged_dot(xs, w3.astype(cd), gsi)
    o = jax.lax.ragged_dot(jax.nn.silu(a1) * a3, w2.astype(cd), gsi)
    g = jnp.take(gate.reshape(-1), rows).astype(cd)
    o = jnp.where(live, o, 0) * g[:, None]
    return jnp.zeros_like(x).at[tok].add(o), _tile_rows(gsi)


def _tile_rows(gs):
    """Rows a grouped product computes for groups ``gs`` laid end to end:
    each group in whole ``RAGGED_TILE``-row tiles."""
    end = jnp.cumsum(gs)
    start = end - gs
    tiles = jnp.where(gs > 0, -(-end // RAGGED_TILE) - start // RAGGED_TILE,
                      0)
    return (tiles.sum() * RAGGED_TILE).astype(jnp.float32)


def _chunks(local, n: int):
    """(the pairs sorted by held expert, absent experts last; the held
    experts' group sizes; the chunks of ``T`` rows that hold them).  A chunk
    holds as many pairs as there are tokens, several times what a share of
    the experts expects (top_k · held / n_experts pairs a token: 1/6 for
    Kimi's chip); more pairs take more chunks, none is dropped."""
    t = local.shape[0]
    order = jnp.argsort(local.reshape(-1), stable=True)
    gs = jnp.bincount(local.reshape(-1), length=n + 1)[:n].astype(jnp.int32)
    return order, gs, -(-gs.sum() // t)


def _fwd_rows(x, gate, local, w1, w3, w2):
    order, gs, n_chunks = _chunks(local, w1.shape[0])
    c = x.shape[0]

    def body(i, carry):
        y, rows = carry
        dy, r = _chunk(x, gate, order, gs, i * c, c, w1, w3, w2)
        return y + dy, rows + r

    y, rows = jax.lax.fori_loop(0, n_chunks, body,
                                (jnp.zeros_like(x), jnp.float32(0.0)))
    return y, jnp.full(x.shape[:1], rows / x.shape[0], jnp.float32)


def _bwd_rows(x, gate, local, dy, w1, w3, w2):
    order, gs, n_chunks = _chunks(local, w1.shape[0])
    c = x.shape[0]

    def body(i, carry):
        dx, dg = carry
        _, vjp = jax.vjp(lambda x, g: _chunk(x, g, order, gs, i * c, c, w1,
                                             w3, w2)[0], x, gate)
        ddx, ddg = vjp(dy)
        return dx + ddx, dg + ddg

    return jax.lax.fori_loop(0, n_chunks, body,
                             (jnp.zeros_like(x), jnp.zeros_like(gate)))


def _fold_rows(fn, n_rows: int):
    """``fn`` computes each row (token) of its first ``n_rows`` arguments
    independently; the others are shared.  Under ``vmap`` the mapped axis
    joins the rows and ``fn`` runs once on all of them."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        if any(in_batched[n_rows:]):
            raise NotImplementedError("shared arguments may not be mapped")
        rows = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args[:n_rows], in_batched[:n_rows])]
        out = f(*[a.reshape((-1,) + a.shape[2:]) for a in rows],
                *args[n_rows:])
        out = jax.tree.map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, jax.tree.map(lambda _: True, out)

    return f


_fwd_folded = _fold_rows(_fwd_rows, 3)
_bwd_folded = _fold_rows(_bwd_rows, 4)


@jax.custom_vjp
def routed_experts(x, gate, local, w1, w3, w2):
    """The held experts' part of the layer, for every pair routed to them.

    x (T, D); gate (T, k) routing weights; local (T, k) int32, the held
    expert of each pair or ``n`` (= w1.shape[0]) where it is absent.
    Returns (y (T, D), rows (T,)): each token's share of the rows the
    grouped products compute.  The pairs go through in chunks, sorted by
    expert (a loop that runs as many chunks as hold pairs); the backward
    pass recomputes each chunk and takes its vector-Jacobian product."""
    return _fwd_folded(x, gate, local, w1, w3, w2)


def _routed_fwd(x, gate, local, w1, w3, w2):
    return _fwd_folded(x, gate, local, w1, w3, w2), (x, gate, local, w1, w3,
                                                    w2)


def _routed_bwd(res, ct):
    x, gate, local, w1, w3, w2 = res
    dx, dg = _bwd_folded(x, gate, local, ct[0], w1, w3, w2)
    return dx, dg, None, None, None, None


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def held_apply(p, x, cfg, ad=None, masks=None):
    """Returns (y, counts): ``moe_routed``, the token–expert pairs computed
    on the held experts, and ``moe_rows``, an estimate of the rows the
    grouped products compute for them, padding included (whole
    ``RAGGED_TILE``-row tiles)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    ids, w = route(xf, p["router"], cfg)
    e0, n = cfg.held
    local = ids - e0
    held = (local >= 0) & (local < n)
    local = jnp.where(held, local, n).astype(jnp.int32)
    y, rows = routed_experts(xf, w, local, p["w1"]["w"], p["w3"]["w"],
                             p["w2"]["w"])
    y = y.reshape(b, s, d)
    if "shared" in p:
        y = y + MLP.mlp_apply(p["shared"], x, cfg, ad=(ad or {}).get("shared"),
                              masks=(masks or {}).get("shared"))
    return y, {"moe_routed": held.sum().astype(jnp.float32),
               "moe_rows": rows.sum()}
