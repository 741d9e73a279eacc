"""The round loop's compiled prune and placement (federated/server.py
``round_programs``) against the eager CommPru prune, RankDet gate and
per-client stack they replace: the same bits, on the shardings the cohort
program reads."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.distilbert import MINI
from repro.configs.kimi_k2_1t_a32b import CHIP_SMOKE
from repro.core import comm as COMM
from repro.federated.baselines import all_strategies
from repro.federated.server import pin_params, round_programs
from repro.fedsim import cohort as CH
from repro.models import Model

SLOTS = 4


def _random_like(shapes, seed):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda s: rng.standard_normal(s.shape).astype(s.dtype), shapes)


def _masks(shapes, seed, dead):
    """Random masks, about half the ranks live, with each module whose
    path holds one of ``dead`` wholly pruned."""
    rng = np.random.default_rng(seed)

    def one(path, s):
        if any(d in jax.tree_util.keystr(path) for d in dead):
            return np.zeros(s.shape, bool)
        return rng.random(s.shape) < 0.5
    return jax.tree_util.tree_map_with_path(one, shapes)


def _model_case(cfg, peft, seed):
    model = Model(cfg, peft=peft, unroll=True)
    _, t_shapes = jax.eval_shape(model.init, jax.random.key(0))
    return _random_like(t_shapes, seed), jax.eval_shape(model.init_masks)


def _distilbert():
    trainable, m_shapes = _model_case(MINI, "bea", 1)
    return (all_strategies()["fedara"], trainable,
            _masks(m_shapes, 2, dead=("['wq']",)),
            _masks(m_shapes, 3, dead=("['wq']", "['w2']")))


def _kimi_experts():
    """Kimi's program tree at smoke widths, plus expert-stacked modules:
    (E, r, d) against an (r,) mask, and (L, E, r, d) against (L, r)."""
    trainable, m_shapes = _model_case(CHIP_SMOKE, "bea", 4)
    E, L, r, d_in, d_out = 3, 2, 4, 16, 24
    sds = jax.ShapeDtypeStruct
    experts = {"w1": {"A": sds((E, r, d_in), np.float32),
                      "B": sds((E, d_out, r), np.float32),
                      "E": sds((E, r), np.float32)},
               "w2": {"A": sds((L, E, r, d_in), np.float32),
                      "B": sds((L, E, d_out, r), np.float32),
                      "E": sds((L, E, r), np.float32)}}
    trainable["adapters"]["experts"] = _random_like(experts, 5)
    m_shapes = dict(m_shapes, experts={"w1": sds((r,), np.bool_),
                                       "w2": sds((L, r), np.bool_)})
    return (all_strategies()["fedara"], trainable,
            _masks(m_shapes, 6, dead=("['wkv_b']",)),
            _masks(m_shapes, 7, dead=("['wkv_b']", "['experts']['w1']")))


def _ffa():
    """FFA freezes A by a gate built from constants; it uses no masks."""
    trainable, _ = _model_case(MINI, "lora", 8)
    return all_strategies()["ffa_lora"], trainable, None, None


CASES = {"distilbert": _distilbert, "kimi_experts": _kimi_experts,
         "ffa": _ffa}


def _assert_bits(got, want):
    got, want = jax.device_get((got, want))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _assert_on(tree, sharding):
    for x in jax.tree.leaves(tree):
        assert x.committed
        assert x.sharding.is_equivalent_to(sharding, x.ndim), x.sharding


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_programs_match_eager_ops(case):
    """Both prunes (before the broadcast and after arbitration, with new
    mask values) and the placement give the eager ops' bits, land on the
    mesh as the cohort program reads them, and compile once."""
    strategy, trainable, masks_np, masks_np2 = CASES[case]()
    mesh = CH.cohort_mesh()
    rep = NamedSharding(mesh, P())
    lanes = NamedSharding(mesh, P("clients"))
    rp = round_programs(strategy, rep, slots=SLOTS, slot_sharding=lanes)

    want = trainable
    masks = None
    tr, _ = pin_params(trainable, sharding=rep)
    if masks_np is not None:
        want = dict(trainable, adapters=COMM.prune_tree(
            trainable["adapters"], masks_np))
        tr, masks = rp.prune(tr, masks_np)
        _assert_bits(tr, want)
        _assert_bits(masks, masks_np)
        _assert_on((tr, masks), rep)

    bc, m, gate, stacked = rp.place(tr, masks)
    _assert_bits(bc, want)
    _assert_bits(m, masks_np)
    _assert_bits(gate, strategy.optimizer_gate(want, masks_np))
    _assert_bits(stacked, CH.stack_params(want, SLOTS))
    assert gate is not None
    _assert_on((bc, m, gate), rep)
    _assert_on(stacked, lanes)

    if masks_np2 is not None:
        after, m2 = rp.prune(bc, masks_np2)
        _assert_bits(after, dict(want, adapters=COMM.prune_tree(
            want["adapters"], masks_np2)))
        _assert_bits(m2, masks_np2)
        _assert_on((after, m2), rep)
        assert rp.prune._cache_size() == 1
    assert rp.place._cache_size() == 1


def _slots_like(tree, masks_np, seed):
    """SLOTS per-client trees around ``tree``, stacked: each perturbed,
    and every other one with its masked ranks pruned to exactly zero."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(SLOTS):
        t = jax.tree.map(lambda x: x + rng.standard_normal(x.shape).astype(
            x.dtype), tree)
        if s % 2 and masks_np is not None:
            t = dict(t, adapters=jax.device_get(COMM.prune_tree(
                t["adapters"], masks_np)))
        out.append(t)
    return jax.tree.map(lambda *x: np.stack(x), *out)


@pytest.mark.parametrize("method", ["mag", "grad", "mixed"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_upload_program_matches_host_scores_and_wire_norms(case, method):
    """The upload program's per-slot scores are the host's flattened
    ``score_tree`` (expert axes averaged, pruned ranks exactly zero) and
    its norms those of the host's identity wire ``pc − bc``; new mask
    values compile nothing."""
    from repro.core import importance as IMP
    from repro.fedsim import transport as T
    strategy, trainable, masks_np, masks_np2 = CASES[case]()
    if strategy.uses_masks():
        strategy.importance = method
        strategy.n_experts = 3 if case == "kimi_experts" else 0
    mesh = CH.cohort_mesh()
    rep = NamedSharding(mesh, P())
    lanes = NamedSharding(mesh, P("clients"))
    rp = round_programs(strategy, rep, slots=SLOTS, slot_sharding=lanes)
    bc = trainable if masks_np is None else dict(trainable, adapters=(
        jax.device_get(COMM.prune_tree(trainable["adapters"], masks_np))))
    pc = _slots_like(bc, masks_np, 11)
    gc = _slots_like(bc, masks_np, 12)
    reads = IMP.reads_grads(method) and strategy.uses_masks()

    for mnp in (masks_np, masks_np2):
        bc_d, masks = pin_params(bc, mnp, sharding=rep)
        pc_d, gc_d = jax.device_put((pc, gc), lanes)
        scores, norms = jax.device_get(rp.upload(
            pc_d, gc_d if reads else None, bc_d, masks))
        for i in range(SLOTS):
            p_i, g_i = CH.slice_client(pc, i), CH.slice_client(gc, i)
            delta = jax.tree.map(lambda a, b: a - b, p_i, bc)
            want = np.linalg.norm(T.flatten_update(delta, mnp))
            np.testing.assert_allclose(norms[i], want, rtol=1e-5)
            if not strategy.uses_masks():
                assert scores is None
                continue
            tree, _ = IMP.score_tree(p_i["adapters"], g_i["adapters"],
                                     method, n_experts=strategy.n_experts)
            flat, layout = IMP.flat_concat(tree)
            assert layout == IMP.score_layout(bc["adapters"],
                                              strategy.n_experts)
            np.testing.assert_allclose(scores[i], flat, rtol=1e-6, atol=0)
            if i % 2:      # ranks pruned by masks_np score exactly 0
                dead = ~IMP.flat_concat(masks_np)[0].astype(bool)
                assert dead.any() and not scores[i][dead].any()
    assert rp.upload._cache_size() == 1
