"""Weighted bags end-to-end through the hybrid step (HybridDef/DLRMConfig
``weighted=True``): the batch carries per-lookup weights in the idx
layout, the forward computes ``sum(w * row)`` and the sparse update
scales each lookup's cotangent.

Contracts:
* all-ones weights == unweighted, BITWISE (state and loss) — w * 1.0
  multiplies exactly on both the forward and the update path;
* the weighted forward matches a manual weighted-bag computation;
* zero-weighting one slot removes its table's rows from the update
  entirely (bit-exact no-op on those rows) while the unweighted run
  moves them — the backward really is scaled per lookup.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core import dlrm as D
from repro.core import hybrid as H
from repro.launch.mesh import make_mesh

TABLES = (100, 60, 40, 30, 20, 200, 51, 77)
BASE = D.DLRMConfig(name="t", num_dense=16, bottom=(32, 8), top=(32,),
                    table_rows=TABLES, emb_dim=8, pooling=3, batch=16)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _batch(seed, weights=None):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.integers(0, max(2, m // 8), (16, 3))
                    for m in TABLES], 1).astype(np.int32)
    b = {"idx": jnp.asarray(idx),
         "dense_x": jnp.asarray(rng.standard_normal((16, 16)), jnp.float32),
         "labels": jnp.asarray(rng.integers(0, 2, 16), jnp.float32)}
    if weights is not None:
        b["weights"] = jnp.asarray(weights, jnp.float32)
    return b


def _emb(state):
    return tuple(np.asarray(v) for v in state["emb"].values())


@pytest.mark.parametrize("mode", ["row", "table"])
def test_all_ones_weights_bitwise_equal_unweighted(mode):
    mesh = _mesh()
    res = {}
    for tag in ("plain", "ones"):
        cfg = dataclasses.replace(BASE, emb_mode=mode,
                                  weighted=(tag == "ones"))
        state, layout = D.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step, _, _, _ = D.make_train_step(cfg, mesh)
        for s in range(2):
            b = _batch(s, weights=(np.ones((16, 8, 3), np.float32)
                                   if tag == "ones" else None))
            state, loss = step(state, b)
        res[tag] = (float(loss), _emb(state))
    assert res["plain"][0] == res["ones"][0]
    for a, b in zip(res["plain"][1], res["ones"][1]):
        assert np.array_equal(a, b)


def test_weighted_forward_matches_manual_bag():
    """eval (serve) path: sigmoid(logits) computed with random weights ==
    the same forward with a manually weighted bag output."""
    mesh = _mesh()
    cfg = dataclasses.replace(BASE, emb_mode="row", weighted=True)
    state, layout = D.init_state(jax.random.PRNGKey(1), cfg, mesh)
    ev, _, _, _ = H.make_score_step(D.as_hybrid_def(cfg), mesh)
    rng = np.random.default_rng(2)
    # power-of-two weights: bf16-row * w products are exact in fp32 and a
    # 3-term sum of 8-bit mantissas fits fp32 exactly, so the manual bag
    # is order-independent (no association-rounding flakiness)
    w = rng.choice([0.0, 0.5, 1.0, 2.0], (16, 8, 3)).astype(np.float32)
    b = _batch(2, weights=w)
    got = np.asarray(ev(state, b))

    # manual: weighted bag on the hi table (bf16 wire of the row fwd),
    # then the same dense forward
    hi = np.asarray(state["emb"]["hi"], np.float32)
    g = np.asarray(b["idx"]) + np.asarray(layout.row_offsets,
                                          np.int32)[None, :, None]
    bag = (hi[g] * w[..., None]).sum(axis=2)            # [B, S, E] fp32
    bag = np.asarray(jnp.asarray(bag, jnp.bfloat16), np.float32)
    logits = D.forward_local(state["dense"]["hi"], jnp.asarray(bag),
                             b["dense_x"])
    want = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_zero_weight_slot_freezes_its_table():
    """Weights gate the update per lookup: zeroing slot 5's weights leaves
    table 5's rows bit-identical to init after a step, while the same step
    with ones moves them."""
    mesh = _mesh()
    cfg = dataclasses.replace(BASE, emb_mode="row", weighted=True)
    spec = cfg.spec
    lo5, hi5 = (int(spec.row_offsets[5]),
                int(spec.row_offsets[5] + spec.padded_rows[5]))
    touched = {}
    for tag in ("zeroed", "ones"):
        state, layout = D.init_state(jax.random.PRNGKey(0), cfg, mesh)
        init_hi = np.asarray(state["emb"]["hi"], np.float32).copy()
        init_lo = np.asarray(state["emb"]["lo"]).copy()
        step, _, _, _ = D.make_train_step(cfg, mesh)
        w = np.ones((16, 8, 3), np.float32)
        if tag == "zeroed":
            w[:, 5, :] = 0.0
        state, _ = step(state, _batch(0, weights=w))
        hi = np.asarray(state["emb"]["hi"], np.float32)
        lo = np.asarray(state["emb"]["lo"])
        touched[tag] = not (np.array_equal(hi[lo5:hi5], init_hi[lo5:hi5])
                            and np.array_equal(lo[lo5:hi5],
                                               init_lo[lo5:hi5]))
        # other tables always move (weights 1, duplicate-heavy stream)
        assert not np.array_equal(hi[:lo5], init_hi[:lo5])
    assert touched["ones"] and not touched["zeroed"]


def test_weighted_presort_bakes_weights():
    """host_presort + weighted: the loader bakes bag weights into
    psort_wgt and the presorted step tracks the weighted reference step
    (same kernel-vs-reference tolerance as the unweighted fp32 contract)."""
    from repro.data.pipeline import presort_batch
    mesh = _mesh()
    rng = np.random.default_rng(3)
    w = rng.uniform(0.5, 1.5, (16, 8, 3)).astype(np.float32)
    res = {}
    for tag in ("plain", "presort"):
        cfg = dataclasses.replace(BASE, emb_mode="row", weighted=True,
                                  host_presort=(tag == "presort"))
        state, layout = D.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step, _, _, _ = D.make_train_step(cfg, mesh)
        b = _batch(0, weights=w)
        if tag == "presort":
            ps = presort_batch(layout, np.asarray(b["idx"]), w)
            b = {**b, **{k: jnp.asarray(v) for k, v in ps.items()}}
        state, loss = step(state, b)
        res[tag] = (float(loss), _emb(state))
    assert res["plain"][0] == res["presort"][0]
    a_hi, a_lo = res["plain"][1]
    b_hi, b_lo = res["presort"][1]
    from repro.optim.split_sgd import combine_split
    wa = np.asarray(combine_split(jnp.asarray(a_hi, jnp.bfloat16),
                                  jnp.asarray(a_lo)))
    wb = np.asarray(combine_split(jnp.asarray(b_hi, jnp.bfloat16),
                                  jnp.asarray(b_lo)))
    np.testing.assert_allclose(wa, wb, rtol=1e-6, atol=1e-7)


def test_score_step_weighted_and_retrieval_rejects():
    from repro.models import recsys as R
    mesh = _mesh()
    mdef = dataclasses.replace(R.make_fm((50,) * 6, batch=8), weighted=True)
    state, layout = H.init_state(jax.random.PRNGKey(0), mdef, mesh)
    sc, _, bspecs, _ = H.make_score_step(mdef, mesh)
    assert "weights" in bspecs and "psort_rows" not in bspecs
    rng = np.random.default_rng(0)
    b = {"idx": jnp.asarray(rng.integers(0, 50, (8, 6, 1)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, 2, 8), jnp.float32),
         "weights": jnp.asarray(rng.uniform(0.5, 1.5, (8, 6, 1)),
                                jnp.float32)}
    s1 = np.asarray(sc(state, b))
    s2 = np.asarray(sc(state, {**b, "weights": b["weights"] * 2}))
    assert s1.shape == (8,) and not np.array_equal(s1, s2)
    with pytest.raises(ValueError, match="weighted"):
        H.make_retrieval_step(mdef, mesh, n_candidates=8, target_slot=0)
