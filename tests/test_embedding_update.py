"""Fused sparse-backward + Split-SGD embedding update (paper Alg. 3 + C5):
bit-exactness vs the segment_sum + combine_split reference, duplicate
accumulation, ragged/padded bags, untouched-row preservation, and the
blocked forward kernel."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import embedding as E
from repro.kernels import ops, ref
from repro.kernels import embedding_update as EU
from repro.optim.row import apply_rows_split_sgd
from repro.optim.split_sgd import combine_split, split_fp32

RNG = np.random.default_rng(7)

# jitted reference: the fused kernel matches the REFERENCE AS COMPILED
# (XLA contracts the mul+sub of the update identically in both paths;
# the eager op-by-op dispatch of the same expression does not contract)
_ref_split = jax.jit(apply_rows_split_sgd)


def _fused_split(hi, lo, tgt, dY, lr, valid=None, weights=None, pooling=1):
    """Kernel-level helper: the split_sgd kind of the collapsed
    ``fused_row_update`` surface (the former fused_embedding_update)."""
    out = ops.fused_row_update("split_sgd", {"hi": hi, "lo": lo}, tgt, dY,
                               lr, valid=valid, weights=weights,
                               pooling=pooling, interpret=True)
    return out["hi"], out["lo"]


def _fused_fp32(W, tgt, dY, lr, valid=None, weights=None, pooling=1):
    return ops.fused_row_update("sgd", {"w": W}, tgt, dY, lr, valid=valid,
                                weights=weights, pooling=pooling,
                                interpret=True)["w"]


def _mk(M, E_, L, P, dup_vocab=None, seed=0):
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.standard_normal((M, E_)), jnp.float32)
    hi, lo = split_fp32(W)
    tgt = jnp.asarray(rng.integers(0, dup_vocab or M, (L,)), jnp.int32)
    dY = jnp.asarray(rng.standard_normal((L // P, E_)), jnp.float32)
    return W, hi, lo, tgt, dY


@pytest.mark.parametrize("M,E_,L,P", [(50, 16, 24, 3), (200, 8, 300, 5),
                                      (8, 4, 64, 4), (1000, 32, 128, 1),
                                      (16, 128, 160, 8), (60, 17, 40, 2)])
def test_fused_split_bit_exact_duplicate_heavy(M, E_, L, P):
    """Duplicate-heavy zipf-like targets: fused == jitted reference, bitwise."""
    W, hi, lo, tgt, dY = _mk(M, E_, L, P, dup_vocab=max(2, M // 10))
    nh, nl = _fused_split(hi, lo, tgt, dY, 0.05, pooling=P)
    grad = jnp.take(dY, jnp.arange(L) // P, axis=0)
    rh, rl = _ref_split(hi, lo, tgt, grad, 0.05)
    np.testing.assert_array_equal(np.asarray(combine_split(nh, nl)),
                                  np.asarray(combine_split(rh, rl)))


def test_fused_split_flag_on_reference_entrypoint():
    """apply_rows_split_sgd(fused=True) is the same kernel behind the
    reference signature (A/B flag of the acceptance criteria)."""
    W, hi, lo, tgt, dY = _mk(100, 8, 64, 1, dup_vocab=9)
    nh, nl = jax.jit(apply_rows_split_sgd, static_argnames=("fused",))(
        hi, lo, tgt, dY, 0.1, fused=True)
    rh, rl = _ref_split(hi, lo, tgt, dY, 0.1)
    np.testing.assert_array_equal(np.asarray(combine_split(nh, nl)),
                                  np.asarray(combine_split(rh, rl)))


def test_duplicate_accumulation_explicit():
    """All lookups hit ONE row: update must be w - lr * sum(all grads)."""
    E_ = 8
    W = jnp.asarray(RNG.standard_normal((10, E_)), jnp.float32)
    hi, lo = split_fp32(W)
    tgt = jnp.full((12,), 3, jnp.int32)
    dY = jnp.asarray(RNG.standard_normal((12, E_)), jnp.float32)
    nh, nl = _fused_split(hi, lo, tgt, dY, 0.5, pooling=1)
    got = np.asarray(combine_split(nh, nl))
    want = np.asarray(W).copy()
    acc = np.zeros(E_, np.float32)
    for i in range(12):
        acc = (acc + np.asarray(dY)[i]).astype(np.float32)
    want[3] = want[3] - np.float32(0.5) * acc
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # every other row untouched, bitwise
    rest = np.setdiff1d(np.arange(10), [3])
    np.testing.assert_array_equal(got[rest], np.asarray(W)[rest])


def test_untouched_rows_never_modified():
    W, hi, lo, tgt, dY = _mk(500, 16, 32, 1, dup_vocab=20)
    nh, nl = _fused_split(hi, lo, tgt, dY, 0.1)
    got = np.asarray(combine_split(nh, nl))
    untouched = np.setdiff1d(np.arange(500), np.asarray(tgt))
    np.testing.assert_array_equal(got[untouched], np.asarray(W)[untouched])


def test_ragged_padded_bags_masked_out():
    """Invalid (padding) lookups — valid=False or out-of-range targets —
    contribute nothing and corrupt no row."""
    M, E_, L = 40, 8, 30
    W = jnp.asarray(RNG.standard_normal((M, E_)), jnp.float32)
    hi, lo = split_fp32(W)
    tgt = jnp.asarray(RNG.integers(0, M, (L,)), jnp.int32)
    dY = jnp.asarray(RNG.standard_normal((L, E_)), jnp.float32)
    valid = jnp.asarray(RNG.integers(0, 2, (L,)).astype(bool))
    nh, nl = _fused_split(hi, lo, tgt, dY, 0.1, valid=valid)
    # reference on the VALID subset only (invalid -> zero grads at tgt 0)
    grad = jnp.where(valid[:, None], dY, 0.0)
    rh, rl = _ref_split(hi, lo, jnp.where(valid, tgt, 0), grad, 0.1)
    np.testing.assert_array_equal(np.asarray(combine_split(nh, nl)),
                                  np.asarray(combine_split(rh, rl)))
    # out-of-range targets are dropped, not clamped into real rows
    tgt_oob = jnp.where(valid, tgt, M + 1000)
    nh2, nl2 = _fused_split(hi, lo, tgt_oob, dY, 0.1)
    np.testing.assert_array_equal(np.asarray(combine_split(nh2, nl2)),
                                  np.asarray(combine_split(rh, rl)))


def test_all_invalid_is_noop():
    W, hi, lo, tgt, dY = _mk(30, 8, 16, 1)
    valid = jnp.zeros((16,), bool)
    nh, nl = _fused_split(hi, lo, tgt, dY, 0.1, valid=valid)
    np.testing.assert_array_equal(np.asarray(combine_split(nh, nl)),
                                  np.asarray(W))


def test_fused_fp32_variant_matches_dedup_semantics():
    M, E_, L, P = 80, 8, 60, 3
    W, _, _, tgt, dY = _mk(M, E_, L, P, dup_vocab=11)
    out = _fused_fp32(W, tgt, dY, 0.1, pooling=P)
    want = np.asarray(W).copy()
    dyn = np.asarray(dY)
    for r in np.unique(np.asarray(tgt)):
        acc = np.zeros(E_, np.float32)
        for i in range(L):
            if int(tgt[i]) == r:
                acc = (acc + dyn[i // P]).astype(np.float32)
        want[r] = want[r] - np.float32(0.1) * acc
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)


def test_bag_update_dispatch():
    """core.embedding.bag_update(method='fused') and bag_update_split."""
    B, S, P, E_, M = 4, 3, 2, 16, 50
    W = jnp.asarray(RNG.standard_normal((M, E_)), jnp.float32)
    g = jnp.asarray(RNG.integers(0, M, (B, S, P)), jnp.int32)
    dY = jnp.asarray(RNG.standard_normal((B, S, E_)), jnp.float32)
    w_f = E.bag_update(W, g, dY, 0.1, method="fused")
    w_s = E.bag_update(W, g, dY, 0.1, method="scatter")
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_s),
                               rtol=1e-5, atol=1e-6)
    hi, lo = split_fp32(W)
    nh, nl = E.bag_update_split(hi, lo, g, dY, 0.1)
    rh, rl = _ref_split(hi, lo, g.reshape(-1),
                        jnp.broadcast_to(dY[:, :, None, :],
                                         (B, S, P, E_)).reshape(-1, E_), 0.1)
    np.testing.assert_array_equal(np.asarray(combine_split(nh, nl)),
                                  np.asarray(combine_split(rh, rl)))


def test_sort_lookups_properties():
    tgt = jnp.asarray([5, 2, 9, 2, 100, -1, 5], jnp.int32)
    w = jnp.asarray([0.5, 1.5, 2.0, 3.0, 4.0, 5.0, 6.0], jnp.float32)
    rows, bags, msk, wgt = EU.sort_lookups(tgt, None, 10, 1, weights=w)
    rn = np.asarray(rows)
    assert (np.diff(rn) >= 0).all()                 # sorted
    assert np.asarray(msk).sum() == 5               # 100 and -1 dropped
    assert (rn < 10).all() and (rn >= 0).all()      # in-range (tail clamped)
    # bag ids of the valid positions point at the original flat slots
    mb = np.asarray(bags)[np.asarray(msk) == 1]
    assert set(mb.tolist()) == {0, 1, 2, 3, 6}
    # weights ride the same permutation as the bag ids
    np.testing.assert_array_equal(np.asarray(wgt),
                                  np.asarray(w)[np.asarray(bags)])
    # no weights -> exact ones
    _, _, _, w1 = EU.sort_lookups(tgt, None, 10, 1)
    np.testing.assert_array_equal(np.asarray(w1), np.ones(7, np.float32))


# ---------------------------------------------------------------------------
# Weighted bags (per-lookup weights) on the fused path
# ---------------------------------------------------------------------------

def test_weighted_split_matches_scaled_reference():
    """Fused weighted update vs jitted reference on pre-scaled grads: the
    kernel scales each lookup's dY row by its weight inside the sorted-
    order pre-reduction.  The compiler contracts scale+accumulate into an
    FMA (one rounding instead of two per lookup), so the weighted result
    is within 1 ulp/step of the pre-scaled reference — NOT bitwise (the
    unweighted path multiplies by exactly 1.0 and keeps its bit-identity
    contract, enforced by the tests above).  Untouched rows stay bitwise
    intact."""
    M, E_, L = 60, 16, 48
    W, hi, lo, tgt, dY = _mk(M, E_, L, 1, dup_vocab=7, seed=3)
    w = jnp.asarray(RNG.standard_normal(L).astype(np.float32))
    nh, nl = _fused_split(hi, lo, tgt, dY, 0.05, weights=w, pooling=1)
    rh, rl = _ref_split(hi, lo, tgt, dY * w[:, None], 0.05)
    np.testing.assert_allclose(np.asarray(combine_split(nh, nl)),
                               np.asarray(combine_split(rh, rl)),
                               rtol=1e-6, atol=1e-6)
    untouched = np.setdiff1d(np.arange(M), np.asarray(tgt))
    np.testing.assert_array_equal(
        np.asarray(combine_split(nh, nl))[untouched],
        np.asarray(W)[untouched])


def test_weighted_fused_bag_update_matches_scatter():
    """bag_update(method='fused') now accepts per-lookup weights and
    matches the weighted scatter-add reference."""
    B, S, P, E_, M = 5, 3, 4, 8, 40
    W = jnp.asarray(RNG.standard_normal((M, E_)), jnp.float32)
    g = jnp.asarray(RNG.integers(0, M // 4, (B, S, P)), jnp.int32)
    dY = jnp.asarray(RNG.standard_normal((B, S, E_)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((B, S, P)), jnp.float32)
    w_f = E.bag_update(W, g, dY, 0.1, weights=w, method="fused")
    w_s = E.bag_update(W, g, dY, 0.1, weights=w, method="scatter")
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_s),
                               rtol=1e-5, atol=1e-6)
    # rows untouched by any lookup stay bitwise intact
    untouched = np.setdiff1d(np.arange(M), np.asarray(g).ravel())
    np.testing.assert_array_equal(np.asarray(w_f)[untouched],
                                  np.asarray(W)[untouched])


def test_weighted_split_bag_update():
    """bag_update_split with weights: pooled (P>1) weighted bags, fused vs
    reference on the weighted grad expansion (1-ulp FMA tolerance)."""
    B, S, P, E_, M = 4, 2, 3, 8, 30
    W = jnp.asarray(RNG.standard_normal((M, E_)), jnp.float32)
    hi, lo = split_fp32(W)
    g = jnp.asarray(RNG.integers(0, M // 3, (B, S, P)), jnp.int32)
    dY = jnp.asarray(RNG.standard_normal((B, S, E_)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((B, S, P)), jnp.float32)
    nh, nl = E.bag_update_split(hi, lo, g, dY, 0.1, weights=w)
    grad = jnp.broadcast_to(dY[:, :, None, :], (B, S, P, E_)) \
        * w[..., None]
    rh, rl = _ref_split(hi, lo, g.reshape(-1), grad.reshape(-1, E_), 0.1)
    np.testing.assert_allclose(np.asarray(combine_split(nh, nl)),
                               np.asarray(combine_split(rh, rl)),
                               rtol=1e-6, atol=1e-6)
    untouched = np.setdiff1d(np.arange(M), np.asarray(g).ravel())
    np.testing.assert_array_equal(
        np.asarray(combine_split(nh, nl))[untouched],
        np.asarray(W)[untouched])


# ---------------------------------------------------------------------------
# Blocked forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,e,n,p", [(500, 96, 40, 7), (64, 64, 13, 3),
                                        (200, 17, 8, 4), (100, 130, 33, 5)])
@pytest.mark.parametrize("bpb", [1, 4, 8])
def test_blocked_forward_matches_ref(rows, e, n, p, bpb):
    W = jnp.asarray(RNG.standard_normal((rows, e)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, rows, (n, p)), jnp.int32)
    out = ops.embedding_bag(W, idx, bags_per_block=bpb, interpret=True)
    r = ref.embedding_bag(W, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                               rtol=1e-5, atol=1e-5)


def test_blocked_forward_bf16_hi_path():
    """Forward off the bf16 hi half (2 bytes/elem): fp32-accumulated, close
    to the fp32 table within bf16 storage error."""
    W = jnp.asarray(RNG.standard_normal((300, 64)), jnp.float32)
    hi, _ = split_fp32(W)
    idx = jnp.asarray(RNG.integers(0, 300, (24, 6)), jnp.int32)
    out = ops.embedding_bag(hi, idx, interpret=True)
    exact = ref.embedding_bag(hi, idx)     # same storage, jnp oracle
    np.testing.assert_allclose(np.asarray(out), np.asarray(exact),
                               rtol=1e-5, atol=1e-5)
    full = ref.embedding_bag(W, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# End-to-end: train step trajectories identical with fused on/off
# ---------------------------------------------------------------------------

def test_dlrm_step_fused_trajectory_identical():
    from repro.core import dlrm as D
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    base = D.DLRMConfig(name="t", num_dense=8, bottom=(16, 8), top=(16,),
                        table_rows=(50, 30, 20, 10), emb_dim=8, pooling=3,
                        batch=16)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(np.stack([rng.integers(0, m, (16, 3))
                                for m in base.table_rows], 1), jnp.int32)
    batch = {"idx": idx,
             "dense_x": jnp.asarray(rng.standard_normal((16, 8)),
                                    jnp.bfloat16),
             "labels": jnp.asarray(rng.integers(0, 2, (16,)), jnp.float32)}
    out = {}
    for fused in (False, True):
        cfg = dataclasses.replace(base, fused_update=fused)
        state, _ = D.init_state(jax.random.PRNGKey(0), cfg, mesh)
        step, _, _, _ = D.make_train_step(cfg, mesh)
        for _ in range(3):
            state, loss = step(state, batch)
        out[fused] = (float(loss), np.asarray(state["emb"]["hi"], np.float32),
                      np.asarray(state["emb"]["lo"]))
    assert out[False][0] == out[True][0]
    np.testing.assert_array_equal(out[False][1], out[True][1])
    np.testing.assert_array_equal(out[False][2], out[True][2])


# ---------------------------------------------------------------------------
# Chunked stream and slab orientation
# ---------------------------------------------------------------------------

def test_rows_on_lanes_follows_the_tpu_layout_rule():
    """Narrow slabs are held transposed (rows on lanes), lane-multiple
    widths row-major — the layout XLA:TPU's default picks (it pads less)."""
    assert EU.rows_on_lanes((8_000_000, 64), jnp.bfloat16)
    assert EU.rows_on_lanes((8_000_000, 64), jnp.float32)
    assert EU.rows_on_lanes((8_000_000, 1), jnp.int32)
    assert not EU.rows_on_lanes((8_000_000, 128), jnp.uint16)
    assert not EU.rows_on_lanes((8_000_000, 256), jnp.float32)
    assert EU.rows_on_lanes((5000, 192), jnp.float32)
    assert not EU.rows_on_lanes((60, 64), jnp.float32)     # tie: row-major
    assert EU.rows_on_lanes((60, 32), jnp.bfloat16)


@pytest.mark.parametrize("name", ["split_sgd", "momentum", "momentum_bf16",
                                  "adagrad_rowwise"])
@pytest.mark.parametrize("E_", [16, 128])
def test_chunked_stream_matches_reference_bitwise(monkeypatch, name, E_):
    """A stream cut into many kernel calls (runs straddling every cut,
    carried partial sums) equals the one-call kernel AND the jitted
    reference bitwise, in both slab orientations (E=16: every slab on
    lanes; E=128: row-major weights beside a lanes-held [M, 1] slab)."""
    from functools import partial
    from repro.optim import row
    opt = row.get(name)
    M, L, P = 300, 96, 3
    W, _, _, tgt, dY = _mk(M, E_, L, P, dup_vocab=13, seed=5)
    store = opt.init_store(W)
    if opt.state:
        # nonzero state, so the transition is not a degenerate first step
        store = opt.apply_sparse(store, row.SparseStream(
            idx=tgt.reshape(-1, 1, P), dY=dY[:, None]), 0.05, seed=1)
    srt = EU.sort_lookups(tgt, None, M, P)
    keys = opt.slab_keys

    def kernel():
        out = EU.sparse_row_update_pallas(partial(opt.step, opt),
                                          [store[k] for k in keys], *srt,
                                          dY, 0.05, 7, interpret=True)
        return dict(zip(keys, out))

    whole = kernel()
    monkeypatch.setattr(EU, "CHUNK", 5)
    cut = kernel()
    want = jax.jit(lambda s: opt.apply_sparse(s, row.SparseStream(
        idx=tgt.reshape(-1, 1, P), dY=dY[:, None]), 0.05, seed=7))(store)
    for k in keys:
        for got in (whole[k], cut[k]):
            np.testing.assert_array_equal(
                np.asarray(got).view(np.uint8), np.asarray(want[k]).view(
                    np.uint8), err_msg=k)
