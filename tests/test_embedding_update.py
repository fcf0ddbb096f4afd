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
    """Fused weighted update vs jitted reference on pre-scaled grads: each
    lookup's dY row is scaled by its weight before the kernel (one
    rounding, as the reference's product), and the kernel only adds the
    scaled rows in sorted order, so the result equals the pre-scaled
    reference bitwise.  Untouched rows stay bitwise intact."""
    M, E_, L = 60, 16, 48
    W, hi, lo, tgt, dY = _mk(M, E_, L, 1, dup_vocab=7, seed=3)
    w = jnp.asarray(RNG.standard_normal(L).astype(np.float32))
    nh, nl = _fused_split(hi, lo, tgt, dY, 0.05, weights=w, pooling=1)
    rh, rl = _ref_split(hi, lo, tgt, dY * w[:, None], 0.05)
    np.testing.assert_array_equal(np.asarray(combine_split(nh, nl)),
                                  np.asarray(combine_split(rh, rl)))
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
    reference on the weighted grad expansion, bitwise."""
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
    np.testing.assert_array_equal(np.asarray(combine_split(nh, nl)),
                                  np.asarray(combine_split(rh, rl)))
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


# (CHUNK, BLOCK) of the cut stream: one call; calls of 5 lookups, one grid
# step each (a run of about 7 lookups straddles three calls); calls of 24
# lookups in grid steps of 4 (groups straddle grid steps and calls)
_CUTS = {"whole": None, "calls": (5, 5), "blocks": (24, 4)}


def _masked_stream(M, E_, L, P, seed=5):
    """A duplicate-heavy stream over 13 rows 23 apart, some lookups masked
    (``valid``): sorted to the tail on row M - 1, whose group nothing
    else reaches.  The presorted stream also masks every lookup of row
    276 in place, mid-stream, alone in its group.  Returns (W, tgt, dY,
    valid of the reference, presorted stream of the kernel)."""
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.standard_normal((M, E_)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, 13, (L,)) * 23, jnp.int32)
    dY = jnp.asarray(rng.standard_normal((L // P, E_)), jnp.float32)
    valid = jnp.asarray(rng.random(L) > 0.2)
    rows, bags, msk, wgt = EU.sort_lookups(tgt, valid, M, P)
    assert int(jnp.sum((rows == 276) & (msk != 0))) > 0
    msk = jnp.where(rows == 276, 0, msk)
    return W, tgt, dY, valid & (tgt != 276), (rows, bags, msk, wgt)


@pytest.mark.parametrize("cut", list(_CUTS))
@pytest.mark.parametrize("name", ["split_sgd", "momentum", "momentum_bf16",
                                  "adagrad_rowwise"])
@pytest.mark.parametrize("E_", [16, 128])
def test_chunked_stream_matches_reference_bitwise(monkeypatch, name, E_,
                                                  cut):
    """The kernel on a stream cut into calls and grid steps (groups and
    runs straddling every cut, carried partial sums) equals the jitted
    reference bitwise, in both slab orientations (E=16: every slab on
    lanes; E=128: row-major weights beside a lanes-held [M, 1] slab).  A
    row reached only by masked lookups, in the tail's group or alone in a
    group mid-stream, keeps its weights' and its state's bits."""
    from functools import partial
    from repro.optim import row
    opt = row.get(name)
    M, L, P = 300, 96, 3
    W, tgt, dY, valid, srt = _masked_stream(M, E_, L, P)
    store = opt.init_store(W)
    if opt.state:
        # nonzero state on every row the stream reaches, masked or not, so
        # a step that should not run would change it
        store = opt.apply_sparse(store, row.SparseStream(
            idx=jnp.concatenate([tgt, jnp.full((P,), M - 1, jnp.int32)]
                                ).reshape(-1, 1, P),
            dY=jnp.concatenate([dY, dY[:1]])[:, None]), 0.05, seed=1)
    if _CUTS[cut]:
        monkeypatch.setattr(EU, "CHUNK", _CUTS[cut][0])
        monkeypatch.setattr(EU, "BLOCK", _CUTS[cut][1])
    keys = opt.slab_keys
    got = dict(zip(keys, EU.sparse_row_update_pallas(
        partial(opt.step, opt), [store[k] for k in keys], *srt, dY, 0.05, 7,
        interpret=True)))
    want = jax.jit(lambda s: opt.apply_sparse(s, row.SparseStream(
        idx=tgt.reshape(-1, 1, P), dY=dY[:, None],
        valid=valid.reshape(-1, 1, P)), 0.05, seed=7))(store)
    for k in keys:
        np.testing.assert_array_equal(
            np.asarray(got[k]).view(np.uint8),
            np.asarray(want[k]).view(np.uint8), err_msg=k)
        # the masked-only rows kept their bits (the reference's claim too)
        for r in (276, M - 1):
            np.testing.assert_array_equal(
                np.asarray(got[k][r]).view(np.uint8),
                np.asarray(store[k][r]).view(np.uint8), err_msg=(k, r))


def test_kernel_copies_race_free_under_the_tpu_interpreter(monkeypatch,
                                                           capsys):
    """The kernel's group and block copies under the TPU interpreter,
    which counts DMA semaphores, moves data only when a copy is waited on
    and reports reads and writes that race: no race, and every row of
    every touched group equals the reference.  (That interpreter leaves
    an aliased output's untouched groups unwritten, so the comparison is
    one call's touched groups.)"""
    from functools import partial
    from jax.experimental.pallas import tpu as pltpu
    from repro.optim import row
    opt = row.get("momentum")
    M, L, P = 300, 96, 3
    W, tgt, dY, valid, srt = _masked_stream(M, 16, L, P)
    store = opt.init_store(W)
    monkeypatch.setattr(EU, "BLOCK", 8)
    out = EU.sparse_row_update_pallas(
        partial(opt.step, opt), [store[k] for k in opt.slab_keys], *srt, dY,
        0.05, 7, interpret=pltpu.InterpretParams(
            dma_execution_mode="on_wait", detect_races=True))
    want = jax.jit(lambda s: opt.apply_sparse(s, row.SparseStream(
        idx=tgt.reshape(-1, 1, P), dY=dY[:, None],
        valid=valid.reshape(-1, 1, P)), 0.05, seed=7))(store)
    assert "RACE DETECTED" not in capsys.readouterr().out
    groups = np.unique(np.asarray(srt[0]) // EU.LANES)
    rows = np.concatenate([np.arange(g * EU.LANES, min(M, (g + 1) * EU.LANES))
                           for g in groups])
    for k, o in zip(opt.slab_keys, out):
        np.testing.assert_array_equal(
            np.asarray(o)[rows].view(np.uint8),
            np.asarray(want[k])[rows].view(np.uint8), err_msg=k)


def _kernel_call(jaxpr):
    """The first Pallas call's equation in a (closed) jaxpr, nested ones
    included."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    found = _kernel_call(sub)
                    if found is not None:
                        return found
    return None


# (rows M, rows the stream reaches) of the E=256 streams: dense, 96
# lookups on 272 rows (lam = 0.35), on both sides of the boundaries of
# 128-row groups 0|1 and 1|2 and of 16-row tiles, the last group (rows
# 256..271) one whole 16-row tile; sparse, 96 lookups on 1,996 rows
# (lam = 0.048), on both sides of 16-row groups 0|1, 1|2, 2|3 and
# 123|124, the last group (rows 1984..1995) holding 12 of its 16 rows
_DENSITIES = {
    "dense": (272, [0, 1, 15, 16, 127, 128, 129, 200, 255, 256, 257, 271],
              128),
    "sparse": (1996, [0, 15, 16, 17, 31, 32, 47, 48, 100, 1983, 1984,
                      1995], 16),
}


@pytest.mark.parametrize("density", list(_DENSITIES))
@pytest.mark.parametrize("cut", list(_CUTS))
def test_e256_row_major_groups_of_16_match_reference_bitwise(monkeypatch,
                                                             cut, density):
    """dlrm-large's width, E=256: the bf16 and 16-bit slabs are row-major
    and ``pre`` is Wp=384 lanes wide (the count lane opens a third lane
    tile).  A dense stream, which touches every 16-row tile group, runs
    in groups of G=128 rows; a sparse one keeps G=16 (one 16-bit tile).
    Duplicate runs on both sides of group boundaries, a partial last
    group, and the stream cut into calls and grid steps (groups
    straddling both): the kernel equals the jitted reference bitwise, and
    untouched rows keep their bits."""
    from functools import partial
    from repro.optim import row
    opt = row.get("split_sgd")
    M, near, G = _DENSITIES[density]
    E_, L, P = 256, 96, 3
    rng = np.random.default_rng(11)
    W = jnp.asarray(rng.standard_normal((M, E_)), jnp.float32)
    hi, lo = split_fp32(W)
    near = np.array(near)
    tgt = jnp.asarray(near[rng.integers(0, near.size, L)], jnp.int32)
    dY = jnp.asarray(rng.standard_normal((L // P, E_)), jnp.float32)
    assert not EU.rows_on_lanes((M, E_), jnp.bfloat16)
    assert not EU.rows_on_lanes((M, E_), jnp.uint16)
    if _CUTS[cut]:
        monkeypatch.setattr(EU, "CHUNK", _CUTS[cut][0])
        monkeypatch.setattr(EU, "BLOCK", _CUTS[cut][1])
    srt = EU.sort_lookups(tgt, None, M, P)

    def update(hi, lo):
        return EU.sparse_row_update_pallas(partial(opt.step, opt), [hi, lo],
                                           *srt, dY, 0.05, 7, interpret=True)

    call = _kernel_call(jax.make_jaxpr(update)(hi, lo))
    gm = call.params["grid_mapping"]
    vmem = [(tuple(a.shape), str(a.dtype)) for a in gm.scratch_avals
            if str(a.dtype) in ("bfloat16", "uint16", "float32")]
    assert vmem == [((3, G, E_), "bfloat16"), ((3, G, E_), "uint16"),
                    ((G, 384), "float32")]
    assert gm.block_mappings[0].block_shape[-1].block_size == 384
    nh, nl = update(hi, lo)
    grad = jnp.take(dY, jnp.arange(L) // P, axis=0)
    rh, rl = _ref_split(hi, lo, tgt, grad, 0.05)
    for got, want, old in ((nh, rh, hi), (nl, rl, lo)):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                      np.asarray(want).view(np.uint8))
        untouched = np.setdiff1d(np.arange(M), near)
        np.testing.assert_array_equal(
            np.asarray(got)[untouched].view(np.uint8),
            np.asarray(old)[untouched].view(np.uint8))


# one shard's [M, W] slabs: dlrm-large's chip share (64 x 93,750 rows,
# 64 x 100 x 256 lookups a step) and dlrm-small's (8 x 1M rows, E=64,
# 8 x 50 x 8192 lookups); dlrm-mlperf's Criteo tables at E=128 (26
# tables, 187,767,399 rows, 26 x 16,384 lookups)
_LARGE = (6_000_128, 256)
_SMALL = (8_000_000, 64)
_MLPERF = (187_767_399, 128)
_SPLIT = (jnp.bfloat16, jnp.uint16)


@pytest.mark.parametrize("shape,dtypes,live,want", [
    pytest.param(_LARGE, _SPLIT, 1_638_400, 128, id="dlrm-large"),
    pytest.param(_SMALL, _SPLIT, 3_276_800, 128, id="e64-lanes"),
    pytest.param(_SMALL, _SPLIT, 1_000, 128, id="e64-lanes-sparse"),
    pytest.param(_MLPERF, _SPLIT, 26 * 16_384, 16, id="mlperf-e128"),
    pytest.param(_LARGE, _SPLIT, 0, 16, id="no-live-lookups"),
    # fp32 tiles are 8 rows: at dlrm-large's density 16 rows would copy
    # 11% more than 8, so the tile stays; at one lookup a row it widens
    pytest.param(_LARGE, (jnp.float32,), 1_638_400, 8, id="fp32-tile"),
    pytest.param(_LARGE, (jnp.float32,), 6_000_128, 128, id="fp32-dense"),
    # row placement over four shards: each is handed the whole stream of
    # 96 lookups, a quarter of it live on its 272 rows (lam 0.088, where
    # all 96 would make 0.35 and groups of 128)
    pytest.param((272, 256), _SPLIT, 96 / 4, 16, id="row-shards-4"),
])
def test_group_rows(shape, dtypes, live, want):
    """The row group of a store: a lane tile when any slab is held on
    lanes, else the widest of the tile, twice it, ... 128 rows whose
    groups copy at most 5% more rows than the tile's under uniform ids."""
    assert EU.group_rows([shape] * len(dtypes), dtypes, live) == want


def test_row_shards_divide_the_live_lookups():
    """A row shard's stream is the whole step's, the other shards'
    lookups masked: the kernel sizes its groups by the live ones.  The
    dense E=256 stream runs in groups of 128 rows on one shard and of 16
    when it is spread over four."""
    from repro.optim import row
    M, near, _ = _DENSITIES["dense"]
    L, P = 96, 3
    rng = np.random.default_rng(11)
    store = row.get("split_sgd").init_store(
        jnp.asarray(rng.standard_normal((M, 256)), jnp.float32))
    tgt = jnp.asarray(np.array(near)[rng.integers(0, len(near), L)],
                      jnp.int32)
    srt = EU.sort_lookups(tgt, None, M, P)
    dY = jnp.zeros((L // P, 256), jnp.float32)
    for shards, G in ((1, 128), (4, 16)):
        call = _kernel_call(jax.make_jaxpr(
            lambda s: ops.fused_row_update_presorted(
                "split_sgd", s, *srt, dY, 0.05, interpret=True,
                shards=shards))(store))
        assert call.params["grid_mapping"].scratch_avals[0].shape == (
            3, G, 256), shards
