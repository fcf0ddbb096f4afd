"""Frequency-tiered hot-row embedding cache (repro/core/cache.py).

Contracts under test:

* ``hot_sync='allreduce'`` is BITWISE invisible: with ``hot_rows > 0`` the
  trained weights (every slab) AND the stochastic-rounding ``sr`` counter
  equal the ``hot_rows=0`` run for {sgd, split_sgd, momentum_bf16} x
  M in {1, 2} x host_presort on/off — while the cache demonstrably serves
  a nonzero fraction of bags.
* Promotion is deterministic and layout-independent: the same counters
  (keyed by spec-global gid) and seed select the identical hot set on a
  4-shard row layout and a 3-shard table layout, under count ties.
* Save/restore mid-run resumes bitwise INCLUDING the cache subtree
  (hot_ids / hot_w / tick) and the counter slab.
* ``hot_sync='deferred:N'`` drifts (the cache is really serving stale
  rows) but stays under a pinned bound over a 50-step zipf stream.
* The reserved ``cnt`` touch-counter slab counts identically on every
  update path (reference, fused kernel, host-presorted, batch-chunked)
  and equals the per-lookup bincount oracle.
* ``adagrad_freq`` (frequency-adaptive LR off the same counters) matches
  its closed-form oracle on all three paths.
* Misconfigurations (bad hot_sync, promote_every < 1, hot_rows < 0 or
  larger than the row space) fail loudly at validate_pipeline time.
"""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core import cache as hot_cache
from repro.core import dlrm as D
from repro.core import hybrid as H
from repro.core import sharded_embedding as se
from repro.core.embedding import EmbeddingSpec
from repro.optim import row

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TABLES = (50, 30, 20, 10)


def _cfg(**kw):
    from repro.core.dlrm import DLRMConfig
    base = dict(name="t", num_dense=4, bottom=(8, 8), top=(8,),
                table_rows=TABLES, emb_dim=8, pooling=3, batch=16,
                emb_mode="table", idx_input="sharded", lr=0.05)
    base.update(kw)
    return DLRMConfig(**base)


def _mesh():
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


def _zipf_batch(i, batch=16):
    """Zipf-ish multi-hot batch: heavy repeat mass on each table's head."""
    r = np.random.default_rng(500 + i)
    hi = np.array([m - 1 for m in TABLES])[None, :, None]
    idx = np.minimum(r.zipf(1.5, size=(batch, len(TABLES), 3)) - 1,
                     hi).astype(np.int32)
    return {"idx": jnp.asarray(idx),
            "dense_x": jnp.asarray(r.normal(size=(batch, 4)), jnp.bfloat16),
            "labels": jnp.asarray(r.integers(0, 2, batch), jnp.float32)}


def _emb_bits(state):
    return {k: np.asarray(v).view(np.uint8).copy()
            for k, v in state["emb"].items()}


# ---------------------------------------------------------------------------
# Units: parsing, positions, layout-independent promotion
# ---------------------------------------------------------------------------

def test_parse_hot_sync():
    assert hot_cache.parse_hot_sync("allreduce") == 1
    assert hot_cache.parse_hot_sync("deferred:4") == 4
    for bad in ("deferred:0", "deferred:x", "psum", "deferred:-2"):
        with pytest.raises(ValueError, match="hot_sync"):
            hot_cache.parse_hot_sync(bad)


def test_validate_rejects_bad_cache_config():
    from repro.core import dlrm as D
    mesh = _mesh()
    for kw, match in ((dict(hot_rows=-1), "hot_rows"),
                      (dict(hot_rows=8, promote_every=0), "promote_every"),
                      (dict(hot_rows=8, hot_sync="bogus"), "hot_sync"),
                      (dict(hot_rows=10**6), "row space")):
        with pytest.raises(ValueError, match=match):
            D.make_train_step(_cfg(**kw), mesh)


def test_hot_positions_inverts_ids_and_drops_empties():
    ids = jnp.asarray([7, -1, 0, 12], jnp.int32)
    pos = hot_cache.hot_positions(16, ids)
    assert pos.shape == (16,)
    assert int(pos[7]) == 0 and int(pos[0]) == 2 and int(pos[12]) == 3
    # every other gid is cold; -1 must NOT wrap to the last entry
    assert int((pos >= 0).sum()) == 3 and int(pos[15]) == -1


def test_select_hot_layout_independent_under_ties():
    """The same per-gid counts select the identical hot set (ids AND
    order) on a 4-shard row layout and a 3-shard table layout — count
    ties broken by the seeded gid hash, never by shard position."""
    spec = EmbeddingSpec(TABLES, dim=4)
    rng = np.random.default_rng(7)
    counts = np.zeros(spec.total_rows, np.int32)
    for t, rows_t in enumerate(TABLES):
        base = int(spec.row_offsets[t])
        # few distinct count values => plenty of ties
        counts[base:base + rows_t] = rng.integers(0, 4, rows_t)
    got = {}
    for name, layout in (("row4", se.make_layout(spec, 4, "row")),
                         ("tab3", se.make_layout(spec, 3, "table"))):
        l2g, g2l = se.layout_gid_maps(layout)
        cnt_full = np.zeros(layout.total_rows, np.int32)
        owned = l2g >= 0
        cnt_full[owned] = counts[l2g[owned]]
        got[name] = np.asarray(hot_cache.select_hot(
            layout, jnp.asarray(cnt_full), 6, seed=5))
    np.testing.assert_array_equal(got["row4"], got["tab3"])
    # per-table chunks hold gids of that table (or -1), counts descending
    ids = got["row4"].reshape(len(TABLES), 6)
    tab = hot_cache.spec_gid_to_table(spec)
    for t in range(len(TABLES)):
        live = ids[t][ids[t] >= 0]
        assert np.all(tab[live] == t)
        c = counts[live]
        assert np.all(np.diff(c) <= 0) and np.all(c > 0)
    # a different seed reorders ties
    other = np.asarray(hot_cache.select_hot(
        se.make_layout(spec, 4, "row"),
        jnp.asarray(np.where(se.layout_gid_maps(
            se.make_layout(spec, 4, "row"))[0] >= 0,
            counts[np.clip(se.layout_gid_maps(
                se.make_layout(spec, 4, "row"))[0], 0, None)], 0)
            .astype(np.int32)), 6, seed=6))
    assert not np.array_equal(got["row4"], other)


def test_gid_maps_row_and_table_agree():
    spec = EmbeddingSpec(TABLES, dim=4)
    for layout in (se.make_layout(spec, 4, "row"),
                   se.make_layout(spec, 3, "table")):
        l2g, g2l = se.layout_gid_maps(layout)
        owned = np.nonzero(l2g >= 0)[0]
        # bijection between owned layout rows and real gids
        np.testing.assert_array_equal(g2l[l2g[owned]], owned)
        assert len(np.unique(l2g[owned])) == sum(TABLES)


# ---------------------------------------------------------------------------
# Bitwise matrix: allreduce cache on == cache off
# ---------------------------------------------------------------------------

def _run(cfg, mesh, steps, presort_layout=None):
    from repro.core import dlrm as D
    step, _, _, layout = D.make_train_step(cfg, mesh)
    state, _ = D.init_state(jax.random.PRNGKey(0), cfg, mesh)
    for i in range(steps):
        batch = _zipf_batch(i)
        if presort_layout is not None:
            from repro.data.pipeline import presort_batch
            batch.update({k: jnp.asarray(v) for k, v in presort_batch(
                presort_layout, np.asarray(batch["idx"])).items()})
        state, loss = step(state, batch)
    return state, float(loss), layout


@pytest.mark.parametrize("optimizer", ["sgd", "split_sgd", "momentum_bf16"])
@pytest.mark.parametrize("M", [1, 2])
@pytest.mark.parametrize("presort", [False, True])
def test_allreduce_cache_is_bitwise_invisible(optimizer, M, presort):
    """hot_rows=8 + hot_sync='allreduce' must be bit-identical to
    hot_rows=0 on every weight/state slab and the sr counter — while the
    hot slab serves a substantial fraction of bags (zipf head)."""
    mesh = _mesh()
    base = _cfg(sparse_optimizer=optimizer, microbatches=M,
                host_presort=presort, sr_seed=3)
    layout = None
    if presort:
        layout = H.make_layout(D.as_hybrid_def(base), mesh)
    off, loss_off, _ = _run(base, mesh, 4, presort_layout=layout)
    on, loss_on, _ = _run(
        dataclasses.replace(base, hot_rows=8, promote_every=2), mesh, 4,
        presort_layout=layout)
    assert loss_off == loss_on
    bits_off, bits_on = _emb_bits(off), _emb_bits(on)
    for k in bits_off:      # cache-on additionally carries the cnt slab
        np.testing.assert_array_equal(bits_on[k], bits_off[k]), k
    if "sr" in off:
        assert int(off["sr"]) == int(on["sr"])
    # the identity must not be vacuous: the final hot set really hits
    hit, _ = hot_cache.hot_bag_local(
        H.make_layout(D.as_hybrid_def(base), mesh), on["cache"]["hot_w"],
        on["cache"]["hot_pos"], _zipf_batch(3)["idx"])
    assert float(jnp.mean(hit)) > 0.3


def test_cache_save_restore_resume_bitwise(tmp_path):
    """Mid-run save/restore with the cache on: counters, hot set, mirror
    and tick all persist, and the resumed run is bitwise the
    uninterrupted one (promotion replays identically)."""
    from repro.checkpoint import CheckpointManager
    from repro.core import dlrm as D
    mesh = _mesh()
    cfg = _cfg(sparse_optimizer="momentum_bf16", sr_seed=3, hot_rows=8,
               promote_every=2)
    step, shardings, _, _ = D.make_train_step(cfg, mesh)

    def fresh():
        return D.init_state(jax.random.PRNGKey(0), cfg, mesh)[0]

    want = fresh()
    for i in range(6):
        want, _ = step(want, _zipf_batch(i))

    mid = fresh()
    for i in range(3):
        mid, _ = step(mid, _zipf_batch(i))
    mgr = CheckpointManager(tmp_path)
    mgr.save(3, mid, blocking=True)
    structs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), mid)
    got_step, got = mgr.restore(structs, shardings=shardings)
    assert got_step == 3
    assert int(got["cache"]["tick"]) == 3
    for i in range(3, 6):
        got, _ = step(got, _zipf_batch(i))
    for k, v in _emb_bits(want).items():
        np.testing.assert_array_equal(_emb_bits(got)[k], v), k
    assert int(got["sr"]) == int(want["sr"])
    for k in ("hot_ids", "tick"):
        np.testing.assert_array_equal(np.asarray(got["cache"][k]),
                                      np.asarray(want["cache"][k])), k
    np.testing.assert_array_equal(
        np.asarray(got["cache"]["hot_w"]).view(np.uint8),
        np.asarray(want["cache"]["hot_w"]).view(np.uint8))


def test_deferred_sync_drift_is_real_and_bounded():
    """deferred:8 over 50 zipf steps: the run must DIFFER from cache-off
    (stale rows really served) but the weight drift stays pinned — the
    cold store is authoritative and absorbs every update."""
    mesh = _mesh()
    base = _cfg(sparse_optimizer="sgd")
    off, _, _ = _run(base, mesh, 50)
    on, _, _ = _run(dataclasses.replace(
        base, hot_rows=8, promote_every=5, hot_sync="deferred:8"),
        mesh, 50)
    w_off = np.asarray(off["emb"]["w"])
    w_on = np.asarray(on["emb"]["w"])
    drift = float(np.max(np.abs(w_off - w_on)))
    assert drift > 0.0, "deferred run identical: the cache never served"
    assert drift < 5e-3, f"deferred drift {drift} above the pinned bound"


# ---------------------------------------------------------------------------
# Multi-rank: cross-rank hot-set identity + bitwise invisibility
# ---------------------------------------------------------------------------

def test_cache_multirank_bitwise_and_hotset_identity():
    from test_row_optim import run_sub
    out = run_sub("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import compat
    from repro.core.dlrm import DLRMConfig, make_train_step, init_state

    mesh = compat.make_mesh((2, 4), ('data', 'model'))
    TABLES = (100, 60, 40, 30)
    base = DLRMConfig(name='t', num_dense=8, bottom=(16, 8), top=(16,),
                      table_rows=TABLES, emb_dim=8, pooling=3, batch=16,
                      emb_mode='table', idx_input='sharded',
                      sparse_optimizer='split_sgd', lr=0.05)

    def batch(i):
        r = np.random.default_rng(300 + i)
        hi = np.array([m - 1 for m in TABLES])[None, :, None]
        idx = np.minimum(r.zipf(1.5, size=(16, 4, 3)) - 1,
                         hi).astype(np.int32)
        return {'idx': jnp.asarray(idx),
                'dense_x': jnp.asarray(r.normal(size=(16, 8)),
                                       jnp.bfloat16),
                'labels': jnp.asarray(r.integers(0, 2, 16), jnp.float32)}

    def run(cfg):
        step, _, _, _ = make_train_step(cfg, mesh)
        state, _ = init_state(jax.random.PRNGKey(0), cfg, mesh)
        for i in range(4):
            state, loss = step(state, batch(i))
        return state, float(loss)

    s0, l0 = run(base)
    s1, l1 = run(dataclasses.replace(base, hot_rows=8, promote_every=2))
    assert l0 == l1, (l0, l1)
    for k in s0['emb']:
        a = np.asarray(s0['emb'][k]).view(np.uint8)
        b = np.asarray(s1['emb'][k]).view(np.uint8)
        assert np.array_equal(a, b), k
    # the replicated cache must hold the SAME hot set on every device
    for k in ('hot_ids', 'hot_w', 'hot_pos'):
        shards = [np.asarray(sh.data)
                  for sh in s1['cache'][k].addressable_shards]
        assert len(shards) == 8, k
        for sh in shards[1:]:
            assert np.array_equal(
                sh.view(np.uint8), shards[0].view(np.uint8)), k
    hot = np.asarray(s1['cache']['hot_ids'])
    assert (hot >= 0).sum() > 0
    print('MULTI_OK')
    """)
    assert out.count("MULTI_OK") == 1


# ---------------------------------------------------------------------------
# Counter slab: path identity + bincount oracle
# ---------------------------------------------------------------------------

def _count_oracle(idx, valid, num_rows):
    tgt = np.asarray(idx).reshape(-1)
    if valid is not None:
        tgt = tgt[np.asarray(valid).reshape(-1)]
    tgt = tgt[(tgt >= 0) & (tgt < num_rows)]
    return np.bincount(tgt, minlength=num_rows).astype(np.int32)[:, None]


def test_counter_bump_identical_on_every_path():
    """The cnt slab advances by exactly the per-lookup bincount on the
    reference, fused-kernel and host-presorted paths — counting happens
    once, before optimizer dispatch, regardless of path."""
    from repro.kernels.embedding_update import sort_lookups
    rng = np.random.default_rng(9)
    M, E, B, S, P = 40, 8, 6, 2, 3
    W = jnp.asarray(rng.standard_normal((M, E)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 12, (B, S, P)), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, (B, S, P)), bool)
    dY = jnp.asarray(rng.standard_normal((B, S, E)), jnp.float32)
    opt = row.get("sgd")
    store = opt.init_store(W, counters=True)
    start = np.asarray(store["cnt"])
    want = start + _count_oracle(idx, valid, M)

    ref = opt.apply_sparse(store, row.SparseStream(idx=idx, dY=dY,
                                                   valid=valid), 0.05,
                           fused=False)
    fus = opt.apply_sparse(store, row.SparseStream(idx=idx, dY=dY,
                                                   valid=valid), 0.05,
                           fused=True, interpret=True)
    srows, sbags, smsk, swgt = sort_lookups(idx.reshape(-1),
                                            valid.reshape(-1), M, P, None)
    pre = opt.apply_sparse(
        store, row.SparseStream(idx=idx, dY=dY,
                                presort=(srows, sbags, smsk, swgt)),
        0.05, fused=True, interpret=True)
    for name, out in (("reference", ref), ("fused", fus),
                      ("presorted", pre)):
        np.testing.assert_array_equal(np.asarray(out["cnt"]), want), name


def test_counter_bump_chunked_matches(monkeypatch):
    """The batch-chunked apply_update branches (stateless scan AND the
    stateful chunked path) bump once per valid lookup, same as the
    unchunked paths."""
    from jax.sharding import PartitionSpec as P_
    from repro import compat
    layout = se.make_layout(EmbeddingSpec((40, 24), 8), 1, "row")
    rng = np.random.default_rng(4)
    idx = jnp.asarray(rng.integers(0, 6, (8, 2, 3)), jnp.int32)
    dY = jnp.asarray(rng.standard_normal((8, 2, 8)), jnp.float32)
    g = np.asarray(idx) + np.asarray(layout.row_offsets,
                                     np.int32)[None, :, None]
    mesh = _mesh()
    axes = ("data", "model")

    def run(opt, store):
        def f(st, i, d):
            return se.apply_update(layout, st, opt, i, d, 0.05, axes,
                                   fused=False)
        sm = jax.jit(compat.shard_map(
            f, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P_(axes, None), store),
                      P_(None, None, None), P_(None, None, None)),
            out_specs=jax.tree.map(lambda _: P_(axes, None), store),
            check_vma=False))
        return {k: np.asarray(v) for k, v in sm(store, idx, dY).items()}

    for name in ("sgd", "momentum"):     # stateless scan / stateful chunk
        opt = row.get(name)
        W = jnp.asarray(rng.standard_normal((layout.total_rows, 8)),
                        jnp.float32)
        store = opt.init_store(W, counters=True)
        want = _count_oracle(g, None, layout.total_rows)
        # per-row bytes = S*P*E*4 = 192; 200-byte budget forces 8 chunks
        monkeypatch.setenv("REPRO_EMB_CHUNK_BUDGET", "200")
        chunked = run(opt, store)
        monkeypatch.delenv("REPRO_EMB_CHUNK_BUDGET")
        plain = run(opt, store)
        np.testing.assert_array_equal(chunked["cnt"], want), name
        np.testing.assert_array_equal(plain["cnt"], want), name


def test_adagrad_freq_matches_oracle_on_all_paths():
    """w -= lr * g_summed / (sqrt(max(cnt, 1)) + eps) with cnt counted
    BEFORE the step; reference / fused kernel / presorted agree with the
    numpy oracle to fp32 tolerance and count identically."""
    from repro.kernels.embedding_update import sort_lookups
    rng = np.random.default_rng(12)
    M, E, B, S, P = 30, 8, 6, 2, 3
    W = jnp.asarray(rng.standard_normal((M, E)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 9, (B, S, P)), jnp.int32)
    dY = jnp.asarray(rng.standard_normal((B, S, E)), jnp.float32)
    opt = row.get("adagrad_freq")
    assert opt.state_keys == ("cnt",)
    store = opt.init_store(W)
    store = dict(store, cnt=jnp.asarray(
        rng.integers(0, 50, (M, 1)), jnp.int32))

    cnt1 = np.asarray(store["cnt"]) + _count_oracle(idx, None, M)
    g = np.repeat(np.asarray(dY, np.float64).reshape(-1, E), P, axis=0)
    tgt = np.asarray(idx).reshape(-1)
    want_w = np.asarray(W, np.float64).copy()
    for r in np.unique(tgt):
        Gr = g[tgt == r].sum(axis=0)
        denom = np.sqrt(max(float(cnt1[r, 0]), 1.0)) + opt.eps
        want_w[r] -= 0.05 * Gr / denom

    ref = jax.jit(lambda s, t: opt.apply_sparse(s, t, 0.05, fused=False))(
        store, row.SparseStream(idx=idx, dY=dY))
    fus = opt.apply_sparse(store, row.SparseStream(idx=idx, dY=dY), 0.05,
                           fused=True, interpret=True)
    srows, sbags, smsk, swgt = sort_lookups(idx.reshape(-1), None, M, P,
                                            None)
    pre = opt.apply_sparse(
        store, row.SparseStream(idx=idx, dY=dY,
                                presort=(srows, sbags, smsk, swgt)),
        0.05, fused=True, interpret=True)
    for name, out in (("reference", ref), ("fused", fus),
                      ("presorted", pre)):
        np.testing.assert_array_equal(np.asarray(out["cnt"]), cnt1), name
        np.testing.assert_allclose(np.asarray(out["w"]), want_w,
                                   rtol=1e-5, atol=1e-6), name
