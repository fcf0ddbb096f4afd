"""One chip's share of a row-wise deployment (``configs/dlrm_paper
.chip_share``) against the uncut model: a tiny row-placed table set cut
into D = 4 row slices, each slice given to the program's one-device
embedding stages with the lookups that fall in it, rebased (a lookup of
another slice rides along with bag weight 0).  Summed over the slices,
the pooled partials are the uncut bags; stitched together, the rows each
slice's sparse update changed are the uncut update.  The values are
exact (table entries and cotangents on coarse power-of-two grids, a
power-of-two rate; the partial bags exact in the bfloat16 the row-wise
forward hands on), so both hold bit for bit."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.dlrm_paper import chip_share
from repro.core import dlrm as D
from repro.core import hybrid as H
from repro.core import pipeline
from repro.launch.mesh import make_mesh
from repro.optim import row as row_optim
from repro.optim.split_sgd import combine_split

D_SLICES, ROWS, S, E, POOL, B, LR = 4, 64, 3, 16, 5, 8, 0.5


def _uncut():
    return D.DLRMConfig(name="share-test", num_dense=4, bottom=(8, E),
                        top=(8,), table_rows=(ROWS,) * S, emb_dim=E,
                        pooling=POOL, batch=B, lr=LR)


def _stages(cfg, fused):
    """The program's forward and sparse update of ``cfg`` on one device:
    ``fwd(store, idx, wgt) -> [B, S, E]``, ``upd(store, idx, dY, wgt) ->
    store``."""
    cfg = dataclasses.replace(cfg, weighted=True, fused_update=fused)
    mesh = make_mesh((1, 1), ("data", "model"))
    mdef = D.as_hybrid_def(cfg)
    layout = H.make_layout(mdef, mesh)
    st = pipeline.build_stages(mdef, mesh, layout)
    opt = row_optim.resolve(mdef)

    def fwd(store, idx, wgt):
        i, _ = st.index_exchange(idx, fwd_only=True)
        w, _ = st.index_exchange(wgt, fwd_only=True)
        return st.embedding_fwd(opt.fwd_weights(store), i, w)

    def upd(store, idx, dY, wgt):
        _, i = st.index_exchange(idx)
        _, w = st.index_exchange(wgt)
        return st.sparse_update(store, i, dY, weights=w)

    def wrap(f):
        return jax.jit(compat.shard_map(f, mesh=mesh, in_specs=P(),
                                        out_specs=P(), check_vma=False))
    return wrap(fwd), wrap(upd), layout, opt


def _tables(seed):
    """Uncut float32 tables: a nonzero multiple of 1/8 (the upper bfloat16
    half, which the forward reads) plus a multiple of 2**-20 (the lower
    half), so that every bag and every partial bag is exact in bfloat16,
    the wire format of the row-wise forward."""
    g = np.random.default_rng(seed)
    k = g.integers(1, 9, (S, ROWS, E)) / 8
    m = g.integers(0, 64, (S, ROWS, E)) * 2.0**-20
    return (g.choice([-1, 1], (S, ROWS, E)) * (k + m)).astype(np.float32)


def _store(opt, layout, tables):
    """The program's store holding ``tables`` (one block each)."""
    W = np.zeros((layout.total_rows, E), np.float32)
    for t, rows in enumerate(tables):
        off = int(layout.spec.row_offsets[t])
        W[off:off + rows.shape[0]] = rows
    return opt.init_store(jnp.asarray(W))


def _fp32_rows(store, layout, t, n):
    off = int(layout.spec.row_offsets[t])
    return np.asarray(combine_split(store["hi"], store["lo"]))[off:off + n]


@pytest.mark.parametrize("fused", [False, True])
def test_slices_add_up_to_the_uncut_embedding_layer(fused):
    g = np.random.default_rng(3)
    tables = _tables(4)
    idx = g.integers(0, ROWS, (B, S, POOL)).astype(np.int32)
    dY = (g.integers(-128, 129, (B, S, E)) / 256).astype(np.float32)

    # the uncut reference: plain sums of the forward halves, and SGD on
    # the float32 tables with every lookup's cotangent
    fwd_w = (tables.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
    want_bags = np.stack([fwd_w[s][idx[:, s]].sum(axis=1) for s in range(S)],
                         axis=1)
    want_tab = tables.copy()
    for s in range(S):
        grad = np.zeros((ROWS, E), np.float32)
        np.add.at(grad, idx[:, s].reshape(-1),
                  np.repeat(dY[:, s], POOL, axis=0))
        want_tab[s] = tables[s] - np.float32(LR) * grad

    share = chip_share(_uncut(), D_SLICES, batch=B)
    n = ROWS // D_SLICES
    assert share.table_rows == (n,) * S and share.deployment_chips == D_SLICES
    fwd, upd, layout, opt = _stages(share, fused)
    bags = np.zeros((B, S, E), np.float32)
    got_tab = np.empty_like(tables)
    for d in range(D_SLICES):
        mine = (idx >= d * n) & (idx < (d + 1) * n)
        local = np.where(mine, idx - d * n, 0).astype(np.int32)
        wgt = mine.astype(np.float32)
        store = _store(opt, layout, tables[:, d * n:(d + 1) * n])
        bags += np.asarray(fwd(store, local, wgt))
        new = upd(store, local, jnp.asarray(dY), wgt)
        for s in range(S):
            got_tab[s, d * n:(d + 1) * n] = _fp32_rows(new, layout, s, n)
    np.testing.assert_array_equal(bags, want_bags)
    np.testing.assert_array_equal(got_tab.view(np.uint32),
                                  want_tab.view(np.uint32))
    # the update changed exactly the rows some lookup reached
    changed = (got_tab != tables).any(axis=2)
    for s in range(S):
        assert set(np.flatnonzero(changed[s])) == set(idx[:, s].ravel())

