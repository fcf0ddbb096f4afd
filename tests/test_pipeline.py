"""Staged microbatch pipeline (repro/core/pipeline.py).

Contracts under test:
* M=1 is bit-identical to the legacy monolithic hybrid step (re-implemented
  inline here as the pinned reference — the pre-refactor ``step_local``).
* M in {1,2,4} produce IDENTICAL embedding state after a step on
  duplicate-heavy index streams (split and non-split SGD): every microbatch
  runs against the step's initial weights and the concatenated update
  stream is restored to full-batch order, so the single sparse update sees
  exactly the M=1 stream.  The accumulated DENSE gradient sums
  per-microbatch partial sums — a reassociation of the same reduction —
  so dense state matches to fp32 reassociation tolerance, not bitwise
  (that tolerance, not exactness, is the documented dense semantics).
* The ppermute-chunked ring exchange == the fused all_gather, bitwise.
* table-mode idx_input='sharded' (on-chip permute) == the replicated
  padded loader, trajectory-identical.
* Unsupported combinations are rejected with clear errors.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, timeout=900):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


COMMON = """
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import compat
    from repro.core.dlrm import DLRMConfig, make_train_step, init_state
    from repro.core import sharded_embedding as se

    mesh = compat.make_mesh((2, 4), ('data', 'model'))
    BASE = DLRMConfig(name='t', num_dense=16, bottom=(32, 8), top=(32,),
                      table_rows=(100, 60, 40, 30, 20, 200, 51, 77),
                      emb_dim=8, pooling=3, batch=32)

    def mk_batch(seed, cfg, layout):
        rng = np.random.default_rng(seed)
        # duplicate-heavy: draw from a tiny sub-vocabulary per table
        B = cfg.batch
        idx = np.stack([rng.integers(0, max(2, m // 8), (B, 3))
                        for m in cfg.table_rows], 1).astype(np.int32)
        if cfg.emb_mode == 'table' and cfg.idx_input == 'replicated':
            idx = np.asarray(se.permute_indices(layout, jnp.asarray(idx)))
        return {'idx': jnp.asarray(idx),
                'dense_x': jnp.asarray(rng.standard_normal((B, 16)),
                                       jnp.bfloat16),
                'labels': jnp.asarray(rng.integers(0, 2, B), jnp.float32)}

    def emb_np(state):
        if 'w' in state['emb']:
            return (np.asarray(state['emb']['w']),)
        return (np.asarray(state['emb']['hi'], np.float32),
                np.asarray(state['emb']['lo']))

    def dense_np(state):
        return np.asarray(jax.flatten_util.ravel_pytree(jax.tree.map(
            lambda x: np.asarray(x, np.float32), state['dense']['hi']))[0])
"""


def test_microbatch_state_identity_property():
    """Property over (mode x idx_input x split_sgd x seed): one pipelined
    step at M in {2,4} leaves the embedding state BIT-IDENTICAL to M=1 and
    the dense state within reassociation tolerance.

    Batch 64 gives every device at least two rows per microbatch (64 /
    (4 * 8)).  At one row XLA:CPU runs the dense dots on its
    matrix-vector kernel, whose fp32 sum order differs from the
    matrix-matrix kernel's, so the per-sample cotangents entering the
    sparse update would differ from M=1's in the last bit."""
    out = run_sub(COMMON + """
    for mode, inp in (('row', 'replicated'), ('row', 'sharded'),
                      ('table', 'replicated'), ('table', 'sharded')):
        for split in (True, False):
            for seed in (0, 7):
                res = {}
                for M in (1, 2, 4):
                    cfg = dataclasses.replace(
                        BASE, emb_mode=mode, idx_input=inp,
                        sparse_optimizer='split_sgd' if split else 'sgd',
                        microbatches=M, batch=64)
                    state, layout = init_state(jax.random.PRNGKey(seed),
                                               cfg, mesh)
                    step, _, _, _ = make_train_step(cfg, mesh)
                    batch = mk_batch(seed, cfg, layout)
                    state, loss = step(state, batch)
                    res[M] = (emb_np(state), dense_np(state), float(loss))
                for M in (2, 4):
                    for a, b in zip(res[1][0], res[M][0]):
                        assert np.array_equal(a, b), (mode, inp, split, M)
                    np.testing.assert_allclose(res[1][1], res[M][1],
                                               rtol=0, atol=4e-3)
                    assert abs(res[1][2] - res[M][2]) < 1e-4
    print('MB_PROP_OK')
    """)
    assert "MB_PROP_OK" in out


def test_m1_bit_identical_to_legacy_monolithic_step():
    """The M=1 pipeline == the pre-refactor monolithic step_local (pinned
    here verbatim), bitwise over a 3-step trajectory (split-SGD path)."""
    out = run_sub(COMMON + """
    from jax.sharding import PartitionSpec as P
    from repro.core import hybrid as H, dlrm as D
    from repro.optim import data_parallel as dp
    from repro.optim import row as row_optim

    def legacy_train_step(cfg, mesh):
        mdef = D.as_hybrid_def(cfg)
        structs, specs, shardings, layout = H.state_struct(mdef, mesh)
        bstructs, bspecs = H.batch_struct(mdef, mesh, layout)
        all_axes, model, batch_axes = H._mesh_axes(mesh)
        emb_ax, replica_ax = H._emb_axes(mdef, mesh)
        B = cfg.batch

        def step_local(state, batch):
            emb_store = state['emb']
            W_fwd = emb_store['hi']
            idx = batch['idx']
            if cfg.emb_mode == 'row' and cfg.idx_input == 'sharded':
                idx = jax.lax.all_gather(idx, emb_ax, axis=0, tiled=True)
            emb_out = se.sharded_bag_fwd(layout, W_fwd, idx, emb_ax)

            def loss_fn(dense_hi, emb_out):
                return mdef.dense_loss(dense_hi, emb_out, batch) / B

            (loss, (g_dense, d_emb)) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(state['dense']['hi'], emb_out)
            dY = se.gather_dY(layout, d_emb, emb_ax, replica_ax)
            new_emb = se.apply_update(
                layout, {'hi': emb_store['hi'], 'lo': emb_store['lo']},
                row_optim.get('split_sgd'), idx, dY, cfg.lr, emb_ax,
                replica_axes=replica_ax, fused=False)
            hi2, lo2 = new_emb['hi'], new_emb['lo']
            st = dp.DPState(hi=state['dense']['hi'],
                            lo_shard=state['dense']['lo'],
                            mom_shard=None, err_shard=state['dense']['err'])
            st2 = dp.rs_ag_split_sgd(st, g_dense, cfg.lr, all_axes,
                                     num_buckets=4, mean=False)
            return ({'emb': {'hi': hi2, 'lo': lo2},
                     'dense': {'hi': st2.hi, 'lo': st2.lo_shard,
                               'err': st2.err_shard}},
                    jax.lax.psum(loss, all_axes))

        step = compat.shard_map(step_local, mesh=mesh,
                                in_specs=(specs, bspecs),
                                out_specs=(specs, P()), check_vma=False)
        return jax.jit(step, donate_argnums=(0,))

    for mode, inp in (('row', 'replicated'), ('row', 'sharded'),
                      ('table', 'replicated')):
        cfg = dataclasses.replace(BASE, emb_mode=mode, idx_input=inp,
                                  fused_update=False)
        outs = {}
        for tag in ('legacy', 'pipeline'):
            state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
            step = (legacy_train_step(cfg, mesh) if tag == 'legacy'
                    else make_train_step(cfg, mesh)[0])
            batch = mk_batch(0, cfg, layout)
            for _ in range(3):
                state, loss = step(state, batch)
            outs[tag] = (float(loss), emb_np(state), dense_np(state),
                         np.asarray(state['dense']['lo']))
        l, p = outs['legacy'], outs['pipeline']
        assert l[0] == p[0], (mode, inp)
        for a, b in zip(l[1], p[1]):
            assert np.array_equal(a, b), (mode, inp)
        assert np.array_equal(l[2], p[2]), (mode, inp)
        assert np.array_equal(l[3], p[3]), (mode, inp)
        print(mode, inp, 'LEGACY_EQ')
    """)
    assert out.count("LEGACY_EQ") == 3


def test_ring_exchange_bit_identical():
    """ppermute-chunked ring all_gather == lax.all_gather (unit), and the
    end-to-end ring-exchange step == the fused-exchange step, bitwise."""
    out = run_sub(COMMON + """
    from jax.sharding import PartitionSpec as P
    from repro.core import pipeline

    x = jnp.arange(48 * 3, dtype=jnp.int32).reshape(48, 3)
    for axes in ('model', ('data', 'model')):
        f1 = jax.jit(compat.shard_map(
            lambda v: pipeline.ring_all_gather(v, axes), mesh=mesh,
            in_specs=P(axes, None), out_specs=P(None, None),
            check_vma=False))
        f2 = jax.jit(compat.shard_map(
            lambda v: jax.lax.all_gather(v, axes, axis=0, tiled=True),
            mesh=mesh, in_specs=P(axes, None), out_specs=P(None, None),
            check_vma=False))
        assert np.array_equal(np.asarray(f1(x)), np.asarray(f2(x))), axes

    from repro.dist.exchange import ExchangeConfig
    for mode in ('row', 'table'):
        outs = {}
        for impl in ('fused', 'ring'):
            cfg = dataclasses.replace(BASE, emb_mode=mode,
                                      idx_input='sharded', microbatches=2,
                                      exchange=ExchangeConfig(impl=impl))
            state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
            step, _, _, _ = make_train_step(cfg, mesh)
            batch = mk_batch(0, cfg, layout)
            for _ in range(2):
                state, loss = step(state, batch)
            outs[impl] = (float(loss), emb_np(state))
        assert outs['fused'][0] == outs['ring'][0], mode
        for a, b in zip(outs['fused'][1], outs['ring'][1]):
            assert np.array_equal(a, b), mode
    print('RING_OK')
    """)
    assert "RING_OK" in out


def test_table_sharded_idx_matches_replicated():
    """Satellite: table-mode idx_input='sharded' (original-slot stream +
    on-chip permute/slice) == the paper's replicated padded loader,
    trajectory-identical."""
    out = run_sub(COMMON + """
    traj = {}
    for inp in ('replicated', 'sharded'):
        cfg = dataclasses.replace(BASE, emb_mode='table', idx_input=inp)
        state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
        step, _, _, _ = make_train_step(cfg, mesh)
        batch = mk_batch(0, cfg, layout)
        ls = []
        for _ in range(4):
            state, loss = step(state, batch)
            ls.append(float(loss))
        traj[inp] = (ls, emb_np(state))
    assert np.allclose(traj['replicated'][0], traj['sharded'][0],
                       rtol=1e-5), traj
    for a, b in zip(traj['replicated'][1], traj['sharded'][1]):
        assert np.array_equal(a, b)
    print('TABLE_SHARDED_OK')
    """)
    assert "TABLE_SHARDED_OK" in out


def test_score_step_sharded_inputs():
    """Serve path reuses the exchange stage: scores identical between
    replicated and sharded index input, row and table mode."""
    out = run_sub(COMMON + """
    from repro.core import dlrm as D, hybrid as H
    for mode in ('row', 'table'):
        sc = {}
        for inp in ('replicated', 'sharded'):
            cfg = dataclasses.replace(BASE, emb_mode=mode, idx_input=inp)
            state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
            ev, _, _, _ = H.make_score_step(D.as_hybrid_def(cfg), mesh)
            batch = mk_batch(3, cfg, layout)
            sc[inp] = np.asarray(ev(state, batch))
        np.testing.assert_allclose(sc['replicated'], sc['sharded'],
                                   rtol=1e-5, atol=1e-6)
    print('SCORE_OK')
    """)
    assert "SCORE_OK" in out


# ---------------------------------------------------------------------------
# Single-device: validation errors + retrieval extras normalization
# ---------------------------------------------------------------------------

def test_unsupported_combinations_rejected():
    import dataclasses

    import jax
    from repro.core import pipeline
    from repro.core.dlrm import DLRMConfig, make_train_step
    from repro.dist.exchange import ExchangeConfig
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    base = DLRMConfig(name="t", num_dense=8, bottom=(16, 8), top=(16,),
                      table_rows=(50, 30, 20, 10), emb_dim=8, pooling=3,
                      batch=16)
    with pytest.raises(ValueError, match="idx_input"):
        make_train_step(dataclasses.replace(base, idx_input="banana"), mesh)
    with pytest.raises(ValueError, match="emb_mode"):
        pipeline.validate_pipeline(
            dataclasses.replace(base, emb_mode="diagonal"), mesh, 1)
    with pytest.raises(ValueError, match="microbatches"):
        make_train_step(base, mesh, microbatches=0)
    with pytest.raises(ValueError, match="divisible"):
        make_train_step(base, mesh, microbatches=5)
    with pytest.raises(ValueError, match="ExchangeConfig.impl"):
        ExchangeConfig(impl="smoke")
    with pytest.raises(TypeError, match="ExchangeConfig"):
        make_train_step(dataclasses.replace(base, exchange="ring"), mesh)


def test_retrieval_rejects_sharded_and_normalizes_extras():
    """Satellite: make_retrieval_step broadcasts extras via the schema —
    a rank-1 (B-squeezed) extra is normalized, not dropped — and rejects a
    sharded index stream with a clear error."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    from repro.core import hybrid as H
    from repro.launch.mesh import make_mesh
    from repro.models import recsys as R

    mesh = make_mesh((1, 1), ("data", "model"))
    mdef = R.make_sasrec(64, batch=1)
    with pytest.raises(ValueError, match="sharded"):
        H.make_retrieval_step(dc.replace(mdef, idx_input="sharded"),
                              mesh, n_candidates=16, target_slot=50)

    state, layout = H.init_state(jax.random.PRNGKey(0), mdef, mesh)
    retr, arg_structs, _, _ = H.make_retrieval_step(
        mdef, mesh, n_candidates=16, target_slot=50, topk=4)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 64, (1, 150, 1)), jnp.int32)
    cand = jnp.asarray(rng.standard_normal((16, 50)), jnp.bfloat16)
    batch_2d = {"idx": idx, "seq_mask": jnp.ones((1, 50), jnp.float32)}
    batch_1d = {"idx": idx, "seq_mask": jnp.ones((50,), jnp.float32)}
    v2, i2 = retr(state, batch_2d, cand)
    v1, i1 = retr(state, batch_1d, cand)
    # rank-1 extra is normalized via the extras schema -> same result
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1, np.float32),
                                  np.asarray(v2, np.float32))
    assert np.asarray(v1).shape == (4,)
