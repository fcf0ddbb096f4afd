"""Compressed exchange collectives behind the typed ExchangeConfig API
(repro/dist/exchange.py).

Contracts under test:
* API: ExchangeConfig validation; resolve_exchange returns the typed
  config and rejects anything else; ``parse_hot_sync`` rejects malformed
  strings.
* an explicit fp32 wire is BIT-IDENTICAL to the default-config step
  across M in {1,2} x row/table x impl fused/ring (the default
  config's step is itself pinned against the pre-refactor monolithic
  step in tests/test_pipeline.py, so equality here closes the chain back
  to the pre-PR step).
* ``bf16_sr`` is deterministic: two identical runs agree bitwise, a
  different ``sr_seed`` diverges, and a checkpoint-resume replays the
  exact wire dither (state incl. the ``sr`` counter is bitwise equal to
  the uninterrupted run).
* Degenerations: zero cotangents / zero gradients survive EVERY wire
  format bitwise (state unchanged), and bf16-representable payloads are
  wire-format-invariant.
* The dense error-feedback ``err`` slab round-trips through a
  checkpoint (save -> restore -> continue == uninterrupted, bitwise).
"""

import os
import subprocess
import sys
import textwrap

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
    from repro.dist.exchange import ExchangeConfig

    mesh = compat.make_mesh((2, 4), ('data', 'model'))
    BASE = DLRMConfig(name='t', num_dense=16, bottom=(32, 8), top=(32,),
                      table_rows=(100, 60, 40, 30, 20, 200, 51, 77),
                      emb_dim=8, pooling=3, batch=32, fused_update=False)
    # both wires stochastically rounded: the launcher's --exchange-dtype bf16_sr
    SR_WIRE = ExchangeConfig(dY_dtype='bf16_sr', dense_dtype='bf16_sr')

    def mk_batch(seed, cfg, layout):
        rng = np.random.default_rng(seed)
        idx = np.stack([rng.integers(0, max(2, m // 8), (32, 3))
                        for m in cfg.table_rows], 1).astype(np.int32)
        if cfg.emb_mode == 'table' and cfg.idx_input == 'replicated':
            idx = np.asarray(se.permute_indices(layout, jnp.asarray(idx)))
        return {'idx': jnp.asarray(idx),
                'dense_x': jnp.asarray(rng.standard_normal((32, 16)),
                                       jnp.bfloat16),
                'labels': jnp.asarray(rng.integers(0, 2, 32), jnp.float32)}

    def snap(state):
        flat, _ = jax.flatten_util.ravel_pytree(jax.tree.map(
            lambda x: np.asarray(x, np.float32), state))
        return np.asarray(flat)
"""


# ---------------------------------------------------------------------------
# API surface (no mesh needed)
# ---------------------------------------------------------------------------

def test_exchange_config_validation():
    from repro.dist.exchange import ExchangeConfig
    cfg = ExchangeConfig()
    assert (cfg.impl, cfg.dY_dtype, cfg.dense_dtype) == ("fused", "fp32",
                                                         "fp32")
    assert not cfg.needs_sr and not cfg.needs_err
    assert ExchangeConfig(dense_dtype="bf16").needs_err
    assert not ExchangeConfig(dense_dtype="bf16",
                              error_feedback=False).needs_err
    assert ExchangeConfig(dY_dtype="bf16_sr").needs_sr
    assert ExchangeConfig(dense_dtype="bf16_sr").needs_sr
    with pytest.raises(ValueError, match="ExchangeConfig.impl"):
        ExchangeConfig(impl="smoke")
    with pytest.raises(ValueError, match="dY_dtype"):
        ExchangeConfig(dY_dtype="fp16")
    with pytest.raises(ValueError, match="dense_dtype"):
        ExchangeConfig(dense_dtype="int8")
    with pytest.raises(ValueError, match="num_buckets"):
        ExchangeConfig(num_buckets=0)


def test_resolve_exchange_coercion_and_conflicts():
    import dataclasses as dc
    from repro.core.dlrm import DLRMConfig
    from repro.dist.exchange import ExchangeConfig, resolve_exchange

    base = DLRMConfig(name="t", num_dense=4, bottom=(8, 4), top=(8,),
                      table_rows=(10, 20), emb_dim=4, pooling=2)
    # unset resolves to the defaults
    assert resolve_exchange(base) is base.exchange
    assert resolve_exchange(base) == ExchangeConfig()
    # a typed config comes back unchanged
    typed = ExchangeConfig(impl="ring", dense_dtype="bf16", num_buckets=2)
    assert resolve_exchange(dc.replace(base, exchange=typed)) is typed
    # anything else is refused
    for bad in ("bf16", None):
        with pytest.raises(TypeError, match="ExchangeConfig"):
            resolve_exchange(dc.replace(base, exchange=bad))


def test_parse_hot_sync_validation():
    from repro.core.cache import parse_hot_sync
    assert parse_hot_sync("allreduce") == 1
    assert parse_hot_sync("deferred:3") == 3
    for bad in ("deferred:", "deferred:-1", "deferred:0", "deferred:x",
                "psum", ""):
        with pytest.raises(ValueError, match="hot_sync"):
            parse_hot_sync(bad)


# ---------------------------------------------------------------------------
# Degeneration / identity contracts (subprocess, 8 devices)
# ---------------------------------------------------------------------------

def test_fp32_bit_identity_matrix():
    """An explicit fp32 wire == the default-config step, bitwise, across
    M x mode x impl; so does an fp32 wire with error feedback off (it has
    nothing to feed back)."""
    out = run_sub(COMMON + """
    for mode in ('row', 'table'):
        for M in (1, 2):
            for impl in ('fused', 'ring'):
                base = dataclasses.replace(BASE, emb_mode=mode,
                                           idx_input='sharded',
                                           microbatches=M)
                fp32 = dict(impl=impl, dY_dtype='fp32', dense_dtype='fp32')
                variants = {
                    'default': (base if impl == 'fused' else
                                dataclasses.replace(
                                    base, exchange=ExchangeConfig(impl=impl))),
                    'fp32': dataclasses.replace(
                        base, exchange=ExchangeConfig(**fp32)),
                    'typed': dataclasses.replace(
                        base, exchange=ExchangeConfig(**fp32,
                                                      error_feedback=False)),
                }
                res = {}
                for tag, cfg in variants.items():
                    state, layout = init_state(jax.random.PRNGKey(0), cfg,
                                               mesh)
                    step, _, _, _ = make_train_step(cfg, mesh)
                    batch = mk_batch(0, cfg, layout)
                    for _ in range(2):
                        state, loss = step(state, batch)
                    res[tag] = (float(loss), snap(state))
                for tag in ('fp32', 'typed'):
                    assert res['default'][0] == res[tag][0], (mode, M, impl,
                                                              tag)
                    assert np.array_equal(res['default'][1], res[tag][1]), (
                        mode, M, impl, tag)
                print(mode, M, impl, 'FP32_EQ')
    """)
    assert out.count("FP32_EQ") == 8


def test_wire_degenerations_bitwise():
    """Zero cotangents / zero gradients survive every wire format bitwise,
    and bf16-representable payloads are wire-format-invariant (unit-level,
    inside shard_map, both modes)."""
    out = run_sub(COMMON + """
    from jax.sharding import PartitionSpec as P
    from repro.core.dlrm import as_hybrid_def
    from repro.core import hybrid as H
    from repro.optim import data_parallel as dp

    for mode in ('row', 'table'):
        cfg = dataclasses.replace(BASE, emb_mode=mode)
        mdef = as_hybrid_def(cfg)
        layout = H.make_layout(mdef, mesh)
        emb_ax, replica_ax = H._emb_axes(mdef, mesh)
        S = layout.num_orig_slots

        def gd(dY, dt):
            f = compat.shard_map(
                lambda v: se.gather_dY(layout, v, emb_ax, replica_ax,
                                       wire_dtype=dt, seed=jnp.int32(5),
                                       tag=1),
                mesh=mesh, in_specs=P(('data', 'model'), None, None),
                out_specs=(P(None, None, None) if mode == 'row'
                           else P(None, 'model', None)),
                check_vma=False)
            return np.asarray(jax.jit(f)(dY))

        zeros = jnp.zeros((32, S, 8), jnp.float32)
        # bf16-representable payload: small integers are exact in bf16
        rng = np.random.default_rng(3)
        exact = jnp.asarray(rng.integers(-8, 9, (32, S, 8)), jnp.float32)
        for dt in ('fp32', 'bf16', 'bf16_sr'):
            assert (gd(zeros, dt) == 0).all(), (mode, dt)
            assert np.array_equal(gd(exact, dt), gd(exact, 'fp32')), (
                mode, dt)
        print(mode, 'GATHER_DEGEN_OK')

    # dense RS+AG: zero grads leave (hi, lo, err) bitwise unchanged under
    # every wire format
    params = {'w': jnp.arange(64, dtype=jnp.float32) / 7.0,
              'b': jnp.ones((16,), jnp.float32) / 3.0}
    for dt, with_err in (('fp32', False), ('bf16', True), ('bf16', False),
                         ('bf16_sr', False)):
        arrays = dp.dp_global_arrays(params, 8, compress=with_err,
                                     num_buckets=2)
        def one(dense, grads):
            st = dp.DPState(hi=dense['hi'], lo_shard=dense['lo'],
                            mom_shard=None, err_shard=dense['err'])
            st2 = dp.rs_ag_split_sgd(st, grads, 0.1, ('data', 'model'),
                                     num_buckets=2, mean=False,
                                     wire_dtype=dt, seed=jnp.int32(3))
            return {'hi': st2.hi, 'lo': st2.lo_shard, 'err': st2.err_shard}
        specs = {'hi': jax.tree.map(lambda _: P(), arrays['hi']),
                 'lo': P(('data', 'model')),
                 'err': P(('data', 'model')) if with_err else None}
        f = jax.jit(compat.shard_map(
            one, mesh=mesh,
            in_specs=(specs, jax.tree.map(lambda _: P(), params)),
            out_specs=specs, check_vma=False))
        dense = {'hi': arrays['hi'], 'lo': arrays['lo'],
                 'err': arrays['err']}
        out = f(dense, jax.tree.map(jnp.zeros_like, params))
        for k in ('w', 'b'):
            assert np.array_equal(np.asarray(out['hi'][k]),
                                  np.asarray(dense['hi'][k])), (dt, k)
        assert np.array_equal(np.asarray(out['lo']),
                              np.asarray(dense['lo'])), dt
        if with_err:
            assert (np.asarray(out['err']) == 0).all(), dt
        print(dt, with_err, 'RS_DEGEN_OK')
    """)
    assert out.count("GATHER_DEGEN_OK") == 2
    assert out.count("RS_DEGEN_OK") == 4


# ---------------------------------------------------------------------------
# bf16_sr determinism + checkpoint resume (subprocess, 8 devices)
# ---------------------------------------------------------------------------

def test_bf16_sr_deterministic_and_seeded():
    out = run_sub(COMMON + """
    for mode in ('row', 'table'):
        res = {}
        for tag, seed in (('a', 0), ('b', 0), ('c', 11)):
            cfg = dataclasses.replace(BASE, emb_mode=mode,
                                      exchange=SR_WIRE,
                                      microbatches=2, sr_seed=seed)
            state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
            step, _, _, _ = make_train_step(cfg, mesh)
            batch = mk_batch(0, cfg, layout)
            for _ in range(3):
                state, loss = step(state, batch)
            res[tag] = (float(loss), snap(state))
        assert res['a'][0] == res['b'][0], mode
        assert np.array_equal(res['a'][1], res['b'][1]), mode
        # a different sr_seed dithers differently (the wire is live)
        assert not np.array_equal(res['a'][1], res['c'][1]), mode
        print(mode, 'SR_DET_OK')
    """)
    assert out.count("SR_DET_OK") == 2


def test_bf16_sr_checkpoint_resume_replays_wire_dither():
    out = run_sub(COMMON + """
    import tempfile
    from repro.checkpoint import CheckpointManager

    cfg = dataclasses.replace(BASE, emb_mode='table', idx_input='sharded',
                              exchange=SR_WIRE, microbatches=2)
    step, shardings, _, _ = make_train_step(cfg, mesh)

    state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
    structs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    batch = mk_batch(0, cfg, layout)
    straight = state
    for _ in range(4):
        straight, loss_s = step(straight, batch)

    state, _ = init_state(jax.random.PRNGKey(0), cfg, mesh)
    for _ in range(2):
        state, _ = step(state, batch)
    assert int(state['sr']) == 2
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(2, state, blocking=True)
        got_step, restored = mgr.restore(structs)
        assert got_step == 2 and int(restored['sr']) == 2
        resumed = jax.device_put(restored, shardings)
    for _ in range(2):
        resumed, loss_r = step(resumed, batch)

    assert float(loss_s) == float(loss_r)
    assert int(resumed['sr']) == int(straight['sr']) == 4
    assert np.array_equal(snap(straight), snap(resumed))
    print('SR_RESUME_OK')
    """)
    assert "SR_RESUME_OK" in out


def test_err_slab_checkpoint_roundtrip():
    """The dense error-feedback residual is step-dependent state: dropping
    it on restore would silently change the next update.  (The repo's
    dense grads are natively bf16 — the bf16 wire is lossless for them —
    so a fresh run keeps the slab at zero; a deterministic nonzero slab is
    injected to make the round-trip non-vacuous.)  save -> restore ->
    continue == uninterrupted, bitwise, err slab included; and the
    injected slab demonstrably changes the next update."""
    out = run_sub(COMMON + """
    import tempfile
    from repro.checkpoint import CheckpointManager

    cfg = dataclasses.replace(
        BASE, emb_mode='table',
        exchange=ExchangeConfig(dense_dtype='bf16'))
    step, shardings, _, _ = make_train_step(cfg, mesh)

    state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
    structs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    batch = mk_batch(0, cfg, layout)
    err0 = np.asarray(state['dense']['err'])
    assert (err0 == 0).all()
    rng = np.random.default_rng(7)
    inj = jnp.asarray(rng.standard_normal(err0.shape) * 1e-2, jnp.float32)
    state['dense']['err'] = inj
    state = jax.device_put(state, shardings)

    with tempfile.TemporaryDirectory() as d:
        # save FIRST: the jitted step donates its input state buffers
        mgr = CheckpointManager(d)
        mgr.save(0, state, blocking=True)

        straight = state
        for _ in range(3):
            straight, loss_s = step(straight, batch)

        _, restored = mgr.restore(structs)
        # the slab survived the round-trip bit-for-bit (and is nonzero)
        assert np.array_equal(np.asarray(restored['dense']['err']),
                              np.asarray(inj))
        resumed = jax.device_put(restored, shardings)
    for _ in range(3):
        resumed, loss_r = step(resumed, batch)

    assert float(loss_s) == float(loss_r)
    assert np.array_equal(snap(straight), snap(resumed))

    # the slab is LIVE state: a zeroed slab yields a different trajectory
    clean, _ = init_state(jax.random.PRNGKey(0), cfg, mesh)
    for _ in range(3):
        clean, _ = step(clean, batch)
    assert not np.array_equal(snap(clean), snap(straight))
    print('ERR_RESUME_OK')
    """)
    assert "ERR_RESUME_OK" in out
