"""Pluggable sparse RowOptimizer API (repro/optim/row.py).

Contracts under test:
* Registry/resolve: the five built-ins resolve by name, hyperparameter
  overrides apply, the legacy ``split_sgd`` bool maps to
  'split_sgd'/'sgd', unknown names fail loudly.
* Degeneration properties: ``momentum(beta=0)`` is BITWISE ``sgd`` on the
  fused path; ``split_sgd`` matches the jitted ``split_fp32``/
  ``combine_split`` reference bitwise; a zero-initialized Adagrad first
  step equals SGD scaled by ``1/(sqrt(acc_1)+eps)`` to fp32 tolerance.
* Pinned legacy kernel: the new ``apply_sparse`` split path is bitwise
  the PRE-REFACTOR ``fused_embedding_update`` wrapper (re-implemented
  here verbatim against the unchanged Pallas kernel).
* State hygiene: masked/padding streams never decay momentum or inflate
  accumulators; untouched rows keep weights AND state bitwise.
* Acceptance (subprocess, 8 devices): all registered optimizers —
  including the compressed-state ``momentum_bf16``/``adagrad_bf16``,
  whose per-step seed rides the replicated ``state["sr"]`` counter — run
  through ``make_pipelined_train_step`` for M in {1, 2} with
  ``host_presort`` on and off — embedding stores bit-identical across M,
  and the host-pre-sorted path bitwise matches the fused device-sort
  path (row AND table mode).
* Checkpoint round-trip: save/restore/resume is bit-identical to an
  uninterrupted run for every optimizer (state slabs persist and restore
  next to the weights), and ``reshard_store`` relays every slab across
  an elastic shard-count change.
* No caller outside optim/row.py touches the kernels.ops fused update
  entry points (source scan).
"""

import dataclasses
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.optim import row
from repro.optim.split_sgd import combine_split, split_fp32

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RNG = np.random.default_rng(11)


def _mk(M=60, E=16, B=8, S=2, P=3, vocab=None, seed=0):
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.standard_normal((M, E)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, vocab or M, (B, S, P)), jnp.int32)
    dY = jnp.asarray(rng.standard_normal((B, S, E)), jnp.float32)
    return W, idx, dY


# ---------------------------------------------------------------------------
# Registry / resolve
# ---------------------------------------------------------------------------

def test_registry_names_and_overrides():
    assert set(row.names()) >= {"sgd", "split_sgd", "momentum",
                                "adagrad_rowwise", "adagrad",
                                "momentum_bf16", "adagrad_bf16",
                                "adagrad_freq"}
    # compressed-state layout: bf16 slabs + the stochastic_round flag
    bf = row.get("momentum_bf16")
    assert bf.stochastic_round and not row.get("momentum").stochastic_round
    assert bf.store_struct(32, 8)["mom"].dtype == jnp.bfloat16
    assert row.get("momentum").beta == 0.9
    assert row.get("momentum", beta=0.5).beta == 0.5
    assert row.get("adagrad", eps=1e-4).eps == 1e-4
    with pytest.raises(ValueError, match="unknown sparse optimizer"):
        row.get("rmsprop")
    # store layout ownership
    assert row.get("split_sgd").weight_keys == ("hi", "lo")
    assert row.get("momentum").state_keys == ("mom",)
    st = row.get("adagrad_rowwise").store_struct(32, 8)
    assert st["acc"].shape == (32, 1) and st["w"].shape == (32, 8)


def test_resolve_legacy_and_explicit():
    class Obj:
        sparse_optimizer = None
        split_sgd = True
    assert row.resolve(Obj()).name == "split_sgd"
    Obj.split_sgd = False
    assert row.resolve(Obj()).name == "sgd"
    Obj.sparse_optimizer = "momentum"
    Obj.opt_beta = 0.25
    assert row.resolve(Obj()).beta == 0.25
    Obj.sparse_optimizer = row.get("adagrad")
    del Obj.opt_beta
    assert row.resolve(Obj()).name == "adagrad"


def test_ops_entry_points_only_called_from_row():
    """Acceptance: no production caller outside optim/row.py invokes the
    kernels.ops fused update entry points (the model-facing surface is
    RowOptimizer.apply_sparse); the pre-refactor names are gone."""
    from repro.kernels import ops
    for legacy in ("fused_embedding_update", "fused_embedding_update_fp32",
                   "fused_embedding_update_presorted",
                   "fused_embedding_update_fp32_presorted"):
        assert not hasattr(ops, legacy), legacy
    # ops.fused_row_update* calls (the _split-suffixed jnp oracle in
    # kernels/ref.py is a pure reference, not a kernel invocation)
    pat = re.compile(r"fused_row_update(?!_split)|fused_embedding_update")
    offenders = []
    for root, _, files in os.walk(os.path.join(SRC, "repro")):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            rel = os.path.relpath(path, SRC)
            if rel in (os.path.join("repro", "optim", "row.py"),
                       os.path.join("repro", "kernels", "ops.py")):
                continue
            if pat.search(open(path).read()):
                offenders.append(rel)
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# Degeneration properties
# ---------------------------------------------------------------------------

def test_momentum_beta0_bitwise_sgd_fused():
    """momentum(beta=0) == sgd, bitwise, on the fused path (both
    pre-reduce duplicates; 0*m + acc is an exact fp32 identity) — over a
    duplicate-heavy stream and several steps of carried state."""
    W, idx, dY = _mk(vocab=7, seed=3)
    sgd, mom0 = row.get("sgd"), row.get("momentum", beta=0.0)
    s_sgd = {"w": W}
    s_mom = mom0.init_store(W)
    for i in range(3):
        stream = row.SparseStream(idx=idx, dY=dY * (i + 1))
        s_sgd = sgd.apply_sparse(s_sgd, stream, 0.05, fused=True,
                                 interpret=True)
        s_mom = mom0.apply_sparse(s_mom, stream, 0.05, fused=True,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(s_sgd["w"]),
                                  np.asarray(s_mom["w"]))


def test_split_sgd_matches_jitted_split_reference():
    """split_sgd.apply_sparse(fused=True) == the jitted split_fp32/
    combine_split dedup reference, bitwise."""
    W, idx, dY = _mk(vocab=9, seed=4)
    ss = row.get("split_sgd")
    store = ss.init_store(W)
    out = ss.apply_sparse(store, row.SparseStream(idx=idx, dY=dY), 0.05,
                          fused=True, interpret=True)
    B, S, P = idx.shape
    E = dY.shape[-1]
    grad = jnp.broadcast_to(dY[:, :, None, :],
                            (B, S, P, E)).reshape(-1, E)
    rh, rl = jax.jit(row.apply_rows_split_sgd)(store["hi"], store["lo"],
                                               idx.reshape(-1), grad, 0.05)
    np.testing.assert_array_equal(
        np.asarray(combine_split(out["hi"], out["lo"])),
        np.asarray(combine_split(rh, rl)))


@pytest.mark.parametrize("name", ["adagrad_rowwise", "adagrad"])
@pytest.mark.parametrize("fused", [True, False])
def test_adagrad_first_step_is_scaled_sgd(name, fused):
    """Zero-initialized Adagrad's first step == SGD with the per-row
    (rowwise) / per-element (adagrad) scale ``1/(sqrt(acc_1)+eps)``
    computed from the deduped gradient — documented tolerance 1e-6
    (one extra fp32 division vs the closed form)."""
    W, idx, dY = _mk(vocab=11, seed=5)
    opt = row.get(name)
    out = (opt.apply_sparse(opt.init_store(W),
                            row.SparseStream(idx=idx, dY=dY), 0.05,
                            fused=True, interpret=True)
           if fused else
           jax.jit(lambda s, t: opt.apply_sparse(s, t, 0.05, fused=False)
                   )(opt.init_store(W), row.SparseStream(idx=idx, dY=dY)))
    # numpy oracle: dedup, scale, step
    B, S, P = idx.shape
    E = dY.shape[-1]
    g = np.repeat(np.asarray(dY, np.float32).reshape(-1, E), P, axis=0)
    tgt = np.asarray(idx).reshape(-1)
    want_w = np.asarray(W, np.float64).copy()
    acc1 = np.zeros((W.shape[0], E))
    for r in np.unique(tgt):
        Gr = g[tgt == r].sum(axis=0, dtype=np.float64)
        s1 = (np.mean(Gr * Gr) if name == "adagrad_rowwise" else Gr * Gr)
        scale = 1.0 / (np.sqrt(s1) + opt.eps)
        want_w[r] = want_w[r] - 0.05 * Gr * scale    # scaled SGD
        acc1[r] = s1
    np.testing.assert_allclose(np.asarray(out["w"]), want_w,
                               rtol=1e-5, atol=1e-6)
    got_acc = np.asarray(out["acc"])
    want_acc = (acc1[:, :1] if name == "adagrad_rowwise" else acc1)
    np.testing.assert_allclose(got_acc, want_acc, rtol=1e-5, atol=1e-6)


def test_momentum_reference_matches_fused_and_state_hygiene():
    """Reference (dedup) momentum == fused momentum to fp32 tolerance over
    a trajectory; masked lookups never decay state on either path."""
    W, idx, dY = _mk(vocab=6, seed=6)
    mom = row.get("momentum")
    st_f = mom.init_store(W)
    st_r = mom.init_store(W)
    ref = jax.jit(lambda s, t: mom.apply_sparse(s, t, 0.02, fused=False))
    for i in range(4):
        stream = row.SparseStream(idx=idx, dY=dY * ((-1.0) ** i))
        st_f = mom.apply_sparse(st_f, stream, 0.02, fused=True,
                                interpret=True)
        st_r = ref(st_r, stream)
    np.testing.assert_allclose(np.asarray(st_f["w"]), np.asarray(st_r["w"]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(st_f["mom"]),
                               np.asarray(st_r["mom"]),
                               rtol=1e-6, atol=1e-7)
    untouched = np.setdiff1d(np.arange(W.shape[0]), np.asarray(idx))
    assert np.all(np.asarray(st_f["mom"])[untouched] == 0)
    np.testing.assert_array_equal(np.asarray(st_f["w"])[untouched],
                                  np.asarray(W)[untouched])
    # all-masked stream: exact no-op on weights AND state, both paths
    stm = {**mom.init_store(W), "mom": jnp.ones_like(st_f["mom"])}
    masked = row.SparseStream(idx=idx, dY=dY,
                              valid=jnp.zeros(idx.shape, bool))
    for out in (mom.apply_sparse(stm, masked, 0.02, fused=True,
                                 interpret=True),
                jax.jit(lambda s, t: mom.apply_sparse(s, t, 0.02,
                                                      fused=False)
                        )(stm, masked)):
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(W))
        assert np.all(np.asarray(out["mom"]) == 1.0)


def test_pinned_legacy_split_kernel_bit_identity():
    """The split_sgd path through the RowOptimizer surface is bitwise the
    PRE-REFACTOR ``ops.fused_embedding_update`` wrapper: the sort, then
    the Pallas kernel with the Split-SGD row math (combine, step, split)
    pinned here verbatim."""
    from repro.kernels.embedding_update import (sort_lookups,
                                                sparse_row_update_pallas)
    from repro.optim.split_sgd import combine_split, split_fp32

    def split_step(blocks, g, lr, seed, rows):
        hi, lo = blocks
        return split_fp32(combine_split(hi, lo) - lr * g)

    def legacy_fused_embedding_update(hi, lo, tgt, dY, lr, valid=None,
                                      weights=None, pooling=1):
        # pre-refactor ops.py wrapper, interpret branch (CPU)
        M = hi.shape[0]
        srows, sbags, smsk, swgt = sort_lookups(tgt, valid, M, pooling,
                                                weights)
        return sparse_row_update_pallas(split_step, (hi, lo), srows, sbags,
                                        smsk, swgt, dY, lr, interpret=True)

    W, idx, dY = _mk(vocab=8, seed=7)
    B, S, P = idx.shape
    ss = row.get("split_sgd")
    store = ss.init_store(W)
    new = ss.apply_sparse(store, row.SparseStream(idx=idx, dY=dY), 0.05,
                          fused=True, interpret=True)
    lh, ll = jax.jit(legacy_fused_embedding_update,
                     static_argnames=("pooling",))(
        store["hi"], store["lo"], idx.reshape(-1),
        dY.reshape(B * S, -1), 0.05, pooling=P)
    np.testing.assert_array_equal(np.asarray(new["hi"], np.float32),
                                  np.asarray(lh, np.float32))
    np.testing.assert_array_equal(np.asarray(new["lo"]), np.asarray(ll))


def test_chunked_stateful_reference_single_transition(monkeypatch):
    """Batch-chunking the stateful reference path (tiny
    REPRO_EMB_CHUNK_BUDGET) must NOT re-run the optimizer transition per
    chunk: the chunked result matches the unchunked reference to fp32
    accumulation tolerance, i.e. the momentum decay fires once per step,
    not beta^n-compounded across n chunks."""
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import sharded_embedding as se
    from repro.core.embedding import EmbeddingSpec
    from repro.launch.mesh import make_mesh

    layout = se.make_layout(EmbeddingSpec((40, 30), 8), 1, "row")
    mom = row.get("momentum", beta=0.9)
    rng = np.random.default_rng(2)
    W = jnp.asarray(rng.standard_normal((layout.total_rows, 8)),
                    jnp.float32)
    idx = jnp.asarray(rng.integers(0, 5, (8, 2, 3)), jnp.int32)
    dY = jnp.asarray(rng.standard_normal((8, 2, 8)), jnp.float32)
    store = {**mom.init_store(W), "mom": jnp.ones((layout.total_rows, 8),
                                                  jnp.float32)}
    mesh = make_mesh((1, 1), ("data", "model"))
    axes = ("data", "model")

    def run():
        def f(st, idxj, dYj):
            return se.apply_update(layout, st, mom, idxj, dYj, 0.05, axes,
                                   fused=False)
        sm = jax.jit(compat.shard_map(
            f, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(axes, None), store),
                      P(None, None, None), P(None, None, None)),
            out_specs=jax.tree.map(lambda _: P(axes, None), store),
            check_vma=False))
        return {k: np.asarray(v) for k, v in sm(store, idx, dY).items()}

    # per-row bytes = S*P*E*4 = 192; a 200-byte budget forces 8 chunks
    monkeypatch.setenv("REPRO_EMB_CHUNK_BUDGET", "200")
    chunked = run()
    monkeypatch.delenv("REPRO_EMB_CHUNK_BUDGET")
    unchunked = run()
    for k in store:
        np.testing.assert_allclose(chunked[k], unchunked[k],
                                   rtol=1e-5, atol=1e-6)
    # single decay: touched rows carry ~0.9*1 + sum(g), never 0.9^n
    g = np.asarray(idx) + np.asarray(layout.row_offsets,
                                     np.int32)[None, :, None]
    touched = np.unique(g)
    assert not np.array_equal(chunked["mom"][touched],
                              np.ones_like(chunked["mom"][touched]))
    untouched = np.setdiff1d(np.arange(layout.total_rows), touched)
    np.testing.assert_array_equal(chunked["mom"][untouched], 1.0)


@pytest.mark.parametrize("name", ["split_sgd", "momentum"])
def test_chunked_reference_matches_fused_bitwise(monkeypatch, name):
    """The batch-chunked reference sums each row's gradient over every
    chunk in flat order and steps the row once, so it stays bitwise
    equal to the fused kernel (which sums the same lookups in the same
    order): a split_sgd row is rounded once per step, not once per
    chunk."""
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import sharded_embedding as se
    from repro.core.embedding import EmbeddingSpec
    from repro.launch.mesh import make_mesh

    layout = se.make_layout(EmbeddingSpec((40, 30), 8), 1, "row")
    opt = row.get(name)
    rng = np.random.default_rng(3)
    W = jnp.asarray(rng.standard_normal((layout.total_rows, 8)),
                    jnp.float32)
    idx = jnp.asarray(rng.integers(0, 5, (8, 2, 3)), jnp.int32)
    dY = jnp.asarray(rng.standard_normal((8, 2, 8)), jnp.float32)
    store = opt.init_store(W)
    mesh = make_mesh((1, 1), ("data", "model"))
    axes = ("data", "model")

    def run(fused):
        def f(st, idxj, dYj):
            return se.apply_update(layout, st, opt, idxj, dYj, 0.05, axes,
                                   fused=fused)
        sm = jax.jit(compat.shard_map(
            f, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(axes, None), store),
                      P(None, None, None), P(None, None, None)),
            out_specs=jax.tree.map(lambda _: P(axes, None), store),
            check_vma=False))
        return {k: np.asarray(v) for k, v in sm(store, idx, dY).items()}

    fused = run(True)
    # per-row bytes = S*P*E*4 = 192; a 200-byte budget forces 8 chunks
    monkeypatch.setenv("REPRO_EMB_CHUNK_BUDGET", "200")
    chunked = run(False)
    for k in store:
        np.testing.assert_array_equal(chunked[k].view(np.uint8),
                                      fused[k].view(np.uint8), err_msg=k)


# ---------------------------------------------------------------------------
# Checkpoint round-trip + elastic reshard (per optimizer)
# ---------------------------------------------------------------------------

def _small_cfg(optimizer):
    from repro.core.dlrm import DLRMConfig
    return DLRMConfig(name="t", num_dense=8, bottom=(16, 8), top=(16,),
                      table_rows=(50, 30, 20, 10), emb_dim=8, pooling=3,
                      batch=16, sparse_optimizer=optimizer)


def _small_batch(seed):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.integers(0, max(2, m // 6), (16, 3))
                    for m in (50, 30, 20, 10)], 1).astype(np.int32)
    return {"idx": jnp.asarray(idx),
            "dense_x": jnp.asarray(rng.standard_normal((16, 8)),
                                   jnp.bfloat16),
            "labels": jnp.asarray(rng.integers(0, 2, (16,)), jnp.float32)}


@pytest.mark.parametrize("optimizer", ["sgd", "split_sgd", "momentum",
                                       "adagrad_rowwise", "adagrad"])
def test_checkpoint_roundtrip_resume_bit_identity(optimizer, tmp_path):
    """Save at step 2 / restore / resume == uninterrupted 3-step run,
    bitwise, for every registered optimizer — per-row state slabs persist
    and restore next to the weights (satellite: checkpoint/manager.py)."""
    from repro.checkpoint import CheckpointManager
    from repro.core import dlrm as D
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = _small_cfg(optimizer)
    step, shardings, _, _ = D.make_train_step(cfg, mesh)

    state, _ = D.init_state(jax.random.PRNGKey(0), cfg, mesh)
    mgr = CheckpointManager(tmp_path, keep=2)
    for i in range(2):
        state, _ = step(state, _small_batch(i))
    mgr.save(2, state, blocking=True)
    state, _ = step(state, _small_batch(2))
    want = {k: np.asarray(v) for k, v in state["emb"].items()}

    # restore into the struct tree and resume
    structs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    got_step, restored = mgr.restore(structs, shardings=shardings)
    assert got_step == 2
    opt = row.resolve(cfg)
    assert set(restored["emb"]) == set(opt.weight_keys) | set(opt.state_keys)
    restored, _ = step(restored, _small_batch(2))
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(restored["emb"][k]), v), k


def test_reshard_store_preserves_every_slab():
    """reshard_store relays weights AND optimizer-state slabs across a
    shard-count change (elastic restart) table-for-table."""
    from repro.checkpoint.manager import reshard_store
    from repro.core import sharded_embedding as se
    from repro.core.embedding import EmbeddingSpec
    spec = EmbeddingSpec((100, 30, 70, 20), dim=4)
    old = se.make_layout(spec, 4, "row")
    new = se.make_layout(spec, 8, "row")
    rng = np.random.default_rng(0)
    opt = row.get("adagrad_rowwise")
    W = jnp.asarray(rng.standard_normal((old.total_rows, 4)), jnp.float32)
    store = opt.init_store(W)
    store["acc"] = jnp.asarray(
        rng.standard_normal((old.total_rows, 1)) ** 2, jnp.float32)
    out = reshard_store(old, new, store)
    assert set(out) == set(store)
    for t, rows_t in enumerate(spec.table_rows):
        src = int(spec.row_offsets[t])
        for k in store:
            np.testing.assert_array_equal(
                np.asarray(out[k])[src:src + rows_t],
                np.asarray(store[k])[src:src + rows_t])


# ---------------------------------------------------------------------------
# Acceptance (subprocess, 8 devices): all five optimizers through the
# pipelined step, M in {1, 2}, host_presort on and off, row + table mode
# ---------------------------------------------------------------------------

def run_sub(code: str, timeout=1200):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_all_optimizers_through_pipeline():
    """Every registered optimizer x M in {1, 2} x host_presort on/off runs
    the pipelined hybrid step: finite loss, weights move, state slabs
    move, embedding store BIT-IDENTICAL across M, and the host-pre-sorted
    stream bitwise matches the fused device-sort path."""
    out = run_sub("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import compat
    from repro.core.dlrm import DLRMConfig, make_train_step, init_state
    from repro.data.pipeline import presort_batch
    from repro.optim import row

    mesh = compat.make_mesh((2, 4), ('data', 'model'))
    TABLES = (100, 60, 40, 30)
    BASE = DLRMConfig(name='t', num_dense=8, bottom=(16, 8), top=(16,),
                      table_rows=TABLES, emb_dim=8, pooling=3, batch=16)
    rng = np.random.default_rng(0)
    idx = np.stack([rng.integers(0, max(2, m // 8), (16, 3))
                    for m in TABLES], 1).astype(np.int32)
    base_batch = {'idx': jnp.asarray(idx),
                  'dense_x': jnp.asarray(rng.standard_normal((16, 8)),
                                         jnp.bfloat16),
                  'labels': jnp.asarray(rng.integers(0, 2, 16),
                                        jnp.float32)}

    def emb_np(state):
        return {k: np.asarray(v, np.float32) if v.dtype == jnp.bfloat16
                else np.asarray(v) for k, v in state['emb'].items()}

    for name in row.names():
        opt = row.get(name)
        res = {}
        for presort in (False, True):
            for M in (1, 2):
                cfg = dataclasses.replace(
                    BASE, sparse_optimizer=name, microbatches=M,
                    host_presort=presort,
                    # presort always runs the fused kernel; run the
                    # device-sort path fused too so the two are the SAME
                    # kernel on host- vs device-sorted streams (stable
                    # sorts agree => bitwise).  The reference path's
                    # parity with the kernel is unit-tested in
                    # test_row_optim / test_embedding_update.
                    fused_update=True)
                state, layout = init_state(jax.random.PRNGKey(0), cfg,
                                           mesh)
                init = emb_np(state)
                step, _, _, _ = make_train_step(cfg, mesh)
                batch = dict(base_batch)
                if presort:
                    batch.update({k: jnp.asarray(v) for k, v in
                                  presort_batch(layout, idx).items()})
                state, loss = step(state, batch)
                emb1 = emb_np(state)
                state, loss2 = step(state, batch)
                assert np.isfinite(float(loss2)), (name, M, presort)
                got = emb_np(state)
                wk = 'hi' if opt.split else 'w'
                assert not np.array_equal(got[wk], init[wk]), \\
                    (name, M, presort, 'weights did not move')
                for k in opt.state_keys:
                    assert not np.array_equal(got[k], init[k]), \\
                        (name, M, presort, k, 'state did not move')
                res[(presort, M)] = (float(loss), emb1, got)
        for presort in (False, True):
            a, b = res[(presort, 1)], res[(presort, 2)]
            # loss sums per-microbatch partial sums (reassociation), and
            # the ACCUMULATED DENSE grad reassociates too — so the
            # bitwise M-identity contract covers the embedding store
            # after the FIRST step (step 2 sees M-dependent dense nets)
            assert abs(a[0] - b[0]) < 1e-4, (name, presort,
                                             'loss across M')
            for k in a[1]:
                assert np.array_equal(a[1][k], b[1][k]), \\
                    (name, presort, k, 'M-identity')
        # host presort (fused kernel, host-sorted) == device sort (same
        # kernel, device-sorted): stable sorts agree => bitwise, over
        # the full 2-step trajectory
        for M in (1, 2):
            a, b = res[(False, M)], res[(True, M)]
            assert a[0] == b[0], (name, M, 'loss presort vs device')
            for emb_a, emb_b in ((a[1], b[1]), (a[2], b[2])):
                for k in emb_a:
                    assert np.array_equal(emb_a[k], emb_b[k]), \\
                        (name, M, k, 'presort parity')
        print(name, 'ROW_OK')

    # TABLE mode: padded-slot permute folded into the host sort.  The
    # device-sort side runs the same kernel on the device-sorted stream,
    # so parity is BITWISE; split_sgd also matches the reference path
    # (fused_update=False) bitwise, by contract.
    for name in ('split_sgd', 'adagrad_rowwise'):
        opt = row.get(name)
        res = {}
        for presort, fused in ((False, True), (True, True), (False, False)):
            cfg = dataclasses.replace(
                BASE, sparse_optimizer=name, emb_mode='table',
                idx_input='sharded', host_presort=presort,
                fused_update=fused)
            state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
            step, _, _, _ = make_train_step(cfg, mesh)
            batch = dict(base_batch)
            if presort:
                batch.update({k: jnp.asarray(v) for k, v in
                              presort_batch(layout, idx).items()})
            for _ in range(2):
                state, loss = step(state, batch)
            res[(presort, fused)] = (float(loss), emb_np(state))
        pairs = [((False, True), (True, True))]
        if name == 'split_sgd':
            pairs.append(((False, True), (False, False)))
        for a, b in pairs:
            assert res[a][0] == res[b][0], (name, a, b, 'table loss')
            for k in res[a][1]:
                assert np.array_equal(res[a][1][k], res[b][1][k]), \\
                    (name, k, a, b, 'table parity')
        print(name, 'TABLE_OK')
    """)
    assert out.count("ROW_OK") == 8
    assert out.count("TABLE_OK") == 2
