"""Streaming ingestion subsystem (repro/data/{format,reader,pipeline}.py).

Contracts under test:
* Packed round-trip: synthetic stream -> shards -> ShardedReader yields
  the ORIGINAL batches bit-for-bit (idx/dense/labels/weights).
* Reader determinism: the global epoch order is rank-count-invariant
  (concat of rank slices == the single-reader stream), seeded (same seed
  => same order, different epoch/seed => different), and — with an
  explicit shuffle window — invariant to how the dataset was re-sharded
  on disk.
* Host pre-sort == device sort_lookups, bitwise, per shard.
* THE round-trip property (acceptance): synthetic stream -> packed
  shards -> ShardedReader -> pipelined train step with the host
  pre-sorted index path is BIT-IDENTICAL (Split-SGD embedding state and
  loss) to training directly on the in-process stream, for M in {1, 2}
  microbatches.  The non-split fp32 path matches to tolerance (the
  documented fused-kernel pre-reduction vs reference scatter-add gap).
* HostPipeline worker failures poison the queue and re-raise promptly.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import sharded_embedding as se
from repro.core.embedding import EmbeddingSpec
from repro.data.format import (DatasetSpec, ShardWriter, load_manifest,
                               write_shards)
from repro.data.pipeline import HostPipeline, presort_batch
from repro.data.reader import ShardedReader
from repro.data.synthetic import SparseBatchSpec, sparse_batch

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TABLES = (100, 60, 40, 30, 20, 200, 51, 77)


def _stream(seed, batch=32, weighted=False, alpha=0.6):
    rng = np.random.default_rng(seed)
    spec = SparseBatchSpec(TABLES, None, 3, batch, num_dense=16, alpha=alpha)
    while True:
        b = sparse_batch(rng, spec)
        if weighted:
            b["weights"] = rng.uniform(0.5, 1.5, b["idx"].shape).astype(
                np.float32)
        yield b


def _pack(tmp_path, n=192, per_shard=40, weighted=False, seed=0):
    out = str(tmp_path / f"ds{'w' if weighted else ''}{n}_{per_shard}")
    spec = DatasetSpec(table_rows=TABLES, pooling=3, num_dense=16,
                       weighted=weighted)
    write_shards(_stream(seed, weighted=weighted), out, spec, n,
                 samples_per_shard=per_shard)
    return out


# ---------------------------------------------------------------------------
# Format + reader
# ---------------------------------------------------------------------------

def test_packed_round_trip_bitwise(tmp_path):
    d = _pack(tmp_path, n=192, per_shard=40)   # batches cross shard edges
    ref = _stream(0)
    got = 0
    for mine, orig in zip(ShardedReader(d, batch=32, shuffle=False)
                          .batches(epochs=1), ref):
        for k in ("idx", "dense_x", "labels"):
            assert np.array_equal(mine[k], orig[k]), k
        got += 1
    assert got == 192 // 32


def test_weighted_round_trip_bitwise(tmp_path):
    d = _pack(tmp_path, n=96, per_shard=48, weighted=True)
    ref = _stream(0, weighted=True)
    for mine, orig in zip(ShardedReader(d, batch=32, shuffle=False)
                          .batches(epochs=1), ref):
        assert np.array_equal(mine["weights"], orig["weights"])
        assert np.array_equal(mine["idx"], orig["idx"])


def test_manifest_and_spec_check(tmp_path):
    d = _pack(tmp_path, n=64, per_shard=64)
    spec, manifest = load_manifest(d)
    assert spec.table_rows == TABLES and spec.pooling == 3
    assert manifest["num_samples"] == 64
    spec.check(TABLES, 3, num_dense=16)              # compatible
    with pytest.raises(ValueError, match="pooling"):
        spec.check(TABLES, 5, num_dense=16)
    with pytest.raises(ValueError, match="table_rows"):
        spec.check((10,) * 8, 3, num_dense=16)
    with pytest.raises(ValueError, match="weights"):
        spec.check(TABLES, 3, num_dense=16, weighted=True)


def test_writer_rejects_bad_batches(tmp_path):
    w = ShardWriter(str(tmp_path / "bad"), DatasetSpec(TABLES, 3), 16)
    with pytest.raises(ValueError, match="does not match spec"):
        w.append_batch({"idx": np.zeros((4, 2, 3), np.int32),
                        "labels": np.zeros(4, np.float32)})
    with pytest.raises(ValueError, match="out of range"):
        bad = np.zeros((4, 8, 3), np.int32)
        bad[0, 0, 0] = 1_000_000
        w.append_batch({"idx": bad, "labels": np.zeros(4, np.float32)})


def test_reader_rank_invariance(tmp_path):
    """Same seed => identical GLOBAL epoch order across rank counts."""
    d = _pack(tmp_path, n=192, per_shard=40)
    whole = list(ShardedReader(d, batch=48, shuffle=True, seed=3)
                 .batches(epochs=2))
    for R in (2, 4):
        parts = [list(ShardedReader(d, batch=48, shuffle=True, seed=3,
                                    rank=r, num_ranks=R).batches(epochs=2))
                 for r in range(R)]
        for i, ref in enumerate(whole):
            cat = {k: np.concatenate([parts[r][i][k] for r in range(R)])
                   for k in ref}
            for k in ref:
                assert np.array_equal(cat[k], ref[k]), (R, i, k)


def test_reader_reshard_invariance(tmp_path):
    """Identical batch contents no matter how the dataset was sharded on
    disk — sequential always; shuffled with an explicit window."""
    d_small = _pack(tmp_path, n=192, per_shard=24)
    d_large = _pack(tmp_path, n=192, per_shard=96)
    for kw in (dict(shuffle=False), dict(shuffle=True, window=48, seed=5)):
        a = list(ShardedReader(d_small, batch=32, **kw).batches(epochs=1))
        b = list(ShardedReader(d_large, batch=32, **kw).batches(epochs=1))
        for x, y in zip(a, b):
            for k in x:
                assert np.array_equal(x[k], y[k]), (kw, k)


def test_reader_shuffle_seeded_and_epoch_varies(tmp_path):
    d = _pack(tmp_path, n=128, per_shard=32)
    r = ShardedReader(d, batch=32, shuffle=True, seed=1)
    o0, o0b = r.epoch_order(0), r.epoch_order(0)
    assert np.array_equal(o0, o0b)                     # deterministic
    assert sorted(o0.tolist()) == list(range(128))     # a permutation
    assert not np.array_equal(o0, r.epoch_order(1))    # epoch decorrelates
    r2 = ShardedReader(d, batch=32, shuffle=True, seed=2)
    assert not np.array_equal(o0, r2.epoch_order(0))   # seed decorrelates
    # two-level structure: with window == samples_per_shard, every window
    # stays contiguous in id space (shard permutation + intra-shard)
    win = o0.reshape(-1, 32)
    assert sorted(set(w.min() // 32 for w in win)) == [0, 1, 2, 3]
    for w in win:
        assert w.max() - w.min() < 32


def test_reader_validation(tmp_path):
    d = _pack(tmp_path, n=64, per_shard=32)
    with pytest.raises(ValueError, match="divisible"):
        ShardedReader(d, batch=30, num_ranks=4)
    with pytest.raises(ValueError, match="rank"):
        ShardedReader(d, batch=32, rank=4, num_ranks=4)
    with pytest.raises(FileNotFoundError):
        ShardedReader(str(tmp_path / "nope"), batch=8)


# ---------------------------------------------------------------------------
# Host pipeline
# ---------------------------------------------------------------------------

def _layout(ns=4):
    return se.make_layout(EmbeddingSpec(TABLES, 8), ns, "row")


def test_presort_matches_device_sort_lookups():
    """Host presort_batch == kernels.embedding_update.sort_lookups, bitwise
    per shard (stable-sort permutations are unique)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.kernels.embedding_update import sort_lookups
    layout = _layout(4)
    rng = np.random.default_rng(0)
    idx = np.stack([rng.integers(0, m, (16, 3)) for m in TABLES],
                   1).astype(np.int32)
    wgt = rng.uniform(0.5, 1.5, idx.shape).astype(np.float32)
    ps = presort_batch(layout, idx, wgt)
    g = idx + np.asarray(layout.row_offsets, np.int32)[None, :, None]
    R = layout.rows_per_shard
    for s in range(4):
        local = jnp.asarray((g - np.int32(s * R)).reshape(-1))
        sr, sb, sm, sw = sort_lookups(local, None, R, 3,
                                      jnp.asarray(wgt.reshape(-1)))
        assert np.array_equal(np.asarray(sr), ps["psort_rows"][s])
        assert np.array_equal(np.asarray(sb), ps["psort_bags"][s])
        assert np.array_equal(np.asarray(sm), ps["psort_msk"][s])
        assert np.array_equal(np.asarray(sw), ps["psort_wgt"][s])


def test_presort_table_mode_folds_padded_permute():
    """Table-mode host pre-sort (ROADMAP leftover): presort_batch folds
    the padded-slot permute in and matches the device-side
    permute_indices + sort_lookups stream, bitwise, per model shard."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.kernels.embedding_update import sort_lookups
    layout = se.make_layout(EmbeddingSpec(TABLES, 8), 4, "table")
    rng = np.random.default_rng(1)
    idx = np.stack([rng.integers(0, m, (16, 3)) for m in TABLES],
                   1).astype(np.int32)
    wgt = rng.uniform(0.5, 1.5, idx.shape).astype(np.float32)
    ps = presort_batch(layout, idx, wgt)
    K, R = layout.slots_per_shard, layout.rows_per_shard
    assert ps["psort_rows"].shape == (4, 16 * K * 3)
    # device side: permute to padded order (dummy slots -> idx 0 / wgt 0),
    # slice this shard's slots, add the slot offsets, sort
    padded = np.asarray(se.permute_indices(layout, jnp.asarray(idx)))
    wp = wgt[:, np.where(layout.padded_slots >= 0, layout.padded_slots, 0)]
    wp[:, layout.padded_slots < 0] = 0.0
    off = np.asarray(layout.slot_local_offsets, np.int32).reshape(4, K)
    for s in range(4):
        local = (padded[:, s * K:(s + 1) * K] + off[s][None, :, None])
        sr, sb, sm, sw = sort_lookups(
            jnp.asarray(local.reshape(-1)), None, R, 3,
            jnp.asarray(wp[:, s * K:(s + 1) * K].reshape(-1)))
        assert np.array_equal(np.asarray(sr), ps["psort_rows"][s])
        assert np.array_equal(np.asarray(sb), ps["psort_bags"][s])
        assert np.array_equal(np.asarray(sm), ps["psort_msk"][s])
        assert np.array_equal(np.asarray(sw), ps["psort_wgt"][s])


def test_presort_rejects_unknown_mode():
    import dataclasses as dc
    layout = dc.replace(_layout(4), mode="diagonal")
    with pytest.raises(ValueError, match="mode"):
        presort_batch(layout, np.zeros((4, 8, 3), np.int32))


def test_hostpipeline_attaches_psort_and_preserves_stream(tmp_path):
    d = _pack(tmp_path, n=96, per_shard=48)
    layout = _layout(4)
    plain = list(ShardedReader(d, batch=32, shuffle=False).batches(epochs=1))
    hp = HostPipeline(ShardedReader(d, batch=32, shuffle=False)
                      .batches(epochs=1), layout=layout, presort=True)
    piped = list(hp)
    assert len(piped) == len(plain)
    L = 32 * 8 * 3
    for a, b in zip(piped, plain):
        for k in b:
            assert np.array_equal(a[k], b[k]), k
        for k in ("psort_rows", "psort_bags", "psort_msk", "psort_wgt"):
            assert a[k].shape == (4, L)
        ref = presort_batch(layout, b["idx"])
        assert np.array_equal(a["psort_rows"], ref["psort_rows"])
    assert hp.stats["batches"] == len(plain)


def test_hostpipeline_stats_pinned_after_drain(tmp_path):
    """Full ``stats`` contract after a clean drain — the train-loop
    heartbeat serializes this dict verbatim, so keys and values are
    pinned: every batch counted, no retries, worker prep time observed."""
    d = _pack(tmp_path, n=96, per_shard=48)
    hp = HostPipeline(ShardedReader(d, batch=32, shuffle=False)
                      .batches(epochs=1))
    n = sum(1 for _ in hp)
    st = hp.stats
    assert set(st) == {"prep_s", "wait_s", "batches", "retries"}
    assert n == 3 and st["batches"] == 3
    assert st["retries"] == 0
    assert st["prep_s"] > 0.0 and st["wait_s"] >= 0.0


def test_hostpipeline_poisons_on_worker_failure():
    def bad():
        yield {"idx": np.zeros((2, 8, 3), np.int32)}
        raise OSError("shard vanished")

    hp = HostPipeline(bad())
    next(hp)
    with pytest.raises(OSError, match="shard vanished"):
        next(hp)


def test_chained_pipeline_prefetch_close_does_not_strand(tmp_path):
    """launch/train.py chains HostPipeline -> prefetch_to_device and closes
    the INNER pipeline first; the outer worker must observe the sticky
    end-of-stream sentinel and finish instead of blocking forever."""
    import threading
    pytest.importorskip("jax")
    from repro.train import prefetch_to_device
    d = _pack(tmp_path, n=64, per_shard=32)
    hp = HostPipeline(ShardedReader(d, batch=32, shuffle=False))  # infinite
    it = prefetch_to_device(hp, size=2)
    next(it)
    hp.close()

    done = threading.Event()

    def drain():
        for _ in it:        # must terminate via the sticky _DONE
            pass
        done.set()

    t = threading.Thread(target=drain, daemon=True)
    t.start()
    assert done.wait(timeout=10.0), "outer prefetch worker stranded"
    it.close()


def test_hostpipeline_validation_and_close(tmp_path):
    with pytest.raises(ValueError, match="layout"):
        HostPipeline(iter(()), presort=True)
    with pytest.raises(ValueError, match="depth"):
        HostPipeline(iter(()), depth=0)
    d = _pack(tmp_path, n=64, per_shard=32)
    hp = HostPipeline(ShardedReader(d, batch=32, shuffle=False))  # infinite
    next(hp)
    hp.close()                                          # no hang


def test_batch_struct_from_spec(tmp_path):
    jax = pytest.importorskip("jax")
    from repro.core import dlrm as D, hybrid as H
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = D.DLRMConfig(name="t", num_dense=16, bottom=(16, 8), top=(16,),
                       table_rows=TABLES, emb_dim=8, pooling=3, batch=16)
    mdef = D.as_hybrid_def(cfg)
    layout = H.make_layout(mdef, mesh)
    spec, _ = load_manifest(_pack(tmp_path, n=32, per_shard=32))
    structs, specs = H.batch_struct_from_spec(mdef, mesh, layout, spec)
    assert structs["idx"].shape == (16, 8, 3)
    bad = DatasetSpec(table_rows=TABLES, pooling=5, num_dense=16)
    with pytest.raises(ValueError, match="pooling"):
        H.batch_struct_from_spec(mdef, mesh, layout, bad)
    wspec = DatasetSpec(table_rows=TABLES, pooling=3, num_dense=16,
                        weighted=True)
    with pytest.raises(ValueError, match="weighted"):
        H.batch_struct_from_spec(mdef, mesh, layout, wspec)
    # extras the format cannot carry are rejected at wiring time, not as
    # a pytree mismatch inside shard_map
    from repro.models import recsys as R
    sas = R.make_sasrec(64, batch=16)
    with pytest.raises(ValueError, match="seq_mask"):
        spec.check_model(sas)


# ---------------------------------------------------------------------------
# THE round-trip property (acceptance criterion) — 8-device subprocess
# ---------------------------------------------------------------------------

def run_sub(code: str, timeout=900):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_packed_presorted_train_round_trip(tmp_path):
    """Acceptance: synthetic stream -> packed shards -> ShardedReader ->
    pipelined train step with host pre-sort is bit-identical (Split-SGD
    state + loss) to training directly on the in-process stream, for
    M in {1, 2}; the non-split fp32 path matches to tolerance."""
    pytest.importorskip("jax")
    out = run_sub(f"""
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    import sys; sys.path.insert(0, {os.path.dirname(__file__)!r})
    from test_ingest import TABLES, _pack, _stream
    from pathlib import Path
    from repro import compat
    from repro.core.dlrm import DLRMConfig, make_train_step, init_state
    from repro.data.pipeline import HostPipeline
    from repro.data.reader import ShardedReader

    tmp = Path({str(tmp_path)!r})
    mesh = compat.make_mesh((2, 4), ('data', 'model'))
    BASE = DLRMConfig(name='t', num_dense=16, bottom=(32, 8), top=(32,),
                      table_rows=TABLES, emb_dim=8, pooling=3, batch=32)
    d = _pack(tmp, n=96, per_shard=40)   # 3 steps, batches cross shards

    def emb_np(state):
        return tuple(np.asarray(v) for v in state['emb'].values())

    for split in (True, False):
        for M in (1, 2):
            res = {{}}
            for tag in ('inproc', 'packed'):
                cfg = dataclasses.replace(
                    BASE, emb_mode='row',
                    sparse_optimizer='split_sgd' if split else 'sgd',
                    microbatches=M, host_presort=(tag == 'packed'))
                state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
                step, _, _, _ = make_train_step(cfg, mesh)
                if tag == 'packed':
                    stream = HostPipeline(
                        ShardedReader(d, batch=32, shuffle=False)
                        .batches(epochs=1), layout=layout, presort=True)
                else:
                    stream = _stream(0)
                for _ in range(3):
                    b = {{k: jnp.asarray(v) for k, v in next(stream).items()}}
                    state, loss = step(state, b)
                res[tag] = (float(loss), emb_np(state))
            if split:
                assert res['inproc'][0] == res['packed'][0], ('loss', M)
                for a, b in zip(res['inproc'][1], res['packed'][1]):
                    assert np.array_equal(a, b), ('emb', M)
                print(f'split M={{M}} BITWISE_OK')
            else:
                # fp32 non-split: presorted path always uses the fused
                # kernel (per-row pre-reduction); the reference scatter-add
                # differs by documented rounding only
                assert abs(res['inproc'][0] - res['packed'][0]) < 1e-5
                for a, b in zip(res['inproc'][1], res['packed'][1]):
                    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
                print(f'fp32 M={{M}} CLOSE_OK')
    """)
    assert out.count("BITWISE_OK") == 2
    assert out.count("CLOSE_OK") == 2
