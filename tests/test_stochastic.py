"""Compressed optimizer state: bf16-hi + seeded stochastic rounding
(repro/optim/stochastic.py, the ``*_bf16`` RowOptimizers) and the
register-only optimizer flow (the PR-5 registration refactor).

Contracts under test:
* The dither helpers: pure function of (seed, row, lane) — bitwise
  reproducible, seed-sensitive — and UNBIASED: the mean rounding error
  over many seeds vanishes where plain truncation biases toward zero.
* Seeded determinism: for one per-step seed the reference scan, the
  fused device-sorted kernel and the host-pre-sorted stream produce
  BITWISE-identical stores (weights AND compressed state) over a
  multi-step trajectory; changing the seed changes the stored state.
* Trajectory: ``momentum_bf16`` stays within a pinned tolerance of fp32
  ``momentum`` over 50 steps on a zipf lookup stream.
* Register-only flow: a toy optimizer registered HERE (one row step,
  ``register()`` only) runs the pipelined train step end-to-end with
  zero edits to ``kernels/ops.py``, ``core/sharded_embedding.py`` or
  ``core/pipeline.py`` — and a source scan proves those modules carry no
  per-optimizer dispatch to edit.
"""

import ast
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.optim import row
from repro.optim.stochastic import mix32, sr_noise, sr_round_bf16

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
BF16_OPTS = ("momentum_bf16", "adagrad_bf16")


def _mk(M=60, E=16, B=8, S=2, P=3, vocab=None, seed=0):
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.standard_normal((M, E)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, vocab or M, (B, S, P)), jnp.int32)
    dY = jnp.asarray(rng.standard_normal((B, S, E)), jnp.float32)
    return W, idx, dY


def _np_store(store):
    return {k: np.asarray(v, np.float32) if v.dtype == jnp.bfloat16
            else np.asarray(v) for k, v in store.items()}


# ---------------------------------------------------------------------------
# The dither helpers
# ---------------------------------------------------------------------------

def test_noise_is_pure_counter_function():
    """Same (seed, rows, width) => identical bits; any counter change =>
    different stream (the property that makes the three update paths
    agree without sharing sampler state)."""
    rows = jnp.asarray([0, 3, 3, 17], jnp.int32)
    a = np.asarray(sr_noise(7, rows, 8))
    assert a.shape == (4, 8) and a.dtype == np.uint32
    np.testing.assert_array_equal(a, np.asarray(sr_noise(7, rows, 8)))
    assert not np.array_equal(a, np.asarray(sr_noise(8, rows, 8)))
    # duplicate row ids draw duplicate noise (row identity, not position)
    np.testing.assert_array_equal(a[1], a[2])
    assert not np.array_equal(a[0], a[1])
    # lanes decorrelated: 3 distinct rows x 8 lanes = 24 distinct words
    assert len(np.unique(a)) == 24
    assert mix32(jnp.uint32(0)).dtype == jnp.uint32


def test_stochastic_round_unbiased_and_bounded():
    """Mean rounding error over many seeds ~ 0 (well under the one-ulp
    truncation bias); every draw lands on one of the two bf16 neighbours."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64,)) * 10.0 ** rng.integers(
        -3, 4, (64,)), jnp.float32)
    rows = jnp.zeros((), jnp.int32)   # one row id, 64 lanes
    bits = np.asarray(x).view(np.uint32)
    lo32 = ((bits >> 16) << 16).view(np.float32)        # truncation
    hi32 = (((bits >> 16) + 1) << 16).view(np.float32)  # next bf16 outward
    ulp = np.abs(hi32.astype(np.float64) - lo32.astype(np.float64))
    n_seeds = 400
    acc = np.zeros(64, np.float64)
    for s in range(n_seeds):
        rf = np.asarray(sr_round_bf16(x, sr_noise(s, rows, 64)), np.float32)
        # each draw is one of the two neighbours
        assert np.all((rf == lo32) | (rf == hi32))
        acc += rf
    mean_err = np.abs(acc / n_seeds - np.asarray(x, np.float64))
    # statistical bound: std <= 0.5*ulp/sqrt(N) ~ 0.025 ulp; 0.2 is ~8 sigma
    assert np.max(mean_err / ulp) < 0.2
    # plain truncation is biased by the dropped mantissa half (sanity:
    # SR beats it by an order of magnitude on average)
    trunc_err = np.abs(lo32.astype(np.float64) - np.asarray(x, np.float64))
    assert np.mean(mean_err) < 0.1 * np.mean(trunc_err)


def test_exact_bf16_values_round_trip_unchanged():
    """A value already representable in bf16 has zero discarded bits: every
    seed must store it EXACTLY (dither < 1 shifts nothing)."""
    x = jnp.asarray([1.0, -2.5, 0.0, 384.0], jnp.float32)
    for s in (0, 1, 12345):
        out = np.asarray(sr_round_bf16(x, sr_noise(s, jnp.zeros((), jnp.int32),
                                                   4)), np.float32)
        np.testing.assert_array_equal(out, np.asarray(x))


# ---------------------------------------------------------------------------
# Seeded determinism across the three update paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", BF16_OPTS)
def test_three_paths_bitwise_identical_per_seed(name):
    """reference scan == fused device-sort == host-pre-sorted, BITWISE
    (weights and compressed state), over a 3-step duplicate-heavy
    trajectory with per-step seeds — and rerunning with the same seeds
    reproduces the bits."""
    from repro.kernels.embedding_update import sort_lookups
    M, E, P = 60, 16, 3
    W, idx, dY = _mk(M=M, E=E, P=P, vocab=7, seed=1)
    opt = row.get(name)
    st0 = opt.init_store(W)
    ref = jax.jit(lambda s, i, d, sd: opt.apply_sparse(
        s, row.SparseStream(idx=i, dY=d), 0.05, seed=sd, fused=False))
    sort = jax.jit(lambda t: sort_lookups(t, None, M, P))

    def run(mode):
        st = dict(st0)
        for i in range(3):
            d = dY * (i + 1)
            if mode == "ref":
                st = ref(st, idx, d, i)
            elif mode == "fused":
                st = opt.apply_sparse(st, row.SparseStream(idx=idx, dY=d),
                                      0.05, seed=i, fused=True,
                                      interpret=True)
            else:
                st = opt.apply_sparse(
                    st, row.SparseStream(presort=sort(idx.reshape(-1)),
                                         dY=d.reshape(-1, E)),
                    0.05, seed=i, interpret=True)
        return _np_store(st)

    a, b, c = run("ref"), run("fused"), run("presort")
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{k} ref/fused")
        np.testing.assert_array_equal(b[k], c[k],
                                      err_msg=f"{k} fused/presort")
    b2 = run("fused")
    for k in b:
        np.testing.assert_array_equal(b[k], b2[k], err_msg=f"{k} rerun")


@pytest.mark.parametrize("name", BF16_OPTS)
def test_seed_changes_stored_state(name):
    """Different per-step seeds => different stored state bits (the dither
    actually reaches the slab); the fp32 weight slab is seed-independent
    on the FIRST step (state decoded from zeros, rounding only affects
    what the next step sees)."""
    W, idx, dY = _mk(vocab=7, seed=2)
    opt = row.get(name)
    st0 = opt.init_store(W)
    stream = row.SparseStream(idx=idx, dY=dY)
    s1 = opt.apply_sparse(dict(st0), stream, 0.05, seed=0, fused=True,
                          interpret=True)
    s2 = opt.apply_sparse(dict(st0), stream, 0.05, seed=123, fused=True,
                          interpret=True)
    (k,) = opt.state_keys
    assert not np.array_equal(np.asarray(s1[k], np.float32),
                              np.asarray(s2[k], np.float32))
    np.testing.assert_array_equal(np.asarray(s1["w"]), np.asarray(s2["w"]))


def test_masked_runs_never_touch_compressed_state():
    """All-masked streams are exact no-ops on weights AND bf16 state, both
    paths (the SMEM liveness flag / reference drop both apply before any
    rounding)."""
    W, idx, dY = _mk(vocab=6, seed=3)
    opt = row.get("momentum_bf16")
    st = dict(opt.init_store(W))
    st["mom"] = jnp.full_like(st["mom"], jnp.bfloat16(0.5))
    masked = row.SparseStream(idx=idx, dY=dY,
                              valid=jnp.zeros(idx.shape, bool))
    for out in (opt.apply_sparse(st, masked, 0.05, seed=9, fused=True,
                                 interpret=True),
                jax.jit(lambda s, t: opt.apply_sparse(s, t, 0.05, seed=9,
                                                      fused=False))(st,
                                                                    masked)):
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(W))
        np.testing.assert_array_equal(
            np.asarray(out["mom"], np.float32),
            np.asarray(st["mom"], np.float32))


# ---------------------------------------------------------------------------
# Trajectory: compressed momentum tracks fp32 momentum
# ---------------------------------------------------------------------------

def test_momentum_bf16_tracks_fp32_over_50_zipf_steps():
    """50 steps on a zipf stream: the compressed-state trajectory stays
    within a PINNED tolerance of the fp32 momentum trajectory — the
    unbiased dither accumulates as a random walk, not a drift.  The pin
    (2% of the total weight displacement, max-norm) has ~4x headroom
    over the observed value; loosening it is a regression."""
    from repro.data.synthetic import zipf_indices
    rng = np.random.default_rng(0)
    M, E, B, S, P = 2000, 32, 64, 1, 4
    W = jnp.asarray(rng.standard_normal((M, E)) * 0.1, jnp.float32)
    fp = row.get("momentum")
    bf = row.get("momentum_bf16")
    assert fp.beta == bf.beta
    st_fp = fp.init_store(W)
    st_bf = bf.init_store(W)
    step_fp = jax.jit(lambda s, i, d: fp.apply_sparse(
        s, row.SparseStream(idx=i, dY=d), 0.05, fused=False))
    step_bf = jax.jit(lambda s, i, d, sd: bf.apply_sparse(
        s, row.SparseStream(idx=i, dY=d), 0.05, seed=sd, fused=False))
    for t in range(50):
        idx = jnp.asarray(zipf_indices(rng, M, (B, S, P), 1.1).astype(
            np.int32))
        dY = jnp.asarray(rng.standard_normal((B, S, E)), jnp.float32)
        st_fp = step_fp(st_fp, idx, dY)
        st_bf = step_bf(st_bf, idx, dY, t)
    w_fp = np.asarray(st_fp["w"], np.float64)
    w_bf = np.asarray(st_bf["w"], np.float64)
    move = np.max(np.abs(w_fp - np.asarray(W, np.float64)))
    drift = np.max(np.abs(w_bf - w_fp))
    assert move > 0.1          # the stream actually trained something
    assert drift < 0.02 * move, (drift, move)


# ---------------------------------------------------------------------------
# Register-only optimizer flow + source scan
# ---------------------------------------------------------------------------

def _toy_step(opt, blocks, g, lr, seed, rows):
    """Toy 'touch-count LR' rule: per touched row ``tc += 1``,
    ``w -= lr * g / sqrt(tc)`` — the frequency-adaptive shape that
    graduated into the first-class ``adagrad_freq`` optimizer and the
    reserved ``cnt`` touch-counter slab (repro/optim/row.py), kept here
    cut down to a registration-flow probe.  The state key is ``tc`` (not
    ``cnt``) on purpose: ``cnt`` now has reserved generic bump semantics
    in ``apply_sparse`` and this toy owns its own counting."""
    w, tc = blocks
    s_new = tc + 1.0
    return w - lr * g / jnp.sqrt(s_new), s_new


def test_toy_optimizer_register_only_flow():
    """Acceptance: a toy optimizer registered HERE — one row step +
    ``register()`` — runs the pipelined train step end-to-end (fused
    kernel AND reference path), with NO edits to kernels/ops.py,
    core/sharded_embedding.py or core/pipeline.py."""
    import dataclasses
    from repro.core.dlrm import DLRMConfig, init_state, make_train_step
    from repro.launch.mesh import make_mesh

    row.register(row.RowOptimizer(name="toy_counter", state=(("tc", 0),),
                                  step=_toy_step))
    try:
        mesh = make_mesh((1, 1), ("data", "model"))
        rng = np.random.default_rng(0)
        batch = {
            "idx": jnp.asarray(np.stack(
                [rng.integers(0, max(2, m // 6), (16, 3))
                 for m in (50, 30, 20, 10)], 1).astype(np.int32)),
            "dense_x": jnp.asarray(rng.standard_normal((16, 8)),
                                   jnp.bfloat16),
            "labels": jnp.asarray(rng.integers(0, 2, (16,)), jnp.float32),
        }
        results = {}
        layout = None
        for fused in (True, False):
            cfg = DLRMConfig(name="t", num_dense=8, bottom=(16, 8),
                             top=(16,), table_rows=(50, 30, 20, 10),
                             emb_dim=8, pooling=3, batch=16,
                             sparse_optimizer="toy_counter",
                             fused_update=fused)
            state, layout = init_state(jax.random.PRNGKey(0), cfg, mesh)
            step, _, _, _ = make_train_step(cfg, mesh)
            state, loss = step(state, batch)
            assert np.isfinite(float(loss))
            results[fused] = {k: np.asarray(v)
                              for k, v in state["emb"].items()}
        # touched rows in the GLOBAL row space (per-slot table offsets)
        touched = np.unique(np.asarray(batch["idx"])
                            + np.asarray(layout.row_offsets)[None, :, None])
        cnt = results[True]["tc"]
        # counter semantics: one global batch => every touched row at 1
        assert np.all(cnt[:, 0][np.isin(np.arange(cnt.shape[0]),
                                        touched, invert=True)] == 0)
        assert np.any(cnt == 1.0)
        # fused kernel vs reference scan agree on the toy math
        for k in results[True]:
            np.testing.assert_allclose(results[True][k], results[False][k],
                                       rtol=1e-6, atol=1e-7)
    finally:
        row.unregister("toy_counter")


def _code_strings(path):
    """All string constants in a module EXCLUDING docstrings."""
    with open(path) as f:
        tree = ast.parse(f.read())
    doc_ids = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef,
                             ast.AsyncFunctionDef, ast.ClassDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant) and isinstance(
                    body[0].value.value, str):
                doc_ids.add(id(body[0].value))
    return [n.value for n in ast.walk(tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
            and id(n) not in doc_ids]


def test_no_per_optimizer_dispatch_outside_registry():
    """Source scan: kernels/ops.py, core/sharded_embedding.py and
    core/pipeline.py contain NO optimizer-name string literals (no
    if-chains to edit when registering one) and ops.py references no
    specific kernel entry (the ``kernel`` hook owns that)."""
    files = [os.path.join(SRC, "repro", "kernels", "ops.py"),
             os.path.join(SRC, "repro", "core", "sharded_embedding.py"),
             os.path.join(SRC, "repro", "core", "pipeline.py")]
    names = set(row.names()) | {"toy_counter"}
    for path in files:
        for s in _code_strings(path):
            for name in names:
                assert name not in s, (path, name, s)
    ops_src = open(files[0]).read()
    assert "fused_update_" not in ops_src   # kernel entries live on hooks
    assert ".kind" not in ops_src           # the old dispatch key is gone
