"""Entry points: ``launch/train.py --paper``, the compile cache, the
backend-decided interpret switch and ``chip_smoke.py``'s refusals
(nothing here needs a chip)."""

import dataclasses
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("mode", ["row", "table"])
def test_paper_flag_trains_the_registered_config(mode):
    from repro.configs.dlrm_paper import dlrm_small
    from repro.launch import train as T
    args = T.parse_args(["--arch", "dlrm-small", "--paper",
                         "--emb-mode", mode])
    cfg = T.dlrm_config(args)
    want = dlrm_small(mode=mode)
    run_flags = ("sparse_optimizer", "opt_beta", "opt_eps", "microbatches",
                 "host_presort", "weighted", "sr_seed", "hot_rows",
                 "promote_every", "hot_sync", "exchange", "step_metrics")
    assert dataclasses.replace(
        cfg, **{k: getattr(want, k) for k in run_flags}) == want
    assert cfg.batch == 8192 and cfg.lr == want.lr
    smaller = T.dlrm_config(T.parse_args(
        ["--arch", "dlrm-small", "--paper", "--batch", "512"]))
    assert smaller.batch == 512 and smaller.table_rows == want.table_rows


STEP_FLAGS = [
    (["--lr", "0.3"], "lr", 0.3),
    (["--optimizer", "momentum"], "sparse_optimizer", "momentum"),
    (["--optimizer", "momentum", "--beta", "0.5"], "opt_beta", 0.5),
    (["--optimizer", "adagrad", "--eps", "0.001"], "opt_eps", 0.001),
    (["--microbatches", "2"], "microbatches", 2),
    (["--host-presort"], "host_presort", True),
    (["--weighted"], "weighted", True),
    (["--seed", "7"], "sr_seed", 7),
    (["--hot-rows", "8"], "hot_rows", 8),
    (["--promote-every", "3"], "promote_every", 3),
    (["--hot-sync", "deferred:4"], "hot_sync", "deferred:4"),
    (["--exchange-dtype", "bf16"], "exchange", "bf16"),
    (["--step-metrics"], "step_metrics", True),
]


@pytest.mark.parametrize("flags, field, value", STEP_FLAGS,
                         ids=[f for _, f, _ in STEP_FLAGS])
def test_step_flag_reaches_every_arch(flags, field, value):
    """A step flag reaches the HybridDef a dlrm arch and a recsys arch
    train with the same value, and the cases cover every step option the
    launcher sets."""
    from repro.core.dlrm import as_hybrid_def
    from repro.dist.exchange import ExchangeConfig
    from repro.launch import train as T
    if field == "exchange":
        value = ExchangeConfig(dY_dtype=value, dense_dtype=value)
    dlrm = as_hybrid_def(T.dlrm_config(T.parse_args(
        ["--arch", "dlrm-small", *flags])))
    fm = T.hybrid_def(T.parse_args(["--arch", "fm", *flags]))
    assert getattr(dlrm, field) == getattr(fm, field) == value
    plain = T.hybrid_def(T.parse_args(["--arch", "fm"]))
    assert getattr(plain, field) != value
    assert set(T.step_options(T.parse_args(["--arch", "fm"]), 0.05)) == {
        f for _, f, _ in STEP_FLAGS}


def test_paper_flag_rejects_archs_without_a_paper_config():
    from repro.launch import train as T
    with pytest.raises(SystemExit):
        T.parse_args(["--arch", "dlrm-100m", "--paper"])
    with pytest.raises(SystemExit):
        T.parse_args(["--arch", "fm", "--emb-mode", "table"])


def test_share_of_builds_one_chips_share_at_published_widths():
    """``--share-of 64``: one chip's share of dlrm-large deployed row-wise
    over 64 chips, 93,750 rows of each of the 64 tables at E=256 and a
    batch of 16,384 / 64; every other size as registered."""
    from repro.configs import dlrm_paper
    from repro.launch import train as T
    cfg = T.dlrm_config(T.parse_args(
        ["--arch", "dlrm-large", "--paper", "--emb-mode", "row",
         "--share-of", "64"]))
    whole = dlrm_paper.dlrm_large()
    assert cfg.table_rows == (93_750,) * 64 and cfg.emb_dim == 256
    assert cfg.batch == 256 and cfg.deployment_chips == 64
    assert whole.table_rows == (6_000_000,) * 64 and whole.batch == 16_384
    assert whole.deployment_chips == 1
    assert (cfg.num_dense, cfg.bottom, cfg.top, cfg.pooling, cfg.emb_mode,
            cfg.lr) == (2048, (2048,) * 7 + (256,), (4096,) * 16, 100,
                        "row", whole.lr)
    assert cfg.top_sizes == [2336, *(4096,) * 16, 1]
    assert dataclasses.replace(cfg, table_rows=whole.table_rows,
                               batch=whole.batch, deployment_chips=1,
                               sr_seed=whole.sr_seed) == whole
    # --batch sets the batch the share trains
    half = T.dlrm_config(T.parse_args(
        ["--arch", "dlrm-large", "--paper", "--share-of", "64",
         "--batch", "128"]))
    assert half.batch == 128 and half.table_rows == cfg.table_rows
    # the registry's build carries the deployment in its meta
    assert dlrm_paper.dlrm_large(share_of=64) == dataclasses.replace(
        cfg, sr_seed=whole.sr_seed)


@pytest.mark.parametrize("argv", [
    ["--arch", "dlrm-large", "--share-of", "64"],            # no --paper
    ["--arch", "dlrm-large", "--paper", "--share-of", "3"],  # rows, batch
    ["--arch", "dlrm-large", "--paper", "--share-of", "0"],
    ["--arch", "dlrm-small", "--paper", "--share-of", "5"],  # the batch
    ["--arch", "dlrm-large", "--paper", "--share-of", "64",
     "--emb-mode", "table"]])
def test_share_of_refuses_what_it_cannot_build(argv):
    from repro.launch import train as T
    with pytest.raises(SystemExit):
        T.parse_args(argv)


def test_registry_meta_carries_the_deployment():
    from repro.configs import base
    from repro.configs import dlrm_paper  # noqa: F401 — registers them
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    build = base.get("dlrm-small").build("train", mesh, batch=64)
    assert build.meta["deployment_chips"] == 1


def test_table_mode_stream_reads_padded_slots():
    """The synthetic stream of a table-mode run is in the padded slot
    order the step reads, the host twin of ``permute_indices``."""
    from repro.core import sharded_embedding as se
    from repro.core.dlrm import DLRMConfig
    from repro.data.synthetic import dlrm_stream
    cfg = DLRMConfig(name="t", num_dense=4, bottom=(8, 4), top=(8,),
                     table_rows=(50, 30, 20, 10, 40), emb_dim=4, pooling=3,
                     batch=8, emb_mode="table")
    layout = se.make_layout(cfg.spec, 2, "table")
    assert layout.num_padded_slots > len(cfg.table_rows)   # has dummies
    plain = next(dlrm_stream(3, cfg))
    padded = next(dlrm_stream(3, cfg, layout=layout))
    np.testing.assert_array_equal(
        padded["idx"],
        np.asarray(se.permute_indices(layout, jnp.asarray(plain["idx"]))))


def test_compile_cache_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    prev_re = jax.config.jax_hlo_source_file_canonicalization_regex
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # source locations in cache keys are relative to the checkout
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        here = os.path.realpath(__file__)
        assert re.sub(pattern, "", here) == os.path.join("tests",
                                                         "test_launch.py")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          prev_re)
        cc.reset_cache()


def test_default_interpret_follows_the_backend(monkeypatch):
    from repro.kernels import ops
    for backend, interpret in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops._default_interpret() is interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError):
        ops._default_interpret()


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_refuses_without_a_tpu(tmp_path, alone):
    """No TPU, or no repo beside the script: non-zero exit, no result."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_chip_smoke_serve_phase_runs_on_the_built_run():
    """``chip_smoke.serve_phase`` on what ``build_dlrm`` returns, at the
    reduced dlrm-small widths on the CPU: every bucket runs and the
    server's scores equal ``make_score_step``'s, bitwise."""
    import importlib.util

    from repro.launch import train as T
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    args = T.parse_args(["--arch", "dlrm-small", "--emb-mode", "table",
                         "--batch", str(smoke.N_REQUESTS)])
    mesh = T.local_mesh()
    run = T.build_dlrm(args, mesh, jax.random.PRNGKey(0))
    smoke.serve_phase(run, mesh, run.state)
