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
                 "promote_every", "hot_sync", "exchange_dtype",
                 "step_metrics")
    assert dataclasses.replace(
        cfg, **{k: getattr(want, k) for k in run_flags}) == want
    assert cfg.batch == 8192 and cfg.lr == want.lr
    smaller = T.dlrm_config(T.parse_args(
        ["--arch", "dlrm-small", "--paper", "--batch", "512"]))
    assert smaller.batch == 512 and smaller.table_rows == want.table_rows


def test_paper_flag_rejects_archs_without_a_paper_config():
    from repro.launch import train as T
    with pytest.raises(SystemExit):
        T.parse_args(["--arch", "dlrm-100m", "--paper"])
    with pytest.raises(SystemExit):
        T.parse_args(["--arch", "fm", "--emb-mode", "table"])


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
