"""The dlrm-large cell's yardstick on the CPU: its configuration file is
what the program builds for ``--share-of 64``, its FLOP and byte counts
by hand, and the system that builds and reads its state one table at a
time (``systems/dlrm_by_table.py``) gives the state, the norms and the
verdicts of ``systems/dlrm.py``."""

from __future__ import annotations

import json

import numpy as np
import pytest

import bench_testlib as lib

LARGE = json.loads((lib.BENCH / "configs" / "dlrm-large.json").read_text())


def _ref():
    return lib.common.load_module(lib.BENCH / "configs" / "dlrm_reference.py")


def _system_mod(name="dlrm"):
    return lib.common.load_module(lib.BENCH / "systems" / f"{name}.py")


def test_config_file_is_the_programs_share():
    from repro.launch import train as T
    cfg = T.dlrm_config(T.parse_args(LARGE["program_args"]))
    assert cfg.table_rows == tuple(LARGE["table_rows"]) == (93_750,) * 64
    assert (cfg.emb_dim, cfg.pooling, cfg.num_dense, cfg.batch, cfg.lr) == (
        LARGE["emb_dim"], LARGE["pooling"], LARGE["num_dense"],
        LARGE["batch"], LARGE["lr"]) == (256, 100, 2048, 256, 0.1)
    assert cfg.bottom == tuple(LARGE["bottom"]) == (2048,) * 7 + (256,)
    assert cfg.top == tuple(LARGE["top"]) == (4096,) * 16
    dep = LARGE["deployment"]
    assert cfg.deployment_chips == dep["chips"] == 64
    assert dep["published"]["table_rows"] == 64 * 93_750
    assert dep["published"]["batch"] == 64 * 256


def test_flops_per_sample_of_dlrm_large_by_hand():
    sz = _ref().sizes_of(LARGE)
    assert sz["top"] == [2336, *[4096] * 16, 1]       # 256 + 65 * 64 / 2
    bottom = 2 * (7 * 2048 * 2048 + 2048 * 256)       # 59,768,832
    top = 2 * (2336 * 4096 + 15 * 4096 * 4096 + 4096)  # 522,461,184
    inter = 2 * 65 * 65 * 256                         # 2,163,200
    emb = 2 * 64 * 100 * 256                          # 3,276,800
    want = 3 * (bottom + top + inter) + 2 * emb
    got = _system_mod("dlrm_by_table").flops_per_sample(sz)
    assert got == want == 1_759_733_248


def test_dlrm_large_flops_match_the_repo_model_flops():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "repo_model_flops", lib.REPO / "benchmarks" / "model_flops.py")
    mf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mf)
    sz = _ref().sizes_of(LARGE)
    meta = dict(batch=256, slots=64, pooling=100, emb_dim=256, kind="train",
                bottom=sz["bottom"], top=sz["top"])
    assert mf.dlrm_flops(meta) == 256 * 1_759_733_248


def test_step_bytes_of_dlrm_large_by_hand():
    sm = _system_mod("dlrm_by_table")
    sz = _ref().sizes_of(LARGE)
    bottom = 7 * (2048 * 2048 + 2048) + 2048 * 256 + 256           # 29,899,008
    top = (2336 * 4096 + 4096 + 15 * (4096 * 4096 + 4096)
           + 4096 + 1)                                              # 261,296,129
    assert sm.dense_param_count(sz) == bottom + top == 291_195_137
    idx = np.zeros((2, 64, 100), np.int32)        # one distinct row per table
    idx[1] = 1                                    # two per table
    fwd = 2 * 64 * 100 * 256 * 2                  # one bf16 row per lookup
    update = 2 * 128 * 256 * 4 + 2 * 64 * 256 * 4 + 16 * 2 * 64 * 100
    dense = 291_195_137 * (2 + 2 + 8)
    assert sm.step_bytes(idx, sz) == fwd + update + dense


@pytest.mark.parametrize("rules", ["layers", "stages"])
def test_by_table_rules_are_the_dlrm_rules(rules):
    """Layer and stage rules go by the system's name; the per-table system
    runs the same program, so its rules are the same files."""
    mine = sorted((lib.BENCH / rules / "dlrm_by_table").glob("*.json"))
    theirs = sorted((lib.BENCH / rules / "dlrm").glob("*.json"))
    assert theirs and [p.name for p in mine] == [p.name for p in theirs]
    assert all(a.read_bytes() == b.read_bytes() for a, b in zip(mine, theirs))


def test_by_table_state_and_norms_are_dlrm_systems():
    """The same seeded state, bit for bit, and the same norms and changed
    rows read back, from a state with some rows changed."""
    import jax
    import jax.numpy as jnp
    from harness import common
    ref = _ref()
    cfg = dict(json.loads((lib.BENCH / "configs" / "dlrm-small.json")
                          .read_text()), **lib.TINY,
               program_args=["--arch", "dlrm-smoke", "--batch", "64"])
    a = _system_mod("dlrm").SYSTEM(cfg, ref)
    b = _system_mod("dlrm_by_table").SYSTEM(cfg, ref)
    key = common.seed_key(2**41 + 9)
    dense0 = jax.device_put(ref.init_dense(2**41 + 9, a.sz))
    sa = a.make_state_fn()(key, dense0)
    sb = b.make_state_fn()(key, dense0)
    assert jax.tree.structure(sa) == jax.tree.structure(sb)
    for x, y in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))
    # change a few rows of two tables and one MLP leaf
    sa["emb"]["lo"] = sa["emb"]["lo"].at[jnp.asarray([3, 4, 6000])].add(1)
    sa["dense"]["lo"] = sa["dense"]["lo"].at[7].add(5)
    na, ra = jax.device_get(a.change_norms_fn()(sa, key, dense0))
    nb, rb = jax.device_get(b.change_norms_fn()(sa, key, dense0))
    assert np.array_equal(na, nb) and np.array_equal(ra, rb)
    assert list(ra[:2]) == [2, 1] and not ra[2:].any()


@pytest.fixture(scope="module")
def by_table_bench(tmp_path_factory):
    """The tiny benchmark with one more cell whose configuration runs on
    the per-table system."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_hlo_source_file_canonicalization_regex")
    old = {k: getattr(jax.config, k) for k in keys}
    bench = lib.tiny_bench(tmp_path_factory.mktemp("checkout"))
    cfg = dict(json.loads((bench / "configs" / "tiny.json").read_text()),
               name="tiny-bt", system="dlrm_by_table")
    (bench / "configs" / "tiny-bt.json").write_text(json.dumps(cfg))
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-bt", "source": "test",
                            "reduced": [], "why": "tiny",
                            "file": "bench/configs/tiny-bt.json"})
    spec["workloads"].append({"name": "tiny-bt.train-uniform",
                              "config": "tiny-bt", "traffic": "train-uniform",
                              "chips": 1, "why": "tiny"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-bt.train-uniform")
    (bench.parent / "BENCHMARK.json").write_text(json.dumps(spec))
    yield bench
    for k, v in old.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


@pytest.mark.parametrize("fault,batch,trace", [
    (None, None, True),
    ("state_unchanged", None, False),
    (None, 32, False),                  # half of every batch left out
    ("control", None, False)])          # bfloat16 master weights
def test_by_table_system_runs_the_cell(by_table_bench, fault, batch, trace):
    out = lib.run_tiny(by_table_bench, "tiny-bt.train-uniform", fault=fault,
                       batch=batch, trace=trace)
    sound = fault is None and batch is None
    assert out["correct"] is sound, out["checks"]
    if trace:
        assert out["metrics"] and set(out["breakdown"]) == {"device_ops",
                                                            "idle_gaps"}
    if not sound:
        assert any(c["value"] > c["limit"] for c in out["checks"].values())
