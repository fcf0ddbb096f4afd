"""The benchmark's harness driven end to end on the CPU at a tiny size,
with its look for a chip skipped: sound runs are correct, planted faults
under the timed path are not, new cells are found from new files, and the
command itself refuses to run without a TPU."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import bench_testlib as lib


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_hlo_source_file_canonicalization_regex")
    old = {k: getattr(jax.config, k) for k in keys}
    yield lib.tiny_bench(tmp_path_factory.mktemp("checkout"))
    # the run turned the persistent compilation cache on; later tests in
    # this process must not find it on
    for k, v in old.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


@pytest.mark.parametrize("workload", ["tiny.train-uniform",
                                      "tiny-table.train-zipf"])
def test_sound_run_is_correct(bench, workload):
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    cell = lib.common.find_cell(workload, spec, bench)
    out = lib.run_tiny(bench, workload)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks" and out["checks"]
    assert out["device"]["platform"] == "cpu" and out["failed"] == 0


@pytest.mark.parametrize("workload", ["tiny.train-uniform",
                                      "tiny-table.train-zipf"])
def test_traced_run_reports_per_layer_metrics(bench, workload):
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    cell = lib.common.find_cell(workload, spec, bench)
    out = lib.run_tiny(bench, workload, trace=True)
    assert out["correct"] is True
    # the CPU has no device plane: the device readers find nothing and
    # say so by leaving their metric out
    assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert out["metrics"], "host-side per-layer metrics are always read"
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload,fault,batch", [
    ("tiny.train-uniform", "state_unchanged", None),
    ("tiny-table.train-zipf", "state_unchanged", None),
    ("tiny.train-uniform", None, 32),            # half of every batch left out
    ("tiny-table.train-zipf", None, 32),
    ("tiny.train-uniform", "control", None),     # bfloat16 master weights
    ("tiny-table.train-zipf", "control", None)])
def test_planted_fault_is_not_correct(bench, workload, fault, batch):
    out = lib.run_tiny(bench, workload, fault=fault, batch=batch)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def _hashes(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_found_from_new_files(bench, tmp_path):
    """A later PR adds a configuration, a mix and a per-layer metric as
    files of their own plus entries in BENCHMARK.json; the harness finds
    them, and no file that was there changes."""
    import shutil
    root = tmp_path / "checkout"
    shutil.copytree(bench.parent, root, symlinks=True)
    b = root / "bench"
    before = _hashes(b)
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["name"] = "tiny-wide"
    cfg["batch"] = 32
    cfg["program_args"] = ["--arch", "dlrm-smoke", "--batch", "32",
                           "--emb-mode", "row"]
    (b / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "train-uniform.json").read_text())
    mix["pool"] = 5
    (b / "traffic" / "train-uniform-5.json").write_text(json.dumps(mix))
    (b / "metrics" / "train.window_steps.py").write_text(
        "def read(r):\n    return float(r.steps)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-wide", "source": "test",
                            "file": "bench/configs/tiny-wide.json",
                            "reduced": [], "why": "tiny"})
    spec["workloads"].append({"name": "tiny-wide.train-uniform-5",
                              "config": "tiny-wide",
                              "traffic": "train-uniform-5", "chips": 1,
                              "why": "tiny"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_samples_per_s":
            m["workloads"].append("tiny-wide.train-uniform-5")
    spec["per_layer"].append({"name": "train.window_steps", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step",
                              "moves": "train_samples_per_s",
                              "workloads": ["tiny-wide.train-uniform-5"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = lib.common.find_cell("tiny-wide.train-uniform-5", spec, b)
    assert cell.config["batch"] == 32 and cell.traffic["pool"] == 5
    assert [m["name"] for m in cell.per_layer] == ["train.window_steps"]
    out = lib.run_tiny(b, "tiny-wide.train-uniform-5", trace=True)
    assert out["correct"] is True
    assert out["metrics"]["train.window_steps"]["value"] >= 1
    after = _hashes(b)
    assert {k: after[k] for k in before} == before


def test_command_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "dlrm-small.train-uniform", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=lib.REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_readings_tool_runs_the_cell_and_its_faults(bench):
    """``tools/readings.py`` reads the limits' numbers through the run's
    own path: the program is correct, the control and the half-batch
    fault are not."""
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    cell = lib.common.find_cell("tiny.train-uniform", spec, bench)
    tool = lib.common.load_module(bench / "tools" / "readings.py",
                                  "bench_readings_under_test")
    recs = {r["side"]: r for r in tool.readings(cell, [2**35 + 1],
                                                [2**35 + 1], 0.3)}
    assert set(recs) == {"program", "control", "half_batch"}
    assert recs["program"]["correct"] is True
    assert recs["control"]["correct"] is False
    assert recs["half_batch"]["correct"] is False
    assert set(recs["program"]["checks"]) == set(
        cell.config["limits"]["train"]) | {"start_state_norm"}
