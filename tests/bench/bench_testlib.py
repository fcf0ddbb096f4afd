"""Shared helpers of the benchmark's CPU tests: a copy of the benchmark
with a tiny configuration of the same program, run on the CPU with the
harness's look for a chip skipped."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import common  # noqa: E402

# the program's reduced DLRM (``launch.train`` without --paper) at batch 64
TINY = {"table_rows": [5000] * 8, "emb_dim": 32, "pooling": 10,
        "num_dense": 64, "bottom": [64, 32], "top": [64, 32], "batch": 64,
        "lr": 0.05}

def tiny_bench(root: Path) -> Path:
    """A checkout-like directory under ``root``: a copy of ``bench/``, the
    program's ``src`` linked, and a BENCHMARK.json holding tiny cells
    (``tiny.train-uniform``, ``tiny-table.train-zipf``) beside the real
    ones.  Returns the bench dir."""
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", root / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    base = json.loads((bench / "configs" / "dlrm-small.json").read_text())
    for name, mode in (("tiny", "row"), ("tiny-table", "table")):
        cfg = dict(base, **TINY, name=name, placement=mode,
                   program_args=["--arch", "dlrm-smoke", "--batch", "64",
                                 "--emb-mode", mode])
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test", "reduced": [],
                                "file": f"bench/configs/{name}.json",
                                "why": "tiny"})
    cells = {"tiny.train-uniform": ("tiny", "train-uniform"),
             "tiny-table.train-zipf": ("tiny-table", "train-zipf")}
    for cell, (cfg, mix_name) in cells.items():
        spec["workloads"].append({"name": cell, "config": cfg,
                                  "traffic": mix_name, "chips": 1,
                                  "why": "tiny"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    peaks = json.loads((bench / "peaks.json").read_text())
    # the CPU has no published peak: the test borrows the chip's so that
    # the per-layer readers run end to end
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return bench


def run_tiny(bench: Path, workload: str, *, seed: int = 2**33 + 5,
             seconds: float = 0.5, trace: bool = False, fault=None,
             batch=None) -> dict:
    """One run of a tiny cell on the CPU; the parsed result line."""
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    cell = common.find_cell(workload, spec, bench)
    run = common.load_module(bench / "run.py", "bench_run_under_test")
    line, _ = run.run_cell(cell, seed, seconds, trace, t0=time.perf_counter(),
                           fault=fault, batch=batch)
    return json.loads(line)
