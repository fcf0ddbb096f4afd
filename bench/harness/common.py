"""What every run shares: finding a cell's files by name, the seed's
keys, the device record, the correctness checks and the result line."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def load_module(path: Path, name: str | None = None):
    """Import one file by path (names may hold dots or dashes)."""
    name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything found for it by name."""
    name: str
    chips: int
    config: dict           # the configuration file
    traffic: dict          # the traffic mix file
    end_to_end: list       # the end-to-end metric entries it reports
    per_layer: list        # the per-layer metric entries it reports
    bench: Path            # the benchmark directory the files came from


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """A per-layer metric is read where it lists the cell, or, without a
    list, in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def find_cell(workload: str, spec: dict, bench: Path = BENCH) -> Cell:
    """The cell ``workload`` of ``spec`` (a parsed BENCHMARK.json) with its
    configuration (``file``), its traffic mix (``traffic/<name>.json``)
    and the metrics it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    root = bench.parent
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer, bench)


def seed_key(seed: int):
    """A JAX key from any whole number (``PRNGKey`` alone keeps only the
    low 32 bits)."""
    import jax
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def require_chips(n: int):
    """The devices to run on; exits non-zero, printing no result, without
    ``n`` TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        sys.exit(f"bench: needs {n} TPU chips, found {len(devices)}")
    return devices


def device_record(n_used: int) -> dict:
    import jax
    devices = jax.devices()
    peaks = []
    for d in devices[:n_used]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit: the run
    is correct only where every value is finite and at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: list, breakdown: dict | None = None
                ) -> str:
    """The last line of standard output; ``checks`` comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)


def print_checks(checks: list) -> None:
    """Each number compared beside its limit, as the last lines of
    standard error."""
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
