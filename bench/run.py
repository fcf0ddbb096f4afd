#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its files
are found by name: the configuration (``file``), the traffic mix
(``bench/traffic/<traffic>.json``, whose ``driver`` names
``bench/drivers/<driver>.py``), the configuration's system
(``bench/systems/<system>.py``) and plain reference
(``bench/configs/<reference>.py``), each per-layer metric's reader
(``bench/metrics/<metric>.py``) and the device peaks
(``bench/peaks.json``, by ``device_kind``).  A new cell, mix or metric
is new files and entries; no file here changes.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  Either way
the run checks what the timed path produced against the plain reference
after the window, prints each number compared beside its limit as the
last lines of standard error, and prints one JSON object as the last
line of standard output.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import common  # noqa: E402


def make_ctx(cell: common.Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, fault: str | None = None, batch: int | None = None):
    """What a driver needs for one run of ``cell``: the configuration's
    system (the program, built) and reference, the device's peaks.
    ``fault`` and ``batch`` plant a fault under the timed path (tests
    and ``tools/readings.py`` only)."""
    root = cell.bench.parent
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    peaks = json.loads((cell.bench / "peaks.json").read_text())["devices"]
    kind = jax.devices()[0].device_kind
    peak = peaks.get(kind)
    if trace and peak is None:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    cfg = cell.config
    system_mod = common.load_module(cell.bench / "systems" / f"{cfg['system']}.py")
    reference = common.load_module(cell.bench / "configs" / f"{cfg['reference']}.py")
    return types.SimpleNamespace(
        cell=cell, seed=seed, seconds=seconds, trace=trace, t0=t0,
        fault=fault, peak=peak, system=system_mod.SYSTEM(cfg, reference, batch=batch),
        system_mod=system_mod, reference=reference,
        driver=common.load_module(cell.bench / "drivers" / f"{cell.traffic['driver']}.py"))


def run_cell(cell: common.Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, fault: str | None = None, batch: int | None = None
             ) -> tuple[str, types.SimpleNamespace]:
    """Run ``cell`` once on the devices JAX has; returns the result line
    and the driver's record (``checks``, ``detail``)."""
    ctx = make_ctx(cell, seed, seconds, trace, t0=t0, fault=fault, batch=batch)
    out = ctx.driver.run(ctx)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            reader = common.load_module(cell.bench / "metrics" / f"{m['name']}.py")
            v = reader.read(out.readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.e2e[m["name"]], "unit": m["unit"]}
    correct = out.failed == 0 and all(c.ok for c in out.checks)
    line = common.result_line(correct, out.attempted, out.failed, metrics,
                              out.device, out.checks, out.breakdown)
    print(f"[bench] {json.dumps(out.detail)}", file=sys.stderr, flush=True)
    return line, out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = common.find_cell(args.workload, spec, BENCH)
    common.require_chips(cell.chips)
    line, out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         t0=T0)
    common.print_checks(out.checks)
    print(line, flush=True)


if __name__ == "__main__":
    main()
