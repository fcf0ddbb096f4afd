#!/usr/bin/env python3
"""Readings that set a train cell's correctness limits, on the chip at the
cell's own size, many seeds in one process.

    python bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 1] [--out F]

Every reading is one run of the cell through ``run.run_cell``, the same
set-up, timed path and comparison as ``bench/run.py``, with a short
window.  Per seed it records the numbers compared (``checks``), the
losses' gap and the step times of:

- ``program``: the program as the cell runs it (every seed of
  ``--seeds``);
- ``control``: the reference at bfloat16 master weights put in the
  program's place (each seed of ``--control-seeds``);
- ``half_batch``: the program built for half the batch, so that the
  reference sees every batch whole and the program only its first half
  (each seed of ``--control-seeds``).

A state left unchanged reads 1 by construction and needs no run.  Needs
the chips the cell asks for.  One JSON line per run on stdout and in
``--out``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import common  # noqa: E402


def readings(cell, seeds, control_seeds, seconds):
    """One record per run: the program on every seed, the control and the
    half-batch fault on each control seed."""
    run = common.load_module(BENCH / "run.py", "bench_run")
    sides = [("program", s, {}) for s in seeds]
    sides += [(side, s, kw) for s in control_seeds for side, kw in (
        ("control", {"fault": "control"}),
        ("half_batch", {"batch": cell.config["batch"] // 2}))]
    for side, seed, kw in sides:
        t = time.perf_counter()
        line, out = run.run_cell(cell, seed, seconds, False, t0=t, **kw)
        res = json.loads(line)
        yield {"workload": cell.name, "side": side, "seed": seed,
               "correct": res["correct"],
               "checks": {c.name: c.value for c in out.checks},
               "loss_gap": out.detail["loss_gap"],
               "step_s": out.detail["step_s"],
               "seconds": time.perf_counter() - t}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = common.find_cell(args.workload, spec, BENCH)
    common.require_chips(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    out = open(args.out, "a") if args.out else None
    for rec in readings(cell, seeds, ctl, args.seconds):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()


if __name__ == "__main__":
    main()
