#!/usr/bin/env python3
"""Record a small device trace of a train cell for the reducer's tests and
for reading by hand: one traced run, then the device ops and host spans of
a slice around the first step boundary of the window (the end of one
step's sparse update, the gap, the start of the next step), with the
layer of every instruction those ops name, as JSON.

    python bench/tools/record_trace.py --workload <cell> --out F.json \\
        [--seconds 4] [--before-ms 40] [--after-ms 120] [--seed 1]

Needs the chips the cell asks for.
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
from harness import trace as trace_mod  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--before-ms", type=float, default=40.0)
    ap.add_argument("--after-ms", type=float, default=120.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = common.find_cell(args.workload, spec, BENCH)
    common.require_chips(cell.chips)
    run = common.load_module(BENCH / "run.py", "bench_run")
    ctx = run.make_ctx(cell, args.seed, args.seconds, True,
                       t0=time.perf_counter())
    out = ctx.driver.run(ctx)
    tr, layer_of = out.trace, out.readings.layers
    full = {"busy_s": tr.busy_s, "window_s": tr.window_s,
            "layer_seconds": tr.layer_seconds(layer_of),
            "top_ops": tr.top_ops(10, layer_of), "idle_gaps": tr.idle_gaps(10)}
    fetch = min(s[2] for s in tr.spans if s[0] == "bench/loss_fetch"
                and s[2] > tr.window[0])
    a, b = fetch - args.before_ms * 1e6, fetch + args.after_ms * 1e6
    cut = trace_mod.Trace(
        [[trace_mod.Op(o.name, o.label, o.start, o.end) for o in ops
          if o.end > a and o.start < b] for ops in tr.devices],
        [s for s in tr.spans if s[2] > a and s[1] < b], (a, b))
    rec = json.loads(cut.to_json())
    rec["layers"] = {o.name: layer_of.get(o.name, "other")
                     for ops in cut.devices for o in ops}
    rec["full"] = full
    Path(args.out).write_text(json.dumps(rec))
    print(json.dumps(full))


if __name__ == "__main__":
    main()
