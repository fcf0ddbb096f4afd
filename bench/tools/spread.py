#!/usr/bin/env python3
"""Spread of a cell's end-to-end metrics over sets of runs, and the bound
it suggests.

    python bench/tools/spread.py SET1.jsonl SET2.jsonl [...]

Each file holds the result lines (the last stdout line of ``run.py``) of
one set of runs of one cell.  For each metric and set: the median, the
first and third quartiles (``statistics.quantiles(values, n=4)``) and the
spread, (q3 - q1) / median; then the widest spread over the sets, five
times it (the bound it suggests, never under 1%), and each set's median
against the first's.  Also the mean over the sets of the spread without
each set's run farthest from its median (a bound under twice that is too
tight), and whether every run was correct.
"""

from __future__ import annotations

import json
import statistics
import sys


def load(path: str) -> list[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                out.append(json.loads(line))
    return out


def main() -> None:
    sets = [load(p) for p in sys.argv[1:]]
    names = sorted({k for s in sets for r in s for k in r["metrics"]})
    print("runs per set:", [len(s) for s in sets],
          "all correct:", all(r["correct"] for s in sets for r in s))
    for name in names:
        rows, spreads, meds, trimmed = [], [], [], []
        for s in sets:
            v = [r["metrics"][name]["value"] for r in s if name in r["metrics"]]
            q1, q2, q3 = statistics.quantiles(v, n=4)
            med = statistics.median(v)
            spreads.append((q3 - q1) / med)
            far = max(v, key=lambda x: abs(x - med))
            t = list(v)
            t.remove(far)
            t1, _, t3 = statistics.quantiles(t, n=4)
            trimmed.append((t3 - t1) / statistics.median(t))
            meds.append(med)
            rows.append(f"median {med!r} q1 {q1!r} q3 {q3!r} "
                        f"spread {100 * (q3 - q1) / med:.3f}%")
        wide = max(spreads)
        print(f"{name}:")
        for r in rows:
            print(f"    {r}")
        print(f"    widest spread {100 * wide:.3f}%  5x = "
              f"{100 * max(5 * wide, 0.01):.2f}%  medians vs first: "
              + ", ".join(f"{100 * (m / meds[0] - 1):+.3f}%" for m in meds)
              + f"  trimmed mean spread {100 * statistics.mean(trimmed):.3f}%")


if __name__ == "__main__":
    main()
