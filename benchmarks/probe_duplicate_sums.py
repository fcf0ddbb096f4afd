"""Probe: in what order does the device sum the duplicate lookups of a row?

    PYTHONPATH=src python benchmarks/probe_duplicate_sums.py
        [--rows 1000000] [--dim 64] [--bags 8192] [--pooling 50] [--seed 0]

The default is one dlrm-small table: 1M rows, E=64, 8192 bags of 50
lookups on uniformly drawn rows.  A touched row's gradient is the sum of
the cotangent rows of its lookups; the host reference adds them one by
one, in flat lookup order, in fp32 (``np.add.at``).  On the default
device four sums are compared with it, row by row and bitwise:

* the fused kernel (``sgd`` on a zero table at lr -1 writes the sum),
* ``jax.ops.segment_sum`` in flat order,
* a scatter-add in flat order, and one in stably sorted order;

and the fused ``split_sgd`` update is compared with its jitted reference
(``apply_rows_split_sgd``), ``hi`` and ``lo``.  Each line counts the rows
that differ, split by how many lookups the row has; the exit status is 1
when any row differs.  What it measures is the backend's compiler: run it
on the chip for XLA:TPU.  On the CPU the kernel is interpreted, which
takes minutes at the default size.
"""

import argparse
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def differing_rows(got, want, count) -> tuple[int, dict]:
    """Rows whose bits differ, in all and by lookups per row."""
    got, want = np.asarray(got), np.asarray(want)
    bad = np.any(got.view(np.uint32 if got.itemsize == 4 else np.uint16)
                 != want.view(np.uint32 if want.itemsize == 4
                              else np.uint16), axis=1)
    return int(bad.sum()), {int(k): int((bad & (count == k)).sum())
                            for k in range(1, int(count.max()) + 1)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--bags", type=int, default=8192)
    ap.add_argument("--pooling", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.optim.row import apply_rows_split_sgd
    from repro.optim.split_sgd import split_fp32

    rng = np.random.default_rng(args.seed)
    n_lookups = args.bags * args.pooling
    ids = rng.integers(0, args.rows, n_lookups).astype(np.int32)
    dY = rng.standard_normal((args.bags, args.dim), dtype=np.float32)
    grad = dY[np.arange(n_lookups) // args.pooling]
    want = np.zeros((args.rows, args.dim), np.float32)
    np.add.at(want, ids, grad)
    count = np.bincount(ids, minlength=args.rows)
    print(f"device {jax.devices()[0].device_kind}; {n_lookups} lookups, "
          f"{int((count > 1).sum())} rows looked up more than once, at most "
          f"{int(count.max())} times")

    ids_d, dY_d, grad_d = map(jnp.asarray, (ids, dY, grad))
    order = jnp.asarray(np.argsort(ids, kind="stable"))
    zeros = jnp.zeros((args.rows, args.dim), jnp.float32)
    scatter = jax.jit(lambda z, i, g: z.at[i].add(g))
    sums = {
        "kernel": ops.fused_row_update("sgd", {"w": zeros}, ids_d, dY_d,
                                       -1.0, pooling=args.pooling)["w"],
        "segment_sum": jax.jit(jax.ops.segment_sum,
                               static_argnames="num_segments")(
            grad_d, ids_d, num_segments=args.rows),
        "scatter-add": scatter(zeros, ids_d, grad_d),
        "scatter-add sorted": scatter(zeros, ids_d[order], grad_d[order]),
    }
    failed = False
    for name, got in sums.items():
        n, by = differing_rows(got, want, count)
        failed |= n > 0
        print(f"{name}: rows != sequential host sum {n}; by lookups per "
              f"row {by}")

    hi, lo = split_fp32(jnp.asarray(
        0.01 * rng.standard_normal((args.rows, args.dim), dtype=np.float32)))
    fused = ops.fused_row_update("split_sgd", {"hi": hi, "lo": lo}, ids_d,
                                 dY_d, 0.01, pooling=args.pooling)
    ref = dict(zip(("hi", "lo"), jax.jit(apply_rows_split_sgd)(
        hi, lo, ids_d, grad_d, 0.01)))
    for k in ("hi", "lo"):
        n, by = differing_rows(fused[k], ref[k], count)
        failed |= n > 0
        print(f"split_sgd {k}: rows fused != reference {n}; by lookups per "
              f"row {by}")
    return int(failed)


if __name__ == "__main__":
    sys.exit(main())
