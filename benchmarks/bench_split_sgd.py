"""Paper Fig. 16 + Sect. VII accounting: Split-SGD-BF16 convergence parity,
capacity/bandwidth table, and the fused-vs-reference embedding update
roofline (kernels/embedding_update.py) — now swept over every registered
sparse RowOptimizer (repro/optim/row.py).

    PYTHONPATH=src python benchmarks/bench_split_sgd.py [--fused|--reference]
        [--optimizer sgd|split_sgd|momentum|adagrad_rowwise|adagrad|all]
        [--smoke] [--json BENCH_embedding_update.json]

The update section reports THEORETICAL bytes/step for both paths (the
acceptance metric: the fused path touches O(unique_rows) data — weights
AND per-row optimizer state — while the reference path touches
O(shard_rows)) plus measured wall-clock.  ``--optimizer`` adds the named
optimizer's state-slab traffic to the roofline and times its fused
interpret-mode kernel on a tiny shard; ``all`` sweeps the registry.
``--smoke`` skips the 120-step convergence study (the CI sweep).  The
fused kernel runs in Pallas interpret mode on CPU — its wall-clock is an
emulation artifact; the bytes model is the TPU-relevant number.

The JSON write is a KEY-STABLE MERGE into any existing file
(:func:`merge_sections`): partial runs update only the sections they
computed, so `benchmarks/check_bench.py` can diff the artifact against
the committed baseline without one sweep clobbering another's rows.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))


def rows():
    from split_sgd_convergence import run
    import numpy as np
    out = []
    finals = {}
    for mode in ("fp32", "split", "split8", "bf16"):
        losses = run(mode, steps=120)
        finals[mode] = float(np.mean(losses[-20:]))
        out.append((f"split_sgd_{mode}_final_loss", finals[mode] * 1e6,
                    "x1e-6 (Fig.16 final-20 mean)"))
    out.append(("split_vs_fp32_gap", abs(finals["split"] - finals["fp32"])
                * 1e6, "x1e-6 — paper: ~0"))
    out.append(("bf16_vs_fp32_gap", abs(finals["bf16"] - finals["fp32"])
                * 1e6, "x1e-6 — naive bf16 drifts"))
    # capacity table (paper Sect. VII): bytes/param
    out.append(("bytes_per_param_fp32", 4.0, "fp32 weights"))
    out.append(("bytes_per_param_split", 4.0, "hi+lo: zero overhead"))
    out.append(("bytes_per_param_fp16_master", 6.0, "fp16 + fp32 master"))
    out.append(("fwd_bwd_bytes_per_param_split", 2.0,
                "2x bandwidth saving on 2 of 3 passes"))
    return out


def _timeit(fn, *args, iters=5):
    # local copy of bench_ops.timeit: these files run both as scripts and
    # as benchmarks.* modules, so a cross-file import would need dual-path
    # resolution for a three-line helper
    import jax
    jax.block_until_ready(fn(*args))          # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6   # us


def optimizer_bytes_row(name: str, U: int, E: int, NB: int, L: int) -> dict:
    """Roofline bytes/step of one registered RowOptimizer's FUSED update:
    touched weight rows in+out, per-row state slab in+out (the second
    row-addressed operand of kernels/embedding_update.py), dY once, and
    the int32 index sort.  State traffic per touched row follows each
    slab's WIDTH and DTYPE: momentum / elementwise adagrad E lanes (fp32,
    or 2-byte bf16-hi for the compressed ``*_bf16`` kinds — half the
    state bytes), row-wise adagrad ONE fp32 scalar, the stateless kinds
    zero.  A lower bound on the chip: the kernel moves the tile-aligned
    row GROUP holding each touched row (128 rows of a narrow slab, 8 or 16
    of a 128-lane-multiple one), so rows that share no group cost a whole
    group each."""
    from repro.optim import row as row_optim
    opt = row_optim.get(name)
    state_bytes = sum((w or E) * dt.itemsize
                      for _, w, dt in opt.state_slabs())
    b = {
        "touched_rows_rw": 2 * U * E * 4,
        "state_rows_rw": 2 * U * state_bytes,
        "dY_read": NB * E * 4,
        "index_sort": 3 * L * 4,
    }
    return {"bytes_per_step": sum(b.values()), "bytes_breakdown": b,
            "state_bytes_per_row": state_bytes,
            "touches": "O(unique_rows)"}


def embedding_update_bench(modes=("reference", "fused"),
                           M=200_000, E=64, B=512, S=8, P=4, zipf=1.05,
                           measure_fused=False, optimizers=()):
    """Fused vs reference sparse Split-SGD update on one shard, plus the
    per-RowOptimizer bytes/step roofline rows (``optimizers``).

    Returns a JSON-able dict with the bytes/step roofline model and
    measured wall-clock per requested mode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import zipf_indices
    from repro.kernels import ops
    from repro.optim import row as row_optim
    from repro.optim.row import apply_rows_split_sgd
    from repro.optim.split_sgd import split_fp32

    rng = np.random.default_rng(0)
    L, NB = B * S * P, B * S
    W = jnp.asarray(rng.standard_normal((M, E)), jnp.float32)
    hi, lo = split_fp32(W)
    tgt = jnp.asarray(
        zipf_indices(rng, M, (L,), zipf).astype(np.int32))
    dY = jnp.asarray(rng.standard_normal((NB, E)), jnp.float32)
    grad_rows = jnp.take(dY, jnp.arange(L) // P, axis=0)
    U = int(len(np.unique(np.asarray(tgt))))

    # --- bytes/step roofline model --------------------------------------
    # reference: materialize the [L, E] per-lookup gradient (write+read),
    # segment-sum it (write), gather+combine the L candidate rows, then the
    # functional scatter COPIES the whole (hi, lo) shard (read+write of
    # M rows x 4 B/elem).
    ref_bytes = {
        "grad_expand_rw": 2 * L * E * 4,
        "segment_sum_out": L * E * 4,
        "row_gather": L * E * 4,
        "shard_copy_rw": 2 * M * E * 4,
    }
    # fused: touched rows in+out (2+2 B/elem each way), dY once, and the
    # int32 sort of the L flat row ids. No dense dW, no shard copy.
    fused_bytes = {
        "touched_rows_rw": 2 * U * E * 4,
        "dY_read": NB * E * 4,
        "index_sort": 3 * L * 4,
    }
    result = {
        "config": {"shard_rows": M, "dim": E, "batch": B, "slots": S,
                   "pooling": P, "flat_lookups": L, "unique_rows": U,
                   "zipf": zipf},
        "reference": {"bytes_per_step": sum(ref_bytes.values()),
                      "bytes_breakdown": ref_bytes,
                      "touches": "O(shard_rows)"},
        "fused": {"bytes_per_step": sum(fused_bytes.values()),
                  "bytes_breakdown": fused_bytes,
                  "touches": "O(unique_rows)"},
    }
    result["model_speedup"] = (result["reference"]["bytes_per_step"]
                               / result["fused"]["bytes_per_step"])

    # --- per-RowOptimizer roofline rows --------------------------------
    if optimizers:
        result["optimizers"] = {}
        for name in optimizers:
            r = optimizer_bytes_row(name, U, E, NB, L)
            if measure_fused:
                # tiny shard, one iteration: interpret-mode emulation is
                # O(shard) per grid step (see the note below); the bytes
                # model is the hardware-relevant number
                Mm, Lm = 5_000, 256
                opt = row_optim.get(name)
                store = opt.init_store(W[:Mm])
                f = jax.jit(lambda s, t, d: opt.apply_sparse(
                    s, row_optim.SparseStream(
                        idx=t.reshape(-1, 1, P),
                        dY=d.reshape(-1, 1, E)), 0.05,
                    fused=True, interpret=True))
                r["us_measured_interpret"] = _timeit(
                    f, store, jnp.minimum(tgt[:Lm], Mm - 1),
                    dY[:Lm // P], iters=1)
            result["optimizers"][name] = r

    # --- hot-row cache rows (repro/core/cache.py, docs/cache.md) -------
    # counter-driven promotion on this shard's OWN zipf stream: the first
    # half of the flat lookups trains the touch counters, the real
    # ``select_hot`` promotion picks the top-K, and the second half
    # measures the all-hot-bag hit rate.  A hot bag ships no exchange
    # payload, so ``exchange_bytes_saved`` is hit_bags * E * 4 per step.
    # Counters and promotion are integer-exact on the seeded stream, so
    # both keys are EXACT gate keys in benchmarks/check_bench.py.
    from repro.core import cache as hot_cache
    from repro.core import sharded_embedding as se
    from repro.core.embedding import EmbeddingSpec

    layout1 = se.make_layout(EmbeddingSpec((M,), E), 1, "row")
    warm, ev = np.asarray(tgt[:L // 2]), np.asarray(tgt[L // 2:])
    cnt = np.bincount(warm, minlength=layout1.total_rows).astype(np.int32)
    result["cache"] = {"warmup_lookups": len(warm),
                       "eval_bags": len(ev) // P}
    for K in (0, 64):
        hot = np.zeros(layout1.total_rows, bool)
        if K:
            ids = np.asarray(hot_cache.select_hot(
                layout1, jnp.asarray(cnt), K, seed=0))
            hot[ids[ids >= 0]] = True
        bag_hit = hot[ev].reshape(-1, P).all(axis=1)
        hit = float(bag_hit.mean())
        result["cache"][f"hot{K}"] = {
            "hot_rows": K,
            "hit_rate": hit,
            "exchange_bytes_saved": int(bag_hit.sum()) * E * 4,
        }

    # --- measured wall-clock -------------------------------------------
    if "reference" in modes:
        f = jax.jit(apply_rows_split_sgd)
        result["reference"]["us_measured"] = _timeit(f, hi, lo, tgt,
                                                     grad_rows, 0.05)
    if measure_fused and "fused" in modes:
        # CPU interpret emulation runs the grid as an XLA while-loop that
        # round-trips EVERY carried buffer per step — O(shard_rows) per
        # touched row, the exact inverse of the kernel's on-TPU profile.
        # So: opt-in (--fused), tiny shard, one iteration.  The bytes model
        # above is the hardware-relevant number.
        Mm, Lm = 5_000, 256
        f = jax.jit(lambda h, l, t, d: ops.fused_row_update(
            "split_sgd", {"hi": h, "lo": l}, t, d, 0.05, pooling=P,
            interpret=True))
        us = _timeit(f, hi[:Mm], lo[:Mm],
                     jnp.minimum(tgt[:Lm], Mm - 1), dY[:Lm // P], iters=1)
        result["fused"]["us_measured_interpret"] = us
        result["fused"]["measured_lookups"] = Lm
        result["fused"]["measured_shard_rows"] = Mm
    return result


def merge_sections(old, new):
    """KEY-STABLE deep merge of a fresh bench result into the existing
    JSON: every dict level merges per key (``optimizers`` per optimizer
    name, ``reference``/``fused`` per metric), so a partial run — a
    ``--smoke`` sweep, a single ``--optimizer`` row, a ``--fused``-only
    timing — updates exactly the keys it computed and never drops the
    sections it didn't.  This is what lets the CI bench-regression gate
    (benchmarks/check_bench.py) diff the file against the committed
    baseline without spurious section-loss failures."""
    for k, v in new.items():
        if isinstance(v, dict) and isinstance(old.get(k), dict):
            merge_sections(old[k], v)
        else:
            old[k] = v
    return old


def main(argv=None):
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--fused", action="store_true",
                   help="measure only the fused Pallas path")
    g.add_argument("--reference", action="store_true",
                   help="measure only the segment_sum reference path")
    ap.add_argument("--optimizer", default="all",
                    help="RowOptimizer(s) for the per-optimizer roofline "
                         "rows: a registry name, or 'all' (default) for "
                         "the full registered sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="skip the 120-step convergence study; emit only "
                         "the bytes/step roofline rows (the CI sweep)")
    ap.add_argument("--json", default="BENCH_embedding_update.json",
                    help="where to write the update-bench JSON")
    ap.add_argument("--fresh", action="store_true",
                    help="write the JSON from scratch instead of the "
                         "key-stable merge — use when REFRESHING a "
                         "committed baseline, so sections a removed/"
                         "renamed optimizer no longer emits actually "
                         "disappear (the merge would carry them forever "
                         "and the CI gate would flag them as lost)")
    args, _ = ap.parse_known_args(argv)

    if not args.smoke:
        for name, val, derived in rows():
            print(f"{name},{val:.2f},{derived}")

    from repro.optim import row as row_optim
    optimizers = (row_optim.names() if args.optimizer == "all"
                  else (args.optimizer,))
    modes = (("fused",) if args.fused else
             ("reference",) if args.reference else ("reference", "fused"))
    res = embedding_update_bench(modes, measure_fused=args.fused,
                                 optimizers=optimizers)
    for path in ("reference", "fused"):
        b = res[path]["bytes_per_step"]
        print(f"embed_update_{path}_bytes_per_step,{b:.0f},"
              f"{res[path]['touches']}")
    print(f"embed_update_model_speedup,{res['model_speedup']:.1f},"
          f"bytes(ref)/bytes(fused) at U={res['config']['unique_rows']}")
    for name, r in res.get("optimizers", {}).items():
        print(f"embed_update_opt_{name}_bytes_per_step,"
              f"{r['bytes_per_step']:.0f},"
              f"state {r['state_bytes_per_row']}B/row, {r['touches']}")
    for k, r in res["cache"].items():
        if isinstance(r, dict):
            print(f"embed_update_cache_{k}_hit_rate,{r['hit_rate']:.4f},"
                  f"saves {r['exchange_bytes_saved']} B/step exchange")
    for path in ("reference", "fused"):
        for k in ("us_measured", "us_measured_interpret"):
            if k in res[path]:
                print(f"embed_update_{path}_{k},{res[path][k]:.1f},us")
    out_path = Path(args.json)
    if out_path.exists() and not args.fresh:
        try:
            res = merge_sections(json.loads(out_path.read_text()), res)
        except json.JSONDecodeError:
            pass          # corrupt/absent previous file: write fresh
    out_path.write_text(json.dumps(res, indent=2))
    print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
