"""Telemetry bench: tracer overhead + in-graph metrics reproduction.

Two sections, landing in ``BENCH_telemetry.json`` (gated by
benchmarks/check_bench.py):

* ``tracer`` — the cost of the observability layer itself.  A disabled
  tracer must be compiled-in-permanently cheap (one attribute check, a
  shared no-op context manager), and an ENABLED tracer wrapping a
  realistic ~1 ms step workload must cost < 3% wall-clock
  (``overhead_ok`` is an exact-gated bool; ``overhead_ratio`` rides the
  two-sided band for visibility).  Per-span costs are measured bare
  (span around ``pass``), the overhead ratio around a deterministic
  numpy workload sized like a small train step.
* ``metrics`` — the in-graph step-metrics vector
  (repro/telemetry/metrics.py) must REPRODUCE the cache bench: train the
  BENCH_pipeline.json cache config (zipf(1.05), hot_rows=64,
  promote_every=2, 8 forced devices) for 6 steps, run ONE more step on
  the held-out measurement batch, and the drained per-window
  ``skipped_bags / bags`` must equal the ``hot64.hit_rate`` the cache
  bench measured via ``hot_bag_local`` — exactly (both are an exact
  small-integer f32 sum and one f32 divide).  The window is also emitted
  as tracer counters and read back through ``repro.telemetry
  summarize``, pinning the whole trace -> summary path.  Every key in
  this section is deterministic, so the gate is EXACT.

Run:  PYTHONPATH=src python benchmarks/bench_telemetry.py
"""

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
sys.path.insert(0, str(SRC))

from repro.telemetry import Tracer  # noqa: E402

OVERHEAD_BUDGET = 1.03  # enabled tracer must cost < 3% on a ~1 ms step


# ---------------------------------------------------------------------------
# Section 1: tracer overhead (in-process, no jax)
# ---------------------------------------------------------------------------


def _span_cost_us(tracer: Tracer, n: int = 50_000, rounds: int = 5) -> float:
    """Per-span cost of ``with tracer.span(...): pass`` (min of rounds)."""
    best = float("inf")
    for _ in range(rounds):
        tracer.reset()
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.span("bench/span", step=0):
                pass
        best = min(best, time.perf_counter() - t0)
    return best / n * 1e6


def _workload_ms(tracer: Tracer, iters: int = 200, rounds: int = 5) -> float:
    """Mean wall per iteration of a deterministic ~1 ms numpy workload
    wrapped in one span, min over rounds (min rejects scheduler noise
    without hiding a systematic per-span cost)."""
    import numpy as np

    a = np.random.default_rng(0).standard_normal((256, 256))
    best = float("inf")
    for _ in range(rounds):
        tracer.reset()
        t0 = time.perf_counter()
        for i in range(iters):
            with tracer.span("bench/step", step=i):
                (a @ a).sum()
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def tracer_section() -> dict:
    off = Tracer(enabled=False)
    on = Tracer(enabled=True)
    cost_off = _span_cost_us(off)
    cost_on = _span_cost_us(on)
    wl_off = _workload_ms(off)
    wl_on = _workload_ms(on)
    ratio = wl_on / wl_off
    return {
        "span_cost_disabled_us": cost_off,
        "span_cost_enabled_us": cost_on,
        "workload_disabled_ms": wl_off,
        "workload_enabled_ms": wl_on,
        "overhead_ratio": ratio,
        "overhead_budget": OVERHEAD_BUDGET,
        "overhead_ok": bool(ratio < OVERHEAD_BUDGET),
    }


# ---------------------------------------------------------------------------
# Section 2: in-graph metrics vs the cache bench (forced-device subprocess)
# ---------------------------------------------------------------------------

SUB_METRICS = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ranks}"
import json, tempfile, jax, jax.numpy as jnp, numpy as np
from repro.core.dlrm import DLRMConfig, make_train_step, init_state
from repro.core import cache as hot_cache
from repro.data.synthetic import zipf_indices
from repro.launch.mesh import make_mesh
from repro.telemetry import Tracer
from repro.telemetry import metrics as step_mx
from repro.telemetry import summarize as tsum

mesh = make_mesh((1, {ranks}), ("data", "model"))
cfg = DLRMConfig(name="bench", num_dense=32, bottom=(64, 16), top=(64,),
                 table_rows=(2000,) * 8, emb_dim=16, pooling=5,
                 batch={batch}, emb_mode="table", idx_input="sharded",
                 hot_rows={hot}, promote_every=2, step_metrics=True)
step, shardings, bspecs, layout = make_train_step(cfg, mesh)
state, _ = init_state(jax.random.PRNGKey(0), cfg, mesh)
rng = np.random.default_rng(0)

def batch(i):
    idx = np.stack([zipf_indices(rng, m, ({batch}, 5), {zipf})
                    for m in cfg.table_rows], 1).astype(np.int32)
    return {{"idx": jnp.asarray(idx),
             "dense_x": jnp.asarray(rng.standard_normal(({batch}, 32)),
                                    jnp.bfloat16),
             "labels": jnp.asarray(rng.integers(0, 2, {batch}),
                                   jnp.float32)}}

for i in range({steps}):
    state, loss = step(state, batch(i))
jax.block_until_ready(loss)
# the cache bench's measurement: all-hot-bag fraction on the held-out
# batch, read straight off the post-training hot set
mb = batch({steps})
hit, _ = hot_cache.hot_bag_local(layout, state["cache"]["hot_w"],
                                 state["cache"]["hot_pos"], mb["idx"])
bench_hit_rate = float(jnp.mean(hit))
# the metrics path: one more step ON that batch — its epilogue reads the
# same pre-step hot set — and the drain window is that step alone
tdir = tempfile.mkdtemp()
tr = Tracer(enabled=True, trace_dir=tdir)
before = step_mx.drain(state)
step_mx.emit(tr, before)
state, loss = step(state, mb)
jax.block_until_ready(loss)
after = step_mx.drain(state)
step_mx.emit(tr, after)
win = step_mx.window(after, before)
trace = tr.export()
summ = tsum.summarize(trace)["metrics"]
print(json.dumps(dict(
    trained_steps={steps}, hot_rows={hot},
    bench_hit_rate=bench_hit_rate,
    window_hit_rate=step_mx.hit_rate(win),
    summarize_hit_rate=summ["last_window_hit_rate"],
    window={{k: win[k] for k in ("steps", "hit_lookups", "skipped_bags",
                                 "bags", "rows_touched",
                                 "exchange_payload_bytes")}},
    cumulative_steps=after["steps"],
)))
"""


def _run_sub(code: str) -> dict:
    # forced host devices: the child stays on the CPU, off any chip
    # the parent process may hold
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def metrics_section(ranks: int, batch: int, hot: int, steps: int,
                    zipf: float) -> dict:
    rec = _run_sub(SUB_METRICS.format(ranks=ranks, batch=batch, hot=hot,
                                      steps=steps, zipf=zipf))
    rec["measured_ranks"] = ranks
    rec["measured_batch"] = batch
    rec["zipf"] = zipf
    rec["reproduces_cache_bench"] = bool(
        rec["window_hit_rate"] == rec["bench_hit_rate"]
        == rec["summarize_hit_rate"])
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=8)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--hot-rows", type=int, default=64)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--zipf", type=float, default=1.05)
    ap.add_argument("--skip-metrics", action="store_true",
                    help="tracer-overhead section only (no subprocess)")
    ap.add_argument("--json", default=str(ROOT / "BENCH_telemetry.json"))
    args = ap.parse_args(argv)

    doc = {"tracer": tracer_section()}
    t = doc["tracer"]
    print(f"span_cost_disabled_us,{t['span_cost_disabled_us']:.4f}")
    print(f"span_cost_enabled_us,{t['span_cost_enabled_us']:.4f}")
    print(f"overhead_ratio,{t['overhead_ratio']:.5f},budget "
          f"{OVERHEAD_BUDGET} -> {'OK' if t['overhead_ok'] else 'FAIL'}")
    if not args.skip_metrics:
        doc["metrics"] = metrics_section(args.ranks, args.batch,
                                         args.hot_rows, args.steps,
                                         args.zipf)
        m = doc["metrics"]
        print(f"metrics_window,{json.dumps(m['window'])}")
        print(f"metrics_hit_rate,{m['window_hit_rate']:.9f},"
              f"bench {m['bench_hit_rate']:.9f},"
              f"summarize {m['summarize_hit_rate']:.9f},"
              f"{'EXACT' if m['reproduces_cache_bench'] else 'MISMATCH'}")
    Path(args.json).write_text(json.dumps(doc, indent=2))
    print(f"telemetry_json,1.0,{args.json}")
    if not doc["tracer"]["overhead_ok"]:
        return 1
    if not args.skip_metrics and not doc["metrics"]["reproduces_cache_bench"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
