"""Paper Tab. II / Eq. 1-2 reproduction + staged-pipeline overlap model.

Analytic communication volumes per DLRM config, cross-checked against the
collective bytes parsed out of compiled HLO:

    Eq. 1:  SZ_allreduce  = sum_l (f_i^l * f_o^l + f_o^l)   (per rank,
            rank-count independent -> the strong-scaling wall)
    Eq. 2:  SZ_alltoall   = S * N * E                        (global; per-rank
            share shrinks as ranks grow)

``--microbatches M0,M1,...`` additionally evaluates the staged microbatch
pipeline (repro/core/pipeline.py) at each M: the analytic step-time model
applies the paper's Sect. VI comm/compute OVERLAP term — with M
microbatches, microbatch i+1's index exchange + all-to-all runs under
microbatch i's dense compute, so

    t_serial(M)  = M * (t_ex/M + t_comp/M) + t_tail          (no overlap)
    t_overlap(M) = t_ex/M + (M-1) * max(t_comp/M, t_ex/M)
                   + t_comp/M + t_tail                        (pipelined)

and the overlap efficiency is the fraction of exchange time hidden under
compute.  Each M is also lowered+compiled on a forced-multi-device CPU
subprocess (the pipeline's regression surface) and, without ``--dry-run``,
timed end-to-end (CPU wall-clock: schedule-shape only, NOT
hardware-representative — the modeled numbers target TPU_V5E).  Results
land in ``BENCH_pipeline.json``.

``--exchange-dtype D0,D1,...`` (e.g. ``fp32,bf16``) additionally evaluates
the compressed exchange wire formats (repro/dist/exchange.py) at each
dtype: an analytic per-rank wire-volume model at the 64 modeled ranks
(the bwd dY all_to_all share of Eq. 2 + the dense-gradient
reduce-scatter share of Eq. 1 scale with the wire itemsize; the index
stream, the fwd layout switch, and the always-bf16 weight all-gather do
not), the Sect. VI overlap model re-run with the compressed exchange,
and a compiled-HLO leg (the wire dtype threaded into the measured
subprocess) whose collective bytes shrink accordingly.  Paired rows land
in the ``wire`` section next to ``wire_reduction_x`` — the modeled
compressible-byte reduction vs the fp32 wire, an EXACT gate key
(benchmarks/check_bench.py): it is a pure ratio of itemsizes, 2.0 for
bf16 — and ``wire_reduction_ok`` (>= 1.9, the acceptance floor).

``--cache-rows K0,K1,...`` additionally measures the frequency-tiered
hot-row cache (repro/core/cache.py, docs/cache.md) at each hot_rows=K on
a zipf(1.05) stream: the subprocess trains the table-mode pipelined step
for a few steps so the touch counters promote a real hot set, then reads
the measured all-hot-bag hit rate.  A bag served from the replicated hot
slab ships no all-to-all payload, so the paired rows report the payload-
effective exchange volume ``a2a * (1 - hit_rate)`` next to the K=0
baseline — the index stream (promotion is counter-local) and the HLO
collective set are unchanged.  The JSON write is a KEY-STABLE MERGE (same
contract as bench_split_sgd.py): a cache-only or pipeline-only run
updates exactly the sections it computed.
"""

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

from repro.configs.dlrm_paper import dlrm_large, dlrm_mlperf, dlrm_small
from repro.hw import TPU_V5E
from repro.models.mlp import allreduce_bytes

ROOT = Path(__file__).resolve().parents[1]
RESULTS = ROOT / "results" / "dryrun"
SRC = ROOT / "src"


def analytic(cfg):
    sz_allreduce = allreduce_bytes(cfg.bottom_sizes) + \
        allreduce_bytes(cfg.top_sizes)
    S, N, E = len(cfg.table_rows), cfg.batch, cfg.emb_dim
    sz_alltoall = S * N * E * 4
    emb_gib = cfg.spec.bytes(4) / 2**30
    return sz_allreduce, sz_alltoall, emb_gib


def dense_flops(cfg) -> float:
    """fwd+bwd MLP FLOPs per GLOBAL batch (3x fwd: fwd + dgrad + wgrad)."""
    total = 0
    for sizes in (cfg.bottom_sizes, cfg.top_sizes):
        for cin, cout in zip(sizes[:-1], sizes[1:]):
            total += 2 * cin * cout * cfg.batch
    return 3.0 * total


def pipeline_model(cfg, ranks: int, M: int, chip=TPU_V5E) -> dict:
    """Modeled per-rank step time with and without the overlap term."""
    S, N, E, P = len(cfg.table_rows), cfg.batch, cfg.emb_dim, cfg.pooling
    ici_bw = chip.ici_bw_per_link * chip.ici_links
    # per-rank exchange volume per STEP: index stream (int32) + the
    # fwd/bwd layout-switch share of Eq. 2 (both directions)
    idx_bytes = S * N * P * 4 / ranks
    a2a_bytes = 2 * (S * N * E * 4) / ranks
    t_ex = (idx_bytes + a2a_bytes) / ici_bw
    t_comp = dense_flops(cfg) / ranks / chip.peak_flops_bf16
    # tail (not pipelined): sparse touched-row update + dense RS+AG
    sz_ar = allreduce_bytes(cfg.bottom_sizes) + allreduce_bytes(cfg.top_sizes)
    t_tail = (sz_ar / ici_bw
              + (2 * N * S * E * 4 / ranks) / chip.hbm_bw)
    ex_mb, comp_mb = t_ex / M, t_comp / M
    t_serial = M * (ex_mb + comp_mb) + t_tail
    t_overlap = ex_mb + (M - 1) * max(comp_mb, ex_mb) + comp_mb + t_tail
    hidden = (M - 1) * min(comp_mb, ex_mb)
    return {
        "microbatches": M,
        "exchange_ms_per_microbatch": ex_mb * 1e3,
        "compute_ms_per_microbatch": comp_mb * 1e3,
        "tail_ms": t_tail * 1e3,
        "modeled_serial_ms": t_serial * 1e3,
        "modeled_overlap_ms": t_overlap * 1e3,
        "overlap_efficiency": (hidden / t_ex) if t_ex else 0.0,
    }


# ---------------------------------------------------------------------------
# Measured leg: lower/compile (and optionally time) the pipelined step on a
# forced-multi-device CPU subprocess.
# ---------------------------------------------------------------------------

SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ranks}"
import json, time, jax, jax.numpy as jnp, numpy as np
from repro.core.dlrm import DLRMConfig, make_train_step, init_state
from repro.core import sharded_embedding as se
from repro.dist.exchange import ExchangeConfig
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import parse_collective_bytes

mesh = make_mesh((1, {ranks}), ("data", "model"))
wire = {exdt}
cfg = DLRMConfig(name="bench", num_dense=32, bottom=(64, 16), top=(64,),
                 table_rows=(2000,) * 8, emb_dim=16, pooling=5,
                 batch={batch}, emb_mode="table", microbatches={mb},
                 exchange=(ExchangeConfig() if wire is None else
                           ExchangeConfig(dY_dtype=wire, dense_dtype=wire)))
step, shardings, bspecs, layout = make_train_step(cfg, mesh)
state, _ = init_state(jax.random.PRNGKey(0), cfg, mesh)
rng = np.random.default_rng(0)
idx = np.stack([rng.integers(0, m, ({batch}, 5))
                for m in cfg.table_rows], 1).astype(np.int32)
idx = np.asarray(se.permute_indices(layout, jnp.asarray(idx)))
batch = {{"idx": jnp.asarray(idx),
         "dense_x": jnp.asarray(rng.standard_normal(({batch}, 32)),
                                jnp.bfloat16),
         "labels": jnp.asarray(rng.integers(0, 2, {batch}), jnp.float32)}}
lowered = step.lower(state, batch)
compiled = lowered.compile()
coll = parse_collective_bytes(compiled.as_text())
measured_ms = None
if not {dry_run}:
    state, loss = step(state, batch)     # warm donation-compatible call
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(5):
        state, loss = step(state, batch)
    jax.block_until_ready(loss)
    measured_ms = (time.perf_counter() - t0) / 5 * 1e3
print(json.dumps(dict(microbatches={mb}, measured_ms=measured_ms,
                      collective_bytes=coll["bytes_by_op"],
                      collective_counts=coll["counts"])))
"""


def run_measured(ranks: int, batch: int, mb: int, dry_run: bool,
                 wire: str | None = None) -> dict:
    return _run_sub(SUB.format(ranks=ranks, batch=batch, mb=mb,
                               dry_run=dry_run, exdt=repr(wire)))


def _run_sub(code: str) -> dict:
    # forced host devices: the child stays on the CPU, off any chip
    # the parent process may hold
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


# Hot-row cache leg: train the REAL table-mode pipelined step on a
# zipf(1.05) stream so the counter-driven promotion picks an actual hot
# set, then measure the all-hot-bag hit rate on a held-out batch.  The
# batch stream is seed-deterministic and promotion is integer-exact, so
# hit_rate is an EXACT gate key (benchmarks/check_bench.py), not a
# tolerance-band one.
SUB_CACHE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ranks}"
import json, jax, jax.numpy as jnp, numpy as np
from repro.core.dlrm import DLRMConfig, make_train_step, init_state
from repro.core import cache as hot_cache
from repro.data.synthetic import zipf_indices
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import parse_collective_bytes

mesh = make_mesh((1, {ranks}), ("data", "model"))
cfg = DLRMConfig(name="bench", num_dense=32, bottom=(64, 16), top=(64,),
                 table_rows=(2000,) * 8, emb_dim=16, pooling=5,
                 batch={batch}, emb_mode="table", idx_input="sharded",
                 hot_rows={hot}, promote_every=2)
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

b0 = batch(0)
coll = parse_collective_bytes(step.lower(state, b0).compile().as_text())
for i in range({steps}):
    state, loss = step(state, b0 if i == 0 else batch(i))
jax.block_until_ready(loss)
hit_rate = 0.0
if {hot} > 0:
    hit, _ = hot_cache.hot_bag_local(layout, state["cache"]["hot_w"],
                                     state["cache"]["hot_pos"],
                                     batch({steps})["idx"])
    hit_rate = float(jnp.mean(hit))
print(json.dumps(dict(hot_rows={hot}, hit_rate=hit_rate,
                      trained_steps={steps},
                      collective_bytes=coll["bytes_by_op"],
                      collective_counts=coll["counts"])))
"""


def rows():
    out = []
    for mk, name in ((dlrm_small, "dlrm-small"), (dlrm_large, "dlrm-large"),
                     (dlrm_mlperf, "dlrm-mlperf")):
        cfg = mk()
        ar, a2a, emb = analytic(cfg)
        out.append((f"{name}_eq1_allreduce_MB", ar / 2**20, "paper Eq.1"))
        out.append((f"{name}_eq2_alltoall_MB", a2a / 2**20, "paper Eq.2"))
        out.append((f"{name}_emb_capacity_GiB", emb, "paper Tab.II row 1"))
        f = RESULTS / f"{name}__train_tablewise__pod1x16x16.json"
        if f.exists():
            rec = json.loads(f.read_text())
            if rec.get("status") == "ok":
                coll = rec["collectives"]["bytes_by_op"]
                out.append((f"{name}_measured_a2a_MB_per_dev",
                            coll.get("all-to-all", 0) / 2**20,
                            "compiled HLO (table mode)"))
    return out


def pipeline_rows(microbatches, ranks: int, batch: int, dry_run: bool,
                  json_path: Path):
    cfg_model = dlrm_small(mode="table")
    points = []
    out = []
    for M in microbatches:
        rec = pipeline_model(cfg_model, ranks=64, M=M)
        measured = run_measured(ranks, batch, M, dry_run)
        rec.update(measured)
        points.append(rec)
        out.append((f"pipeline_M{M}_modeled_serial_ms",
                    rec["modeled_serial_ms"], "no-overlap model @64r"))
        out.append((f"pipeline_M{M}_modeled_overlap_ms",
                    rec["modeled_overlap_ms"], "Sect.VI overlap model @64r"))
        out.append((f"pipeline_M{M}_overlap_efficiency",
                    rec["overlap_efficiency"], "hidden/total exchange"))
        if rec.get("measured_ms") is not None:
            out.append((f"pipeline_M{M}_measured_ms", rec["measured_ms"],
                        f"CPU wall-clock {ranks}r (schedule shape only)"))
    _write_merged(json_path, {
        "model_config": cfg_model.name,
        "modeled_chip": TPU_V5E.name,
        "modeled_ranks": 64,
        "measured_ranks": ranks,
        "measured_batch": batch,
        "measured_backend": "cpu-forced-devices"
                            + (" (dry-run, compile only)" if dry_run else ""),
        "points": points,
    })
    out.append(("pipeline_json", 1.0, str(json_path)))
    return out


def wire_rows(dtypes, ranks: int, batch: int, dry_run: bool,
              json_path: Path, chip=TPU_V5E):
    """Compressed exchange wire formats (repro/dist/exchange.py): analytic
    per-rank wire volume + the Sect. VI overlap model at each dtype, and a
    compiled-HLO leg with the wire dtype threaded into the subprocess.

    The compressible volume is the bwd dY all_to_all share of Eq. 2 plus
    the dense-gradient reduce-scatter share of Eq. 1; the index stream,
    the fwd layout switch (fp32) and the weight all-gather (always bf16 —
    the Split-SGD hi half) are wire-dtype-independent."""
    from repro.dist.exchange import wire_itemsize

    cfg = dlrm_small(mode="table")
    S, N, E, P = len(cfg.table_rows), cfg.batch, cfg.emb_dim, cfg.pooling
    RM, M = 64, 4                      # modeled ranks / microbatches
    ici_bw = chip.ici_bw_per_link * chip.ici_links
    ag_B = (allreduce_bytes(cfg.bottom_sizes, bytes_per_elem=2)
            + allreduce_bytes(cfg.top_sizes, bytes_per_elem=2))

    def model(isz: int) -> dict:
        dY_B = S * N * E * isz / RM
        rs_B = (allreduce_bytes(cfg.bottom_sizes, bytes_per_elem=isz)
                + allreduce_bytes(cfg.top_sizes, bytes_per_elem=isz))
        idx_bytes = S * N * P * 4 / RM
        a2a_bytes = (S * N * E * 4) / RM + dY_B      # fwd fp32 + bwd wire
        t_ex = (idx_bytes + a2a_bytes) / ici_bw
        t_comp = dense_flops(cfg) / RM / chip.peak_flops_bf16
        t_tail = ((rs_B + ag_B) / ici_bw
                  + (2 * N * S * E * 4 / RM) / chip.hbm_bw)
        ex_mb, comp_mb = t_ex / M, t_comp / M
        t_overlap = ex_mb + (M - 1) * max(comp_mb, ex_mb) + comp_mb + t_tail
        return {"wire_itemsize_B": isz,
                "modeled_dY_a2a_B_per_rank": dY_B,
                "modeled_dense_rs_B": rs_B,
                "modeled_dense_ag_B": ag_B,
                "modeled_compressible_B": dY_B + rs_B,
                "modeled_overlap_s": t_overlap}

    fp32_ref = model(4)
    section, out = {}, []
    for dt in dtypes:
        rec = model(wire_itemsize(dt))
        rec["modeled_overlap_speedup_x"] = (fp32_ref["modeled_overlap_s"]
                                            / rec["modeled_overlap_s"])
        measured = run_measured(ranks, batch, 1, dry_run, wire=dt)
        rec["collective_bytes"] = measured["collective_bytes"]
        rec["collective_counts"] = measured["collective_counts"]
        section[dt] = rec
        out.append((f"wire_{dt}_compressible_B_per_rank",
                    rec["modeled_compressible_B"],
                    "bwd dY a2a (Eq.2 share) + dense RS (Eq.1) @64r"))
        out.append((f"wire_{dt}_overlap_speedup_x",
                    rec["modeled_overlap_speedup_x"],
                    "Sect.VI overlap model vs fp32 wire @64r M=4"))
        out.append((f"wire_{dt}_measured_a2a_B",
                    measured["collective_bytes"].get("all-to-all", 0),
                    f"compiled HLO, {ranks}r table mode"))
    if "fp32" in section:
        base_B = section["fp32"]["modeled_compressible_B"]
        for dt in dtypes:
            red = base_B / section[dt]["modeled_compressible_B"]
            section[dt]["wire_reduction_x"] = red
            if dt != "fp32":
                section[dt]["wire_reduction_ok"] = bool(red >= 1.9)
                out.append((f"wire_{dt}_reduction_x", red,
                            "modeled compressible bytes vs fp32 wire"))
    _write_merged(json_path, {"wire": dict(
        section, modeled_ranks=RM, modeled_microbatches=M,
        measured_ranks=ranks, measured_batch=batch)})
    return out


def merge_sections(old, new):
    # local copy of bench_split_sgd.merge_sections (same dual-path import
    # caveat as bench_split_sgd._timeit): key-stable deep merge, so a
    # cache-only run never drops the pipeline points and vice versa
    for k, v in new.items():
        if isinstance(v, dict) and isinstance(old.get(k), dict):
            merge_sections(old[k], v)
        else:
            old[k] = v
    return old


def _write_merged(json_path: Path, new: dict) -> None:
    old = {}
    if json_path.exists():
        try:
            old = json.loads(json_path.read_text())
        except json.JSONDecodeError:
            pass          # corrupt previous file: write fresh
    json_path.write_text(json.dumps(merge_sections(old, new), indent=2))


def cache_rows(ks, ranks: int, batch: int, json_path: Path,
               steps: int = 6, zipf: float = 1.05):
    """Paired hot_rows=K rows: measured hit rate + payload-effective
    all-to-all volume on the zipf stream, vs the K=0 baseline."""
    section = {}
    out = []
    # Eq.2 share of the measured bench config (S=8 tables, E=16, fwd+bwd)
    raw_a2a = 2 * (8 * batch * 16 * 4) / ranks
    for K in ks:
        rec = _run_sub(SUB_CACHE.format(ranks=ranks, batch=batch, hot=K,
                                        steps=steps, zipf=zipf))
        hit = rec["hit_rate"]
        rec["a2a_payload_per_rank"] = raw_a2a
        rec["a2a_payload_effective_per_rank"] = raw_a2a * (1.0 - hit)
        rec["exchange_bytes_saved"] = raw_a2a * hit
        rec["a2a_reduction_x"] = (1.0 / (1.0 - hit)) if hit < 1.0 else \
            float("inf")
        section[f"hot{K}"] = rec
        out.append((f"cache_hot{K}_hit_rate", hit,
                    f"all-hot-bag fraction, zipf({zipf}) after "
                    f"{steps} steps"))
        out.append((f"cache_hot{K}_a2a_effective_B_per_rank",
                    rec["a2a_payload_effective_per_rank"],
                    "a2a payload x (1 - hit_rate)"))
        out.append((f"cache_hot{K}_a2a_reduction_x",
                    rec["a2a_reduction_x"], "vs own raw a2a payload"))
    _write_merged(json_path, {"cache": dict(
        section, measured_ranks=ranks, measured_batch=batch, zipf=zipf)})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--microbatches", default=None,
                    help="comma list, e.g. 1,2,4: evaluate the staged "
                         "pipeline at each M (model + compile + measure)")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile each M but skip wall-clock timing")
    ap.add_argument("--ranks", type=int, default=8,
                    help="forced device count for the measured leg")
    ap.add_argument("--batch", type=int, default=64,
                    help="global batch for the measured leg")
    ap.add_argument("--exchange-dtype", default=None,
                    help="comma list of wire formats, e.g. fp32,bf16: "
                         "model + compile the compressed exchange "
                         "collectives at each dtype "
                         "(repro/dist/exchange.py)")
    ap.add_argument("--cache-rows", default=None,
                    help="comma list of hot_rows K values, e.g. 0,64: "
                         "measure the hot-row cache's bag hit rate and "
                         "payload-effective all-to-all volume at each K "
                         "on a zipf(1.05) stream (docs/cache.md)")
    ap.add_argument("--json", default=str(ROOT / "BENCH_pipeline.json"))
    args = ap.parse_args(argv)

    for name, val, derived in rows():
        print(f"{name},{val:.2f},{derived}")
    if args.microbatches:
        ms = [int(x) for x in args.microbatches.split(",") if x]
        for name, val, derived in pipeline_rows(
                ms, args.ranks, args.batch, args.dry_run, Path(args.json)):
            print(f"{name},{val:.4f},{derived}")
    if args.exchange_dtype:
        dts = [x for x in args.exchange_dtype.split(",") if x]
        for name, val, derived in wire_rows(dts, args.ranks, args.batch,
                                            args.dry_run, Path(args.json)):
            print(f"{name},{val:.4f},{derived}")
    if args.cache_rows:
        ks = [int(x) for x in args.cache_rows.split(",") if x]
        for name, val, derived in cache_rows(ks, args.ranks, args.batch,
                                             Path(args.json)):
            print(f"{name},{val:.4f},{derived}")


if __name__ == "__main__":
    main()
