"""Training driver.

Runs REDUCED-scale versions of the registered architectures on the local
device set by default; ``--paper`` trains the paper's own DLRM configs
(``configs/dlrm_paper.py``: published widths and batch) instead.
Examples:

    PYTHONPATH=src python -m repro.launch.train --arch dlrm-small --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch dlrm-small --paper \
        --emb-mode table --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch fm --steps 100
    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 20 --preset smoke

With XLA_FLAGS=--xla_force_host_platform_device_count=8 the hybrid-parallel
paths run on a real (2, 4) mesh; single-device otherwise.  Compiled
programs persist in JAX's compilation cache (``repro.launch.compile_cache``).

Recsys archs can stream a PACKED dataset (docs/data.md) instead of the
in-process synthetic generator:

    python -m repro.data.format synthetic --out /data/ds \
        --tables 5000,...x8 --pooling 10 --num-dense 64 --num-samples 65536
    python -m repro.launch.train --arch dlrm-small --data-dir /data/ds \
        --data-format packed --host-presort

``--host-presort`` moves the sparse-update index sort off the device and
into the loader's worker thread (row and table mode; see
repro/data/pipeline.py), and ``--optimizer`` selects the sparse
RowOptimizer of the embedding path (docs/optim.md).
"""

from __future__ import annotations

import argparse
import dataclasses
import types
from pathlib import Path

import jax
import numpy as np

from repro import telemetry
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.train import TrainLoop, TrainLoopConfig


def _bspec_shardings(mesh, bspecs):
    """NamedShardings for a batch-spec tree, so the prefetch iterator's
    device_put lands each batch directly in the step's input placement."""
    from repro.dist import sharding
    return sharding.named(mesh, bspecs)


def local_mesh():
    n = len(jax.devices())
    if n >= 8:
        return make_mesh((n // 4, 4), ("data", "model"))
    if n > 1:
        return make_mesh((1, n), ("data", "model"))
    return make_mesh((1, 1), ("data", "model"))


def packed_stream(args, expect, layout, host_presort: bool):
    """Build the packed-shard loader chain for a recsys arch: ShardedReader
    (mmap + two-level shuffle) -> HostPipeline (threaded decode + optional
    per-batch pre-sort).  ``expect`` carries the model-side schema the
    DatasetSpec must match (fail at wiring time, not inside shard_map)."""
    from repro.data.pipeline import HostPipeline
    from repro.data.reader import ShardedReader
    unsupported = sorted(set(expect.get("extras", ()))
                         - {"dense_x", "labels"})
    if unsupported:
        raise SystemExit(
            f"--data-format packed cannot feed this arch: batch extras "
            f"{unsupported} are not representable in the shard format "
            "(dense_x/labels/sparse+weights only) — use the synthetic "
            "stream for it")
    reader = ShardedReader(args.data_dir, batch=expect["batch"],
                           seed=args.seed, shuffle=True)
    reader.spec.check(expect["table_rows"], expect["pooling"],
                      num_dense=expect.get("num_dense", 0),
                      labels=expect.get("labels", True),
                      slot_to_table=expect.get("slot_to_table"),
                      weighted=expect.get("weighted", False))
    if reader.spec.weighted and not expect.get("weighted", False):
        raise SystemExit("dataset carries per-lookup weights but the model "
                         "is unweighted — pass --weighted (or repack "
                         "without weights)")
    print(f"[train] packed dataset: {reader.num_samples} samples in "
          f"{len(reader.shards)} shard(s), "
          f"{reader.batches_per_epoch()} batches/epoch"
          + (", host pre-sort ON" if host_presort else ""))
    return HostPipeline(reader, layout=layout, presort=host_presort)


def serve_smoke(mdef, mesh, publisher, batch, buckets):
    """Post-train serving smoke (--serve-smoke): continuous batching over
    the published snapshot with a burst of single-sample requests sliced
    from one synthetic batch; per-bucket latency + freshness printed."""
    from repro.serve import ContinuousBatchingServer, make_bucket_scorers
    registry = publisher.registry
    score_fns, pad_batch = make_bucket_scorers(
        mdef, mesh, buckets, lambda: registry.current().state)
    n = int(np.asarray(batch["idx"]).shape[0])
    payloads = [{k: np.asarray(v)[i] for k, v in batch.items()}
                for i in range(n)]
    with ContinuousBatchingServer(score_fns, pad_batch,
                                  max_wait_ms=2.0) as srv:
        handles = [srv.submit(p) for p in payloads]
        scores = [h.result(timeout=120.0) for h in handles]
        stats = srv.stats()
        pct = srv.percentiles()
    print(f"[serve] smoke: {len(scores)} requests scored in "
          f"{sum(stats['batches'].values())} batches "
          f"(padded rows: {stats['padded']})")
    for b in sorted(pct):
        p = pct[b]
        print(f"[serve]   bucket {b:>4}: p50 {p['p50_ms']:8.2f} ms   "
              f"p99 {p['p99_ms']:8.2f} ms   n={p['n']}")
    f = publisher.freshness()
    print(f"[serve] snapshot v{f['version']}: {f['steps_behind']} steps / "
          f"{f['seconds_behind']:.2f}s behind the training head")


def reduced_dlrm(name: str, batch: int):
    from repro.core.dlrm import DLRMConfig
    if name == "dlrm-100m":
        # ~103M params: the end-to-end "train a ~100M model" driver
        return DLRMConfig(name=name, num_dense=64, bottom=(128, 64),
                          top=(256, 128), table_rows=(200_000,) * 8,
                          emb_dim=64, pooling=20, batch=batch)
    return DLRMConfig(name=name, num_dense=64, bottom=(64, 32),
                      top=(64, 32), table_rows=(5000,) * 8, emb_dim=32,
                      pooling=10, batch=batch)


def dlrm_config(args):
    """The DLRMConfig a run trains: with ``--paper`` the registered paper
    config (``configs/dlrm_paper.py``) at its published widths and batch,
    or with ``--share-of N`` one chip's share of it deployed over N chips
    (``--batch`` overrides the batch either trains), else the reduced
    one; then the run's optimizer / pipeline flags."""
    if args.paper:
        from repro.configs import dlrm_paper
        make = {"dlrm-small": dlrm_paper.dlrm_small,
                "dlrm-large": dlrm_paper.dlrm_large,
                "dlrm-mlperf": dlrm_paper.dlrm_mlperf}[args.arch]
        cfg = make(mode=args.emb_mode or "row", batch=args.batch,
                   share_of=args.share_of)
    else:
        cfg = reduced_dlrm(args.arch, args.batch or 256)
        if args.emb_mode:
            cfg = dataclasses.replace(cfg, emb_mode=args.emb_mode)
    return dataclasses.replace(
        cfg, **step_options(args, cfg.lr if args.paper else 0.05))


def step_options(args, default_lr: float) -> dict:
    """The step options (``repro.core.hybrid.StepOptions``) a run's flags
    set, the one list every arch's model definition takes; ``default_lr``
    is the rate when ``--lr`` is not given."""
    from repro.dist.exchange import ExchangeConfig
    wire = args.exchange_dtype
    return dict(
        lr=default_lr if args.lr is None else args.lr,
        sparse_optimizer=args.optimizer, opt_beta=args.beta,
        opt_eps=args.eps, microbatches=args.microbatches,
        host_presort=args.host_presort, weighted=args.weighted,
        sr_seed=args.seed, hot_rows=args.hot_rows,
        promote_every=args.promote_every, hot_sync=args.hot_sync,
        exchange=(ExchangeConfig() if wire is None
                  else ExchangeConfig(dY_dtype=wire, dense_dtype=wire)),
        step_metrics=args.step_metrics)


def build_dlrm(args, mesh, key):
    """Everything a DLRM run needs, built the one way both ``main``
    and ``chip_smoke.py`` use: config, initial state, the jitted
    pipelined step, the batch placement and the batch source."""
    from repro.core import dlrm as D
    from repro.data.synthetic import dlrm_stream
    from repro.kernels.embedding_update import group_rows
    from repro.optim import row as row_optim
    cfg = dlrm_config(args)
    state, layout = D.init_state(key, cfg, mesh)
    # what the run stands for, on the trace beside its compiles, and the
    # row groups of the sparse-update kernel on one shard's slabs, sized
    # by that shard's share of the step's lookups
    lookups = cfg.batch * len(cfg.table_rows) * cfg.pooling
    slabs = [state["emb"][k] for k in row_optim.resolve(cfg).slab_keys]
    G = group_rows([(layout.rows_per_shard, s.shape[1]) for s in slabs],
                   [s.dtype for s in slabs], lookups / layout.num_shards)
    telemetry.instant("train/config", cat="train", arch=cfg.name,
                      deployment_chips=cfg.deployment_chips,
                      rows_per_table=list(cfg.table_rows), batch=cfg.batch,
                      lookups_per_step=lookups, sparse_group_rows=G,
                      sparse_groups=-(-layout.rows_per_shard // G))
    step, shardings, bspecs, _ = D.make_train_step(cfg, mesh)
    if args.data_format == "packed":
        stream = packed_stream(
            args, dict(batch=cfg.batch, table_rows=cfg.table_rows,
                       pooling=cfg.pooling, num_dense=cfg.num_dense,
                       weighted=cfg.weighted),
            layout, args.host_presort)
    else:
        stream = dlrm_stream(0, cfg, args.alpha, layout)
    return types.SimpleNamespace(
        cfg=cfg, state=state, layout=layout, step=step,
        shardings=shardings, bspecs=bspecs,
        batch_shardings=_bspec_shardings(mesh, bspecs), stream=stream,
        smoke_stream=lambda: dlrm_stream(1, cfg, args.alpha, layout),
        profile_def=D.as_hybrid_def(cfg))


def hybrid_def(args):
    """The HybridDef a recsys arch (fm | bst | sasrec | din) trains: its
    reduced config, then the run's step options."""
    return dataclasses.replace(reduced_hybrid(args.arch, args.batch or 256),
                               **step_options(args, 0.05))


def reduced_hybrid(name: str, batch: int):
    from repro.models import recsys as R
    if name == "fm":
        return R.make_fm((10_000,) * 39, batch=batch)
    if name == "bst":
        return R.make_bst(50_000, (1000,) * 8, batch=batch)
    if name == "sasrec":
        return R.make_sasrec(50_000, batch=batch)
    if name == "din":
        return R.make_din(50_000, (1000,) * 4, batch=batch)
    raise KeyError(name)


def reduced_lm(name: str, batch: int, seq: int):
    from repro.models.transformer import TransformerConfig
    base = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_head=32,
                d_ff=256, vocab=512, seq_shard=False, tp_size=1)
    if "moe" in name or "deepseek" in name:
        base.update(n_experts=8, top_k=2, moe_d_ff=64)
    if "deepseek" in name:
        base.update(mla=True, q_lora=64, kv_lora=64, qk_nope=16, qk_rope=16,
                    v_head=32, n_heads=4, d_head=32)
    if "gemma2" in name:
        base.update(local_global=True, window=64, attn_softcap=50.0,
                    final_softcap=30.0, embed_scale=True)
    return TransformerConfig(name=name, **base), batch, seq


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--paper", action="store_true",
                    help="train the paper's own config of a dlrm arch "
                         "(configs/dlrm_paper.py: dlrm-small | dlrm-large "
                         "| dlrm-mlperf) at its published widths and "
                         "batch, instead of the reduced one")
    ap.add_argument("--emb-mode", choices=("row", "table"), default=None,
                    help="embedding placement of a dlrm arch: row-wise "
                         "sharded rows or the paper's table-wise "
                         "placement (default: the config's own, row)")
    ap.add_argument("--share-of", type=int, default=1, metavar="N",
                    help="with --paper: train one chip's share of the "
                         "config deployed row-wise over N chips — every "
                         "table's rows divided evenly over them (this "
                         "chip holds one slice of each), the dense half "
                         "data-parallel at the published minibatch / N; "
                         "every width as published, no exchange")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: 256 for the reduced "
                         "archs, the published batch with --paper, the "
                         "published batch / N with --share-of N)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate (default: 0.05 for the reduced "
                         "archs, the config's own with --paper)")
    ap.add_argument("--optimizer", default=None,
                    help="sparse RowOptimizer for the embedding path "
                         "(repro/optim/row.py): sgd | split_sgd | momentum "
                         "| adagrad_rowwise | adagrad | momentum_bf16 | "
                         "adagrad_bf16 (the _bf16 kinds store compressed "
                         "bf16-hi state with seeded stochastic rounding) | "
                         "adagrad_freq (frequency-adaptive LR off the "
                         "hot-row cache's touch counters); default keeps "
                         "the arch's configured optimizer (split_sgd)")
    ap.add_argument("--beta", type=float, default=None,
                    help="momentum coefficient override for --optimizer")
    ap.add_argument("--eps", type=float, default=None,
                    help="adagrad denominator floor override for "
                         "--optimizer")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="checkpoint cadence in completed steps (preemption "
                         "cost: up to ckpt-every-1 steps of lost work)")
    ap.add_argument("--skip-batch-budget", type=int, default=0,
                    help="transient loader failures absorbed per run "
                         "(each skip is logged; beyond the budget the "
                         "failure propagates)")
    ap.add_argument("--event-log", default=None,
                    help="append structured failure/recovery events "
                         "(checkpoint retries, corrupt-checkpoint skips, "
                         "batch skips, preemptions) to this .jsonl file")
    ap.add_argument("--alpha", type=float, default=0.0,
                    help="index-skew for sparse streams (paper Fig. 8)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="staged-pipeline microbatches (core/pipeline.py): "
                         "double-buffered index exchange overlap")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="host-side device_put-ahead window (0 = off)")
    ap.add_argument("--data-dir", default=None,
                    help="packed-shard dataset directory (docs/data.md)")
    ap.add_argument("--data-format", choices=("synthetic", "packed"),
                    default=None,
                    help="batch source; defaults to 'packed' when "
                         "--data-dir is given, else 'synthetic'")
    ap.add_argument("--host-presort", action="store_true",
                    help="pre-sort the sparse-update index stream on the "
                         "loader thread (row and table mode; drops the "
                         "on-device sort from the step)")
    ap.add_argument("--seed", type=int, default=0,
                    help="data order seed (reader epoch shuffle); also "
                         "seeds the stochastic-rounding counter of the "
                         "_bf16 compressed-state optimizers")
    ap.add_argument("--weighted", action="store_true",
                    help="weighted bags: consume the packed dataset's "
                         "per-lookup weight arrays (recsys archs)")
    ap.add_argument("--hot-rows", type=int, default=0,
                    help="frequency-tiered hot-row cache (docs/cache.md): "
                         "replicate the top-K touched rows PER TABLE on "
                         "every rank so hot bags skip the all-to-all "
                         "(table mode); 0 = off")
    ap.add_argument("--promote-every", type=int, default=1,
                    help="hot-set promotion cadence in steps (counter-"
                         "driven, deterministic across ranks/restarts)")
    ap.add_argument("--hot-sync", default="allreduce",
                    help="hot-slab refresh: 'allreduce' (every step; "
                         "bitwise == cache off) or 'deferred:N' (refresh "
                         "every N steps; bounded staleness)")
    ap.add_argument("--exchange-dtype", default=None,
                    choices=("fp32", "bf16", "bf16_sr"),
                    help="wire format of the dY exchange + dense gradient "
                         "reduce-scatter (docs/pipeline.md 'Communication "
                         "precision'): fp32 = today's wire (bitwise), "
                         "bf16 = round-to-nearest (dense leg carries "
                         "error feedback), bf16_sr = seeded stochastic "
                         "rounding (deterministic, checkpoint-replayable)")
    ap.add_argument("--trace-dir", default=None,
                    help="enable the process tracer (docs/telemetry.md): "
                         "writes <dir>/trace.json (Chrome trace-event "
                         "JSON, open in Perfetto), <dir>/heartbeat.jsonl "
                         "(per-window train-loop heartbeats), a "
                         "jax.profiler trace of the steps between the "
                         "first and second heartbeat under <dir>/device/ "
                         "(device ops, stage scopes and the same spans on "
                         "one clock) and — unless --event-log points "
                         "elsewhere — <dir>/events.jsonl")
    ap.add_argument("--step-metrics", action="store_true",
                    help="accumulate in-graph step metrics (cache hits, "
                         "rows touched, exchange payload bytes) in a "
                         "replicated state vector, drained every "
                         "--metrics-every steps (recsys archs)")
    ap.add_argument("--metrics-every", type=int, default=10,
                    help="in-graph metrics drain / heartbeat cadence "
                         "(steps)")
    ap.add_argument("--preempt-at", type=int, default=None,
                    help="preemption drill: request a stop at this step "
                         "(records a 'preempted' event, writes the final "
                         "checkpoint) — gives smoke traces a fault track")
    ap.add_argument("--publish-every", type=int, default=0,
                    help="publish a read-only serving snapshot of the "
                         "bf16-hi tables every N completed steps "
                         "(docs/serve.md; recsys archs); snapshot version "
                         "and train-to-serve freshness ride the heartbeat; "
                         "0 = off")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="after training, drive a continuous-batching "
                         "serving smoke over the published snapshot "
                         "(per-bucket latency percentiles printed)")
    ap.add_argument("--serve-buckets", default="8,32,128",
                    help="compiled serving batch-shape ladder for "
                         "--serve-smoke (ascending, comma-separated)")
    args = ap.parse_args(argv)
    if args.data_format is None:
        args.data_format = "packed" if args.data_dir else "synthetic"
    if args.paper and args.arch not in ("dlrm-small", "dlrm-large",
                                        "dlrm-mlperf"):
        raise SystemExit("--paper trains a registered paper config: "
                         "dlrm-small | dlrm-large | dlrm-mlperf")
    if args.emb_mode and not args.arch.startswith("dlrm"):
        raise SystemExit("--emb-mode places the embeddings of a dlrm arch")
    if args.share_of != 1:
        if not args.paper:
            raise SystemExit("--share-of takes a share of a paper config: "
                             "add --paper")
        if args.emb_mode == "table":
            raise SystemExit("--share-of holds a row-wise slice of every "
                             "table: use --emb-mode row")
        try:
            dlrm_config(args)
        except ValueError as e:
            raise SystemExit(f"--share-of {args.share_of}: {e}") from None
    return args


def main():
    args = parse_args()
    compile_cache.enable()
    if args.trace_dir:
        telemetry.configure(enabled=True, trace_dir=args.trace_dir)
    if args.data_format == "packed" and not args.data_dir:
        raise SystemExit("--data-format packed requires --data-dir")
    if args.weighted and args.data_format != "packed":
        raise SystemExit("--weighted needs a weighted packed dataset "
                         "(the synthetic streams carry no weights); pack "
                         "one with `python -m repro.data synthetic "
                         "--weighted ...`")

    mesh = local_mesh()
    print(f"[train] devices={len(jax.devices())} mesh={dict(mesh.shape)}")
    key = jax.random.PRNGKey(0)
    batch_shardings = None

    if args.host_presort and args.data_format != "packed":
        raise SystemExit("--host-presort rides the packed loader's worker "
                         "thread; add --data-dir/--data-format packed")
    if ((args.beta is not None or args.eps is not None)
            and args.optimizer is None):
        raise SystemExit("--beta/--eps tune a sparse optimizer; name one "
                         "with --optimizer")
    if args.optimizer is not None:
        from repro.optim import row as row_optim
        row_optim.get(args.optimizer)   # unknown name fails here, loudly

    if args.arch.startswith("dlrm"):
        run = build_dlrm(args, mesh, key)
        cfg, state, step = run.cfg, run.state, run.step
        shardings, batch_shardings = run.shardings, run.batch_shardings
        stream, smoke_stream = run.stream, run.smoke_stream
        profile_def = run.profile_def
        n_params = cfg.spec.total_rows * cfg.emb_dim
        share = (f" (one chip's share of {cfg.deployment_chips})"
                 if cfg.deployment_chips > 1 else "")
        print(f"[train] {args.arch}: ~{n_params/1e6:.1f}M embedding "
              f"params{share}")
    elif args.arch in ("fm", "bst", "sasrec", "din"):
        from repro.core import hybrid as H
        from repro.data.synthetic import hybrid_stream
        mdef = hybrid_def(args)
        state, layout = H.init_state(key, mdef, mesh)
        profile_def = mdef
        step, shardings, bspecs, _ = H.make_train_step(mdef, mesh)
        batch_shardings = _bspec_shardings(mesh, bspecs)
        if args.data_format == "packed":
            stream = packed_stream(
                args, dict(batch=mdef.batch,
                           table_rows=mdef.spec.table_rows,
                           pooling=mdef.pooling,
                           num_dense=(mdef.extras["dense_x"][0][0]
                                      if "dense_x" in mdef.extras else 0),
                           labels="labels" in mdef.extras,
                           slot_to_table=mdef.slot_to_table,
                           extras=tuple(mdef.extras),
                           weighted=mdef.weighted),
                layout, args.host_presort)
        else:
            stream = hybrid_stream(0, mdef, args.alpha)
        smoke_stream = lambda: hybrid_stream(1, mdef, args.alpha)  # noqa: E731
    else:
        from repro.models import lm_steps
        from repro.data.synthetic import token_stream
        if args.data_format == "packed":
            raise SystemExit("--data-dir/--data-format packed is the recsys "
                             "ingestion path (dlrm/fm/bst/sasrec/din); LM "
                             "archs stream tokens")
        if args.microbatches != 1:
            raise SystemExit(
                "--microbatches applies to the recsys hybrid pipeline "
                "(dlrm/fm/bst/sasrec/din); LM archs microbatch via "
                "TransformerConfig.microbatch instead")
        if args.optimizer is not None:
            raise SystemExit(
                "--optimizer selects the sparse embedding RowOptimizer of "
                "the recsys hybrid step (dlrm/fm/bst/sasrec/din); LM archs "
                "use the dense Split-SGD path")
        if args.hot_rows:
            raise SystemExit(
                "--hot-rows caches hot embedding rows of the recsys hybrid "
                "step (dlrm/fm/bst/sasrec/din); LM archs have no sparse "
                "embedding path")
        if args.exchange_dtype is not None:
            raise SystemExit(
                "--exchange-dtype compresses the recsys hybrid step's dY "
                "exchange + dense reduce-scatter (dlrm/fm/bst/sasrec/din); "
                "LM archs have no exchange collectives")
        if args.step_metrics:
            raise SystemExit(
                "--step-metrics counts the recsys hybrid step's sparse "
                "traffic (dlrm/fm/bst/sasrec/din); LM archs have no "
                "metrics vector")
        if args.publish_every or args.serve_smoke:
            raise SystemExit(
                "--publish-every/--serve-smoke publish the recsys serving "
                "snapshot (dlrm/fm/bst/sasrec/din); LM archs have no "
                "serving path")
        cfg, B, L = reduced_lm(args.arch, args.batch or 256, args.seq)
        profile_def = None
        state = lm_steps.init_lm_state(key, cfg, mesh)
        step, structs, shardings = lm_steps.make_lm_train_step(
            cfg, mesh, B, L, lr=0.05 if args.lr is None else args.lr)
        shardings = shardings[0]
        stream = ({k: jax.numpy.asarray(v) for k, v in b.items()}
                  for b in token_stream(0, cfg.vocab, B, L))

    publisher = None
    serve_stats = None
    if args.publish_every or args.serve_smoke:
        from repro.serve import SnapshotPublisher, combined_serve_stats
        publisher = SnapshotPublisher(
            profile_def,
            publish_every=args.publish_every or max(args.steps, 1))
        publisher.publish(0, state)   # v1: tables before training starts
        serve_stats = combined_serve_stats(publisher)
        print(f"[serve] snapshot v1 published "
              f"({publisher.registry.current().emb_bytes / 1e6:.2f} MB "
              f"serving table), cadence {publisher.publish_every} steps")

    event_log = None
    if args.event_log or args.trace_dir:
        from repro.faults import FailureLog
        event_log = FailureLog(args.event_log
                               or str(Path(args.trace_dir) / "events.jsonl"))
    faults = None
    if args.preempt_at is not None:
        from repro.faults import FaultPlan
        faults = FaultPlan.single("train.step", "preempt",
                                  step=args.preempt_at)
        faults.log = event_log
    heartbeat_path = (str(Path(args.trace_dir) / "heartbeat.jsonl")
                      if args.trace_dir else None)
    loop = TrainLoop(
        TrainLoopConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every,
                        prefetch=args.prefetch,
                        skip_batch_budget=args.skip_batch_budget,
                        heartbeat_path=heartbeat_path,
                        heartbeat_every=args.metrics_every,
                        metrics_every=args.metrics_every,
                        device_trace_dir=(str(Path(args.trace_dir) / "device")
                                          if args.trace_dir else None)),
        step, state, stream,
        state_shardings=shardings if args.ckpt_dir else None,
        batch_shardings=batch_shardings, faults=faults,
        event_log=event_log, step_hook=publisher, serve_stats=serve_stats)
    try:
        loop.run()
        if args.serve_smoke:
            buckets = tuple(int(b) for b in args.serve_buckets.split(","))
            serve_smoke(profile_def, mesh, publisher,
                        next(smoke_stream()), buckets)
    finally:
        if hasattr(stream, "close"):
            stream.close()        # release the HostPipeline worker
        if args.trace_dir:
            out = telemetry.export()
            print(f"[train] trace written: {out}")
    print(f"[train] done: first loss {loop.losses[0]:.4f} "
          f"-> last {loop.losses[-1]:.4f}")
    if loop.monitor.events:
        print(f"[train] stragglers observed: {len(loop.monitor.events)}")


if __name__ == "__main__":
    main()
