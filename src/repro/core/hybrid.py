"""Generic hybrid-parallel (C3) train/eval step factory — staged pipeline.

Every recsys architecture here (DLRM, FM, BST, SASRec, DIN) shares one
skeleton: model-parallel unified embedding + data-parallel dense net +
all-to-all / reduce-scatter layout switch + fused sparse update + RS+AG
dense optimizer.  This module hosts the skeleton's *definition* —
:class:`StepOptions` (how the step trains, each option declared once) and
:class:`HybridDef` (what a model must provide, plus those options) — and
its state/batch structure builders.  Every model reaches the step as a
``HybridDef``: the recsys makers build one directly, and a
``repro.core.dlrm.DLRMConfig`` builds one with ``as_hybrid_def``.  The
step itself is composed from the explicit
:class:`repro.core.pipeline.Stage` objects —

    index_exchange -> embedding_fwd -> dense_fwd_bwd -> dY_exchange
                   -> sparse_update -> dense_update

— by :func:`repro.core.pipeline.make_pipelined_train_step`, which also
software-pipelines M microbatches with a double-buffered index exchange so
the layout-switch collectives of microbatch i+1 overlap microbatch i's
dense compute (the paper's Sect. VI comm/compute overlap).

:func:`make_train_step` is the ``M = mdef.microbatches`` entry point; with
``microbatches=1`` (the default) it is the degenerate single-stage-chain
case, bit-compatible with the historical monolithic step.  The serve path
(:func:`make_score_step`) reuses the same ``index_exchange`` and
``embedding_fwd`` stages, so a placement or exchange change lands in train
and serve at once.  See docs/pipeline.md for the stage/timeline diagram.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.embedding import EmbeddingSpec
from repro.core import pipeline
from repro.core import sharded_embedding as se
from repro.dist.exchange import ExchangeConfig, resolve_exchange
from repro.optim import data_parallel as dp
from repro.optim import row as row_optim


@dataclasses.dataclass(frozen=True, kw_only=True)
class StepOptions:
    """How the hybrid step trains a model: placement, optimizers, exchange,
    pipeline, hot-row cache and telemetry.  Each option is declared here
    once; :class:`HybridDef` and ``repro.core.dlrm.DLRMConfig`` inherit
    them."""
    emb_mode: str = "row"          # 'row' | 'table' (C3 placement)
    # sparse RowOptimizer (repro/optim/row.py): registry name ('sgd',
    # 'split_sgd', 'momentum', 'adagrad_rowwise', 'adagrad',
    # 'momentum_bf16', 'adagrad_bf16') or a RowOptimizer instance.  Owns
    # the embedding store layout (weight slab(s) + per-row state slabs)
    # and the single fused apply the sparse_update stage dispatches
    # through.  None/'' = 'split_sgd'.
    sparse_optimizer: Optional[Any] = None
    # hyperparameter overrides for the registered optimizer (None = its
    # registered default): momentum coefficient / adagrad denominator floor
    opt_beta: Optional[float] = None
    opt_eps: Optional[float] = None
    # fused Pallas sparse-bwd + row-optimizer update (kernels/
    # embedding_update) — the split path is bit-identical to the reference,
    # touches O(touched rows) instead of O(shard rows).  None (default) =
    # on where the kernel compiles (TPU); off elsewhere, because CPU
    # interpret emulation pays O(shard) per grid step.  True/False forces
    # the choice (A/B, tests).
    fused_update: Optional[bool] = None
    # comm/precision config (repro/dist/exchange.py): the index-exchange
    # lowering, the per-collective wire formats of the dY exchange + dense
    # reduce-scatter ('fp32' | 'bf16' | 'bf16_sr'), the dense error
    # feedback, and the RS+AG bucketing
    exchange: ExchangeConfig = ExchangeConfig()
    lr: float = 0.01               # dense and embedding learning rate
    # 'replicated': every rank reads the full global minibatch (the
    # paper's data loader); 'sharded': batch-sharded indices, exchanged on
    # chip (row AND table mode; table mode also permutes to padded-slot
    # order on chip)
    idx_input: str = "replicated"
    # staged pipeline (repro/core/pipeline.py): number of microbatches the
    # global batch is split into, with the index exchange double-buffered
    # across them.  1 = the monolithic step.
    microbatches: int = 1
    # weighted bags: the batch carries a 'weights' field in the exact
    # layout of 'idx' ([B, S, P] per-lookup bag weights); the forward
    # computes sum(w * row) and the sparse update scales dY per lookup.
    # All-ones weights are bit-identical to unweighted.
    weighted: bool = False
    # host-pre-sorted sparse update (repro/data/pipeline.py): the loader
    # ships per-shard sorted lookup streams as psort_* batch fields and
    # the fused kernel consumes them directly — no on-device sort in the
    # step.  Row AND table mode (the table host sort folds the
    # padded-slot permute in); always the fused kernel on the update path.
    host_presort: bool = False
    # initial value of the per-step stochastic-rounding counter (the
    # replicated int32 ``state["sr"]`` scalar, present only when the
    # resolved RowOptimizer registered stochastic_round=True or a wire
    # format is 'bf16_sr'; incremented once per step and checkpointed, so
    # a resumed run replays the exact dither sequence)
    sr_seed: int = 0
    # frequency-tiered hot-row cache (repro/core/cache.py): > 0 keeps a
    # replicated mirror of the top-``hot_rows`` rows PER TABLE (ranked by
    # the reserved ``cnt`` touch-counter slab) in front of the sharded
    # cold store; bags whose lookups all hit are served locally, off the
    # all-to-all payload (table mode + idx_input='sharded').  0 = off.
    hot_rows: int = 0
    # promotion/demotion cadence: re-rank the hot set from the counters
    # every this-many steps (deterministic, seeded by ``sr_seed``)
    promote_every: int = 1
    # 'allreduce': refresh the mirror from the post-update store every
    # step (bitwise == hot_rows=0); 'deferred:N': refresh every N steps
    # (bounded drift, see docs/cache.md)
    hot_sync: str = "allreduce"
    # in-graph step metrics (repro/telemetry/metrics.py): a replicated
    # float32 counter vector in the train state, accumulated on device by
    # the pipelined step (cache hits, rows touched, exchange payload
    # bytes) and drained by the host every TrainLoopConfig.metrics_every
    # steps — no per-step host syncs.  False (default) adds NO state key
    # and leaves the lowered step bit-identical to a build without it.
    step_metrics: bool = False


@dataclasses.dataclass(frozen=True, kw_only=True)
class HybridDef(StepOptions):
    """What a hybrid-parallel recsys model must provide, and the step
    options it trains with."""
    name: str
    spec: EmbeddingSpec
    pooling: int                   # P (max lookups per slot)
    batch: int                     # global batch
    init_dense: Callable[[jax.Array], Any]
    # dense_loss(dense_hi, emb_out [b,S,E] fp32, batch) -> per-shard SUM loss
    dense_loss: Callable[[Any, jax.Array, dict], jax.Array]
    # dense_score(dense_hi, emb_out, batch) -> [b] scores
    dense_score: Callable[[Any, jax.Array, dict], jax.Array]
    # extra batch fields: name -> (shape-after-B, dtype); all batch-sharded
    extras: dict = dataclasses.field(default_factory=dict)
    # slot -> table map (sequence models share one item table across slots)
    slot_to_table: Optional[tuple] = None


# stage-shaped mesh helpers live in pipeline.py; re-exported for callers
_mesh_axes = pipeline.mesh_axes


def _emb_axes(mdef, mesh):
    return pipeline.emb_axes(mdef, mesh)


def make_layout(mdef: HybridDef, mesh) -> se.ShardedEmbeddingLayout:
    axes, _ = _emb_axes(mdef, mesh)
    ns = int(np.prod([mesh.shape[a] for a in (axes if isinstance(axes, tuple)
                                              else (axes,))]))
    return se.make_layout(mdef.spec, ns, mdef.emb_mode,
                          slot_to_table=mdef.slot_to_table)


def state_struct(mdef: HybridDef, mesh):
    layout = make_layout(mdef, mesh)
    all_axes, model, batch_axes = _mesh_axes(mesh)
    emb_ax, _ = _emb_axes(mdef, mesh)
    ns_total = int(np.prod(list(mesh.shape.values())))
    E = mdef.spec.dim
    dense_tree = jax.eval_shape(lambda: mdef.init_dense(jax.random.PRNGKey(0)))
    n_dense = dp.ravel_size(dense_tree)
    ex_cfg = resolve_exchange(mdef)
    padded = -(-n_dense // (ns_total * ex_cfg.num_buckets)) * (
        ns_total * ex_cfg.num_buckets)
    rows = layout.total_rows
    opt = row_optim.resolve(mdef)
    hot_rows = mdef.hot_rows
    structs = {
        # the RowOptimizer owns the embedding store layout: weight slab(s)
        # plus zero or more per-row state slabs, all sharded by the same
        # row partition (so state persists/reshards next to weights); the
        # hot-row cache adds the reserved ``cnt`` touch-counter slab
        "emb": opt.store_struct(rows, E, counters=hot_rows > 0),
        "dense": {
            "hi": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
                dense_tree),
            "lo": jax.ShapeDtypeStruct((padded,), jnp.uint16),
            "err": (jax.ShapeDtypeStruct((padded,), jnp.float32)
                    if ex_cfg.needs_err else None),
        },
    }
    specs = {
        "emb": jax.tree.map(lambda _: P(emb_ax, None), structs["emb"]),
        "dense": {
            "hi": jax.tree.map(lambda _: P(), structs["dense"]["hi"]),
            "lo": P(all_axes),
            "err": P(all_axes) if ex_cfg.needs_err else None,
        },
    }
    if opt.stochastic_round or ex_cfg.needs_sr:
        # per-step stochastic-rounding counter: replicated int32 scalar,
        # consumed by the compressed-state row optimizers and/or the
        # 'bf16_sr' wire encoders
        structs["sr"] = jax.ShapeDtypeStruct((), jnp.int32)
        specs["sr"] = P()
    if hot_rows > 0:
        from repro.core import cache as hot_cache
        structs["cache"] = hot_cache.cache_struct(mdef, layout, opt)
        specs["cache"] = hot_cache.cache_specs(structs["cache"])
    if mdef.step_metrics:
        from repro.telemetry import metrics as step_mx
        structs["metrics"] = step_mx.metrics_struct()
        specs["metrics"] = P()
    shardings = jax.tree.map(
        lambda s: None if s is None else NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P) or x is None)
    return structs, specs, shardings, layout


def batch_struct(mdef: HybridDef, mesh, layout, batch: int | None = None,
                 *, include_presort: bool | None = None):
    """(ShapeDtypeStructs, PartitionSpecs) for one global batch.

    ``weighted`` models add a ``weights`` field in the exact shape/spec of
    ``idx``.  ``host_presort`` models add the four ``psort_*`` fields of
    ``repro.data.pipeline.presort_batch`` — ``[ns_emb, B*S*P]`` sharded
    over the embedding axes, so each shard receives its own pre-sorted
    update stream.  ``include_presort`` overrides the mdef default (the
    forward-only serve/eval steps never consume the update stream)."""
    all_axes, model, batch_axes = _mesh_axes(mesh)
    B = batch or mdef.batch
    S, Pq = layout.num_orig_slots, mdef.pooling
    if mdef.idx_input not in ("replicated", "sharded"):
        raise ValueError(f"unknown idx_input {mdef.idx_input!r}; "
                         "expected 'replicated' or 'sharded'")
    if mdef.emb_mode == "row":
        idx = jax.ShapeDtypeStruct((B, S, Pq), jnp.int32)
        idx_spec = (P(None, None, None) if mdef.idx_input == "replicated"
                    else P(all_axes, None, None))
    elif mdef.idx_input == "sharded":
        # on-chip exchange: the loader feeds batch-sharded ORIGINAL-slot
        # indices; the index_exchange stage gathers, permutes to padded
        # order and slices this shard's slots (no host-side permute).
        idx = jax.ShapeDtypeStruct((B, S, Pq), jnp.int32)
        idx_spec = P(all_axes, None, None)
    else:
        idx = jax.ShapeDtypeStruct((B, layout.num_padded_slots, Pq),
                                   jnp.int32)
        idx_spec = P(batch_axes if batch_axes else None, model, None)
    structs = {"idx": idx}
    specs = {"idx": idx_spec}
    if mdef.weighted:
        structs["weights"] = jax.ShapeDtypeStruct(idx.shape, jnp.float32)
        specs["weights"] = idx_spec
    include_presort = (mdef.host_presort if include_presort is None
                       else include_presort)
    if include_presort:
        emb_ax, _ = _emb_axes(mdef, mesh)
        axes = emb_ax if isinstance(emb_ax, tuple) else (emb_ax,)
        ns_emb = int(np.prod([mesh.shape[a] for a in axes]))
        # flat lookup count of the per-shard sorted stream: row mode sorts
        # the original-slot stream; table mode the padded-slot stream of
        # each shard's slots (presort_batch folds the permute in)
        slots = S if mdef.emb_mode == "row" else layout.slots_per_shard
        L = B * slots * Pq
        for name, dt in (("psort_rows", jnp.int32),
                         ("psort_bags", jnp.int32),
                         ("psort_msk", jnp.int32),
                         ("psort_wgt", jnp.float32)):
            structs[name] = jax.ShapeDtypeStruct((ns_emb, L), dt)
            specs[name] = P(emb_ax, None)
    for name, (shape, dtype) in mdef.extras.items():
        structs[name] = jax.ShapeDtypeStruct((B, *shape), dtype)
        specs[name] = P(all_axes, *([None] * len(shape)))
    return structs, specs


def batch_struct_from_spec(mdef: HybridDef, mesh, layout, dataset_spec,
                           batch: int | None = None):
    """Batch struct derived from (and validated against) a packed-dataset
    :class:`repro.data.format.DatasetSpec` — the loader-facing entry: a
    spec/model mismatch fails here, at wiring time, with a field-by-field
    message instead of a shape error inside shard_map."""
    dataset_spec.check_model(mdef)
    if dataset_spec.weighted and not mdef.weighted:
        # legal (weights are simply not read) but worth rejecting loudly:
        # the reader WILL yield a weights field the struct won't declare.
        raise ValueError("dataset is weighted but mdef.weighted=False; "
                         "set weighted=True (or strip the weights field)")
    return batch_struct(mdef, mesh, layout, batch)


def init_state(key, mdef: HybridDef, mesh):
    structs, specs, shardings, layout = state_struct(mdef, mesh)
    ke, kd = jax.random.split(key)
    ns_total = int(np.prod(list(mesh.shape.values())))
    scale = 1.0 / np.sqrt(np.mean(mdef.spec.table_rows))
    W = jax.random.uniform(ke, (layout.total_rows, mdef.spec.dim),
                           jnp.float32, -scale, scale)
    opt = row_optim.resolve(mdef)
    hot_rows = mdef.hot_rows
    # one compiled split, and the fp32 table freed before the MLPs are
    # made: eager bit ops would hold three table-sized temporaries (a
    # chip's share of dlrm-large is a 6 GB table)
    emb = jax.jit(opt.init_store, static_argnames="counters")(
        W, counters=hot_rows > 0)
    del W
    dense = mdef.init_dense(kd)
    ex_cfg = resolve_exchange(mdef)
    arrays = dp.dp_global_arrays(dense, ns_total,
                                 compress=ex_cfg.needs_err,
                                 num_buckets=ex_cfg.num_buckets)
    state = {"emb": emb, "dense": {"hi": arrays["hi"], "lo": arrays["lo"],
                                   "err": arrays["err"]}}
    if opt.stochastic_round or ex_cfg.needs_sr:
        state["sr"] = jnp.asarray(mdef.sr_seed, jnp.int32)
    if hot_rows > 0:
        from repro.core import cache as hot_cache
        state["cache"] = hot_cache.init_cache(mdef, layout, opt)
    if mdef.step_metrics:
        from repro.telemetry import metrics as step_mx
        state["metrics"] = step_mx.init_metrics()
    return jax.device_put(state, shardings), layout


def make_train_step(mdef: HybridDef, mesh, microbatches: int | None = None):
    """Staged-pipeline train step; ``microbatches`` defaults to
    ``mdef.microbatches`` (1 = the monolithic step, bit-compatible with the
    historical closure)."""
    M = mdef.microbatches if microbatches is None else microbatches
    return pipeline.make_pipelined_train_step(mdef, mesh, microbatches=M)


# the explicit name used throughout benchmarks/tests
make_pipelined_train_step = pipeline.make_pipelined_train_step


def make_score_step(mdef: HybridDef, mesh, batch: int | None = None):
    """Forward-only scoring (serve_p99 / serve_bulk shapes).  Reuses the
    pipeline's index_exchange + embedding_fwd stages — the serve path sees
    every placement/exchange improvement the train path gets."""
    structs, specs, shardings, layout = state_struct(mdef, mesh)
    bstructs, bspecs = batch_struct(mdef, mesh, layout, batch,
                                    include_presort=False)
    all_axes, model, batch_axes = _mesh_axes(mesh)
    stages = pipeline.build_stages(mdef, mesh, layout)
    opt = row_optim.resolve(mdef)

    def score_local(state, batch_d):
        W_fwd = opt.fwd_weights(state["emb"])
        idx_fwd, _ = stages.index_exchange(batch_d["idx"], fwd_only=True)
        wgt_fwd = None
        if mdef.weighted:
            wgt_fwd, _ = stages.index_exchange(batch_d["weights"],
                                               fwd_only=True)
        emb_out = stages.embedding_fwd(W_fwd, idx_fwd, wgt_fwd)
        return mdef.dense_score(state["dense"]["hi"], emb_out, batch_d)

    sc = compat.shard_map(score_local, mesh=mesh, in_specs=(specs, bspecs),
                       out_specs=P(all_axes), check_vma=False)
    return jax.jit(sc), shardings, bspecs, layout


def make_retrieval_step(mdef: HybridDef, mesh, n_candidates: int,
                        target_slot: int, topk: int = 128):
    """retrieval_cand shape: ONE query against ``n_candidates`` candidates.

    The candidate embedding matrix [n_cand, E] enters pre-sharded over the
    full mesh (the offline-built candidate index of a serving system); the
    query's bag output is computed replicated (psum), the target slot is
    substituted with each local candidate, the dense scorer runs batched
    over the local chunk, and a distributed top-k merge produces the global
    result.  Never a loop over candidates."""
    if mdef.weighted:
        raise ValueError("retrieval scores a single replicated query "
                         "against a prebuilt candidate matrix; weighted "
                         "bags are not supported on this path — replace "
                         "the mdef with weighted=False for retrieval")
    structs, specs, shardings, layout = state_struct(mdef, mesh)
    bstructs, bspecs = batch_struct(mdef, mesh, layout, batch=1,
                                    include_presort=False)
    bspecs = jax.tree.map(lambda s: P(*([None] * len(s))), bspecs,
                          is_leaf=lambda x: isinstance(x, P))  # B=1: replicate
    all_axes, model, batch_axes = _mesh_axes(mesh)
    emb_ax, _ = _emb_axes(mdef, mesh)
    if mdef.emb_mode != "row":
        raise ValueError("retrieval step requires emb_mode='row' "
                         f"(got {mdef.emb_mode!r})")
    if mdef.idx_input != "replicated":
        raise ValueError("retrieval step scores ONE replicated query; a "
                         "batch-sharded index stream (idx_input='sharded') "
                         "cannot shard a single sample — replace the mdef "
                         "with idx_input='replicated' for retrieval")
    ns = int(np.prod(list(mesh.shape.values())))
    per = n_candidates // ns
    E = mdef.spec.dim
    opt = row_optim.resolve(mdef)

    def _normalize_batch(batch):
        """Schema-normalize the single-query batch BEFORE shard_map: every
        declared extra is reshaped to ``(1, *schema_shape)``, so rank-1
        (B-squeezed) extras are accepted instead of silently dropped."""
        out = dict(batch)
        for k, (shape, _) in mdef.extras.items():
            if k in out:
                out[k] = jnp.reshape(out[k], (1,) + tuple(shape))
        return out

    def _broadcast_batch(batch):
        """Candidate-batch view of the (normalized) query: declared extras
        broadcast over the local candidate chunk via the schema; unknown
        fields keep the legacy leading-(1,) heuristic."""
        out = {}
        for k, v in batch.items():
            if k in mdef.extras:
                shape = tuple(mdef.extras[k][0])
                out[k] = jnp.broadcast_to(v, (per,) + shape)
            elif hasattr(v, "shape") and v.shape[:1] == (1,):
                out[k] = jnp.broadcast_to(v, (per,) + v.shape[1:])
            else:
                out[k] = v
        return out

    def local(state, batch, cand):
        W_fwd = opt.fwd_weights(state["emb"])
        emb = se.row_bag_fwd_replicated(layout, W_fwd, batch["idx"], emb_ax)
        emb_c = jnp.broadcast_to(emb, (per,) + emb.shape[1:])
        emb_c = emb_c.at[:, target_slot].set(cand.astype(jnp.float32))
        scores = mdef.dense_score(state["dense"]["hi"], emb_c,
                                  _broadcast_batch(batch))
        v, i = jax.lax.top_k(scores, min(topk, per))
        i = i + jax.lax.axis_index(all_axes) * per
        vg = jax.lax.all_gather(v, all_axes, axis=0, tiled=True)
        ig = jax.lax.all_gather(i, all_axes, axis=0, tiled=True)
        vv, pos = jax.lax.top_k(vg, topk)
        return vv, jnp.take(ig, pos)

    cand_struct = jax.ShapeDtypeStruct((n_candidates, E), jnp.bfloat16)
    cand_spec = P(all_axes, None)
    inner = compat.shard_map(local, mesh=mesh,
                       in_specs=(specs, bspecs, cand_spec),
                       out_specs=(P(), P()), check_vma=False)

    def fn(state, batch, cand):
        return inner(state, _normalize_batch(batch), cand)

    arg_structs = (structs, bstructs, cand_struct)
    arg_shardings = (shardings,
                     jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                                  is_leaf=lambda x: isinstance(x, P)),
                     NamedSharding(mesh, cand_spec))
    return jax.jit(fn), arg_structs, arg_shardings, layout
