"""DLRM assembled from the paper's components, with the hybrid-parallel
train step (contributions C1+C3+C4+C5 composed).

One ``shard_map`` over the full mesh contains the whole step, so every
collective the paper discusses is explicit in the lowered HLO:

    embedding bag fwd        -> psum_scatter (row mode)  |  all_to_all (table)
    dense fwd/bwd            -> local compute (data-parallel over ALL axes)
    embedding fused update   -> all_gather(dY) + owner-masked scatter (C1/Alg.4)
    dense optimizer          -> bucketed reduce-scatter + all-gather (C4)
                                with Split-SGD-BF16 on the shard (C5)

The roofline harness reads those collectives straight out of the compiled
module; EXPERIMENTS.md's comm-volume table checks them against the paper's
Eq. 1 (allreduce) and Eq. 2 (alltoall).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.embedding import EmbeddingSpec
from repro.core import sharded_embedding as se
from repro.dist.exchange import ExchangeConfig
from repro.core.interaction import dot_interaction, interaction_output_dim
from repro.models.mlp import init_mlp, mlp_forward
from repro.optim import row as row_optim


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    name: str
    num_dense: int                  # dense-feature width (bottom MLP input)
    bottom: tuple[int, ...]         # bottom MLP hidden sizes; last == emb dim
    top: tuple[int, ...]            # top MLP hidden sizes; final 1 appended
    table_rows: tuple[int, ...]     # M_i per table
    emb_dim: int                    # E
    pooling: int                    # P look-ups per table (paper's P)
    batch: int = 2048               # global minibatch
    emb_mode: str = "row"           # 'row' | 'table'  (C3 placement)
    # the chips of the deployment this config is one chip's share of
    # (configs/dlrm_paper.chip_share: tables sliced row-wise, batch / N);
    # 1 = the whole model
    deployment_chips: int = 1
    # sparse RowOptimizer for the embedding path (repro/optim/row.py):
    # 'sgd' | 'split_sgd' | 'momentum' | 'adagrad_rowwise' | 'adagrad' |
    # 'momentum_bf16' | 'adagrad_bf16' (compressed bf16-hi state +
    # stochastic rounding) — or a RowOptimizer instance.  None/'' falls
    # back to the legacy ``split_sgd`` bool.  opt_beta / opt_eps override
    # the registered hyperparameter defaults (momentum coefficient,
    # adagrad floor).
    sparse_optimizer: Optional[str] = None
    opt_beta: Optional[float] = None
    opt_eps: Optional[float] = None
    # DEPRECATED C5 on/off sugar (None = the 'split_sgd' default without
    # the DeprecationWarning; read only when sparse_optimizer is unset)
    split_sgd: Optional[bool] = None
    # Pallas fused sparse-bwd + row-optimizer update (the split path is
    # bit-identical to the reference).  None = on where the kernel compiles
    # (TPU), off elsewhere (CPU interpret emulation pays O(shard) per grid
    # step); True/False forces the choice for A/B benchmarking and tests.
    fused_update: Optional[bool] = None
    # typed comm/precision config (repro/dist/exchange.py): exchange
    # lowering + per-collective wire formats + dense error feedback +
    # RS+AG bucketing in ONE frozen dataclass.  Mutually exclusive with
    # the flat kwargs below.
    exchange: Optional[ExchangeConfig] = None
    # sugar: both wire dtypes at once ('fp32' | 'bf16' | 'bf16_sr')
    exchange_dtype: Optional[str] = None
    # DEPRECATED flat kwargs (resolve_exchange coerces + warns):
    compress_grads: Optional[bool] = None   # bf16 wire + error feedback
    num_buckets: Optional[int] = None       # C4 bucketing
    lr: float = 0.1
    mlp_impl: str = "xla"           # 'xla' | 'pallas'
    # 'replicated' reproduces the paper's data loader (every rank reads the
    # full global minibatch — its own noted weak-scaling flaw); 'sharded'
    # feeds batch-sharded indices and all-gathers them over ICI instead,
    # removing the host-side input replication (row AND table mode; table
    # mode also permutes to padded-slot order on chip).
    idx_input: str = "replicated"
    # staged microbatch pipeline (repro/core/pipeline.py): split the global
    # batch into M microbatches with a double-buffered index exchange so
    # the layout-switch collectives overlap dense compute.  1 = monolithic.
    microbatches: int = 1
    # DEPRECATED index-exchange lowering: 'fused' | 'ring' (use
    # exchange=ExchangeConfig(impl=...))
    exchange_impl: Optional[str] = None
    # weighted bags: batch carries 'weights' [B, S, P] in the idx layout
    weighted: bool = False
    # host-pre-sorted sparse update (repro/data/pipeline.py): the loader
    # ships psort_* fields, the step drops the on-device sort (row and
    # table mode — the table host sort folds the padded-slot permute in)
    host_presort: bool = False
    # initial per-step stochastic-rounding counter (only materialized when
    # the resolved optimizer registered stochastic_round=True)
    sr_seed: int = 0
    # frequency-tiered hot-row cache (repro/core/cache.py): replicate the
    # top-``hot_rows`` rows per table (by touch count) on every rank and
    # serve all-hot bags locally, off the all-to-all payload (table mode
    # + idx_input='sharded').  0 = off.
    hot_rows: int = 0
    # re-rank the hot set from the touch counters every this-many steps
    promote_every: int = 1
    # 'allreduce' (mirror refreshed every step; bitwise == cache off) or
    # 'deferred:N' (refresh every N steps; bounded drift)
    hot_sync: str = "allreduce"
    # in-graph step metrics vector (repro/telemetry/metrics.py): cache
    # hits, rows touched, exchange payload bytes, accumulated on device
    # and drained by the train loop.  False (default) = no state key, step
    # bit-identical to a build without telemetry.
    step_metrics: bool = False

    @property
    def spec(self) -> EmbeddingSpec:
        return EmbeddingSpec(self.table_rows, self.emb_dim)

    @property
    def bottom_sizes(self) -> list[int]:
        return [self.num_dense, *self.bottom]

    @property
    def top_sizes(self) -> list[int]:
        f = len(self.table_rows) + 1
        d_in = interaction_output_dim(f, self.emb_dim, "dot")
        return [d_in, *self.top, 1]


def init_dense_params(key: jax.Array, cfg: DLRMConfig) -> dict:
    kb, kt = jax.random.split(key)
    return {"bot": init_mlp(kb, cfg.bottom_sizes),
            "top": init_mlp(kt, cfg.top_sizes)}


def forward_local(dense_hi: dict, emb_out: jax.Array, dense_x: jax.Array,
                  impl: str = "xla") -> jax.Array:
    """Per-device forward on the batch-sharded slice (fully data-parallel)."""
    bot = mlp_forward(dense_hi["bot"], dense_x, final_activation=True,
                      impl=impl)                       # [b, E]
    z = dot_interaction(bot, emb_out)                  # [b, E + F(F-1)/2]
    logits = mlp_forward(dense_hi["top"], z.astype(jnp.bfloat16), impl=impl)
    return logits[:, 0]


def bce_with_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    x, y = logits.astype(jnp.float32), labels.astype(jnp.float32)
    return jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))


# ---------------------------------------------------------------------------
# Hybrid-parallel step factory
# ---------------------------------------------------------------------------

def mesh_axes(mesh) -> tuple[tuple[str, ...], str, tuple[str, ...]]:
    """(all_axes, model_axis, batch_axes).  The last mesh axis is 'model'."""
    names = tuple(mesh.axis_names)
    return names, names[-1], names[:-1]


def emb_axes_for(cfg: DLRMConfig, mesh):
    """Row mode shards the row space over the FULL mesh (paper: pure
    model-parallel embeddings over all ranks); table mode uses the model
    axis and replicates over the rest."""
    all_axes, model, batch_axes = mesh_axes(mesh)
    if cfg.emb_mode == "row":
        return all_axes, None
    return model, (batch_axes if batch_axes else None)


def make_layout(cfg: DLRMConfig, mesh) -> se.ShardedEmbeddingLayout:
    axes, _ = emb_axes_for(cfg, mesh)
    ns = int(np.prod([mesh.shape[a] for a in (axes if isinstance(axes, tuple)
                                              else (axes,))]))
    return se.make_layout(cfg.spec, ns, cfg.emb_mode)


def state_struct(cfg: DLRMConfig, mesh, rngs: bool = True):
    """(state pytree of arrays-or-structs, sharding pytree).  Delegates to
    the generic hybrid builder (the DLRM state IS the hybrid skeleton's:
    embedding store + split dense + optional sr counter + optional hot-row
    cache subtree), so optimizer- and cache-driven layout changes stay
    single-sourced.  ``rngs`` is kept for call-site compatibility; only
    ShapeDtypeStructs are ever produced here."""
    del rngs
    from repro.core import hybrid as H
    return H.state_struct(as_hybrid_def(cfg), mesh)


def init_state(key: jax.Array, cfg: DLRMConfig, mesh) -> dict:
    """Materialize a real initial state (small/smoke configs).  Delegates
    to the hybrid builder — bit-identical to the historical in-module
    initializer (same key split, same init distribution)."""
    from repro.core import hybrid as H
    return H.init_state(key, as_hybrid_def(cfg), mesh)


def batch_struct(cfg: DLRMConfig, mesh, layout, *,
                 include_presort: bool | None = None) -> tuple[dict, dict]:
    """(ShapeDtypeStructs, PartitionSpecs) for one global batch.  Kept as
    the DLRM-named entry for the bench/dry-run paths; delegates to the
    generic hybrid builder so the weighted / host-pre-sorted fields stay
    single-sourced."""
    from repro.core import hybrid as H
    return H.batch_struct(as_hybrid_def(cfg), mesh, layout,
                          include_presort=include_presort)


def dlrm_dense_loss(cfg: DLRMConfig):
    """Stage-shaped loss: (dense_hi, emb_out, batch) -> per-shard SUM loss
    (the pipeline's dense_fwd_bwd stage divides by the global batch)."""
    def loss(dense_hi, emb_out, batch):
        logits = forward_local(dense_hi, emb_out, batch["dense_x"],
                               cfg.mlp_impl)
        return bce_with_logits(logits, batch["labels"]).sum()
    return loss


def dlrm_dense_score(cfg: DLRMConfig):
    """Stage-shaped scorer: (dense_hi, emb_out, batch) -> [b] sigmoid."""
    def score(dense_hi, emb_out, batch):
        return jax.nn.sigmoid(forward_local(dense_hi, emb_out,
                                            batch["dense_x"], cfg.mlp_impl))
    return score


def as_hybrid_def(cfg: DLRMConfig):
    """DLRM expressed as the generic hybrid skeleton: the paper topology's
    fwd/bwd pieces become stage-shaped functions the pipeline composes."""
    from repro.core.hybrid import HybridDef
    return HybridDef(
        name=cfg.name, spec=cfg.spec, pooling=cfg.pooling, batch=cfg.batch,
        init_dense=lambda key: init_dense_params(key, cfg),
        dense_loss=dlrm_dense_loss(cfg),
        dense_score=dlrm_dense_score(cfg),
        extras={"dense_x": ((cfg.num_dense,), jnp.bfloat16),
                "labels": ((), jnp.float32)},
        emb_mode=cfg.emb_mode, sparse_optimizer=cfg.sparse_optimizer,
        opt_beta=cfg.opt_beta, opt_eps=cfg.opt_eps, split_sgd=cfg.split_sgd,
        fused_update=cfg.fused_update, exchange=cfg.exchange,
        exchange_dtype=cfg.exchange_dtype, compress_grads=cfg.compress_grads,
        num_buckets=cfg.num_buckets, lr=cfg.lr, emb_lr=cfg.lr,
        idx_input=cfg.idx_input, microbatches=cfg.microbatches,
        exchange_impl=cfg.exchange_impl, weighted=cfg.weighted,
        host_presort=cfg.host_presort, sr_seed=cfg.sr_seed,
        hot_rows=cfg.hot_rows, promote_every=cfg.promote_every,
        hot_sync=cfg.hot_sync, step_metrics=cfg.step_metrics)


def make_train_step(cfg: DLRMConfig, mesh, microbatches: int | None = None):
    """Build the jitted hybrid-parallel train step (staged pipeline; see
    repro/core/pipeline.py).  ``microbatches`` defaults to
    ``cfg.microbatches``; 1 reproduces the monolithic step bit-for-bit.

    Returns (step, state_shardings, batch_shardings, layout); call as
    ``new_state, loss = step(state, batch)``.
    """
    from repro.core import pipeline
    M = cfg.microbatches if microbatches is None else microbatches
    return pipeline.make_pipelined_train_step(as_hybrid_def(cfg), mesh,
                                              microbatches=M)


def make_eval_step(cfg: DLRMConfig, mesh):
    """Forward-only scoring step (serving); returns per-sample sigmoid.
    Reuses the pipeline's index_exchange + embedding_fwd stages."""
    from repro.core import pipeline
    structs, specs, shardings, layout = state_struct(cfg, mesh)
    bstructs, bspecs = batch_struct(cfg, mesh, layout,
                                    include_presort=False)
    all_axes, model, batch_axes = mesh_axes(mesh)
    stages = pipeline.build_stages(as_hybrid_def(cfg), mesh, layout)
    opt = row_optim.resolve(cfg)

    def eval_local(state, batch):
        W_fwd = opt.fwd_weights(state["emb"])
        idx_fwd, _ = stages.index_exchange(batch["idx"], fwd_only=True)
        wgt_fwd = None
        if cfg.weighted:
            wgt_fwd, _ = stages.index_exchange(batch["weights"],
                                               fwd_only=True)
        emb_out = stages.embedding_fwd(W_fwd, idx_fwd, wgt_fwd)
        logits = forward_local(state["dense"]["hi"], emb_out,
                               batch["dense_x"], cfg.mlp_impl)
        return jax.nn.sigmoid(logits)

    ev = compat.shard_map(eval_local, mesh=mesh, in_specs=(specs, bspecs),
                       out_specs=P(all_axes), check_vma=False)
    return jax.jit(ev), shardings, bspecs, layout
