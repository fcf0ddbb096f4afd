"""DLRM assembled from the paper's components (contributions C1+C3+C4+C5
composed) on the generic hybrid-parallel step.

:class:`DLRMConfig` holds what defines the DLRM model — dense and MLP
widths, tables, E, P, the batch and the deployment it is one chip's share
of — and inherits the step options of :class:`repro.core.hybrid.StepOptions`.
:func:`as_hybrid_def` builds the model's dense half as stage-shaped
functions and passes the options on, so a DLRM trains and scores through
the one hybrid step (``repro.core.hybrid``, ``repro.core.pipeline``).  One
``shard_map`` over the full mesh holds the whole step, so every collective
the paper discusses is explicit in the lowered HLO:

    embedding bag fwd        -> psum_scatter (row mode)  |  all_to_all (table)
    dense fwd/bwd            -> local compute (data-parallel over ALL axes)
    embedding fused update   -> all_gather(dY) + owner-masked scatter (C1/Alg.4)
    dense optimizer          -> bucketed reduce-scatter + all-gather (C4)
                                with Split-SGD-BF16 on the shard (C5)
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import hybrid as H
from repro.core.embedding import EmbeddingSpec
from repro.core.interaction import dot_interaction, interaction_output_dim
from repro.models.mlp import init_mlp, mlp_forward


@dataclasses.dataclass(frozen=True, kw_only=True)
class DLRMConfig(H.StepOptions):
    name: str
    num_dense: int                  # dense-feature width (bottom MLP input)
    bottom: tuple[int, ...]         # bottom MLP hidden sizes; last == emb dim
    top: tuple[int, ...]            # top MLP hidden sizes; final 1 appended
    table_rows: tuple[int, ...]     # M_i per table
    emb_dim: int                    # E
    pooling: int                    # P look-ups per table (paper's P)
    batch: int = 2048               # global minibatch
    # the chips of the deployment this config is one chip's share of
    # (configs/dlrm_paper.chip_share: tables sliced row-wise, batch / N);
    # 1 = the whole model
    deployment_chips: int = 1
    lr: float = 0.1                 # the DLRM default of StepOptions.lr

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


def forward_local(dense_hi: dict, emb_out: jax.Array,
                  dense_x: jax.Array) -> jax.Array:
    """Per-device forward on the batch-sharded slice (fully data-parallel)."""
    bot = mlp_forward(dense_hi["bot"], dense_x,
                      final_activation=True)           # [b, E]
    z = dot_interaction(bot, emb_out)                  # [b, E + F(F-1)/2]
    logits = mlp_forward(dense_hi["top"], z.astype(jnp.bfloat16))
    return logits[:, 0]


def bce_with_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    x, y = logits.astype(jnp.float32), labels.astype(jnp.float32)
    return jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))


# ---------------------------------------------------------------------------
# The DLRM as a HybridDef
# ---------------------------------------------------------------------------

def dense_loss(dense_hi, emb_out, batch) -> jax.Array:
    """Stage-shaped loss: (dense_hi, emb_out, batch) -> per-shard SUM loss
    (the pipeline's dense_fwd_bwd stage divides by the global batch)."""
    logits = forward_local(dense_hi, emb_out, batch["dense_x"])
    return bce_with_logits(logits, batch["labels"]).sum()


def dense_score(dense_hi, emb_out, batch) -> jax.Array:
    """Stage-shaped scorer: (dense_hi, emb_out, batch) -> [b] sigmoid."""
    return jax.nn.sigmoid(forward_local(dense_hi, emb_out, batch["dense_x"]))


def as_hybrid_def(cfg: DLRMConfig) -> H.HybridDef:
    """DLRM expressed as the generic hybrid skeleton: the paper topology's
    fwd/bwd pieces become stage-shaped functions the pipeline composes,
    and the step options pass on as they are."""
    options = {f.name: getattr(cfg, f.name)
               for f in dataclasses.fields(H.StepOptions)}
    return H.HybridDef(
        name=cfg.name, spec=cfg.spec, pooling=cfg.pooling, batch=cfg.batch,
        init_dense=lambda key: init_dense_params(key, cfg),
        dense_loss=dense_loss, dense_score=dense_score,
        extras={"dense_x": ((cfg.num_dense,), jnp.bfloat16),
                "labels": ((), jnp.float32)},
        **options)


def init_state(key: jax.Array, cfg: DLRMConfig, mesh):
    """``(state, layout)``: a real initial state (small/smoke configs)."""
    return H.init_state(key, as_hybrid_def(cfg), mesh)


def make_train_step(cfg: DLRMConfig, mesh, microbatches: int | None = None):
    """Build the jitted hybrid-parallel train step (staged pipeline; see
    repro/core/pipeline.py).  ``microbatches`` defaults to
    ``cfg.microbatches``; 1 reproduces the monolithic step bit-for-bit.

    Returns (step, state_shardings, batch_shardings, layout); call as
    ``new_state, loss = step(state, batch)``.
    """
    return H.make_train_step(as_hybrid_def(cfg), mesh, microbatches)
