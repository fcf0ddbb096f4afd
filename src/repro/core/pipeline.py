"""Staged microbatch pipeline for the hybrid-parallel train step.

The paper's scaling story (Sect. VI) rests on overlapping the embedding
layout-switch collectives (index exchange + all-to-all / reduce-scatter)
with dense compute: on 64 sockets those collectives are the dominant
non-compute cost.  A monolithic step closure gives the compiler one serial
dependence chain per batch; this module decomposes the step into explicit
:class:`Stage` objects and software-pipelines them over M microbatches:

    index_exchange   loader layout -> compute layout for the index stream
                     (row mode: all_gather over the embedding axes; table
                     mode: replica gather / on-chip permute+slice).  DOUBLE
                     BUFFERED: microbatch i+1's exchange is issued before
                     microbatch i's compute consumes buffer i, so the two
                     have no data dependence and XLA's latency-hiding
                     scheduler can overlap them.  jax.lax exposes no public
                     async collective start/done pair; ``ExchangeConfig(
                     impl="ring")`` decomposes the gather into ns-1 ppermute
                     chunks — finer units the scheduler can interleave —
                     and is the hook an async start/done lowers into when
                     the API lands.
    embedding_fwd    model-parallel bag forward + layout switch
                     (psum_scatter in row mode, all_to_all in table mode).
    dense_fwd_bwd    data-parallel dense forward/backward on one
                     microbatch; returns (loss, dense grads, emb cotangent).
    dY_exchange      the mirror collective of the fwd layout switch, per
                     microbatch (overlaps the NEXT microbatch's compute).
    sparse_update    ONE fused sparse-backward + SGD application on the
                     concatenated, order-restored index/cotangent stream
                     (bit-identical to the unpipelined step — see below).
    dense_update     ONE bucketed RS+AG Split-SGD step on the accumulated
                     dense gradient (C4+C5).

Microbatch partition and bit-exactness
--------------------------------------
Microbatch i is "every device's i-th slice of its local batch share".
For batch-sharded inputs that is a contiguous local slice; for replicated
index streams it is the matching strided selection (device-major layout
``[ns, M, c]`` sliced at ``[:, i]``), so the bag output of each microbatch
lands on exactly the rows whose dense features the device already holds.
Every microbatch's forward/backward runs against the step's INITIAL
weights (classic gradient accumulation), per-microbatch update streams are
concatenated and restored to the full-batch order with a static
permutation, and the sparse update is applied ONCE — hence
``make_pipelined_train_step(M=1)`` is bit-identical to the legacy
monolithic step and ``M>1`` is bit-identical on the embedding path (the
accumulated dense gradient sums per-microbatch partial sums, which
reassociates the reduction; see tests/test_pipeline.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import sharded_embedding as se
from repro.data.pipeline import PSORT_KEYS
from repro.dist import exchange as exchange_cfg
from repro.kernels import ops
from repro.optim import data_parallel as dp
from repro.optim import row as row_optim


# ---------------------------------------------------------------------------
# Stage plumbing
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Stage:
    """One named, composable piece of the hybrid step (runs INSIDE
    shard_map).  Its ops run under ``jax.named_scope(name)``, so every
    instruction of the compiled step carries its stage in ``op_name`` —
    trace-time metadata only, the compiled code is the same
    (docs/telemetry.md, "Scopes in the compiled step")."""

    name: str
    fn: Callable

    def __call__(self, *args, **kwargs):
        with jax.named_scope(self.name):
            return self.fn(*args, **kwargs)


@dataclasses.dataclass(frozen=True)
class PipelineStages:
    """The staged decomposition of one hybrid-parallel train step."""

    index_exchange: Stage
    embedding_fwd: Stage
    dense_fwd_bwd: Stage
    dY_exchange: Stage
    sparse_update: Stage
    dense_update: Stage


def mesh_axes(mesh) -> tuple[tuple[str, ...], str, tuple[str, ...]]:
    """(all_axes, model_axis, batch_axes).  The last mesh axis is 'model'."""
    names = tuple(mesh.axis_names)
    return names, names[-1], names[:-1]


def emb_axes(mdef, mesh):
    """Row mode shards the row space over the FULL mesh; table mode uses the
    model axis and replicates over the rest."""
    all_axes, model, batch_axes = mesh_axes(mesh)
    if mdef.emb_mode == "row":
        return all_axes, None
    return model, (batch_axes if batch_axes else None)


# one source of truth for the device-major flattening rule
_combined_axis_index = dp.combined_axis_index


def validate_pipeline(mdef, mesh, microbatches: int) -> None:
    """Reject unsupported (emb_mode, idx_input, M) combinations with a
    clear error instead of silently mis-sharding."""
    if mdef.emb_mode not in ("row", "table"):
        raise ValueError(f"unknown emb_mode {mdef.emb_mode!r}; "
                         "expected 'row' or 'table'")
    if mdef.idx_input not in ("replicated", "sharded"):
        raise ValueError(f"unknown idx_input {mdef.idx_input!r}; "
                         "expected 'replicated' or 'sharded'")
    # an exchange that is not an ExchangeConfig fails here, loudly
    exchange_cfg.resolve_exchange(mdef)
    if microbatches < 1:
        raise ValueError(f"microbatches must be >= 1, got {microbatches}")
    ns = int(np.prod(list(mesh.shape.values())))
    if mdef.batch % (microbatches * ns):
        raise ValueError(
            f"global batch {mdef.batch} must be divisible by microbatches "
            f"* mesh size = {microbatches} * {ns}")
    hot_rows = int(mdef.hot_rows)
    if hot_rows < 0:
        raise ValueError(f"hot_rows must be >= 0, got {hot_rows}")
    # validated even with the cache off: a malformed 'deferred:' string
    # should fail at build time, not when hot_rows is finally turned on
    from repro.core import cache as hot_cache
    hot_cache.parse_hot_sync(mdef.hot_sync)
    if hot_rows > 0:
        if int(mdef.promote_every) < 1:
            raise ValueError("promote_every must be >= 1, got "
                             f"{mdef.promote_every}")
        if hot_rows > mdef.spec.total_rows:
            raise ValueError(
                f"hot_rows {hot_rows} exceeds the unified row space "
                f"({mdef.spec.total_rows} rows)")
    row_optim.resolve(mdef)   # unknown sparse_optimizer fails here, loudly


# ---------------------------------------------------------------------------
# ppermute-chunked exchange (the "async" lowering of the index gather)
# ---------------------------------------------------------------------------

def _ring_all_gather_1d(x: jax.Array, axis_name) -> jax.Array:
    """Tiled all_gather over ONE named axis as ns-1 ppermute steps.  Output
    is bit-identical to ``jax.lax.all_gather(..., tiled=True)`` (pure data
    movement, no arithmetic), but each chunk is an independent op the
    scheduler can interleave with compute."""
    ns = compat.axis_size(axis_name)
    if ns == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    chunk = x.shape[0]
    out = jnp.zeros((ns * chunk,) + x.shape[1:], x.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, x, idx * chunk, axis=0)
    cur = x
    perm = [(i, (i + 1) % ns) for i in range(ns)]
    for k in range(1, ns):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        src = jnp.mod(idx - k, ns)          # after k shifts: chunk of idx-k
        out = jax.lax.dynamic_update_slice_in_dim(out, cur, src * chunk,
                                                  axis=0)
    return out


def ring_all_gather(x: jax.Array, axis_name) -> jax.Array:
    """Tiled all_gather over a (tuple of) mesh axes via ppermute rings,
    minor axis first — same block order as the fused collective."""
    axes = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    for ax in reversed(tuple(axes)):
        x = _ring_all_gather_1d(x, ax)
    return x


def _exchange_collective(x: jax.Array, axis_name, impl: str) -> jax.Array:
    if impl == "ring":
        return ring_all_gather(x, axis_name)
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)


# ---------------------------------------------------------------------------
# Stage construction
# ---------------------------------------------------------------------------

def build_stages(mdef, mesh, layout) -> PipelineStages:
    """Bind the model definition to the five pipeline stages.  All returned
    callables run INSIDE shard_map over the full mesh."""
    all_axes, model, batch_axes = mesh_axes(mesh)
    emb_ax, replica_ax = emb_axes(mdef, mesh)
    nb = (int(np.prod([mesh.shape[a] for a in batch_axes]))
          if batch_axes else 1)
    ex_cfg = exchange_cfg.resolve_exchange(mdef)
    impl = ex_cfg.impl
    B = mdef.batch
    # unset: the fused kernel wherever it runs compiled (a TPU), the
    # reference where it would only be interpreted (the CPU)
    fused = (not ops._default_interpret() if mdef.fused_update is None
             else mdef.fused_update)
    opt = row_optim.resolve(mdef)

    def exchange(idx_mb, fwd_only: bool = False):
        """Index stream: loader layout -> compute layout for one
        microbatch.  Returns (idx_fwd, idx_upd): the forward consumes
        ``idx_fwd``; the sparse update consumes ``idx_upd`` (the full
        microbatch in device-major order, matching dY_exchange).
        ``fwd_only`` (serve path) skips the update-side gather."""
        if mdef.emb_mode == "row":
            if mdef.idx_input == "sharded":
                g = _exchange_collective(idx_mb, emb_ax, impl)
                return g, g
            return idx_mb, idx_mb
        if mdef.idx_input == "sharded":
            # on-chip exchange replaces the replicated loader AND the
            # host-side permute_indices: gather the original-slot stream,
            # permute to padded-slot order, slice this shard's slots.
            full = _exchange_collective(idx_mb, all_axes, impl)
            padded = se.permute_indices(layout, full)     # [Bm, n_pad, P]
            K = layout.slots_per_shard
            m_idx = jax.lax.axis_index(model)
            idx_upd = jax.lax.dynamic_slice_in_dim(padded, m_idx * K, K,
                                                   axis=1)
            if nb > 1:
                c = idx_upd.shape[0] // nb
                d_idx = _combined_axis_index(batch_axes)
                idx_fwd = jax.lax.dynamic_slice_in_dim(idx_upd, d_idx * c,
                                                       c, axis=0)
            else:
                idx_fwd = idx_upd
            return idx_fwd, idx_upd
        # paper loader: padded-slot order, already model-sharded slots;
        # the update additionally needs every replica's batch rows.
        if fwd_only:
            return idx_mb, None
        idx_upd = (_exchange_collective(idx_mb, replica_ax, impl)
                   if replica_ax is not None else idx_mb)
        return idx_mb, idx_upd

    def embedding_fwd(W_fwd, idx_fwd, wgt_fwd=None):
        return se.sharded_bag_fwd(layout, W_fwd, idx_fwd, emb_ax, wgt_fwd)

    def dense_fwd_bwd(dense_hi, emb_out, batch_mb):
        def loss_fn(hi, e):
            return mdef.dense_loss(hi, e, batch_mb) / B
        loss, (g_dense, d_emb) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(dense_hi, emb_out)
        return loss, g_dense, d_emb

    def dY_exchange(d_emb, seed=None, tag=0):
        # seed = the per-step sr counter (None when the state carries
        # none — the dither then keys off step 0);
        # tag = the microbatch index, so no two payloads share a stream
        return se.gather_dY(layout, d_emb, emb_ax, replica_ax,
                            wire_dtype=ex_cfg.dY_dtype, seed=seed, tag=tag)

    def sparse_update(emb_store, idx_upd, dY, weights=None, presort=None,
                      seed=None):
        # ONE dispatcher for every registered RowOptimizer: the presorted
        # stream (repro/data/pipeline.py — no on-device sort, bag weights
        # baked into sorted_wgt) and the sorting scan/fused paths all go
        # through RowOptimizer.apply_sparse.  ``seed`` is the per-step
        # stochastic-rounding counter (state["sr"], present only when the
        # optimizer asked for one) — forwarded opaquely, so this stage
        # stays optimizer-agnostic.  NB: the fused fp32 kernels
        # pre-reduce duplicates (one rounding per row) where the sgd
        # reference scatter-adds per lookup, so those two paths are close
        # but not bit-identical; the split path is bitwise either way.
        return se.apply_update(layout, emb_store, opt, idx_upd, dY,
                               mdef.lr, emb_ax, replica_axes=None,
                               fused=fused, weights=weights,
                               presort=presort, seed=seed)

    def dense_update(dense_state, g_dense, seed=None):
        st = dp.DPState(hi=dense_state["hi"], lo_shard=dense_state["lo"],
                        mom_shard=None, err_shard=dense_state["err"])
        st2 = dp.rs_ag_split_sgd(st, g_dense, mdef.lr, all_axes,
                                 wire_dtype=ex_cfg.dense_dtype,
                                 error_feedback=ex_cfg.error_feedback,
                                 num_buckets=ex_cfg.num_buckets, mean=False,
                                 seed=seed)
        return {"hi": st2.hi, "lo": st2.lo_shard, "err": st2.err_shard}

    return PipelineStages(
        index_exchange=Stage("index_exchange", exchange),
        embedding_fwd=Stage("embedding_fwd", embedding_fwd),
        dense_fwd_bwd=Stage("dense_fwd_bwd", dense_fwd_bwd),
        dY_exchange=Stage("dY_exchange", dY_exchange),
        sparse_update=Stage("sparse_update", sparse_update),
        dense_update=Stage("dense_update", dense_update),
    )


# ---------------------------------------------------------------------------
# Microbatch slicing and stream-order restoration
# ---------------------------------------------------------------------------

def _slice_local(v: jax.Array, i: int, M: int) -> jax.Array:
    c = v.shape[0] // M
    return jax.lax.slice_in_dim(v, i * c, (i + 1) * c, axis=0)


def _slice_idx(idx, i: int, M: int, mdef, repl_width: int):
    """Microbatch i of the index stream.  Batch-sharded streams slice the
    local share contiguously; REPLICATED streams take the matching strided
    selection (device-major ``[width, M, c]`` at ``[:, i]``) so the bag
    output of the microbatch lands on the rows whose dense features each
    device already holds."""
    if M == 1:
        return idx
    if mdef.idx_input == "sharded":
        return _slice_local(idx, i, M)
    Bl = idx.shape[0]
    c = Bl // (repl_width * M)
    r = idx.reshape((repl_width, M, c) + idx.shape[1:])
    return r[:, i].reshape((repl_width * c,) + idx.shape[1:])


def _interleave_perm(B: int, M: int, ns: int) -> np.ndarray:
    """Static permutation restoring the concatenated per-microbatch update
    stream (order: microbatch-major ``(i, device, j)``) to the full-batch
    device-major order ``(device, i, j)`` the M=1 step sees."""
    c = B // (M * ns)
    return np.arange(B).reshape(M, ns, c).transpose(1, 0, 2).reshape(-1)


# ---------------------------------------------------------------------------
# The pipelined step factory
# ---------------------------------------------------------------------------

def make_pipelined_train_step(mdef, mesh, microbatches: int = 1):
    """Build the staged, microbatched hybrid-parallel train step.

    ``microbatches=1`` composes the stages back into exactly the legacy
    monolithic step (bit-identical outputs).  ``microbatches=M`` splits the
    global batch into M microbatches, double-buffers the index exchange
    (microbatch i+1's collective is issued while microbatch i computes),
    accumulates dense gradients across microbatches into a single RS+AG,
    and applies ONE sparse update on the order-restored concatenated
    stream.

    Returns (jitted step, state shardings, batch specs, layout) — the same
    contract as the legacy ``make_train_step``.
    """
    from repro.core import hybrid  # deferred: hybrid imports this module

    M = int(microbatches)
    validate_pipeline(mdef, mesh, M)
    structs, specs, shardings, layout = hybrid.state_struct(mdef, mesh)
    bstructs, bspecs = hybrid.batch_struct(mdef, mesh, layout)
    all_axes, model, batch_axes = mesh_axes(mesh)
    ns = int(np.prod(list(mesh.shape.values())))
    nm = mesh.shape[model]
    stages = build_stages(mdef, mesh, layout)
    # replicated index streams carry the device-major layout of the axes
    # the stream is replicated over: the full mesh in row mode, the model
    # axis in table mode (the batch dim is already sharded over the rest).
    repl_width = ns if mdef.emb_mode == "row" else nm
    perm = (jnp.asarray(_interleave_perm(mdef.batch, M, ns))
            if M > 1 else None)
    weighted = mdef.weighted
    presorted = mdef.host_presort
    opt = row_optim.resolve(mdef)
    emb_ax, _ = emb_axes(mdef, mesh)
    cache_on = int(mdef.hot_rows) > 0
    # the exact forward bypass needs every bag computed whole by ONE
    # shard and the rank's own index slice available locally: table mode
    # with the on-chip index exchange.  Row mode's psum_scatter folds
    # arithmetic INTO the collective, so a bypass there could not be
    # bitwise; the cache still maintains counters / hot set (and serves
    # the bench model), it just cannot substitute bags.
    bypass = (cache_on and mdef.emb_mode == "table"
              and mdef.idx_input == "sharded")
    if cache_on:
        from repro.core import cache as hot_cache
    metrics_on = bool(mdef.step_metrics)
    if metrics_on:
        from repro.telemetry import metrics as step_mx

    def step_local(state, batch):
        emb_store = state["emb"]
        # the step's glue runs under the scope of the stage it serves, so
        # (almost) no device time is left without a stage
        with jax.named_scope("embedding_fwd"):
            W_fwd = opt.fwd_weights(emb_store)
        dense_hi = state["dense"]["hi"]
        # per-step stochastic-rounding seed: a replicated int32 counter in
        # the train state (present when the optimizer registered
        # stochastic_round=True OR a 'bf16_sr' wire format is configured),
        # consumed by the epilogue sparse_update and the bf16_sr wire
        # encoders, incremented once per step — so resume-from-checkpoint
        # replays the exact dither sequence, state AND wire.
        sr = state.get("sr")
        # host-pre-sorted update stream: each shard's [1, L] block of the
        # psort_* batch fields (leading dim = combined mesh index, the
        # same device-major order the restored idx stream carries).  The
        # fields describe the FULL batch, so they bypass microbatching
        # and feed the single epilogue sparse_update.
        presort = (tuple(batch[k][0] for k in PSORT_KEYS)
                   if presorted else None)

        def microbatch(i):
            items = ((k, v) for k, v in batch.items()
                     if k not in PSORT_KEYS)
            if M == 1:
                return dict(items)
            # weights ride the exact layout of idx -> same slicing rule
            return {k: (_slice_idx(v, i, M, mdef, repl_width)
                        if k in ("idx", "weights")
                        else _slice_local(v, i, M))
                    for k, v in items}

        # -- prologue: microbatch 0's index exchange ----------------------
        ex = [None] * M
        exw = [None] * M
        ex[0] = stages.index_exchange(microbatch(0)["idx"])
        if weighted:
            # the weight stream undergoes the IDENTICAL layout switch
            exw[0] = stages.index_exchange(microbatch(0)["weights"])

        loss_acc = None
        g_acc = None
        idx_parts, dY_parts, wgt_parts = [], [], []
        for i in range(M):
            if i + 1 < M:
                # double buffer: issue microbatch i+1's exchange BEFORE
                # microbatch i's compute — no data dependence between the
                # two, so the scheduler can overlap collective and compute.
                ex[i + 1] = stages.index_exchange(microbatch(i + 1)["idx"])
                if weighted:
                    exw[i + 1] = stages.index_exchange(
                        microbatch(i + 1)["weights"])
            idx_fwd, idx_upd = ex[i]
            wgt_fwd, wgt_upd = exw[i] if weighted else (None, None)
            emb_out = stages.embedding_fwd(W_fwd, idx_fwd, wgt_fwd)
            mb = microbatch(i)
            if bypass:
                # hot-row cache: bags whose lookups ALL hit the
                # replicated hot slab are recomputed from the rank's OWN
                # index slice with the owner's exact bag arithmetic and
                # substituted — those bags no longer depend on the
                # all-to-all payload.  The cold-store update below is
                # unchanged (write-through), so under hot_sync=
                # 'allreduce' this is bitwise invisible.
                cache = state["cache"]
                with jax.named_scope("embedding_fwd"):
                    hit, hot_bag = hot_cache.hot_bag_local(
                        layout, cache["hot_w"], cache["hot_pos"], mb["idx"],
                        mb.get("weights") if weighted else None)
                    emb_out = jnp.where(hit[..., None], hot_bag, emb_out)
            loss, g_dense, d_emb = stages.dense_fwd_bwd(
                dense_hi, emb_out, mb)
            dY = stages.dY_exchange(d_emb, seed=sr, tag=i)
            with jax.named_scope("dense_fwd_bwd"):
                loss_acc = loss if loss_acc is None else loss_acc + loss
                g_acc = (g_dense if g_acc is None
                         else jax.tree.map(jnp.add, g_acc, g_dense))
            idx_parts.append(idx_upd)
            dY_parts.append(dY)
            if weighted:
                wgt_parts.append(wgt_upd)

        # -- epilogue: one sparse update on the order-restored stream -----
        def restore(parts):
            if M == 1:
                return parts[0]
            return jnp.take(jnp.concatenate(parts, axis=0), perm, axis=0)

        with jax.named_scope("sparse_update"):
            idx_full, dY_full = restore(idx_parts), restore(dY_parts)
            wgt_full = restore(wgt_parts) if weighted else None
        new_emb = stages.sparse_update(emb_store, idx_full, dY_full,
                                       weights=wgt_full, presort=presort,
                                       seed=sr)
        new_dense = stages.dense_update(state["dense"], g_acc, seed=sr)
        new_state = {"emb": new_emb, "dense": new_dense}
        if sr is not None:
            with jax.named_scope("dense_update"):
                new_state["sr"] = sr + jnp.asarray(1, sr.dtype)
        if cache_on:
            # cache epilogue: promotion + mirror refresh read the POST-
            # update store, so an 'allreduce' mirror equals the cold
            # store entering the next step.
            with jax.named_scope("cache_epilogue"):
                new_state["cache"] = hot_cache.step_cache(
                    mdef, layout, opt, state["cache"], new_emb, emb_ax)
        if metrics_on:
            # metrics epilogue: accumulate this step's counters into the
            # replicated state["metrics"] vector.  Reads only the raw
            # index stream and the PRE-step hot set — the same inputs
            # the forward consumed — and writes only its own slot, so
            # the training outputs are untouched (and with step_metrics
            # off, none of this exists in the lowered program).
            with jax.named_scope("metrics_epilogue"):
                idx_raw = batch["idx"]
                if mdef.idx_input == "sharded":
                    # batch-sharded original-slot stream: every rank counts
                    # its own disjoint slice, psum makes it global
                    rows = jax.lax.psum(
                        step_mx.valid_lookups(layout, idx_raw), all_axes)
                elif mdef.emb_mode == "row":
                    # replicated stream: the local count IS the global count
                    rows = step_mx.valid_lookups(layout, idx_raw)
                else:
                    # paper loader, table mode: padded-slot stream, slots
                    # sharded over 'model', batch over the rest — disjoint
                    # (row, slot) cells, so psum over everything is global
                    rows = jax.lax.psum(
                        step_mx.valid_lookups_padded(layout, idx_raw, model),
                        all_axes)
                if bypass:
                    hl, hb = step_mx.cache_hit_counts(
                        layout, state["cache"]["hot_pos"], idx_raw)
                    hit_lookups = jax.lax.psum(hl, all_axes)
                    skipped = jax.lax.psum(hb, all_axes)
                else:
                    hit_lookups = jnp.float32(0)
                    skipped = jnp.float32(0)
                bags = jnp.float32(mdef.batch * layout.num_orig_slots)
                payload = (bags - skipped) * jnp.float32(mdef.spec.dim * 4)
                new_state["metrics"] = state["metrics"] + step_mx.pack(
                    steps=1.0, hit_lookups=hit_lookups, skipped_bags=skipped,
                    bags=bags, rows_touched=rows,
                    exchange_payload_bytes=payload)
        with jax.named_scope("dense_fwd_bwd"):
            loss = jax.lax.psum(loss_acc, all_axes)
        return new_state, loss

    step = compat.shard_map(step_local, mesh=mesh, in_specs=(specs, bspecs),
                            out_specs=(specs, P()), check_vma=False)
    return jax.jit(step, donate_argnums=(0,)), shardings, bspecs, layout
