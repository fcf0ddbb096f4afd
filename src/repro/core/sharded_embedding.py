"""Hybrid-parallel embedding (paper contribution C3) as shard_map-inner ops.

The model side addresses the embedding through SLOTS: the index array is
``[B, S_slots, P]`` and each slot maps to a table via ``slot_to_table``
(identity by default).  Slot sharing is how sequence models reuse one item
table across positions (BST/SASRec/DIN) — updates from all slots of a table
accumulate into the same rows.

Two model-parallel placements over the unified row space of
:class:`repro.core.embedding.EmbeddingSpec`:

``table`` (paper-faithful)
    Tables are greedy-bin-packed onto the ``model`` axis (paper IV-B: "we
    simply distribute tables across available ranks").  Each shard computes
    full-batch bags for its own slots, then ONE fused
    ``jax.lax.all_to_all`` switches model->data parallel layout before the
    interaction — the end state of the paper's ScatterList -> Fused Scatter ->
    Alltoall hillclimb.  Max model-parallel width = number of tables
    (paper Tab. II "Maximum ranks to scale").

``row`` (beyond-paper)
    Every shard owns a contiguous row-range of ALL tables — the TPU-native
    generalization of the race-free update (Alg. 4): ownership is the
    partition.  Forward = masked local partial bags + ``psum_scatter`` (the
    all-to-all and the bag reduction fuse into one reduce-scatter); width is
    unbounded by the table count, which is what 1000+ node meshes need.

Both modes expose:
    fwd:     idx (+ local weight shard) -> [B_mp, S, E] batch-sharded output
    update:  dY [B_mp, S, E] -> new local weight shard (fused bwd+optimizer,
             contribution C1 — no dense dW is ever materialized)

All functions are designed to run INSIDE ``jax.shard_map``; ``axis_name`` is
the model axis (possibly a tuple of axes).  ``B`` below is the per-data-shard
batch; the fwd output is further batch-split over the model axis
(B_mp = B / num_shards), so the dense net downstream is data-parallel over
every mesh axis, exactly like the paper.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding import EmbeddingSpec, _round_up


@dataclasses.dataclass(frozen=True)
class ShardedEmbeddingLayout:
    """Static placement of a unified embedding space over ``num_shards``."""

    spec: EmbeddingSpec
    num_shards: int
    mode: str                      # "row" | "table"
    rows_per_shard: int
    slot_to_table: np.ndarray      # [S_slots] table id per model slot
    # row mode: global row offset per SLOT:
    row_offsets: Optional[np.ndarray] = None
    # table mode:
    slots_per_shard: int = 0
    # padded (bin-major) slot order; -1 for dummy:
    padded_slots: Optional[np.ndarray] = None   # [num_shards*slots_per_shard]
    # row offset (relative to shard start) per padded position:
    slot_local_offsets: Optional[np.ndarray] = None
    # original slot -> padded position:
    slot_position: Optional[np.ndarray] = None

    @property
    def total_rows(self) -> int:
        return self.num_shards * self.rows_per_shard

    @property
    def num_orig_slots(self) -> int:
        return len(self.slot_to_table)

    @property
    def num_padded_slots(self) -> int:
        return self.num_shards * self.slots_per_shard


def make_layout(spec: EmbeddingSpec, num_shards: int, mode: str = "row",
                slot_to_table=None) -> ShardedEmbeddingLayout:
    s2t = (np.arange(spec.num_tables, dtype=np.int64)
           if slot_to_table is None
           else np.asarray(slot_to_table, dtype=np.int64))
    if mode == "row":
        rows = _round_up(spec.total_rows,
                         num_shards * spec.row_pad) // num_shards
        return ShardedEmbeddingLayout(
            spec=spec, num_shards=num_shards, mode="row",
            rows_per_shard=rows, slot_to_table=s2t,
            row_offsets=spec.row_offsets[s2t])
    if mode != "table":
        raise ValueError(f"unknown mode {mode!r}")
    bins = spec.binpack_tables(num_shards)   # tables -> bins (may be empty)
    padded = spec.padded_rows
    # bin-local row offset per table
    table_bin = np.zeros(spec.num_tables, np.int64)
    table_off = np.zeros(spec.num_tables, np.int64)
    max_bin_rows = 0
    for b, tables in enumerate(bins):
        off = 0
        for t in tables:
            table_bin[t] = b
            table_off[t] = off
            off += int(padded[t])
        max_bin_rows = max(max_bin_rows, off)
    # +row_pad spare guarantees a scratch row for dummy slots on every shard.
    rows_per_shard = _round_up(max_bin_rows + spec.row_pad, spec.row_pad)
    # group SLOTS by their table's bin
    slots_by_bin: list[list[int]] = [[] for _ in range(num_shards)]
    for s, t in enumerate(s2t):
        slots_by_bin[table_bin[t]].append(s)
    slots_per_shard = max(1, max(len(g) for g in slots_by_bin))
    n_pad = num_shards * slots_per_shard
    padded_slots = np.full(n_pad, -1, np.int64)
    local_off = np.full(n_pad, rows_per_shard - 1, np.int64)  # dummies
    slot_position = np.zeros(len(s2t), np.int64)
    for b, group in enumerate(slots_by_bin):
        for j, s in enumerate(group):
            p = b * slots_per_shard + j
            padded_slots[p] = s
            local_off[p] = table_off[s2t[s]]
            slot_position[s] = p
    return ShardedEmbeddingLayout(
        spec=spec, num_shards=num_shards, mode="table",
        rows_per_shard=rows_per_shard, slot_to_table=s2t,
        slots_per_shard=slots_per_shard, padded_slots=padded_slots,
        slot_local_offsets=local_off, slot_position=slot_position)


def layout_gid_maps(layout: ShardedEmbeddingLayout
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Static numpy maps between LAYOUT row positions and SPEC-GLOBAL row
    ids (``gid`` = ``spec.row_offsets[t] + table-local row``, the layout-
    independent identity the hot-row cache keys its membership on so it
    survives elastic reshards).

    Returns ``(l2g [layout.total_rows], g2l [spec.total_rows])``, both
    int32 with -1 for positions that map nowhere: layout padding
    (row-mode tail, table-mode bin slack and the dummy-slot scratch row)
    on the ``l2g`` side, per-table ``row_pad`` gaps in the unified gid
    space on the ``g2l`` side."""
    spec = layout.spec
    l2g = np.full(layout.total_rows, -1, np.int32)
    if layout.mode == "row":
        # row-mode layout rows ARE the unified spec rows, padded up to
        # num_shards * rows_per_shard — but gids inside per-table padding
        # gaps belong to no table, so map only the real rows
        for t, rows_t in enumerate(spec.table_rows):
            base = int(spec.row_offsets[t])
            l2g[base:base + rows_t] = base + np.arange(rows_t, dtype=np.int32)
    else:
        for pos, s in enumerate(layout.padded_slots):
            if s < 0:
                continue
            t = int(layout.slot_to_table[s])
            rows_t = int(spec.table_rows[t])
            base = ((pos // layout.slots_per_shard) * layout.rows_per_shard
                    + int(layout.slot_local_offsets[pos]))
            l2g[base:base + rows_t] = (int(spec.row_offsets[t])
                                       + np.arange(rows_t, dtype=np.int32))
    g2l = np.full(spec.total_rows, -1, np.int32)
    owned = np.nonzero(l2g >= 0)[0]
    g2l[l2g[owned]] = owned.astype(np.int32)
    return l2g, g2l


def permute_indices(layout: ShardedEmbeddingLayout, idx: jax.Array
                    ) -> jax.Array:
    """[B, S, P] original-slot indices -> [B, num_padded_slots, P] padded
    order (table mode).  Dummy slots read index 0 (the scratch row)."""
    assert layout.mode == "table"
    src = np.where(layout.padded_slots >= 0, layout.padded_slots, 0)
    out = jnp.take(idx, jnp.asarray(src), axis=1)
    dummy = jnp.asarray((layout.padded_slots < 0))[None, :, None]
    return jnp.where(dummy, 0, out)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _partial_bag_masked(W_local: jax.Array, local_rows: jax.Array,
                        valid: jax.Array,
                        weights: Optional[jax.Array] = None) -> jax.Array:
    rows = jnp.take(W_local, jnp.clip(local_rows, 0, W_local.shape[0] - 1),
                    axis=0).astype(jnp.float32)
    if weights is not None:
        # weighted bag: Y = sum_p w_p * W[g_p].  w == 1.0 multiplies
        # exactly, so an all-ones weight stream keeps the unweighted
        # bit-identity contract.
        rows = rows * weights[..., None].astype(jnp.float32)
    rows = jnp.where(valid[..., None], rows, 0.0)
    return rows.sum(axis=2)  # [B, S, E] fp32


def _batch_chunks(B: int, S: int, P: int, E: int,
                  budget_bytes: int | None = None) -> int:
    """Pick a batch-chunk count so the transient [chunk,S,P,E] fp32 gather
    stays under ``budget_bytes`` (paper configs reach P=100: the unchunked
    expansion would be tens of GB).  REPRO_EMB_CHUNK_BUDGET overrides (the
    roofline cost builds disable chunking so cost_analysis sees one body)."""
    import os as _os
    if budget_bytes is None:
        budget_bytes = int(_os.environ.get("REPRO_EMB_CHUNK_BUDGET",
                                           128 * 2**20))
    per_row = S * P * E * 4
    chunk = max(1, budget_bytes // max(per_row, 1))
    if chunk >= B:
        return 1
    n = (B + chunk - 1) // chunk
    while B % n:  # need uniform chunks for lax.scan
        n += 1
    return n


def row_sharded_bag_fwd(layout: ShardedEmbeddingLayout, W_local: jax.Array,
                        idx: jax.Array, axis_name,
                        weights: Optional[jax.Array] = None) -> jax.Array:
    """Row mode forward.  ``axis_name`` may be a TUPLE of mesh axes — the
    production config shards the row space over the FULL mesh (the paper's
    pure model-parallel embedding, scaled past the table count).  ``idx``
    [B, S, P] is replicated over ``axis_name``; ``weights`` [B, S, P]
    optional per-lookup bag weights (same layout as ``idx``); output is
    [B/num_shards, S, E] (reduce-scatter over the batch dim).

    The gather+bag is scanned over batch chunks so the [chunk,S,P,E]
    transient stays bounded for large pooling factors."""
    g = idx + jnp.asarray(layout.row_offsets, idx.dtype)[None, :, None]
    start = jax.lax.axis_index(axis_name) * layout.rows_per_shard
    local = g - start
    B, S, P = idx.shape
    E = W_local.shape[1]
    n = _batch_chunks(B, S, P, E)
    if n == 1:
        valid = (local >= 0) & (local < layout.rows_per_shard)
        part = _partial_bag_masked(W_local, local, valid, weights)
    else:
        def body(_, inp):
            loc_c = inp[0]
            w_c = inp[1] if weights is not None else None
            valid = (loc_c >= 0) & (loc_c < layout.rows_per_shard)
            return None, _partial_bag_masked(W_local, loc_c, valid, w_c)
        xs = (local.reshape(n, B // n, S, P),)
        if weights is not None:
            xs += (weights.reshape(n, B // n, S, P),)
        _, part = jax.lax.scan(body, None, xs)
        part = part.reshape(B, S, E)
    # bf16 wire (HC3): the reduce-scatter is the dominant collective of the
    # hybrid step and the bag output feeds a bf16 dense net anyway.
    part = part.astype(jnp.bfloat16)
    return jax.lax.psum_scatter(part, axis_name, scatter_dimension=0,
                                tiled=True).astype(jnp.float32)


def table_sharded_bag_fwd(layout: ShardedEmbeddingLayout, W_local: jax.Array,
                          idx_slots_local: jax.Array, axis_name,
                          weights: Optional[jax.Array] = None
                          ) -> jax.Array:
    """Table mode forward.  ``idx_slots_local`` [B, slots_per_shard, P] is
    the padded-slot index array already sharded over the model axis;
    ``weights`` optional per-lookup bag weights in the same layout.  Output
    is [B/num_shards, S_orig, E] in ORIGINAL slot order."""
    K = layout.slots_per_shard
    shard = jax.lax.axis_index(axis_name)
    off_all = jnp.asarray(layout.slot_local_offsets).reshape(
        layout.num_shards, K)
    local = idx_slots_local + jax.lax.dynamic_index_in_dim(
        off_all, shard, axis=0, keepdims=False)[None, :, None]
    B, _, P = local.shape
    E = W_local.shape[1]
    n = _batch_chunks(B, K, P, E)

    def bag(loc, w=None):
        rows = jnp.take(W_local, jnp.clip(loc, 0, W_local.shape[0] - 1),
                        axis=0).astype(jnp.float32)
        if w is not None:
            rows = rows * w[..., None].astype(jnp.float32)
        return rows.sum(axis=2)

    if n == 1:
        part = bag(local, weights)               # [B, K, E] full local batch
    else:
        xs = (local.reshape(n, B // n, K, P),)
        if weights is not None:
            xs += (weights.reshape(n, B // n, K, P),)
        _, part = jax.lax.scan(
            lambda c, inp: (None, bag(inp[0], inp[1] if weights is not None
                                      else None)), None, xs)
        part = part.reshape(B, K, E)
    out = jax.lax.all_to_all(part, axis_name, split_axis=0, concat_axis=1,
                             tiled=True)         # [B/ns, num_padded, E]
    # back to original slot order (drop dummy slots):
    return jnp.take(out, jnp.asarray(layout.slot_position), axis=1)


def sharded_bag_fwd(layout: ShardedEmbeddingLayout, W_local: jax.Array,
                    idx_local: jax.Array, axis_name,
                    weights: Optional[jax.Array] = None) -> jax.Array:
    if layout.mode == "row":
        return row_sharded_bag_fwd(layout, W_local, idx_local, axis_name,
                                   weights)
    return table_sharded_bag_fwd(layout, W_local, idx_local, axis_name,
                                 weights)


def row_bag_fwd_replicated(layout: ShardedEmbeddingLayout, W_local, idx,
                           axis_name) -> jax.Array:
    """Row-mode bag with a REPLICATED [B, S, E] output (psum instead of
    reduce-scatter).  Used when B < num_shards, e.g. the retrieval step's
    single query."""
    local, valid = _local_rows(layout, idx, axis_name)
    part = _partial_bag_masked(W_local, local, valid)
    return jax.lax.psum(part, axis_name)


# ---------------------------------------------------------------------------
# Fused backward + update (sparse optimizer; C1)
# ---------------------------------------------------------------------------

def _local_rows(layout: ShardedEmbeddingLayout, idx_local: jax.Array,
                axis_name) -> tuple[jax.Array, jax.Array]:
    """(local_row [B,S,P], valid [B,S,P]) for this shard, either mode."""
    if layout.mode == "row":
        g = idx_local + jnp.asarray(layout.row_offsets,
                                    idx_local.dtype)[None, :, None]
        start = jax.lax.axis_index(axis_name) * layout.rows_per_shard
        local = g - start
        valid = (local >= 0) & (local < layout.rows_per_shard)
        return local, valid
    K = layout.slots_per_shard
    shard = jax.lax.axis_index(axis_name)
    off_all = jnp.asarray(layout.slot_local_offsets).reshape(
        layout.num_shards, K)
    local = idx_local + jax.lax.dynamic_index_in_dim(
        off_all, shard, axis=0, keepdims=False)[None, :, None]
    valid = jnp.ones(local.shape, bool)
    return local, valid


def _wire_rank(axis_name, replica_axes) -> jax.Array:
    """Global sender index over every axis the dY exchange spans — the rank
    coordinate of the wire-dither tag, so no two devices' payloads share a
    stream.  Uses the single-sourced device-major flattening rule."""
    from repro.optim.data_parallel import combined_axis_index
    axes: list = []
    if replica_axes is not None:
        axes += list(replica_axes if isinstance(replica_axes, (tuple, list))
                     else [replica_axes])
    axes += list(axis_name if isinstance(axis_name, (tuple, list))
                 else [axis_name])
    return combined_axis_index(tuple(axes))


def gather_dY(layout: ShardedEmbeddingLayout, dY_mp: jax.Array, axis_name,
              replica_axes=None, wire_dtype: str = "fp32", seed=None,
              tag: int = 0) -> jax.Array:
    """Bring the batch-model-sharded cotangent dY [B/ns, S, E] back to the
    layout each shard scatters from: row mode all-gathers the batch over the
    model axes; table mode inverse-all_to_alls to [B, K, E] padded-slot order
    (plus an optional replica gather over the data axes).

    ``wire_dtype`` selects the on-wire precision (repro/dist/exchange.py).
    Row mode has ALWAYS shipped a round-to-nearest bf16 payload (matching
    the bf16 psum_scatter forward), so ``'fp32'`` and ``'bf16'`` both keep
    that historical wire bit-for-bit and ``'bf16_sr'`` swaps the rounding
    for the seeded counter dither.  Table mode moves fp32 by default;
    ``'bf16'``/``'bf16_sr'`` halve the all_to_all (and replica-gather)
    payload.  ``seed`` is the replicated per-step sr counter; ``tag`` the
    static payload site within the step (microbatch index).

    16-bit payloads cross the collective as BITCAST uint16 lanes, not as
    a bf16-typed array: ``convert(collective(convert(x)))`` is a pure
    data-movement sandwich XLA legally simplifies back onto an fp32
    carrier (the rounding survives; the byte saving does not), while a
    bitcast is opaque to the algebraic simplifier — the compiled HLO
    genuinely moves 2 bytes/element (checked by
    benchmarks/bench_comm_model.py --exchange-dtype against the lowered
    collective bytes).  Bitcasting changes no payload bits, so this is
    value-identical to the convert-based wire."""
    from repro.dist import exchange as exchange_cfg
    from repro.optim import stochastic

    def _sr(x):
        return stochastic.sr_round_bf16_wire(
            x, jnp.int32(0) if seed is None else seed,
            exchange_cfg.wire_tag(exchange_cfg.TAG_DY, tag,
                                  _wire_rank(axis_name, replica_axes)))

    if layout.mode == "row":
        payload = (_sr(dY_mp) if wire_dtype == "bf16_sr"
                   else dY_mp.astype(jnp.bfloat16))
        wire = jax.lax.bitcast_convert_type(payload, jnp.uint16)
        wire = jax.lax.all_gather(wire, axis_name, axis=0, tiled=True)
        return jax.lax.bitcast_convert_type(
            wire, jnp.bfloat16).astype(jnp.float32)
    src = np.where(layout.padded_slots >= 0, layout.padded_slots, 0)
    dY_slots = jnp.take(dY_mp, jnp.asarray(src), axis=1)
    dummy = jnp.asarray(layout.padded_slots < 0)[None, :, None]
    dY_slots = jnp.where(dummy, 0.0, dY_slots)
    narrow = wire_dtype in ("bf16", "bf16_sr")
    if narrow:
        dY_slots = (_sr(dY_slots) if wire_dtype == "bf16_sr"
                    else dY_slots.astype(jnp.bfloat16))
        dY_slots = jax.lax.bitcast_convert_type(dY_slots, jnp.uint16)
    dY_local = jax.lax.all_to_all(dY_slots, axis_name, split_axis=1,
                                  concat_axis=0, tiled=True)
    if replica_axes is not None:
        dY_local = jax.lax.all_gather(dY_local, replica_axes, axis=0,
                                      tiled=True)
    if narrow:
        dY_local = jax.lax.bitcast_convert_type(dY_local, jnp.bfloat16)
    return dY_local.astype(jnp.float32)


def _row_sorted_streams(layout: ShardedEmbeddingLayout, g_flat: jax.Array,
                        start, pooling: int,
                        weights_flat: Optional[jax.Array] = None) -> tuple:
    """Device-side sorted streams for the ROW-mode fused update, computed
    from the GLOBAL row ids: one axis-INVARIANT stable argsort of the
    global keys, then an elementwise localization into this shard's
    window (subtract ``start``, mask, clip).  Two reasons this shape —
    and not a per-shard sort of the axis-index-derived local rows:

    * the global sort is computed once and identically on every shard
      (the per-shard sorts were ns identical-cost argsorts of shifted
      keys);
    * per touched row the run holds the SAME lookups in the SAME stable
      flat order as the per-shard local sort (shifting all keys by
      ``start`` permutes nothing within the owned window), so the kernel
      output is bit-identical to the host-pre-sorted stream.

    Non-owned lookups keep ``msk == 0`` and clip to row 0 / R-1 — exact
    no-op rewrites (stateless kinds) or flag-guarded write-throughs
    (stateful kinds) under the kernel's liveness contract."""
    G = layout.total_rows
    R = layout.rows_per_shard
    with jax.named_scope("lookup_sort"):
        in_range = (g_flat >= 0) & (g_flat < G)
        key = jnp.where(in_range, g_flat, G).astype(jnp.int32)
        order = jnp.argsort(key)             # stable: ties in flat order
        skey = jnp.take(key, order)
        bags = (order // pooling).astype(jnp.int32)
        wgt = (jnp.ones(key.shape, jnp.float32) if weights_flat is None
               else jnp.take(weights_flat.astype(jnp.float32), order))
        local = skey - start
        msk = ((skey < G) & (local >= 0) & (local < R)).astype(jnp.int32)
        rows = jnp.clip(local, 0, R - 1)
    return rows, bags, msk, wgt


def apply_update(layout: ShardedEmbeddingLayout, store: dict, optimizer,
                 idx_local, dY: jax.Array, lr, axis_name,
                 replica_axes=None, fused: bool = False,
                 weights: Optional[jax.Array] = None,
                 presort: Optional[tuple] = None, seed=None) -> dict:
    """THE sparse update of the hybrid step: one entry point for every
    registered :class:`repro.optim.row.RowOptimizer`, every placement mode
    and every stream shape (replacing the former ``apply_update_scan`` /
    ``apply_update_presorted`` / ``apply_rows_*`` surface).

    ``store``: the optimizer's EmbeddingStore dict — this shard's weight
    slab(s) plus per-row state slabs, all on the same row partition.
    ``idx_local``: [B, S_or_K, P]; ``dY``: matching [B, S_or_K, E]
    (already passed through :func:`gather_dY`).  ``weights``: optional
    [B, S_or_K, P] per-lookup bag weights in the layout of ``idx_local``.
    In table mode with replica axes the index (and weight) arrays are
    gathered the same way as dY.

    ``presort``: this shard's host-pre-sorted ``(sorted_rows, sorted_bags,
    sorted_msk, sorted_wgt)`` [L] arrays (``repro.data.pipeline
    .presort_batch``, row AND table mode; bag weights already baked into
    ``sorted_wgt``) — always the fused Pallas kernel, no on-device sort,
    no batch chunking (only scalars were shipped and the kernel never
    builds a [B,S,P,E] expansion).  Bit-identical to the sorting path
    whenever that path runs unchunked.

    ``fused=True`` runs the Pallas kernel on the FULL stream, unchunked —
    the kernel ships only [L] scalars and never builds a [B,S,P,E]
    expansion (duplicates pre-reduced in VMEM, weights and state updated
    in place on the touched rows only; split results bit-identical to
    the reference).  ``fused=False`` runs the reference row math, chunked
    over the batch to bound the gradient-expansion transients (paper
    configs reach P=100 where the naive expansion is tens of GB); for
    STATEFUL optimizers the chunked reference accumulates the per-row
    gradient across chunks first and applies the optimizer transition
    once — per-chunk transitions would compound the momentum decay /
    Adagrad accumulate n times per step.

    ``seed``: int32 per-step stochastic-rounding seed, forwarded verbatim
    to every ``apply_sparse``/``apply_rows_reduced`` call (the compressed
    bf16-hi state optimizers dither with it; deterministic optimizers
    ignore it) — this module stays per-optimizer-agnostic."""
    from repro.optim.row import SparseStream
    # a row shard's sorted stream is the whole step's, the other shards'
    # lookups masked: the kernel sizes its row groups by the live ones
    shards = layout.num_shards if layout.mode == "row" else 1
    if presort is not None:
        return optimizer.apply_sparse(store, SparseStream(presort=presort,
                                                          dY=dY), lr,
                                      seed=seed, fused=True, shards=shards)
    if layout.mode == "table" and replica_axes is not None:
        idx_local = jax.lax.all_gather(idx_local, replica_axes, axis=0,
                                       tiled=True)
        if weights is not None:
            weights = jax.lax.all_gather(weights, replica_axes, axis=0,
                                         tiled=True)
    if fused and layout.mode == "row":
        # device-sorted fused path: sort the global stream once
        # (axis-invariant), localize elementwise, and feed the kernel's
        # presorted entry — unchunked, like the host-pre-sorted path (the
        # kernel ships only [L] scalars and never builds a [B,S,P,E]
        # expansion), so the result is bit-identical to host_presort.
        g = idx_local + jnp.asarray(layout.row_offsets,
                                    idx_local.dtype)[None, :, None]
        start = jax.lax.axis_index(axis_name) * layout.rows_per_shard
        streams = _row_sorted_streams(
            layout, g.reshape(-1), start, idx_local.shape[-1],
            None if weights is None else weights.reshape(-1))
        return optimizer.apply_sparse(store, SparseStream(presort=streams,
                                                          dY=dY), lr,
                                      seed=seed, shards=shards)
    local, valid = _local_rows(layout, idx_local, axis_name)
    B, S, P = local.shape
    E = dY.shape[-1]
    if fused:
        # table-mode fused (TPU): the kernel ships only [L] scalars and
        # reads dY rows by bag id — there is no [B,S,P,E] expansion to
        # bound, so never chunk (chunking would also re-run stateful
        # transitions per chunk; one apply keeps them once-per-step)
        return optimizer.apply_sparse(
            store, SparseStream(idx=local, dY=dY, valid=valid,
                                weights=weights), lr, seed=seed, fused=True)
    n = _batch_chunks(B, S, P, E)
    cb = B // n

    def chunk_update(st, loc_c, val_c, dY_c, wgt_c=None):
        return optimizer.apply_sparse(
            st, SparseStream(idx=loc_c, dY=dY_c, valid=val_c,
                             weights=wgt_c), lr, seed=seed, fused=False)

    if n == 1:
        return chunk_update(store, local, valid, dY, weights)
    if optimizer.flat_reference is None:
        # chunked reference: the row step must run ONCE per touched row
        # per step, as in the kernel — re-running it per chunk compounds
        # the momentum decay, squares Adagrad partial sums and rounds a
        # split_sgd row once per chunk.  Two phases: scatter-accumulate
        # the per-row gradient across chunks in flat order (the
        # [cb,S,P,E] expansion stays chunk-bounded), then one reduced
        # transition on the unique rows.
        rows = optimizer.fwd_weights(store).shape[0]

        def acc_chunk(dW, inp):
            loc_c, val_c, dY_c = inp[0], inp[1], inp[2]
            wgt_c = inp[3] if weights is not None else None
            grad = jnp.broadcast_to(dY_c[:, :, None, :],
                                    (cb, S, P, E)).astype(jnp.float32)
            if wgt_c is not None:
                grad = grad * wgt_c[..., None].astype(jnp.float32)
            tgt_c = jnp.where(val_c, loc_c, rows)   # OOB -> scatter-drop
            return dW.at[tgt_c.reshape(-1)].add(grad.reshape(-1, E)), None

        xs = (local.reshape(n, cb, S, P), valid.reshape(n, cb, S, P),
              dY.reshape(n, cb, S, E))
        if weights is not None:
            xs += (weights.reshape(n, cb, S, P),)
        dW, _ = jax.lax.scan(acc_chunk, jnp.zeros((rows, E), jnp.float32),
                             xs)
        from repro.optim.row import bump_counters, dedup_targets
        touch = jnp.where(valid, local, rows).reshape(-1)
        if "cnt" in store:
            # this branch bypasses apply_sparse (which owns the reserved
            # touch-counter bump), so bump the full un-deduplicated stream
            # here — apply_rows_reduced carries the slab through untouched
            store = dict(store)
            store["cnt"] = bump_counters(store["cnt"], touch, rows)
        rep = dedup_targets(touch, rows)
        summed = jnp.take(dW, jnp.minimum(rep, rows - 1), axis=0)
        return optimizer.apply_rows_reduced(store, rep, summed, lr,
                                            seed=seed)

    def body(st, inp):
        return chunk_update(st, *inp), None

    xs = (local.reshape(n, cb, S, P), valid.reshape(n, cb, S, P),
          dY.reshape(n, cb, S, E))
    if weights is not None:
        xs += (weights.reshape(n, cb, S, P),)
    store_out, _ = jax.lax.scan(body, store, xs)
    return store_out


def row_grad_rows(layout: ShardedEmbeddingLayout, idx: jax.Array,
                  dY_mp: jax.Array, axis_name
                  ) -> tuple[jax.Array, jax.Array]:
    """Row mode (unchunked; tests / small configs): all-gather dY over the
    model axes (mirror of the fwd reduce-scatter), mask to OWNED rows —
    Alg. 4 as a sharding rule.  Returns (tgt [n], grad [n, E])."""
    dY = jax.lax.all_gather(dY_mp, axis_name, axis=0, tiled=True)
    local, valid = _local_rows(layout, idx, axis_name)
    B, S, P = idx.shape
    E = dY.shape[-1]
    grad = jnp.broadcast_to(dY[:, :, None, :], (B, S, P, E)
                            ).astype(jnp.float32)
    grad = jnp.where(valid[..., None], grad, 0.0)
    tgt = jnp.where(valid, local, 0).reshape(-1)
    return tgt, grad.reshape(-1, E)


def table_grad_rows(layout: ShardedEmbeddingLayout, idx_slots_local,
                    dY_mp: jax.Array, axis_name
                    ) -> tuple[jax.Array, jax.Array]:
    """Table mode (unchunked; tests / small configs)."""
    dY_local = gather_dY(layout, dY_mp, axis_name)
    local, valid = _local_rows(layout, idx_slots_local, axis_name)
    B, K, P = local.shape
    E = dY_local.shape[-1]
    grad = jnp.broadcast_to(dY_local[:, :, None, :], (B, K, P, E))
    tgt = jnp.clip(local, 0, layout.rows_per_shard - 1).reshape(-1)
    return tgt, grad.astype(jnp.float32).reshape(-1, E)


def grad_rows(layout: ShardedEmbeddingLayout, idx_local: jax.Array,
              dY_mp: jax.Array, axis_name) -> tuple[jax.Array, jax.Array]:
    if layout.mode == "row":
        return row_grad_rows(layout, idx_local, dY_mp, axis_name)
    return table_grad_rows(layout, idx_local, dY_mp, axis_name)


def replicate_grad_rows(tgt: jax.Array, grad: jax.Array, replica_axes
                        ) -> tuple[jax.Array, jax.Array]:
    """Table mode on a 2D+ mesh replicates each table shard over the data
    axes; every replica must apply the updates of ALL replicas to stay
    consistent.  All-gathers the sparse (tgt, grad) row lists over
    ``replica_axes`` — the paper-noted cost of table-wise placement on wide
    meshes (row mode avoids it entirely)."""
    tgt_all = jax.lax.all_gather(tgt, replica_axes, axis=0, tiled=True)
    grad_all = jax.lax.all_gather(grad, replica_axes, axis=0, tiled=True)
    return tgt_all, grad_all


# ---------------------------------------------------------------------------
# NOTE on the optimizer math: the per-row update rules (Split-SGD's
# combine/step/split, momentum, row-wise Adagrad, ...) live in
# ``repro.optim.row`` — this module owns only the PLACEMENT concerns
# (layout -> local rows, replica gathers, batch chunking) and hands each
# chunk to ``RowOptimizer.apply_sparse``.  The reference oracles
# (``dedup_rows``, ``apply_rows_sgd``, ``apply_rows_split_sgd``) moved
# there with it.
# ---------------------------------------------------------------------------
