"""Frequency-tiered hot-row embedding cache (replicated hot slab).

Zipf-distributed lookup streams concentrate most bag traffic on a tiny set
of rows: on the paper's terabyte-scale configs the all-to-all that moves
bag partials between sockets is the dominant non-compute cost, yet the
bulk of its payload is the same few hundred hot rows every step.  This
module puts a small REPLICATED mirror of the top-``hot_rows`` rows per
table (ranked by the reserved ``cnt`` touch-counter slab of
:mod:`repro.optim.row`) in front of the sharded cold store:

* the cold store stays AUTHORITATIVE — every update is applied there by
  the normal fused sparse path (write-through; the cache never absorbs
  gradients);
* the forward substitutes a locally-computed bag for every bag whose
  lookups ALL hit the hot set (table mode + ``idx_input='sharded'``), so
  those bags never depend on the all-to-all payload;
* a deterministic, seeded promotion policy re-ranks the hot set from the
  counters every ``promote_every`` steps, identically on every rank.

Sync modes (``hot_sync``):

* ``"allreduce"`` — the slab is refreshed from the post-update cold store
  every step via a masked integer-bitcast psum (exactly one owner
  contributes each row, so the integer sum is the owner's bits verbatim).
  The mirror therefore always equals the store and the step is BITWISE
  identical to ``hot_rows=0`` for every registered optimizer.
* ``"deferred:N"`` — refresh only every N steps (and on promotion).  Hot
  bags read up-to-N-step-stale weights; cold-store updates are unchanged,
  so the drift is bounded by N steps of hot-row movement (see
  docs/cache.md for when this is safe).

Membership is keyed on SPEC-GLOBAL row ids (``sharded_embedding.
layout_gid_maps``), never layout positions, so counters and the hot set
survive checkpoint/restore and elastic N->N+-k reshards bit-for-bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import sharded_embedding as se


def parse_hot_sync(mode: str) -> int:
    """Refresh cadence in steps: ``"allreduce"`` -> 1, ``"deferred:N"`` ->
    N (N >= 1).  Raises ValueError on anything else."""
    if mode == "allreduce":
        return 1
    if isinstance(mode, str) and mode.startswith("deferred:"):
        try:
            n = int(mode.split(":", 1)[1])
        except ValueError:
            n = 0
        if n >= 1:
            return n
    raise ValueError(
        f"unknown hot_sync {mode!r}; expected 'allreduce' or 'deferred:N' with N >= 1"
    )


def hash32(x: jax.Array, seed: int) -> jax.Array:
    """32-bit avalanche hash (uint32) — the layout-independent tiebreaker
    of the promotion sort.  Rows with equal counts are ordered by
    ``hash32(gid ^ seed)``, so the selected hot set depends only on
    (count, gid, seed) — never on shard position or mesh shape."""
    x = x.astype(jnp.uint32) ^ jnp.uint32(seed & 0xFFFFFFFF)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def spec_gid_to_table(spec) -> np.ndarray:
    """Static map gid -> table id ([spec.total_rows] int32, -1 inside the
    per-table ``row_pad`` gaps of the unified row space)."""
    out = np.full(spec.total_rows, -1, np.int32)
    for t, rows_t in enumerate(spec.table_rows):
        base = int(spec.row_offsets[t])
        out[base : base + rows_t] = t
    return out


# ---------------------------------------------------------------------------
# Cache state subtree
# ---------------------------------------------------------------------------

def cache_struct(mdef, layout, opt) -> dict:
    """ShapeDtypeStructs of the replicated cache subtree.

    ``hot_w`` mirrors the FORWARD slab (``opt.fwd_weights``: bf16 hi for
    split optimizers, fp32 w otherwise) so a hit reads exactly the bits
    the owner's gather would have produced.  ``hot_ids`` are spec-global
    gids (-1 = empty); ``hot_pos`` inverts them over the unified row
    space; ``tick`` drives the promotion / refresh cadence."""
    K_tot = int(mdef.hot_rows) * layout.spec.num_tables
    dt = jnp.bfloat16 if opt.split else jnp.float32
    return {
        "hot_w": jax.ShapeDtypeStruct((K_tot, layout.spec.dim), dt),
        "hot_ids": jax.ShapeDtypeStruct((K_tot,), jnp.int32),
        "hot_pos": jax.ShapeDtypeStruct((layout.spec.total_rows,), jnp.int32),
        "tick": jax.ShapeDtypeStruct((), jnp.int32),
    }


def cache_specs(structs: dict) -> dict:
    """Everything in the cache subtree is replicated."""
    return jax.tree.map(lambda _: P(), structs)


def init_cache(mdef, layout, opt) -> dict:
    """Empty cache: no hot rows, first promotion fills it.  An empty hot
    set misses every bag, so step 1 is trivially identical to cache-off."""
    s = cache_struct(mdef, layout, opt)
    return {
        "hot_w": jnp.zeros(s["hot_w"].shape, s["hot_w"].dtype),
        "hot_ids": jnp.full(s["hot_ids"].shape, -1, jnp.int32),
        "hot_pos": jnp.full(s["hot_pos"].shape, -1, jnp.int32),
        "tick": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Promotion / demotion (deterministic, seeded, layout-independent)
# ---------------------------------------------------------------------------

def select_hot(layout, cnt_full: jax.Array, hot_rows: int, seed: int) -> jax.Array:
    """Top-``hot_rows`` rows per table by touch count -> hot_ids
    [num_tables * hot_rows] int32 (spec-global gids, -1 where a table has
    fewer than ``hot_rows`` touched rows).

    ``cnt_full`` is the [layout.total_rows] counter vector in LAYOUT row
    order, identical on every rank (all_gather over the embedding axes).
    Ranking uses a two-pass stable argsort — by hash first, then stably
    by descending count — i.e. the total order (count desc, hash asc).
    No ``top_k``: its index-position tiebreak would make the selection
    depend on shard layout under count ties; this order is a pure
    function of (count, gid, seed), so every rank and every layout of
    the same store picks the identical set, which is what keeps elastic
    reshards and multi-rank promotion bitwise consistent."""
    spec = layout.spec
    l2g, _ = se.layout_gid_maps(layout)
    gid_table = spec_gid_to_table(spec)
    row_table = np.where(l2g >= 0, gid_table[np.clip(l2g, 0, None)], -1)
    l2g_c = jnp.asarray(l2g)
    o1 = jnp.argsort(hash32(l2g_c, seed))
    cnt_full = cnt_full.reshape(-1).astype(jnp.int32)
    parts = []
    for t in range(spec.num_tables):
        elig = jnp.asarray(row_table == t) & (cnt_full > 0)
        score = jnp.where(elig, cnt_full, -1)
        order = o1[jnp.argsort(-score[o1])]  # jnp.argsort is stable
        top = order[:hot_rows]
        parts.append(jnp.where(score[top] > 0, l2g_c[top], -1))
    return jnp.concatenate(parts)


def hot_positions(spec_total: int, hot_ids: jax.Array) -> jax.Array:
    """Invert hot_ids: gid -> slab position ([spec_total] int32, -1 for
    cold rows).  Empty slots (-1) are routed to an out-of-bounds index
    and dropped (JAX wraps negatives BEFORE the OOB drop, so -1 must not
    reach the scatter directly)."""
    pos = jnp.arange(hot_ids.shape[0], dtype=jnp.int32)
    tgt = jnp.where(hot_ids >= 0, hot_ids, spec_total)
    return jnp.full((spec_total,), -1, jnp.int32).at[tgt].set(pos, mode="drop")


def refresh_hot_slab(layout, W_local: jax.Array, hot_ids: jax.Array, emb_ax) -> jax.Array:
    """Mirror the rows named by ``hot_ids`` out of the sharded forward
    slab, replicated: each row's unique owner contributes its bits, every
    other rank contributes zero, and the psum runs on the INTEGER bit
    patterns (int32 for fp32, sign-extended int32 for bf16) — an integer
    sum with one nonzero term is that term verbatim, so the mirror is
    bit-exact (a float psum could perturb signed zeros / NaN payloads).
    Runs inside shard_map over ``emb_ax``."""
    _, g2l = se.layout_gid_maps(layout)
    glob = jnp.take(jnp.asarray(g2l), jnp.where(hot_ids >= 0, hot_ids, 0))
    R = layout.rows_per_shard
    local = glob - jax.lax.axis_index(emb_ax) * R
    own = (hot_ids >= 0) & (glob >= 0) & (local >= 0) & (local < R)
    rows = jnp.take(W_local, jnp.clip(local, 0, R - 1), axis=0)
    if rows.dtype == jnp.bfloat16:
        bits = jax.lax.bitcast_convert_type(rows, jnp.int16)
        bits = jnp.where(own[:, None], bits.astype(jnp.int32), 0)
        bits = jax.lax.psum(bits, emb_ax)
        return jax.lax.bitcast_convert_type(bits.astype(jnp.int16), jnp.bfloat16)
    bits = jax.lax.bitcast_convert_type(rows.astype(jnp.float32), jnp.int32)
    bits = jnp.where(own[:, None], bits, 0)
    bits = jax.lax.psum(bits, emb_ax)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


# ---------------------------------------------------------------------------
# Forward bypass (table mode + sharded index stream)
# ---------------------------------------------------------------------------

def hot_bag_local(
    layout, hot_w: jax.Array, hot_pos: jax.Array, idx: jax.Array, weights: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """(hit [b, S], bag [b, S, E]) for this rank's OWN batch slice, read
    entirely from the replicated hot slab.

    A bag hits only when ALL P of its lookups are hot — partial splits
    would reassociate the fp32 bag sum and break the bitwise contract.
    The bag arithmetic is the owner's ``table_sharded_bag_fwd`` gather
    verbatim (take -> fp32 -> optional per-lookup weight -> sum over P),
    so under ``hot_sync='allreduce'`` a hit bag is bit-identical to the
    all-to-all row it replaces; the caller substitutes with
    ``jnp.where(hit[..., None], bag, emb_out)``."""
    spec = layout.spec
    off = jnp.asarray(spec.row_offsets[layout.slot_to_table], jnp.int32)  # [S]
    gid = idx + off[None, :, None]
    ok = (gid >= 0) & (gid < spec.total_rows)
    pos = jnp.take(hot_pos, jnp.clip(gid, 0, spec.total_rows - 1))
    lk_hit = ok & (pos >= 0)
    hit = jnp.all(lk_hit, axis=2)
    rows = jnp.take(hot_w, jnp.clip(pos, 0, hot_w.shape[0] - 1), axis=0).astype(jnp.float32)
    if weights is not None:
        rows = rows * weights[..., None].astype(jnp.float32)
    return hit, rows.sum(axis=2)


# ---------------------------------------------------------------------------
# The per-step cache epilogue
# ---------------------------------------------------------------------------

def step_cache(mdef, layout, opt, cache: dict, new_emb: dict, emb_ax) -> dict:
    """Advance the cache one step from the POST-update store (runs inside
    shard_map, after sparse_update).

    Promotion and refresh are computed UNCONDITIONALLY and selected with
    ``jnp.where`` on the tick — a ``lax.cond`` whose branches issue
    collectives would deadlock shard_map, and the unconditional form
    keeps every rank's collective schedule identical.  Promotion (every
    ``promote_every`` steps) re-ranks the hot set from the gathered
    counters and FORCES a refresh; otherwise the slab refreshes on the
    ``hot_sync`` cadence (every step for 'allreduce')."""
    sync_n = parse_hot_sync(mdef.hot_sync)
    every = int(mdef.promote_every)
    tick = cache["tick"] + jnp.asarray(1, jnp.int32)
    cnt_full = jax.lax.all_gather(
        new_emb["cnt"][:, 0].astype(jnp.int32), emb_ax, axis=0, tiled=True
    )
    new_ids = select_hot(layout, cnt_full, int(mdef.hot_rows), int(mdef.sr_seed))
    promote = (tick % every) == 0
    ids = jnp.where(promote, new_ids, cache["hot_ids"])
    refresh = promote | ((tick % sync_n) == 0)
    slab = refresh_hot_slab(layout, opt.fwd_weights(new_emb), ids, emb_ax)
    return {
        "hot_w": jnp.where(refresh, slab, cache["hot_w"]),
        "hot_ids": ids,
        "hot_pos": hot_positions(layout.spec.total_rows, ids),
        "tick": tick,
    }
