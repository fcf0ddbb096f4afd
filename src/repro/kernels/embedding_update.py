"""Pallas TPU kernel: fused sparse embedding backward + row-optimizer update
(paper Alg. 3 + contribution C5 composed — the operator behind the headline
110x).

The embedding backward is NOT a gradient materialization: it is a scatter-SGD
applied directly to the table.  The paper's CPU kernel walks the minibatch's
rows and applies ``W[r] -= lr * sum(dY of bags touching r)`` in one pass; the
TPU-native structure here walks the SORTED flat lookups:

* XLA side (cheap, O(L) on int32): sort the flat local row ids, so duplicate
  rows form contiguous runs and each touched row is visited exactly once.
* The unit of HBM traffic on the tables is the ROW GROUP of ``G``
  consecutive rows (:func:`group_rows`).  A single row of a 16-bit slab,
  or of a lanes-held slab, is not a legal copy on the chip, so a group
  is at least one native TPU tile: 128 rows when any slab holds its rows
  on lanes, else 8 rows for 32-bit slabs and 16 when any is 16-bit.  A
  store of row-major slabs takes wider groups, up to 128 rows, when the
  stream touches nearly every tile group anyway: the wider groups then
  copy hardly more rows, and the fixed cost of a group step (a binary
  search, a wait on its load, a write-back started) is paid up to
  sixteen times less often.  The kernel moves whole groups: the slabs
  stay in HBM, each touched group is copied into VMEM once, while the
  stream is still in the group before it (three buffers per slab: one
  being updated, one loading, one writing back), and copied back once
  when the stream leaves it.
* Per group a [G, Wp] fp32 VMEM accumulator gathers every lookup's
  contribution into the row it hits, in stream order from +0.0; lane
  ``E`` counts the row's valid lookups.  When the stream leaves the group
  the optimizer's row step (the RowOptimizer ``step`` hook) runs ONCE on
  the whole [G, W] group block and is kept on the rows whose count is
  nonzero; the transposes into and out of a slab's stored orientation
  are once per group.
* ``input_output_aliases`` makes the update in-place on the HBM table:
  groups holding no touched row are never read or written, the untouched
  rows of a touched group are written back with their own bits, and no
  dense ``dW`` or fp32 shard copy ever exists.

The cotangent rows reach the kernel as one more XLA-side prep of the
stream, per call: ``pre`` [C, Wp] holds each lookup's ``dY`` row times its
bag weight (zero where masked) in lanes ``:E`` and its mask in lane ``E``,
gathered in sorted order and streamed into VMEM in blocks of ``K``
lookups by the grid's own pipeline.  A copy per lookup from inside the
kernel (a ring of 8-row tile copies) measured 0.2 us a lookup on a TPU
v5e, almost all of it the scalar work of issuing and picking each copy;
``dY`` itself never goes whole into VMEM.

Per call of ``C`` lookups: ``C / K`` grid steps.  Each grid step walks
its block group by group: one binary search of the sorted rows per group
finds where the group ends (and which group to prefetch), and the
lookups inside it are a branch-free loop of one [1, Wp] add each.  So a
step costs about L lookups' adds, one optimizer step per touched group,
and L / K grid steps.

Bytes per step (shard of M rows x E, L flat lookups, T touched groups of
G rows, NB = L / pooling bags, ``s`` bytes per row over all slabs, Wp
lanes of ``pre``):

    path                         reads                       writes
    ------------------------------------------------------------------
    reference (segment_sum +     L*E*4 (grad expand)         M*s (new slab
    functional scatter)          + M*s (scatter copy-in)      copies)
    fused (this kernel)          T*G*s + L*E*4 (gather)      T*G*s
                                 + L*Wp*4 (pre)              + L*Wp*4 (pre)

i.e. the fused path touches ``O(touched groups)`` slab data instead of
``O(M)`` — the bandwidth profile Hsia et al. (2020) identify as the
dominant memory bottleneck of DLRM-class training.

Budgets: the sorted rows of a call live in SMEM (scalar prefetch, 4 B a
lookup: ``CHUNK`` = 32,768 take 128 KiB), so a step's stream is cut into
chunks, one kernel call each, each with its own [C, Wp] ``pre`` in HBM
(16 MiB at Wp = 128).  VMEM holds three group buffers per slab
(``3 * G * W`` elements each), the [G, Wp] accumulator and two [K, Wp]
fp32 blocks of ``pre`` (1 MiB at K = 1024, Wp = 128).  A group that
crosses a chunk boundary hands its [G, Wp] partial sums to the next
call, which continues the SAME sequential accumulation and steps the
group once the stream leaves it — the result does not depend on where
the cuts fall.

Numerics: duplicate contributions are pre-reduced in fp32 in sorted order —
the same order ``jax.ops.segment_sum`` uses on sorted segments — and the
step is applied once per row, so the split result is bit-identical to the
``dedup_rows`` + ``combine_split`` reference path
(:func:`repro.optim.row.apply_rows_split_sgd`).  A row reached ONLY by
masked padding lookups (the sorted tail, other shards' rows) keeps its
bits: ``beta * m`` is not a no-op the way ``w - lr * 0`` is, so the step
is kept only on rows with a nonzero lookup count.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout
from jax.experimental.pallas import tpu as pltpu

# lookups per kernel call: their sorted rows take 4 B each of SMEM (32768
# take 128 KiB of it), and their ``pre`` rows Wp * 4 B each of HBM
CHUNK = 32768
# lookups per grid step (one [K, Wp] block of ``pre``)
BLOCK = 1024
# row-group buffers per slab: one being updated, one loading the next
# group, one writing the previous group back
GROUP_SLOTS = 3
LANES = 128
# the most rows, as a share of what groups of one tile copy, that wider
# groups of a row-major store may copy more under uniform ids
# (:func:`group_rows`)
GROUP_COPY_SLACK = 0.05


def tile_rows(dtype) -> int:
    """Rows of one native TPU tile of ``dtype``: 8 for 32-bit, 16 for
    16-bit (two rows share a 32-bit sublane)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def rows_on_lanes(shape, dtype, device=None) -> bool:
    """Whether an [M, W] slab is stored transposed on a TPU — column-major,
    rows along the 128 lanes, W along sublanes.  The kernel reads a slab
    in the orientation it is stored in: the transposed view of a
    column-major slab is a bitcast, where a row-major operand would cost
    a relayout copy of the whole slab on every call.

    On a TPU (``device``, else the default device when the backend is a
    TPU) the answer is the compiler's default layout for the shape, the
    layout a jitted step's parameters take.  Elsewhere (interpret mode,
    which runs the chip's blocks) it is modelled: XLA:TPU picks the
    layout that pads less, so a narrow slab (W = 64, or a [M, 1]
    per-row scalar) is column-major and a W % 128 == 0 slab row-major.
    The model matches the compiler at every shape tested above 16 rows
    (tests/test_tpu_compile.py); below that the compiler uses smaller
    tiles and may disagree."""
    if device is None and jax.default_backend() == "tpu":
        device = jax.devices()[0]
    if device is not None:
        layout = Layout.from_pjrt_layout(device.client.get_default_layout(
            jnp.dtype(dtype), tuple(shape), device))
        return layout.major_to_minor == (1, 0)
    M, W = shape
    sub = tile_rows(dtype)
    return (_round_up(M, sub) * _round_up(W, LANES)
            > _round_up(W, sub) * _round_up(M, LANES))


def group_rows(shapes, dtypes, live_lookups: float) -> int:
    """Rows ``G`` of one row group of a store whose slabs (one [M, W]
    ``shape`` and ``dtype`` each, as one shard holds them) a stream of
    ``live_lookups`` valid lookups updates.

    A lanes-held slab (:func:`rows_on_lanes`) makes it one lane tile,
    128 rows.  Otherwise let ``t`` be the tallest sublane tile
    (:func:`tile_rows`): ``G`` is the widest of t, 2t, 4t, ... 128 whose
    groups would copy at most ``GROUP_COPY_SLACK`` (5%) more rows than
    groups of ``t`` do.  Under uniform ids, groups of ``g`` rows copy
    ``M * (1 - exp(-lam * g))`` rows, ``lam`` being the live lookups per
    row.  So a dense stream, which touches nearly every tile group
    already, takes wide groups for almost no bytes, and a sparse one
    keeps the tile:

    * dlrm-large's chip share (E=256, 16-bit slabs, 1,638,400 lookups on
      6,000,128 rows, lam = 0.273): 16 rows copy 98.7% of the rows and
      128 rows all of them, 1.3% more, so G = 128;
    * a sparse stream such as dlrm-mlperf's Criteo tables at E=128
      (lam far below 0.1): groups of 32 rows would already copy 20% or
      more than groups of 16, so G = 16.

    The slack sits between the two: 16-row tiles widen from
    lam = ln 20 / 16 = 0.187 on.  ``live_lookups`` counts only the
    lookups live on this shard; a masked lookup touches no row."""
    if any(rows_on_lanes(s, d) for s, d in zip(shapes, dtypes)):
        return LANES
    t = max(tile_rows(d) for d in dtypes)
    lam = live_lookups / shapes[0][0]
    copied = lambda g: -math.expm1(-lam * g)  # noqa: E731  share of rows
    G = t
    while (lam > 0 and 2 * G <= LANES
           and copied(2 * G) <= (1 + GROUP_COPY_SLACK) * copied(t)):
        G *= 2
    return G


def _make_kernel(step, cols: tuple, tiles: tuple, G: int, E: int, M: int,
                 C: int, K: int):
    """The fused kernel body for row-aligned slabs (all read and written
    back; ``cols[k]`` says slab ``k`` arrives transposed, as [W, M]).
    ``step(blocks, g, lr, seed, rows) -> blocks`` is the optimizer's row
    math on [G, W] group blocks (``g`` [G, E] the group's per-row
    pre-reduced gradients, ``rows`` [G, 1] the group's row ids).

    Scalar prefetch: the sorted rows of this chunk of ``C`` lookups,
    ``lr`` [1] fp32, ``sd`` [1] int32 seed, ``ctl`` [2] int32 = (last row
    of the previous chunk or -1, first row of the next chunk or -1).
    Grid step ``i`` walks lookups ``i*K .. i*K+K-1``, whose ``pre`` rows
    are its VMEM block."""
    S = GROUP_SLOTS
    # a partial last group moves each slab's rows up to its own tile
    # boundary, which lies inside the slab's padded memory
    last, rem = divmod(M, G)
    tails = tuple(min(G, _round_up(rem, t)) if rem else G for t in tiles)
    search_steps = C.bit_length()
    n_slabs = len(cols)

    def kernel(rows_ref, lr_ref, sd_ref, ctl_ref, pre_ref, *refs):
        # a slab and its output are one buffer (aliased): groups are read
        # from the input and written to the output, each at most once a
        # call, read before written
        n = n_slabs
        src, cacc_ref = refs[:n], refs[n]
        dst, oacc_ref = refs[n + 1:2 * n + 1], refs[2 * n + 1]
        bufs = refs[2 * n + 2:3 * n + 2]
        acc_ref, sem, st = refs[3 * n + 2:]
        i = pl.program_id(0)

        def group_copy(g, slot, load: bool, start: bool):
            """Start or wait the copies of row group ``g`` between HBM and
            group buffer ``slot``, one per slab."""
            def go(sizes):
                for k, (b, c, size) in enumerate(zip(bufs, cols, sizes)):
                    h = src[k] if load else dst[k]
                    at = pl.ds(pl.multiple_of(g * G, G), size)
                    h = h.at[:, at] if c else h.at[at, :]
                    b = b.at[slot] if size == G else (
                        b.at[slot, :, pl.ds(0, size)] if c
                        else b.at[slot, pl.ds(0, size), :])
                    cp = pltpu.make_async_copy(*((h, b) if load else (b, h)),
                                               sem.at[k, slot])
                    cp.start() if start else cp.wait()

            full = (G,) * n_slabs
            if tails == full:
                go(full)
            elif not last:
                go(tails)
            else:
                pl.when(g != last)(lambda: go(full))
                pl.when(g == last)(lambda: go(tails))

        @pl.when(i == 0)
        def _init():
            # st[0]: the current group's buffer slot; st[1]: the current
            # group; st[2]: the chunk index where it ends; st[3 + s]: the
            # group being written back from slot s, or -1
            st[0], st[1] = 0, -1
            for s in range(S):
                st[3 + s] = -1

        def enter_group(t, grp):
            slot = st[0]

            # nobody prefetched the call's first group
            @pl.when(t == 0)
            def _first_load():
                group_copy(grp, slot, load=True, start=True)

            group_copy(grp, slot, load=True, start=False)
            carried = (t == 0) & (ctl_ref[0] // G == grp)

            @pl.when(carried)
            def _continue():
                acc_ref[...] = cacc_ref[...]

            @pl.when(jnp.logical_not(carried))
            def _fresh():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            # where the group ends (a binary search of the sorted rows);
            # the next group loads into the next slot once that slot's
            # write-back has landed
            bound = (grp + 1) * G

            def halve(_, lh):
                lo, hi = lh
                mid = (lo + hi) // 2
                right = rows_ref[jnp.minimum(mid, C - 1)] < bound
                return (jnp.where((lo < hi) & right, mid + 1, lo),
                        jnp.where((lo < hi) & jnp.logical_not(right),
                                  mid, hi))

            end, _ = jax.lax.fori_loop(0, search_steps, halve, (t + 1, C))
            st[1], st[2] = grp, end

            @pl.when(end < C)
            def _prefetch():
                ns = (slot + 1) % S

                @pl.when(st[3 + ns] >= 0)
                def _landed():
                    group_copy(st[3 + ns], ns, load=False, start=False)

                st[3 + ns] = -1
                group_copy(rows_ref[end] // G, ns, load=True, start=True)

        def leave_group(grp):
            # one optimizer step on the whole group, kept on the rows some
            # valid lookup reached (a row reached only by masked lookups
            # keeps its bits), then one write-back
            slot = st[0]
            rows = grp * G + jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)
            cur = tuple(b[slot].T if c else b[slot]
                        for b, c in zip(bufs, cols))
            acc = acc_ref[...]
            new = step(cur, acc[:, :E], lr_ref[0], sd_ref[0], rows)
            hit = acc[:, E:E + 1] > 0
            for b, c, x, v in zip(bufs, cols, cur, new):
                v = jnp.where(hit, v.astype(b.dtype), x)
                b[slot] = v.T if c else v
            group_copy(grp, slot, load=False, start=True)
            st[3 + slot] = grp
            st[0] = (slot + 1) % S

        def segment(j):
            """The lookups of one group from block index ``j`` on; returns
            the block index after them."""
            t = i * K + j
            grp = rows_ref[t] // G
            pl.when(grp != st[1])(lambda: enter_group(t, grp))
            end = st[2]
            stop = jnp.minimum(end - i * K, K)
            base = grp * G

            def add(jj, carry):
                r = rows_ref[i * K + jj] - base
                acc_ref[pl.ds(r, 1), :] += pre_ref[pl.ds(jj, 1), :]
                return carry

            jax.lax.fori_loop(j, stop, add, 0)
            # the stream leaves the group in this block, unless the next
            # chunk goes on in it
            leaves = (end <= i * K + K) & (
                (end < C) | (ctl_ref[1] // G != grp))
            pl.when(leaves)(lambda: leave_group(grp))
            return stop

        jax.lax.while_loop(lambda j: j < K, segment, 0)

        @pl.when(i == pl.num_programs(0) - 1)
        def _last_block():
            # hand a group the next chunk goes on in to the next call, and
            # let every write-back land
            oacc_ref[...] = acc_ref[...]
            for s in range(S):
                pl.when(st[3 + s] >= 0)(
                    lambda s=s: group_copy(st[3 + s], s, load=False,
                                           start=False))

    return kernel


def _call(step, cols, tiles, slabs, M, rows, pre, E, lr, seed, ctl, cacc,
          K, interpret):
    """One kernel call on one chunk of the sorted stream (slabs already in
    their stored orientation)."""
    n = len(slabs)
    G = cacc.shape[0]
    C = rows.shape[0]
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    whole = pl.BlockSpec(cacc.shape, lambda i, *_: (0, 0))
    out = pl.pallas_call(
        _make_kernel(step, cols, tiles, G, E, M, C, K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(C // K,),
            in_specs=([pl.BlockSpec((K, pre.shape[1]), lambda i, *_: (i, 0))]
                      + [hbm] * n + [whole]),
            out_specs=[hbm] * n + [whole],
            scratch_shapes=[
                pltpu.VMEM((GROUP_SLOTS, s.shape[0], G) if c else
                           (GROUP_SLOTS, G, s.shape[1]), s.dtype)
                for s, c in zip(slabs, cols)] + [
                pltpu.VMEM(cacc.shape, jnp.float32),
                pltpu.SemaphoreType.DMA((n, GROUP_SLOTS)),
                pltpu.SMEM((3 + GROUP_SLOTS,), jnp.int32)],
        ),
        out_shape=([jax.ShapeDtypeStruct(s.shape, s.dtype) for s in slabs]
                   + [jax.ShapeDtypeStruct(cacc.shape, cacc.dtype)]),
        # args: (rows, lr, sd, ctl, pre, *slabs, cacc): the slabs alias
        # their outputs
        input_output_aliases={5 + k: k for k in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the kernel's name in the compiled step and the device trace
        # (docs/telemetry.md: a new kernel brings its own name)
        name="sparse_row_update",
    )(rows, lr, seed, ctl, pre, *slabs, cacc)
    return tuple(out[:n]), out[n]


def sparse_row_update_pallas(step, slabs, sorted_rows, sorted_bags,
                             sorted_msk, sorted_wgt, dY, lr, seed=0,
                             interpret: bool = False,
                             shards: int = 1) -> tuple:
    """Fused sparse-backward + row-optimizer update, in place on ``slabs``.

    ``slabs``: the store's row-aligned [M, W] slabs (weights first, then
    per-row state; any widths, any mix of 32- and 16-bit dtypes).
    ``step(blocks, g, lr, seed, rows) -> blocks``: the optimizer's row
    math on [G, W] group blocks — elementwise per row, so a row's result
    does not depend on its neighbours.  ``sorted_rows`` [L] int32:
    ASCENDING local row id per flat lookup (duplicates contiguous; padding
    entries must repeat an in-range row and carry ``sorted_msk == 0``).
    ``sorted_bags`` [L] int32: row of ``dY`` holding each lookup's
    cotangent.  ``sorted_wgt`` [L] fp32: per-lookup bag weight (1.0 for
    plain sum bags) scaling the cotangent row before the VMEM
    pre-reduction.  ``dY`` [NB, E].  ``seed``: int32 stochastic-rounding
    seed handed to ``step``.  ``shards``: the shards the stream's
    lookups spread over (row placement hands every shard the whole
    stream, the others' lookups masked), so that 1 / ``shards`` of them
    count as live here when the row groups are sized (:func:`group_rows`).
    Returns the updated slabs; groups holding no touched row are
    untouched (aliased buffers, no shard copy).

    Each slab is handed to the kernel in the orientation XLA:TPU stores
    it in (:func:`rows_on_lanes`), on every backend, so interpret mode
    runs the same blocks as the chip.  On the chip a slab's rows are
    padded in memory to whole tiles, and a partial last group is copied
    up to that boundary; interpret mode has no such padding, so there the
    slabs are padded to it around the call."""
    cols = tuple(rows_on_lanes(s.shape, s.dtype) for s in slabs)
    views = tuple(s.T if c else s for s, c in zip(slabs, cols))
    # the tile of each slab along its rows, to a whole number of which
    # XLA:TPU pads them in memory
    tiles = tuple(LANES if c else tile_rows(s.dtype)
                  for s, c in zip(slabs, cols))
    M = slabs[0].shape[0]
    if interpret:
        views = tuple(
            jnp.pad(v, ((0, 0), (0, _round_up(M, t) - M)) if c else
                    ((0, _round_up(M, t) - M), (0, 0)))
            for v, c, t in zip(views, cols, tiles))
    L = sorted_rows.shape[0]
    # one group of rows for every slab
    G = group_rows([s.shape for s in slabs], [s.dtype for s in slabs],
                   L / shards)
    C = min(CHUNK, L)
    K = min(BLOCK, C)
    C = _round_up(C, K)
    nc = -(-L // C)
    lr_arr = jnp.full((1,), lr, jnp.float32)
    sd = jnp.full((1,), seed, jnp.int32)
    E = dY.shape[1]
    # the cotangent lanes, then the lookup count
    Wp = _round_up(E + 1, LANES)
    # pad the stream to whole chunks with masked repeats of the last row
    # (the sort's maximum, so the stream stays ascending)
    pad = nc * C - L
    rows = jnp.concatenate([sorted_rows,
                            jnp.broadcast_to(sorted_rows[-1:], (pad,))])
    bags = jnp.pad(sorted_bags, (0, pad))
    msk = jnp.pad(sorted_msk, (0, pad))
    wgt = jnp.pad(sorted_wgt, (0, pad))

    def body(k, carry):
        views, cacc = carry
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, k * C, C)  # noqa: E731
        live = take(msk) != 0
        g = jnp.take(dY, take(bags), axis=0).astype(jnp.float32)
        g = jnp.where(live[:, None], g * take(wgt)[:, None], 0.0)
        pre = jnp.concatenate(
            [g, live[:, None].astype(jnp.float32),
             jnp.zeros((C, Wp - E - 1), jnp.float32)], axis=1)
        prev = jnp.where(k == 0, -1, rows[jnp.maximum(k * C - 1, 0)])
        nxt = jnp.where(k == nc - 1, -1,
                        rows[jnp.minimum((k + 1) * C, nc * C - 1)])
        ctl = jnp.stack([prev, nxt]).astype(jnp.int32)
        return _call(step, cols, tiles, views, M, take(rows), pre, E, lr_arr,
                     sd, ctl, cacc, K, interpret)

    out, _ = jax.lax.fori_loop(0, nc, body,
                               (views, jnp.zeros((G, Wp), jnp.float32)))
    if interpret:
        out = tuple(v[:, :M] if c else v[:M] for v, c in zip(out, cols))
    return tuple(v.T if c else v for v, c in zip(out, cols))


def sort_lookups(tgt: jax.Array, valid: jax.Array | None, num_rows: int,
                 pooling: int, weights: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Host/XLA-side prep: sort flat lookups by row so duplicates form runs.

    ``tgt`` [L] int32 local row ids (may be out of range where invalid);
    ``valid`` [L] bool or None; flat lookup ``i`` reads bag ``i // pooling``.
    ``weights`` [L] fp32 per-lookup bag weights or None (sum bags).
    Invalid/padding lookups are sorted to the tail as a zero-contribution
    run on the last row (a masked run the kernel skips).  Returns
    (sorted_rows, sorted_bags, sorted_msk, sorted_wgt) — ready for the
    kernel above.  Only scalars are sorted; the [*, E] gradient data is
    never permuted or expanded.
    """
    with jax.named_scope("lookup_sort"):
        valid = ((tgt >= 0) & (tgt < num_rows)) if valid is None else (
            valid & (tgt >= 0) & (tgt < num_rows))
        key = jnp.where(valid, tgt, num_rows).astype(jnp.int32)
        order = jnp.argsort(key)                  # stable: ties in flat order
        sorted_key = jnp.take(key, order)
        sorted_rows = jnp.minimum(sorted_key, num_rows - 1)
        sorted_bags = (order // pooling).astype(jnp.int32)
        sorted_msk = (sorted_key < num_rows).astype(jnp.int32)
        sorted_wgt = (jnp.ones(tgt.shape, jnp.float32) if weights is None
                      else jnp.take(weights.astype(jnp.float32), order))
    return sorted_rows, sorted_bags, sorted_msk, sorted_wgt
