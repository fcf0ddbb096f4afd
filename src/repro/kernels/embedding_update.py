"""Pallas TPU kernel: fused sparse embedding backward + row-optimizer update
(paper Alg. 3 + contribution C5 composed — the operator behind the headline
110x).

The embedding backward is NOT a gradient materialization: it is a scatter-SGD
applied directly to the table.  The paper's CPU kernel walks the minibatch's
rows and applies ``W[r] -= lr * sum(dY of bags touching r)`` in one pass; the
TPU-native structure here is a ``PrefetchScalarGridSpec`` over the SORTED
flat lookups:

* XLA side (cheap, O(L) on int32): sort the flat local row ids, so duplicate
  rows form contiguous runs and each touched row is visited exactly once.
* The sorted row ids are scalar-prefetched and drive the slab DMA.  The
  unit of HBM traffic is the ROW GROUP: the ``G`` consecutive rows of one
  native TPU tile (8 rows for 32-bit slabs, 16 when any slab of the store
  is 16-bit — bf16 ``hi``, uint16 ``lo``, bf16 state).  A single row of a
  16-bit slab is not a legal DMA or block on the chip (the tiling packs
  row pairs into one 32-bit sublane), so the kernel blocks whole groups:
  a group is fetched when the sorted stream enters it, every run inside it
  updates its row in VMEM, and the group is written back once when the
  stream leaves it.  Consecutive runs in one group see each other's
  writes because the output block stays resident while its index repeats.
* Inside the kernel the duplicate contributions are accumulated in a VMEM
  fp32 scratch (segment accumulation); at the run end the optimizer's row
  step (the RowOptimizer ``step`` hook) runs elementwise on the group
  block and a row mask keeps only the run's row.
* ``input_output_aliases`` makes the update in-place on the HBM table:
  groups holding no touched row are never read or written, the untouched
  rows of a touched group are written back with their own bits, and no
  dense ``dW`` or fp32 shard copy ever exists.

Bytes per step (shard of M rows x E, L flat lookups, T touched groups of
G rows, NB = L / pooling bags, ``s`` bytes per row over all slabs):

    path                         reads                       writes
    ------------------------------------------------------------------
    reference (segment_sum +     L*E*4 (grad expand)         M*s (new slab
    functional scatter)          + M*s (scatter copy-in)      copies)
    fused (this kernel)          T*G*s + L*8*E*4 (dY group   T*G*s
                                 per lookup)

i.e. the fused path touches ``O(touched groups)`` slab data instead of
``O(M)`` — the bandwidth profile Hsia et al. (2020) identify as the
dominant memory bottleneck of DLRM-class training.

The sorted stream lives in SMEM (scalar prefetch), which holds a few
hundred KiB, so a step's stream is cut into chunks of at most
``CHUNK`` lookups, one kernel call each.  A run that crosses a chunk
boundary hands its partial fp32 sum and liveness flag to the next call,
which continues the SAME sequential accumulation — the result does not
depend on where the cuts fall.

Numerics: duplicate contributions are pre-reduced in fp32 in sorted order —
the same order ``jax.ops.segment_sum`` uses on sorted segments — and the
step is applied once per row, so the split result is bit-identical to the
``dedup_rows`` + ``combine_split`` reference path
(:func:`repro.optim.row.apply_rows_split_sgd`).  A run made ONLY of masked
padding lookups (the sorted tail, other shards' rows) writes nothing:
``beta * m`` is not a no-op the way ``w - lr * 0`` is, so every run carries
a 1-word SMEM liveness flag and the row step runs only on live runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout
from jax.experimental.pallas import tpu as pltpu

# lookups per kernel call: rows/bags/msk/wgt are 4 x 4 B each per lookup
# in SMEM, so 16384 lookups take 256 KiB of it
CHUNK = 16384
LANES = 128


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


def _make_kernel(step, cols: tuple, G: int, Gd: int):
    """The fused kernel body for row-aligned slabs (all read and written
    back; ``cols[k]`` says slab ``k`` arrives transposed, as [W, M]).
    ``step(blocks, g, lr, seed, rows) -> blocks`` is the optimizer's row
    math on [G, W] group blocks (``g`` [1, E] is the run's pre-reduced
    gradient, ``rows`` [G, 1] the group's row ids).

    Scalar prefetch: sorted rows / bags / msk / wgt of this chunk, ``lr``
    [1] fp32, ``sd`` [1] int32 seed, ``ctl`` [3] int32 = (last row of the
    previous chunk or -1, first row of the next chunk or -1, liveness of
    the run carried in)."""
    n_slabs = len(cols)

    def kernel(rows_ref, bags_ref, msk_ref, wgt_ref, lr_ref, sd_ref, ctl_ref,
               *refs):
        slabs = refs[:n_slabs]
        dY_ref, cacc_ref = refs[n_slabs:n_slabs + 2]
        outs = refs[n_slabs + 2:2 * n_slabs + 2]
        oacc_ref, oflg_ref, acc_ref, flg_ref = refs[2 * n_slabs + 2:]
        i = pl.program_id(0)
        n = pl.num_programs(0)
        row = rows_ref[i]
        prev = jnp.where(i == 0, ctl_ref[0], rows_ref[jnp.maximum(i - 1, 0)])
        nxt = jnp.where(i == n - 1, ctl_ref[1],
                        rows_ref[jnp.minimum(i + 1, n - 1)])

        # entering a group (or a new call): the output block is a fresh
        # VMEM buffer, so seed it with the group's current HBM contents
        @pl.when((i == 0) | (row // G != prev // G))
        def _load_group():
            for o, s in zip(outs, slabs):
                o[...] = s[...]

        @pl.when(row != prev)
        def _start_run():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            flg_ref[0] = 0

        @pl.when((i == 0) & (row == prev))
        def _continue_run():
            acc_ref[...] = cacc_ref[...]
            flg_ref[0] = ctl_ref[2]

        # this lookup's cotangent row out of its bag's tile: a masked
        # sublane sum is exact (one nonzero term; a -0.0 turning +0.0
        # cannot change an accumulation that starts at +0.0)
        pick = (jax.lax.broadcasted_iota(jnp.int32, dY_ref.shape, 0)
                == bags_ref[i] % Gd)
        g = jnp.sum(jnp.where(pick, dY_ref[...].astype(jnp.float32), 0.0),
                    axis=0, keepdims=True)
        acc_ref[...] += jnp.where(msk_ref[i] != 0, g * wgt_ref[i], 0.0)
        flg_ref[0] = flg_ref[0] | msk_ref[i]

        @pl.when((nxt != row) & (flg_ref[0] != 0))
        def _apply():
            rows = (row // G) * G + jax.lax.broadcasted_iota(
                jnp.int32, (G, 1), 0)
            cur = tuple(o[...].T if c else o[...] for o, c in zip(outs, cols))
            new = step(cur, acc_ref[...], lr_ref[0], sd_ref[0], rows)
            hit = rows == row
            for o, c, x, v in zip(outs, cols, cur, new):
                v = jnp.where(hit, v.astype(o.dtype), x)
                o[...] = v.T if c else v

        # the last lookup of the call hands a continuing run to the next
        @pl.when(i == n - 1)
        def _carry_out():
            oacc_ref[...] = acc_ref[...]
            oflg_ref[0] = flg_ref[0]

    return kernel


def _call(step, cols, slabs, rows, bags, msk, wgt, dY, lr, seed, ctl, cacc,
          interpret):
    """One kernel call on one chunk of the sorted stream (slabs already in
    their stored orientation)."""
    n = len(slabs)
    # one group of rows for every slab: a lane tile when any slab holds
    # its rows on lanes, else the tallest sublane tile
    G = LANES if any(cols) else max(tile_rows(s.dtype) for s in slabs)
    Gd = tile_rows(dY.dtype)
    E = dY.shape[1]
    slab_specs = [
        pl.BlockSpec((s.shape[0], G), lambda i, rows, *_: (0, rows[i] // G))
        if c else
        pl.BlockSpec((G, s.shape[1]), lambda i, rows, *_: (rows[i] // G, 0))
        for s, c in zip(slabs, cols)]
    whole = pl.BlockSpec((1, E), lambda i, *_: (0, 0))
    in_specs = slab_specs + [
        pl.BlockSpec((Gd, E), lambda i, rows, bags, *_: (bags[i] // Gd, 0)),
        whole]
    out_specs = slab_specs + [whole, pl.BlockSpec(memory_space=pltpu.SMEM)]
    out = pl.pallas_call(
        _make_kernel(step, cols, G, Gd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(rows.shape[0],),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((1, E), jnp.float32),
                            pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=([jax.ShapeDtypeStruct(s.shape, s.dtype) for s in slabs]
                   + [jax.ShapeDtypeStruct((1, E), jnp.float32),
                      jax.ShapeDtypeStruct((1,), jnp.int32)]),
        # args: (rows, bags, msk, wgt, lr, sd, ctl, *slabs, dY, cacc):
        # the slabs alias their outputs
        input_output_aliases={7 + k: k for k in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the kernel's name in the compiled step and the device trace
        # (docs/telemetry.md: a new kernel brings its own name)
        name="sparse_row_update",
    )(rows, bags, msk, wgt, lr, seed, ctl, *slabs, dY, cacc)
    return tuple(out[:n]), out[n], out[n + 1]


def sparse_row_update_pallas(step, slabs, sorted_rows, sorted_bags,
                             sorted_msk, sorted_wgt, dY, lr, seed=0,
                             interpret: bool = False) -> tuple:
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
    seed handed to ``step``.  Returns the updated slabs; groups holding
    no touched row are untouched (aliased buffers, no shard copy).

    Each slab is handed to the kernel in the orientation XLA:TPU stores
    it in (:func:`rows_on_lanes`), on every backend, so interpret mode
    runs the same blocks as the chip."""
    cols = tuple(rows_on_lanes(s.shape, s.dtype) for s in slabs)
    views = tuple(s.T if c else s for s, c in zip(slabs, cols))
    L = sorted_rows.shape[0]
    C = min(CHUNK, L)
    nc = -(-L // C)
    lr_arr = jnp.full((1,), lr, jnp.float32)
    sd = jnp.full((1,), seed, jnp.int32)
    E = dY.shape[1]
    # pad the stream to whole chunks with masked repeats of the last row
    # (the sort's maximum, so the stream stays ascending)
    pad = nc * C - L
    rows = jnp.concatenate([sorted_rows,
                            jnp.broadcast_to(sorted_rows[-1:], (pad,))])
    bags = jnp.pad(sorted_bags, (0, pad))
    msk = jnp.pad(sorted_msk, (0, pad))
    wgt = jnp.pad(sorted_wgt, (0, pad))

    def body(k, carry):
        views, cacc, cflg = carry
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, k * C, C)  # noqa: E731
        prev = jnp.where(k == 0, -1, rows[jnp.maximum(k * C - 1, 0)])
        nxt = jnp.where(k == nc - 1, -1,
                        rows[jnp.minimum((k + 1) * C, nc * C - 1)])
        ctl = jnp.stack([prev, nxt, cflg[0]]).astype(jnp.int32)
        return _call(step, cols, views, take(rows), take(bags), take(msk),
                     take(wgt), dY, lr_arr, sd, ctl, cacc, interpret)

    out, _, _ = jax.lax.fori_loop(
        0, nc, body, (views, jnp.zeros((1, E), jnp.float32),
                      jnp.zeros((1,), jnp.int32)))
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
