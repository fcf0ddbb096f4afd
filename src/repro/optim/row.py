"""Pluggable sparse RowOptimizer API — ONE update surface for the
embedding path (SGD / Split-SGD / momentum / Adagrad variants, fp32 or
compressed bf16-hi state).

The paper's Split-SGD trick (Sect. V) makes the sparse update O(unique
rows) per step; production DLRM training additionally wants momentum and
row-wise Adagrad on the embeddings (Naumov et al. 2019), and the optimizer
must stay FUSED and ROW-ADDRESSED — a dense optax-style update would
materialize the O(M x E) state/gradient the whole design avoids.  This
module is the plug-in point:

* A :class:`RowOptimizer` owns (a) an **EmbeddingStore** — a flat dict
  pytree of row-aligned slabs: the weight slab(s) (``hi``/``lo`` split
  bf16+uint16, or ``w`` fp32) plus zero or more per-row optimizer-state
  slabs (``mom``/``acc`` rows in fp32 or compressed bf16-hi), all sharded
  by the same ``ShardedEmbeddingLayout`` row partition — and (b) a single
  fused apply, :meth:`RowOptimizer.apply_sparse`, which every path
  (reference scan, fused Pallas kernel, host-pre-sorted stream) goes
  through.

* The per-optimizer MATH lives on the instance, as hooks supplied at
  registration time (the ROADMAP "strategy registration" refactor):

  - ``step``            — the row step: the optimizer's per-row math,
    elementwise over [n, W] row blocks of every slab.  The fused Pallas
    kernel runs it once on each touched tile-aligned row group; the
    reference runs the SAME function on the gathered unique rows with
    their per-row gradient sums, once per row per step (the chunked scan
    path accumulates across chunks first).
  - ``flat_reference``  — optional per-lookup reference (the stateless
    kinds' legacy scatter semantics); defaults to dedup + ``step``.

  ``kernels/ops.py``, ``core/sharded_embedding.py`` and
  ``core/pipeline.py`` contain NO per-optimizer dispatch (enforced by a
  source-scan test): :func:`register` alone — one row step — adds an
  optimizer end-to-end, fused kernel included.

* The registry (:func:`register` / :func:`get` / :func:`make`) names the
  built-ins: ``sgd``, ``split_sgd``, ``momentum``, ``adagrad_rowwise``,
  ``adagrad``, and the compressed-state ``momentum_bf16`` /
  ``adagrad_bf16`` (bf16-hi state + seeded stochastic rounding,
  :mod:`repro.optim.stochastic` — half the state bytes per touched row).
  :func:`resolve` maps a model definition's step options
  (``sparse_optimizer=`` + optional ``opt_beta``/``opt_eps``) to an
  optimizer instance.

Determinism / parity contracts (tests/test_row_optim.py,
tests/test_stochastic.py):

* ``split_sgd``: fused == the jitted ``split_fp32``/``combine_split``
  reference, BITWISE (one row step serves both; pinned).
* ``momentum(beta=0)``: bitwise == ``sgd`` on the fused path (both
  pre-reduce duplicates; ``0 * m + acc`` is an exact fp32 identity).
* ``adagrad`` / ``adagrad_rowwise`` first step from zero state == SGD
  scaled by ``1 / (sqrt(acc_1) + eps)`` (per element / per row) to fp32
  tolerance — one extra division per touched row vs the closed form.
* ``momentum_bf16`` / ``adagrad_bf16``: under one per-step ``seed`` the
  reference scan, fused device-sorted and host-pre-sorted paths are
  BITWISE identical (the stochastic dither is a counter-based pure
  function of (seed, row, lane), never of traversal order).
* State is touched ONLY for rows receiving at least one valid lookup —
  padding/masked streams never decay momentum or inflate accumulators.

The ``cnt`` slab key is RESERVED: it is the per-row touch counter.  A
store may carry it either as an AUXILIARY slab (``store_struct(...,
counters=True)`` — any optimizer; the hot-row embedding cache's
promotion policy reads it, see docs/cache.md) or as a declared STATE
slab (``adagrad_freq``).  In both cases :meth:`RowOptimizer
.apply_sparse` bumps it by +1 per VALID lookup (duplicates accumulate;
O(touched rows) scatter-add) before the optimizer math runs, so a
frequency-driven optimizer reads the post-bump count and an auxiliary
counter rides every path (reference / fused / presorted / chunked)
without the registered hooks knowing it exists.  Register-only toy
optimizers must therefore pick a different key for private counters.

Nothing outside this module calls the ``kernels.ops.fused_row_update*``
entry points; checkpointing, serving snapshots and elastic restarts all
see the store as an opaque dict of row-aligned slabs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.optim.split_sgd import combine_split, split_fp32
from repro.optim.stochastic import sr_noise_col, sr_round_bf16


# ---------------------------------------------------------------------------
# Reference helpers (the scan/oracle path; moved here from
# core.sharded_embedding so the optimizer owns BOTH implementations)
# ---------------------------------------------------------------------------

def dedup_rows(tgt: jax.Array, upd: jax.Array, num_rows: int
               ) -> tuple[jax.Array, jax.Array]:
    """Sum duplicate targets.  Returns (rep [n], summed [n, E]); positions
    for empty run segments get rep == num_rows (out of bounds -> the
    subsequent scatter DROPS them, JAX's default OOB-scatter mode)."""
    order = jnp.argsort(tgt)
    sg = jnp.take(tgt, order)
    su = jnp.take(upd, order, axis=0)
    newseg = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              (sg[1:] != sg[:-1]).astype(jnp.int32)])
    uid = jnp.cumsum(newseg)
    n = tgt.shape[0]
    summed = jax.ops.segment_sum(su, uid, num_segments=n)
    rep = jnp.full((n,), num_rows, dtype=sg.dtype).at[uid].min(sg)
    return rep, summed


def dedup_targets(tgt: jax.Array, num_rows: int) -> jax.Array:
    """Scalar-only half of :func:`dedup_rows`: the unique in-range targets
    of ``tgt`` (one per sorted run), padded with ``num_rows`` fillers that
    a subsequent scatter drops."""
    order = jnp.argsort(tgt)
    sg = jnp.take(tgt, order)
    newseg = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              (sg[1:] != sg[:-1]).astype(jnp.int32)])
    uid = jnp.cumsum(newseg)
    return jnp.full(tgt.shape, num_rows, dtype=sg.dtype).at[uid].min(sg)


def bump_counters(cnt: jax.Array, tgt: jax.Array, num_rows: int
                  ) -> jax.Array:
    """+1 per valid lookup on the reserved ``cnt`` touch-counter slab
    [rows, 1].  ``tgt`` [L] flat row targets; out-of-range entries (masked
    lookups keyed to ``num_rows``, other shards' rows in a local stream)
    are DROPPED — masked explicitly, because JAX wraps negative indices
    before ``mode="drop"`` can reject them.  Duplicates accumulate, so
    every update path (reference / fused / presorted / batch-chunked)
    produces identical integer counts regardless of traversal order."""
    ok = (tgt >= 0) & (tgt < num_rows)
    safe = jnp.where(ok, tgt, num_rows)
    return cnt.at[safe].add(jnp.asarray(1, cnt.dtype), mode="drop")


def apply_rows_sgd(W_local: jax.Array, tgt: jax.Array, grad: jax.Array,
                   lr) -> jax.Array:
    """Plain scatter-add SGD on local rows (duplicates accumulate) —
    Alg. 3 with XLA's deterministic scatter supplying the atomicity."""
    return W_local.at[tgt].add((-lr * grad).astype(W_local.dtype))


def apply_rows_split_sgd(hi: jax.Array, lo: jax.Array, tgt: jax.Array,
                         grad: jax.Array, lr, fused: bool = False
                         ) -> tuple[jax.Array, jax.Array]:
    """Exact-fp32 sparse SGD on split-bf16 storage (see
    repro.optim.split_sgd).  ``tgt`` may contain duplicates.

    ``fused=False`` (reference): segment_sum the per-row gradients, gather
    the touched rows, combine/step/split, and scatter back — the functional
    scatter copies the whole shard.  ``fused=True``: one Pallas pass
    (:mod:`repro.kernels.embedding_update`) that pre-reduces duplicates in
    VMEM and rewrites only the touched rows in place; bit-identical output."""
    if fused:
        from repro.kernels import ops
        out = ops.fused_row_update(get("split_sgd"), {"hi": hi, "lo": lo},
                                   tgt, grad, lr, pooling=1)
        return out["hi"], out["lo"]
    rep, summed = dedup_rows(tgt, grad, hi.shape[0])
    safe = jnp.minimum(rep, hi.shape[0] - 1)   # gather side must be in-bounds
    h = jnp.take(hi, safe, axis=0)
    l = jnp.take(lo, safe, axis=0)
    w32 = combine_split(h, l)
    w32 = w32 - lr * summed
    nh, nl = split_fp32(w32)
    # rep == num_rows rows (empty segments) are dropped by the scatter.
    return hi.at[rep].set(nh), lo.at[rep].set(nl)


# ---------------------------------------------------------------------------
# The update stream
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparseStream:
    """One sparse-update stream for :meth:`RowOptimizer.apply_sparse`.

    Either the UNSORTED shaped stream — ``idx`` [..., P] LOCAL row ids,
    ``dY`` [..., E] bag cotangents over the matching leading dims,
    optional ``valid``/``weights`` in the layout of ``idx`` — or the
    HOST-PRE-SORTED stream: ``presort = (sorted_rows, sorted_bags,
    sorted_msk, sorted_wgt)`` [L] arrays (``repro.data.pipeline
    .presort_batch`` / ``kernels.embedding_update.sort_lookups``) with
    ``dY`` whose flattened leading dims give the bag table."""

    idx: Optional[jax.Array] = None
    dY: Optional[jax.Array] = None
    valid: Optional[jax.Array] = None
    weights: Optional[jax.Array] = None
    presort: Optional[tuple] = None


# ---------------------------------------------------------------------------
# RowOptimizer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RowOptimizer:
    """A sparse embedding optimizer: store layout + one fused apply.

    The callables are the REGISTRATION HOOKS — they carry the whole
    per-optimizer math, so nothing outside the instance dispatches on an
    optimizer kind:

    ``step(opt, blocks, g, lr, seed, rows) -> blocks``
        the row step.  ``blocks``: one [n, W] block per slab, in
        ``slab_keys`` order; ``g`` [n, E] fp32 per-row gradient sums;
        ``lr`` fp32 scalar; ``seed`` int32 stochastic-rounding seed;
        ``rows`` [n, 1] int32 row ids of the blocks.  Returns the new
        blocks, elementwise per row (a row's result must not depend on
        its neighbours: the fused kernel runs it on a whole tile-aligned
        row group and keeps the touched rows).  Applied exactly ONCE per
        touched row per step, by the kernel and by the reference alike.
    ``flat_reference(opt, store, tgt, grad, lr, seed) -> store``
        optional per-lookup reference (the stateless kinds' scatter
        semantics); ``None`` means dedup + ``step``.

    ``split`` says whether the master weights live as (hi bf16, lo
    uint16) or one fp32 ``w`` slab; ``state`` lists the per-row state
    slabs as ``(key, width[, dtype])`` tuples — width 0 meaning the
    embedding dim E, any other value a fixed per-row lane count (1 = the
    row-wise Adagrad scalar), dtype defaulting to fp32 (``"bfloat16"``
    selects the compressed bf16-hi layout).  ``stochastic_round`` asks
    the step factory to thread a fresh int32 seed per step (the ``sr``
    counter in the train state).  Hashable and jit-static-friendly."""

    name: str
    split: bool = False
    state: tuple = ()        # ((slab_key, width[, dtype]), ...); width 0 => E
    beta: float = 0.0            # momentum coefficient
    eps: float = 1e-8            # adagrad denominator floor
    stochastic_round: bool = False
    step: Optional[Callable] = None
    flat_reference: Optional[Callable] = None

    # ---------------------------------------------------------- store --
    @property
    def weight_keys(self) -> tuple:
        return ("hi", "lo") if self.split else ("w",)

    @property
    def state_keys(self) -> tuple:
        return tuple(s[0] for s in self.state)

    @property
    def slab_keys(self) -> tuple:
        """The slabs the row step reads and writes, in its block order."""
        return self.weight_keys + self.state_keys

    def state_slabs(self) -> tuple:
        """Normalized ``(key, width, dtype)`` per state slab."""
        return tuple((s[0], s[1],
                      jnp.dtype(s[2]) if len(s) > 2 else jnp.dtype("float32"))
                     for s in self.state)

    def store_struct(self, rows: int, E: int,
                     counters: bool = False) -> dict:
        """ShapeDtypeStructs of the EmbeddingStore for a [rows, E] slab —
        weights first, then state, all row-aligned (shard the leading dim
        by the embedding layout).  ``counters=True`` appends the reserved
        ``cnt`` touch-counter slab ([rows, 1] int32) unless the optimizer
        already declares it as state (``adagrad_freq``)."""
        out = ({"hi": jax.ShapeDtypeStruct((rows, E), jnp.bfloat16),
                "lo": jax.ShapeDtypeStruct((rows, E), jnp.uint16)}
               if self.split else
               {"w": jax.ShapeDtypeStruct((rows, E), jnp.float32)})
        for key, width, dtype in self.state_slabs():
            out[key] = jax.ShapeDtypeStruct((rows, width or E), dtype)
        if counters and "cnt" not in out:
            out["cnt"] = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
        return out

    def init_store(self, W: jax.Array, counters: bool = False) -> dict:
        """EmbeddingStore from fp32 master weights [rows, E]; state slabs
        (and, with ``counters=True``, the reserved ``cnt`` touch-counter
        slab) zero-initialized."""
        rows, E = W.shape
        if self.split:
            hi, lo = split_fp32(W)
            out = {"hi": hi, "lo": lo}
        else:
            out = {"w": W.astype(jnp.float32)}
        for key, width, dtype in self.state_slabs():
            out[key] = jnp.zeros((rows, width or E), dtype)
        if counters and "cnt" not in out:
            out["cnt"] = jnp.zeros((rows, 1), jnp.int32)
        return out

    def fwd_weights(self, store: dict) -> jax.Array:
        """The slab the forward/backward passes read (bf16 hi or fp32 w)."""
        return store["hi"] if self.split else store["w"]

    def materialize_fp32(self, store: dict) -> jax.Array:
        """Exact fp32 master weights (eval / serving snapshots)."""
        if self.split:
            return combine_split(store["hi"], store["lo"])
        return store["w"]

    # ---------------------------------------------------------- apply --
    def apply_sparse(self, store: dict, stream: SparseStream, lr, *,
                     seed=None, fused: bool = False,
                     interpret: Optional[bool] = None,
                     shards: int = 1) -> dict:
        """THE sparse update dispatcher: new store from one stream.

        ``fused=True`` (and always for pre-sorted streams) runs the Pallas
        fused kernel — per-row VMEM pre-reduction, weights AND state
        updated in place on the touched rows only.  ``fused=False`` runs
        the reference math (scatter / dedup + functional scatter) with
        identical optimizer semantics; the split path is bit-identical
        between the two, the fp32 paths match to the documented
        pre-reduction rounding, and the stochastic-rounding kinds are
        bit-identical across ALL paths for a given ``seed`` (the int32
        per-step stochastic-rounding counter; ignored by the
        deterministic kinds).

        The reserved ``cnt`` touch-counter slab, when present in
        ``store``, is bumped here — +1 per valid lookup, before the
        optimizer math — so a declared-state counter (``adagrad_freq``)
        reads the post-bump count and an auxiliary counter (the hot-row
        cache's promotion signal) is carried through unchanged by hooks
        that never see it.

        ``shards``: the shards a pre-sorted stream's lookups spread over
        (row placement hands every shard the whole stream, the others'
        lookups masked); the kernel sizes its row groups by the lookups
        live here."""
        from repro.kernels import ops
        seed = jnp.asarray(0 if seed is None else seed, jnp.int32)
        num_rows = self.fwd_weights(store).shape[0]
        # flat touch targets for the counter bump: valid in-range row ids,
        # everything else keyed out of range (dropped by bump_counters)
        if stream.presort is not None:
            srows, _, smsk, _ = stream.presort
            touch = jnp.where(smsk != 0, srows, num_rows)
        elif stream.valid is None:
            touch = stream.idx.reshape(-1)
        else:
            touch = jnp.where(stream.valid, stream.idx,
                              num_rows).reshape(-1)
        aux_cnt = None
        if "cnt" in self.state_keys:
            store = dict(store)
            store["cnt"] = bump_counters(store["cnt"], touch, num_rows)
        elif "cnt" in store:
            # auxiliary counter: the hooks must not see it
            store = dict(store)
            aux_cnt = bump_counters(store.pop("cnt"), touch, num_rows)

        def _out(out):
            if aux_cnt is not None:
                out = dict(out)
                out["cnt"] = aux_cnt
            return out

        if stream.presort is not None:
            dY = stream.dY
            dYr = dY.reshape(-1, dY.shape[-1]) if dY.ndim != 2 else dY
            return _out(ops.fused_row_update_presorted(
                self, store, *stream.presort, dYr, lr, seed=seed,
                interpret=interpret, shards=shards))
        idx, dY = stream.idx, stream.dY
        P = idx.shape[-1]
        E = dY.shape[-1]
        if fused:
            tgt = idx.reshape(-1)
            val = None if stream.valid is None else stream.valid.reshape(-1)
            w = (None if stream.weights is None
                 else stream.weights.reshape(-1))
            dYr = dY.reshape(-1, E)
            return _out(ops.fused_row_update(self, store, tgt, dYr, lr,
                                             seed=seed, valid=val,
                                             weights=w, pooling=P,
                                             interpret=interpret))
        # reference: expand dY to per-lookup grads (the thing the fused
        # kernel never materializes), zero the masked entries, and apply
        # the instance's row step to the deduplicated rows
        grad = jnp.broadcast_to(dY[..., None, :],
                                idx.shape + (E,)).astype(jnp.float32)
        if stream.weights is not None:
            grad = grad * stream.weights[..., None].astype(jnp.float32)
        valid = stream.valid
        if valid is not None:
            grad = jnp.where(valid[..., None], grad, 0.0)
        grad = grad.reshape(-1, E)
        if not self.state:
            # stateless contract: masked lookups become zero-grad entries
            # on row 0 (a bit-exact no-op for the stateless kinds)
            tgt = (idx if valid is None
                   else jnp.where(valid, idx, 0)).reshape(-1)
        else:
            # stateful kinds must DROP masked lookups entirely (a zero
            # gradient still decays momentum / rewrites the accumulator):
            # key them out of range so dedup's scatter drops the segment
            tgt = (idx if valid is None
                   else jnp.where(valid, idx, num_rows)).reshape(-1)
        if self.flat_reference is not None:
            return _out(self.flat_reference(self, store, tgt, grad, lr,
                                            seed))
        rep, summed = dedup_rows(tgt, grad, num_rows)
        return _out(self.apply_rows_reduced(store, rep, summed, lr,
                                            seed=seed))

    def apply_rows_reduced(self, store: dict, rep: jax.Array,
                           summed: jax.Array, lr, seed=None) -> dict:
        """Reference transition on a PRE-REDUCED stream: ``rep`` [n]
        unique touched rows (``num_rows`` fillers are dropped by the
        scatter), ``summed`` [n, E] their per-row gradient sums.  Gathers
        the rows of every slab, runs the instance's row ``step`` on them
        and scatters the result back — applied exactly ONCE per row per
        step, the contract a batch-chunked caller must preserve by
        accumulating gradients across chunks first (``se.apply_update``)
        instead of re-running the momentum decay / Adagrad accumulate per
        chunk.

        An AUXILIARY ``cnt`` slab is carried through UNCHANGED — on this
        pre-reduced entry the caller owns the bump (``rep`` is
        deduplicated, so +1 per entry would undercount duplicates); a
        declared-state ``cnt`` (``adagrad_freq``) reaches the step as-is
        and the caller must have bumped it already."""
        seed = jnp.asarray(0 if seed is None else seed, jnp.int32)
        # the sums are complete before the step reads them: without the
        # barrier XLA folds ``beta * m + segment_sum(g)`` into one
        # scatter-add seeded with ``beta * m``, which reassociates the
        # fp32 sum and leaves the kernel's sum-then-step order
        summed = jax.lax.optimization_barrier(summed)
        out = dict(store)
        safe = jnp.minimum(rep, self.fwd_weights(store).shape[0] - 1)
        blocks = tuple(jnp.take(store[k], safe, axis=0)
                       for k in self.slab_keys)
        new = self.step(self, blocks, summed, lr, seed, safe[:, None])
        for k, v in zip(self.slab_keys, new):
            out[k] = store[k].at[rep].set(v.astype(store[k].dtype))
        return out


# ---------------------------------------------------------------------------
# Built-in row steps.  Each is the optimizer's whole per-row math, written
# elementwise over [n, W] row blocks so the SAME expression runs in the
# fused kernel (on one tile-aligned row group, ``g`` [G, E]) and in the
# reference (on the gathered unique rows, ``g`` [n, E]).  ``blocks`` and
# the returned tuple follow ``RowOptimizer.slab_keys``; ``rows`` [n, 1]
# are the blocks' row ids (the stochastic-rounding counter).
# ---------------------------------------------------------------------------

def _step_sgd(opt, blocks, g, lr, seed, rows):
    (w,) = blocks
    return (w - lr * g,)


def _step_split_sgd(opt, blocks, g, lr, seed, rows):
    hi, lo = blocks
    return split_fp32(combine_split(hi, lo) - lr * g)


def _step_momentum(opt, blocks, g, lr, seed, rows):
    w, m = blocks
    m_new = opt.beta * m + g
    return w - lr * m_new, m_new


def _step_adagrad(opt, blocks, g, lr, seed, rows):
    w, s = blocks
    s_new = s + g * g
    return w - lr * g / (jnp.sqrt(s_new) + opt.eps), s_new


def _step_adagrad_rowwise(opt, blocks, g, lr, seed, rows):
    # ONE accumulator scalar per row (Naumov et al. 2019): [n, 1] slab
    w, s = blocks
    s_new = s + jnp.mean(g * g, axis=-1, keepdims=True)
    return w - lr * g / (jnp.sqrt(s_new) + opt.eps), s_new


def _step_momentum_bf16(opt, blocks, g, lr, seed, rows):
    # decode exact, fp32 transition, stochastically round ONLY the stored
    # state — the dither is a pure function of (seed, row, lane)
    w, m = blocks
    m_new = opt.beta * m.astype(jnp.float32) + g
    noise = sr_noise_col(seed, rows, m_new.shape[-1])
    return w - lr * m_new, sr_round_bf16(m_new, noise)


def _step_adagrad_bf16(opt, blocks, g, lr, seed, rows):
    # the weight step divides by the UNROUNDED fp32 accumulator
    w, s = blocks
    s_new = s.astype(jnp.float32) + g * g
    noise = sr_noise_col(seed, rows, s_new.shape[-1])
    return (w - lr * g / (jnp.sqrt(s_new) + opt.eps),
            sr_round_bf16(s_new, noise))


def _step_adagrad_freq(opt, blocks, g, lr, seed, rows):
    # frequency-adaptive sparse LR: the ``cnt`` slab is the POST-bump
    # touch counter (apply_sparse bumps the reserved slab before
    # dispatch), so hot rows take proportionally smaller steps.  The step
    # only READS the counter; the bump owns the transition.
    w, c = blocks
    denom = jnp.sqrt(jnp.maximum(c.astype(jnp.float32), 1.0)) + opt.eps
    return w - lr * g / denom, c


def _flatref_sgd(opt, store, tgt, grad, lr, seed):
    return {"w": apply_rows_sgd(store["w"], tgt, grad, lr)}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, RowOptimizer] = {}


def register(opt: RowOptimizer) -> RowOptimizer:
    if opt.name in _REGISTRY:
        raise ValueError(f"row optimizer {opt.name!r} already registered")
    if opt.step is None:
        raise ValueError(f"row optimizer {opt.name!r} registered no row "
                         "step (step=)")
    _REGISTRY[opt.name] = opt
    return opt


def unregister(name: str) -> None:
    """Remove a registered optimizer (tests tearing down toy entries)."""
    _REGISTRY.pop(name, None)


def names() -> tuple:
    return tuple(_REGISTRY)


def get(name: str, *, beta: Optional[float] = None,
        eps: Optional[float] = None) -> RowOptimizer:
    """Look a registered optimizer up by name, optionally overriding its
    hyperparameters."""
    try:
        opt = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown sparse optimizer {name!r}; registered: "
                         f"{sorted(_REGISTRY)}") from None
    repl = {}
    if beta is not None:
        repl["beta"] = float(beta)
    if eps is not None:
        repl["eps"] = float(eps)
    return dataclasses.replace(opt, **repl) if repl else opt


def make(spec: Any, *, beta: Optional[float] = None,
         eps: Optional[float] = None) -> RowOptimizer:
    """Coerce a config value (name string or RowOptimizer) to an instance."""
    if isinstance(spec, RowOptimizer):
        repl = {}
        if beta is not None:
            repl["beta"] = float(beta)
        if eps is not None:
            repl["eps"] = float(eps)
        return dataclasses.replace(spec, **repl) if repl else spec
    return get(str(spec), beta=beta, eps=eps)


def resolve(mdef: Any) -> RowOptimizer:
    """RowOptimizer of a model definition's step options
    (``repro.core.hybrid.StepOptions``): ``sparse_optimizer`` (name or
    instance; unset = 'split_sgd'), with ``opt_beta``/``opt_eps``
    overriding the registered defaults."""
    return make(mdef.sparse_optimizer or "split_sgd", beta=mdef.opt_beta,
                eps=mdef.opt_eps)


register(RowOptimizer(name="sgd", split=False,
                      step=_step_sgd, flat_reference=_flatref_sgd))
register(RowOptimizer(name="split_sgd", split=True, step=_step_split_sgd))
register(RowOptimizer(name="momentum", split=False,
                      state=(("mom", 0),), beta=0.9, step=_step_momentum))
register(RowOptimizer(name="adagrad_rowwise", split=False,
                      state=(("acc", 1),), eps=1e-8,
                      step=_step_adagrad_rowwise))
register(RowOptimizer(name="adagrad", split=False,
                      state=(("acc", 0),), eps=1e-8, step=_step_adagrad))
# compressed bf16-hi state + seeded stochastic rounding: half the
# state-slab bytes per touched row (see docs/optim.md for when NOT to)
register(RowOptimizer(name="momentum_bf16", split=False,
                      state=(("mom", 0, "bfloat16"),), beta=0.9,
                      stochastic_round=True, step=_step_momentum_bf16))
register(RowOptimizer(name="adagrad_bf16", split=False,
                      state=(("acc", 0, "bfloat16"),), eps=1e-8,
                      stochastic_round=True, step=_step_adagrad_bf16))
# frequency-adaptive sparse LR driven by the reserved touch-counter slab
# (hot rows — large counts — decay faster); the same counters feed the
# hot-row cache's promotion policy (docs/cache.md)
register(RowOptimizer(name="adagrad_freq", split=False,
                      state=(("cnt", 1, "int32"),), eps=1e-8,
                      step=_step_adagrad_freq))
