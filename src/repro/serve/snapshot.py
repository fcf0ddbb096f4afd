"""Read-only serving snapshots of the training state.

The Split-SGD store already keeps a bf16 hi-half of every embedding row —
that slab IS a read-optimized serving table at zero conversion cost (half
the bytes of an fp32 table).  A :class:`ServingSnapshot` captures exactly
the slabs the forward pass reads:

* ``emb_w``   — ``opt.fwd_weights(state["emb"])``: the bf16 ``hi`` slab for
  split optimizers, the fp32 ``w`` slab otherwise.  Never the ``lo`` half,
  never ``mom``/``acc``/``cnt`` optimizer state.
* ``dense_hi`` — the bf16 dense parameters.
* ``hot_w`` / ``hot_pos`` — the replicated hot-row cache slab, when the
  model def enables it (``hot_rows > 0``); it rides along so a serving
  tier can answer hot-row reads without touching the sharded cold store.

JAX arrays are immutable, but the train step DONATES its input state
buffers — so a snapshot taken mid-training must own copies of its slabs
(``snapshot_state(..., copy=True)``, what the publisher does), while a
post-training snapshot can hold zero-cost references.  Either way a
published snapshot keeps scoring the weights it captured while training
moves on.

Determinism contract (pinned in tests/test_serve.py): scoring through
:func:`make_snapshot_score_step` is BITWISE identical to
``repro.core.hybrid.make_score_step`` on the same weights — both run the
same ``index_exchange``/``embedding_fwd`` stages and the same dense
scorer; the snapshot path merely enters at the post-``fwd_weights`` slab.

:class:`SnapshotRegistry` is the versioned publish/retire surface between
one training loop and any number of serving threads: ``publish`` assigns
monotonically increasing versions and auto-retires all but the newest
``keep`` snapshots; ``current()`` is what a server reads per batch.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import hybrid, pipeline
from repro.optim import row as row_optim


def snapshot_state(mdef, state: dict, *, copy: bool = False) -> dict:
    """The forward-only view of a train state: ``{emb_w, dense_hi}`` plus
    ``{hot_w, hot_pos}`` when the hot-row cache is enabled.  Never any
    optimizer-state slab.

    ``copy=False`` returns references — right for scoring a state that
    will not train further.  ``copy=True`` materializes owned buffers:
    REQUIRED when training continues, because the train step DONATES the
    previous state's buffers to XLA and a by-reference snapshot would be
    deleted out from under the server two steps later
    (:class:`repro.serve.publish.SnapshotPublisher` always copies)."""
    opt = row_optim.resolve(mdef)
    snap = {"emb_w": opt.fwd_weights(state["emb"]), "dense_hi": state["dense"]["hi"]}
    if mdef.hot_rows > 0:
        snap["hot_w"] = state["cache"]["hot_w"]
        snap["hot_pos"] = state["cache"]["hot_pos"]
    if copy:
        snap = jax.tree.map(jnp.copy, snap)
    return snap


def snapshot_specs(mdef, mesh) -> dict:
    """PartitionSpecs of the snapshot pytree (the embedding slab keeps the
    store's row sharding; everything else is replicated)."""
    emb_ax, _ = pipeline.emb_axes(mdef, mesh)
    specs: dict = {"emb_w": P(emb_ax, None), "dense_hi": None}
    structs, _, _, _ = hybrid.state_struct(mdef, mesh)
    specs["dense_hi"] = jax.tree.map(lambda _: P(), structs["dense"]["hi"])
    if mdef.hot_rows > 0:
        specs["hot_w"] = P()
        specs["hot_pos"] = P()
    return specs


def _tree_bytes(tree) -> int:
    return int(sum(np.dtype(leaf.dtype).itemsize * leaf.size for leaf in jax.tree.leaves(tree)))


@dataclasses.dataclass(frozen=True)
class ServingSnapshot:
    """One immutable published version of the serving tables."""

    version: int
    step: int
    published_t: float  # wall time of publish (time.time())
    state: dict  # {emb_w, dense_hi[, hot_w, hot_pos]} — jax arrays

    @property
    def emb_bytes(self) -> int:
        """Bytes of the serving embedding table as stored (bf16 hi slab for
        split optimizers: half the fp32 table)."""
        return _tree_bytes(self.state["emb_w"])

    @property
    def fp32_emb_bytes(self) -> int:
        """Bytes the same table would cost at fp32 (the comparison point
        for the bf16-hi serving-bytes claim)."""
        return int(self.state["emb_w"].size) * 4

    @property
    def total_bytes(self) -> int:
        return _tree_bytes(self.state)

    def seconds_behind(self, now: Optional[float] = None) -> float:
        return (time.time() if now is None else now) - self.published_t


def snapshot_from_state(
    mdef, state: dict, *, version: int = 1, step: int = 0, now: Optional[float] = None
) -> ServingSnapshot:
    """Build an immutable snapshot straight from a train state."""
    return ServingSnapshot(
        version=version,
        step=step,
        published_t=time.time() if now is None else now,
        state=snapshot_state(mdef, state),
    )


class SnapshotRegistry:
    """Versioned publish/retire store between ONE publisher and many
    serving readers.  Thread-safe; ``publish`` assigns monotonically
    increasing versions and auto-retires all but the newest ``keep``."""

    def __init__(self, keep: int = 2):
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.keep = keep
        self._lock = threading.Lock()
        self._snaps: dict[int, ServingSnapshot] = {}
        self._next_version = 1

    def publish(self, snap_state: dict, *, step: int = 0) -> ServingSnapshot:
        """Publish a snapshot-state pytree (:func:`snapshot_state`) as the
        next version; snapshots beyond ``keep`` are retired."""
        with self._lock:
            snap = ServingSnapshot(
                version=self._next_version,
                step=step,
                published_t=time.time(),
                state=snap_state,
            )
            self._next_version += 1
            self._snaps[snap.version] = snap
            for v in sorted(self._snaps)[: -self.keep]:
                del self._snaps[v]
            return snap

    def current(self) -> Optional[ServingSnapshot]:
        """Newest published snapshot (None before the first publish)."""
        with self._lock:
            if not self._snaps:
                return None
            return self._snaps[max(self._snaps)]

    def get(self, version: int) -> Optional[ServingSnapshot]:
        with self._lock:
            return self._snaps.get(version)

    def retire(self, version: int) -> bool:
        """Drop one version (readers holding the object keep it alive —
        retirement only stops new lookups).  Returns whether it existed."""
        with self._lock:
            return self._snaps.pop(version, None) is not None

    def versions(self) -> list[int]:
        with self._lock:
            return sorted(self._snaps)


def make_snapshot_score_step(
    mdef, mesh, batch: Optional[int] = None, *, donate_batch: bool = True
):
    """Forward-only scoring from a snapshot-state pytree.

    Same stage composition as ``hybrid.make_score_step`` —
    ``index_exchange(fwd_only=True)`` then ``embedding_fwd`` then
    ``mdef.dense_score`` — entered at the post-``fwd_weights`` slab, so the
    scores are bitwise identical to the full-state path on the same
    weights.  The BATCH argument is donated by default (each serving batch
    is scored once; XLA may reuse its buffers for the outputs) — the
    snapshot argument never is, so one snapshot serves many batches.

    Returns ``(fn, snap_shardings, bstructs, bspecs)``; call as
    ``scores = fn(snapshot.state, batch)``.
    """
    layout = hybrid.make_layout(mdef, mesh)
    bstructs, bspecs = hybrid.batch_struct(mdef, mesh, layout, batch, include_presort=False)
    all_axes, _, _ = pipeline.mesh_axes(mesh)
    stages = pipeline.build_stages(mdef, mesh, layout)
    specs = snapshot_specs(mdef, mesh)

    def score_local(snap, batch_d):
        idx_fwd, _ = stages.index_exchange(batch_d["idx"], fwd_only=True)
        wgt_fwd = None
        if mdef.weighted:
            wgt_fwd, _ = stages.index_exchange(batch_d["weights"], fwd_only=True)
        emb_out = stages.embedding_fwd(snap["emb_w"], idx_fwd, wgt_fwd)
        return mdef.dense_score(snap["dense_hi"], emb_out, batch_d)

    sc = compat.shard_map(
        score_local, mesh=mesh, in_specs=(specs, bspecs), out_specs=P(all_axes), check_vma=False
    )
    fn = jax.jit(sc, donate_argnums=(1,) if donate_batch else ())
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    return fn, shardings, bstructs, bspecs


def make_bucket_scorers(
    mdef,
    mesh,
    buckets: tuple[int, ...],
    source: Callable[[], Any],
    *,
    donate_batch: bool = True,
):
    """Per-bucket compiled score fns over a snapshot source.

    ``source`` returns the snapshot-state pytree to score against (e.g.
    ``lambda: registry.current().state`` — read per batch, so a publish
    between batches is picked up immediately).  Returns ``(score_fns,
    pad_batch)`` in the shape :class:`repro.serve.server
    .ContinuousBatchingServer` consumes: ``score_fns[bucket](batch)`` and
    ``pad_batch(payloads, bucket)`` (zero-padded to the bucket's compiled
    shape, dtypes from the batch struct)."""
    steps = {}
    structs_by = {}
    for b in sorted(buckets):
        fn, _, bstructs, _ = make_snapshot_score_step(mdef, mesh, batch=b, donate_batch=donate_batch)
        steps[b] = fn
        structs_by[b] = bstructs

    def _score(bucket):
        def run(batch):
            return steps[bucket](source(), batch)

        return run

    def pad_batch(payloads: list, bucket: int) -> dict:
        import jax.numpy as jnp

        structs = structs_by[bucket]
        out = {}
        for k, sds in structs.items():
            np_dtype = np.float32 if sds.dtype == jnp.bfloat16 else np.dtype(sds.dtype)
            base = np.zeros(sds.shape, np_dtype)
            for i, p in enumerate(payloads):
                base[i] = np.asarray(p[k])
            out[k] = jnp.asarray(base, sds.dtype)
        return out

    return {b: _score(b) for b in sorted(buckets)}, pad_batch
