"""Threaded host-side ingestion pipeline + per-batch index pre-sort.

Two jobs, both off the device critical path:

1. **Overlap**: a worker thread pulls batches from the source (a
   :class:`repro.data.reader.ShardedReader`, a synthetic generator, ...),
   runs the host prep, and parks the result in a bounded queue — so shard
   decode + prep for batch ``n+1`` runs while the devices execute step
   ``n``.  Compose with :func:`repro.train.loop.prefetch_to_device` for
   the H2D leg (this thread produces host arrays; that one device_puts
   them — both are thin wrappers over :class:`ThreadedIterator`, the one
   shared worker/queue/poison implementation).  Worker failures are
   delivered to the consumer as a POISONED queue entry and re-raised
   promptly — the loop never hangs on a dead loader.

2. **Pre-sort**: the fused sparse-update kernel
   (repro/kernels/embedding_update.py) wants the flat lookup stream
   sorted by local row id so duplicate rows form contiguous runs.
   Without host prep, every step pays an on-device stable sort over
   ``L = B*S*P`` keys.  :func:`presort_batch` computes, per embedding
   shard, the EXACT arrays ``kernels.embedding_update.sort_lookups``
   would produce — stable sort permutations are unique, so numpy here
   and XLA there yield bit-identical results — and ships them as batch
   fields (``psort_*``, sharded over the embedding axes).  The step then
   feeds the kernel directly (``host_presort=True`` on the model def)
   and the device sort disappears from the hot path.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional

import numpy as np

from repro import telemetry
from repro.data.synthetic import to_padded_slots

PSORT_KEYS = ("psort_rows", "psort_bags", "psort_msk", "psort_wgt")


def presort_batch(layout, idx: np.ndarray,
                  weights: Optional[np.ndarray] = None) -> dict:
    """Per-shard sorted lookup streams for one global batch (row AND table
    sharding modes).

    ``layout``: :class:`repro.core.sharded_embedding.ShardedEmbeddingLayout`.
    ``idx`` [B, S, P] ORIGINAL-SLOT per-table indices — the same
    global-order stream the step's sparse update consumes (the microbatch
    pipeline restores device-major == global order before the one sparse
    update, so these fields are M-invariant).  ``weights`` [B, S, P]
    optional per-lookup bag weights.

    Row mode sorts each shard's owner-masked local-row stream
    (``L = B*S*P``).  Table mode first folds in the padded-slot permute
    the device-side exchange performs (``permute_indices``: original ->
    padded order, dummy slots read index 0 / weight 0) and sorts each
    shard's slot-offset stream (``L = B*slots_per_shard*P``) — so
    ``host_presort=True`` works in both placement modes.

    Returns ``{psort_rows, psort_bags, psort_msk, psort_wgt}``, each
    ``[num_shards, L]`` — row ``k`` belongs to the shard with embedding-
    axis index ``k`` (shard the leading dim over the embedding axes).
    Bit-compatibility with the on-device ``sort_lookups`` path is
    structural: same int32 key construction, and a stable argsort's
    permutation is uniquely determined by the keys, so
    ``np.argsort(kind='stable')`` here equals the device's stable sort
    (``sort_by_key``) there.
    """
    B, S, P = idx.shape
    ns, R = layout.num_shards, layout.rows_per_shard
    # int32 end-to-end: the device computes local rows in the index dtype
    if layout.mode == "row":
        off = np.asarray(layout.row_offsets, np.int32)
        g = np.asarray(idx, np.int32) + off[None, :, None]
        locals_ = [(g - np.int32(s * R)).reshape(-1) for s in range(ns)]
        wflat = (None if weights is None
                 else [np.asarray(weights, np.float32).reshape(-1)] * ns)
    elif layout.mode == "table":
        # fold the device-side padded-slot permute into the host sort:
        # original slots -> padded (bin-major) order, dummy slots read
        # index 0 (the scratch row) with weight 0 — exactly the
        # permute_indices + zeroed-weights stream the exchange ships
        padded = to_padded_slots(layout, np.asarray(idx, np.int32))
        if weights is not None:
            wp = to_padded_slots(layout, np.asarray(weights, np.float32))
        K = layout.slots_per_shard
        off = np.asarray(layout.slot_local_offsets,
                         np.int32).reshape(ns, K)
        locals_ = [(padded[:, s * K:(s + 1) * K, :]
                    + off[s][None, :, None]).reshape(-1)
                   for s in range(ns)]
        wflat = (None if weights is None
                 else [wp[:, s * K:(s + 1) * K, :].reshape(-1)
                       for s in range(ns)])
    else:
        raise ValueError(f"unknown layout mode {layout.mode!r}")
    L = locals_[0].shape[0]
    rows = np.empty((ns, L), np.int32)
    bags = np.empty((ns, L), np.int32)
    msk = np.empty((ns, L), np.int32)
    wgt = np.empty((ns, L), np.float32)
    for s in range(ns):
        local = locals_[s]
        valid = (local >= 0) & (local < R)
        key = np.where(valid, local, R).astype(np.int32)
        order = np.argsort(key, kind="stable")
        skey = key[order]
        rows[s] = np.minimum(skey, R - 1)
        bags[s] = (order // P).astype(np.int32)
        msk[s] = (skey < R).astype(np.int32)
        wgt[s] = 1.0 if wflat is None else wflat[s][order]
    return {"psort_rows": rows, "psort_bags": bags, "psort_msk": msk,
            "psort_wgt": wgt}


_DONE = object()


class _Poison:
    """Queue sentinel carrying a worker exception to the consumer."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class _Stopped(Exception):
    """Internal: close() was requested while the worker held an item."""


class ThreadedIterator:
    """Worker thread + bounded queue + poison sentinel, once.

    Pulls from ``source`` on a daemon thread, applies ``transform`` (the
    host prep: shard decode, pre-sort, device_put, ...) and parks results
    in a ``depth``-bounded queue — backpressure keeps the worker at most
    ``depth`` items (+1 in hand) ahead of the consumer.  Order is
    preserved exactly.  A worker exception poisons the queue and
    re-raises at the consumer's next pull: a dead producer FAILS the
    consumer, it never hangs it.

    ``close()`` stops the worker promptly even when it is blocked on a
    full queue (the put loop watches the stop flag), drains the queue
    and joins — abandoning a partially-consumed stream does not leak a
    blocked thread or its queued items.  ``stats`` counts ``prep_s``
    (worker: source pull + transform), ``wait_s`` (consumer blocked on
    the queue), ``batches`` and ``retries``.

    Resilience knobs: ``retries`` bounds a retry-with-backoff on
    TRANSIENT worker exceptions (a flaky shard read whose ``__next__``
    can be called again; generators that die stay dead and simply end
    the stream) — beyond the budget the queue is poisoned as before.
    ``faults`` is an optional :class:`repro.faults.FaultPlan`; the
    worker fires the ``loader.next`` site once per pull (step-indexed by
    pull count), which is where drills inject loader deaths and stalls.
    After a poison is delivered the stream goes STICKY-DEAD: the
    exception is raised once and later pulls see ``StopIteration`` —
    a consumer that absorbs the error (skip-batch budget) must never
    hang on the dead worker's empty queue.
    """

    def __init__(self, source: Iterable, *,
                 transform: Optional[Callable] = None, depth: int = 2,
                 name: str = "ThreadedIterator", retries: int = 0,
                 retry_backoff_s: float = 0.05, faults=None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._source = source
        self._transform = transform
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._retries = retries
        self._retry_backoff_s = retry_backoff_s
        self._faults = faults
        self.stats = {"prep_s": 0.0, "wait_s": 0.0, "batches": 0,
                      "retries": 0}
        self._thread = threading.Thread(target=self._work, daemon=True,
                                        name=name)
        self._started = False

    def _put(self, item) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return
            except queue.Full:
                continue
        raise _Stopped

    def _work(self) -> None:
        try:
            it = iter(self._source)
            failures = 0
            pulls = 0
            while not self._stop.is_set():
                t0 = time.perf_counter()
                try:
                    if self._faults is not None:
                        self._faults.fire("loader.next", step=pulls)
                    # span lands on this worker's own trace track (the
                    # thread name: HostPipeline / prefetch_to_device)
                    with telemetry.span("ingest/prep", cat="ingest",
                                        pull=pulls):
                        item = next(it)
                        if self._transform is not None:
                            item = self._transform(item)
                except StopIteration:
                    self._put(_DONE)
                    return
                except _Stopped:
                    raise
                except Exception as e:  # noqa: BLE001 — bounded retry
                    # transient worker failure: retry the pull (sources
                    # whose __next__ is re-callable survive; a dead
                    # generator raises StopIteration on the retry and the
                    # stream ends); past the budget, poison as usual.
                    # InjectedCrash is a BaseException: never retried.
                    if failures < self._retries:
                        failures += 1
                        self.stats["retries"] += 1
                        time.sleep(self._retry_backoff_s
                                   * (2 ** (failures - 1)))
                        continue
                    raise
                pulls += 1
                self.stats["prep_s"] += time.perf_counter() - t0
                self._put(item)
        except _Stopped:
            pass
        except BaseException as e:  # noqa: BLE001 — poison, don't hang
            try:
                self._put(_Poison(e))
            except _Stopped:
                pass

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if not self._started:
            self._thread.start()
            self._started = True
        t0 = time.perf_counter()
        item = self._q.get()
        self.stats["wait_s"] += time.perf_counter() - t0
        if item is _DONE:
            # sticky: repeated next() calls and CHAINED consumers (e.g.
            # the prefetch_to_device worker reading a closed HostPipeline)
            # must also observe end-of-stream instead of blocking forever
            try:
                self._q.put_nowait(_DONE)
            except queue.Full:
                pass
            raise StopIteration
        if isinstance(item, _Poison):
            # sticky-dead: the worker exited after poisoning, so a consumer
            # that catches this exception (TrainLoop's skip-batch budget)
            # and pulls again must observe end-of-stream, not block forever
            # on an empty queue nothing refills
            try:
                self._q.put_nowait(_DONE)
            except queue.Full:
                pass
            raise item.exc
        self.stats["batches"] += 1
        return item

    def close(self) -> None:
        """Stop the worker (promptly, even when blocked on a full queue),
        drain its items, join, and leave a sticky end-of-stream sentinel
        so any consumer currently blocked in ``__next__`` — or pulling
        later — gets StopIteration instead of hanging.  Idempotent."""
        self._stop.set()
        if self._started:
            deadline = time.monotonic() + 5.0
            while (self._thread.is_alive()
                   and time.monotonic() < deadline):
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    time.sleep(0.005)
            self._thread.join(timeout=1.0)
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        try:
            self._q.put_nowait(_DONE)
        except queue.Full:
            pass


class HostPipeline(ThreadedIterator):
    """Background-thread batch prep with bounded lookahead.

    ``batches``: any iterator/iterable of batch dicts (ShardedReader,
    synthetic stream, ...).  ``presort=True`` attaches the ``psort_*``
    fields of :func:`presort_batch` (requires ``layout``); the model def
    consuming them must set ``host_presort=True`` so its batch struct
    declares the fields.

    Iteration re-raises worker exceptions at the consumer's next pull
    (poisoned-queue sentinel — a dead loader fails the step, it does not
    hang it); ``close()`` releases the worker of an abandoned stream.
    ``stats`` feeds ``bench_ingest.py``'s overlap fraction.
    """

    def __init__(self, batches: Iterable[dict], *, layout=None,
                 presort: bool = False, depth: int = 2, retries: int = 0,
                 faults=None):
        if presort and layout is None:
            raise ValueError("presort=True requires the embedding layout")
        self._layout = layout
        self._presort = presort
        super().__init__(batches, transform=self._prep, depth=depth,
                         name="HostPipeline", retries=retries,
                         faults=faults)

    def _prep(self, b: dict) -> dict:
        out = dict(b)
        if self._presort:
            out.update(presort_batch(self._layout, out["idx"],
                                     out.get("weights")))
        return out
