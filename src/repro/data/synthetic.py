"""Synthetic data generators (seeded, host-side numpy).

The paper evaluates with random datasets for small/large and the Criteo
Terabyte set for MLPerf; the key *performance-relevant* property of real
click logs is the skewed index distribution (the paper's Fig. 8 contention
analysis: "a lot of contention with the terabyte dataset causing up to 10x
slowdown").  ``alpha`` controls a Zipf-like skew so benchmarks can reproduce
both regimes.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import numpy as np


def zipf_indices(rng: np.random.Generator, vocab: int, size, alpha: float
                 ) -> np.ndarray:
    """alpha == 0 -> uniform; larger alpha -> heavier head skew."""
    if alpha <= 0:
        return rng.integers(0, vocab, size, dtype=np.int64)
    # inverse-CDF sampling of a truncated zipf: ranks ~ u^(-1/(alpha));
    # clip in FLOAT space first (tiny alpha overflows any integer type)
    u = rng.random(size)
    with np.errstate(over="ignore"):
        ranks = np.clip(u ** (-1.0 / alpha) - 1.0, 0.0, float(vocab - 1))
    return ranks.astype(np.int64)


@dataclasses.dataclass
class SparseBatchSpec:
    table_rows: tuple          # rows per TABLE
    slot_to_table: Optional[tuple]  # slot -> table (None = identity)
    pooling: int
    batch: int
    num_dense: int = 0
    alpha: float = 0.0         # index skew
    seq_mask: bool = False     # emit all-ones seq_mask (sasrec)
    hist_mask: bool = False    # emit all-ones hist_mask (din)
    labels: bool = True

    @property
    def slots(self):
        return (self.slot_to_table if self.slot_to_table is not None
                else tuple(range(len(self.table_rows))))


def sparse_batch(rng: np.random.Generator, spec: SparseBatchSpec) -> dict:
    """One global batch for the hybrid-parallel models (original slot
    order; callers permute for table mode)."""
    B, P = spec.batch, spec.pooling
    cols = []
    for t in spec.slots:
        cols.append(zipf_indices(rng, spec.table_rows[t], (B, P), spec.alpha))
    batch = {"idx": np.stack(cols, axis=1).astype(np.int32)}
    if spec.num_dense:
        batch["dense_x"] = rng.standard_normal(
            (B, spec.num_dense)).astype(np.float32)
    if spec.labels:
        batch["labels"] = rng.integers(0, 2, (B,)).astype(np.float32)
    if spec.seq_mask:
        batch["seq_mask"] = np.ones((B, 50), np.float32)
    if spec.hist_mask:
        batch["hist_mask"] = np.ones((B, 100), np.float32)
    return batch


def to_padded_slots(layout, idx: np.ndarray) -> np.ndarray:
    """[B, S, P] per-slot values in original slot order (indices, or bag
    weights) -> the [B, num_padded_slots, P] padded (bin-major) order a
    table-mode step reads from a replicated index stream; dummy slots
    hold 0, so they read row 0 with weight 0 (host twin of
    ``sharded_embedding.permute_indices``)."""
    src = np.where(layout.padded_slots >= 0, layout.padded_slots, 0)
    out = idx[:, src]
    out[:, layout.padded_slots < 0] = 0
    return out


def dlrm_stream(seed: int, cfg, alpha: float = 0.0,
                layout=None) -> Iterator[dict]:
    """Batches for repro.core.dlrm.DLRMConfig: original slot order, or
    the padded slot order when ``layout`` is a table-mode layout and the
    config reads a replicated index stream."""
    rng = np.random.default_rng(seed)
    spec = SparseBatchSpec(cfg.table_rows, None, cfg.pooling, cfg.batch,
                           num_dense=cfg.num_dense, alpha=alpha)
    padded = (layout is not None and layout.mode == "table"
              and cfg.idx_input == "replicated")
    while True:
        b = sparse_batch(rng, spec)
        b["dense_x"] = b["dense_x"].astype(np.float32)
        if padded:
            b["idx"] = to_padded_slots(layout, b["idx"])
        yield b


def hybrid_stream(seed: int, mdef, alpha: float = 0.0) -> Iterator[dict]:
    """Batches for repro.core.hybrid.HybridDef models."""
    rng = np.random.default_rng(seed)
    spec = SparseBatchSpec(
        mdef.spec.table_rows, mdef.slot_to_table, mdef.pooling, mdef.batch,
        alpha=alpha, labels="labels" in mdef.extras,
        seq_mask="seq_mask" in mdef.extras,
        hist_mask="hist_mask" in mdef.extras)
    while True:
        yield sparse_batch(rng, spec)


def token_stream(seed: int, vocab: int, batch: int, seq: int
                 ) -> Iterator[dict]:
    rng = np.random.default_rng(seed)
    while True:
        toks = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int64)
        yield {"tokens": toks[:, :-1].astype(np.int32),
               "labels": toks[:, 1:].astype(np.int32)}
