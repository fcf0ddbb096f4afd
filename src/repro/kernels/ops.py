"""Jitted public wrappers around the Pallas kernels.

Handles shape padding to hardware-aligned blocks, GQA head expansion, and
the interpret switch (``interpret=True`` executes the kernel bodies in
Python — the validation mode on the CPU backend; on TPU it compiles to
Mosaic).  Default interpret mode follows the backend: interpreted on the
CPU, compiled on a TPU, and an error anywhere else.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.embedding_update import (sort_lookups,
                                            sparse_row_update_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_mlp import fused_mlp_pallas
from repro.kernels.interaction import interaction_pallas
from repro.kernels.split_sgd import split_sgd_pallas


def _default_interpret() -> bool:
    """Interpret the kernels exactly when the backend is the CPU.  There is
    no fallback: a TPU always runs them compiled, and any other backend is
    refused rather than silently emulated."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels target the TPU (compiled) or the "
                           f"CPU (interpreted), not {backend!r}")
    return backend == "cpu"


def _pad_dim(x: jax.Array, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x, x.shape[axis]
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), x.shape[axis]


@partial(jax.jit, static_argnames=("activation", "interpret"))
def fused_mlp_layer(x, w, b, activation: str = "relu",
                    interpret: bool | None = None):
    """act(x @ w + b) with fp32 accumulation.  Pads to (8,128) multiples."""
    interpret = _default_interpret() if interpret is None else interpret
    xp, M = _pad_dim(x, 0, 8)
    xp, K = _pad_dim(xp, 1, 128)
    wp, _ = _pad_dim(w, 0, 128)
    wp, N = _pad_dim(wp, 1, 128)
    bp, _ = _pad_dim(b, 0, 128)
    bm = min(256, max(8, xp.shape[0] // 8 * 8 if xp.shape[0] < 256 else 256))
    # clamp blocks to padded dims
    def blk(dim, pref):
        return dim if dim < pref else pref
    out = fused_mlp_pallas(xp, wp, bp, activation,
                           bm=blk(xp.shape[0], 256), bn=blk(wp.shape[1], 256),
                           bk=blk(xp.shape[1], 512), interpret=interpret)
    return out[:M, :N]


@partial(jax.jit, static_argnames=("bags_per_block", "interpret"))
def embedding_bag(W, idx, bags_per_block: int = 8,
                  interpret: bool | None = None):
    """W [M, E] (fp32, or the bf16 ``hi`` half for 2-byte/elem reads), idx
    [N, P] -> [N, E] fp32 bag sums.  Lane-pads E and pads N to a multiple of
    ``bags_per_block`` (padding bags read row 0 and are sliced off)."""
    interpret = _default_interpret() if interpret is None else interpret
    Wp, E = _pad_dim(W, 1, 128)
    idxp, N = _pad_dim(idx, 0, min(bags_per_block, idx.shape[0]))
    out = embedding_bag_pallas(Wp, idxp, bags_per_block=bags_per_block,
                               interpret=interpret)
    return out[:N, :E]


# ---------------------------------------------------------------------------
# Fused sparse row-optimizer update — ONE entry point for every registered
# RowOptimizer (repro/optim/row.py).  Nothing outside repro.optim.row
# should call these: model/pipeline code goes through
# ``RowOptimizer.apply_sparse``, which owns the store layout and the
# reference-path parity contracts.
#
# There is NO per-optimizer dispatch here (enforced by a source-scan
# test): the optimizer instance carries its row math as the ``step``
# registration hook, and this module only owns the generic plumbing —
# the sorted-stream prep and the interpret switch.  ``register()`` alone
# adds an optimizer.
# ---------------------------------------------------------------------------


def _coerce_opt(opt):
    """Accept a RowOptimizer instance or a registry name (legacy callers/
    benches pass strings)."""
    if isinstance(opt, str):
        from repro.optim import row as row_optim
        return row_optim.get(opt)
    return opt


def _dispatch_row_kernel(opt, store, srows, sbags, smsk, swgt, dY, lr,
                         seed, interpret, shards=1):
    """Run the fused kernel with the optimizer's row step on its slabs.
    Any slab width goes to the chip as it is: the kernel's blocks span
    the full width, so no lane padding (and no slab copy) is needed."""
    keys = opt.slab_keys
    out = sparse_row_update_pallas(partial(opt.step, opt),
                                   [store[k] for k in keys], srows, sbags,
                                   smsk, swgt, dY, lr, seed, interpret,
                                   shards=shards)
    return dict(zip(keys, out))


@partial(jax.jit, static_argnames=("opt", "pooling", "interpret"))
def fused_row_update(opt, store, tgt, dY, lr, *, seed=0, valid=None,
                     weights=None, pooling: int = 1,
                     interpret: bool | None = None):
    """Fused sparse-backward + row-optimizer update (paper Alg. 3 + C5,
    generalized to pluggable per-row state).

    ``opt``: a registered RowOptimizer (or its registry name) — its
    ``step`` hook is the row math the kernel runs.  ``store``: the
    optimizer's EmbeddingStore dict — weight slab(s) (``hi``/``lo``
    split-bf16 or ``w`` fp32) plus zero or more per-row state slabs
    (``mom``/``acc``, fp32 or compressed bf16-hi), all row-aligned on the
    same shard layout.  ``tgt`` [L] int32 local row per flat lookup
    (out-of-range or ``valid == False`` entries contribute nothing).
    ``dY`` [L // pooling, E]: bag cotangents — flat lookup ``i`` reads
    ``dY[i // pooling]``; the [L, E] per-lookup gradient expansion of the
    reference path is never materialized.  ``weights`` [L] optional
    per-lookup bag weights scaling each cotangent row before the in-VMEM
    duplicate pre-reduction.  ``seed``: int32 per-step stochastic-rounding
    seed (ignored by deterministic optimizers).  Returns the updated
    store: only the tile-aligned row groups holding touched rows (weights
    AND state) are read/written, in place via aliasing.  The unweighted
    ``split_sgd`` result is bit-identical to the jitted
    ``apply_rows_split_sgd`` reference."""
    opt = _coerce_opt(opt)
    interpret = _default_interpret() if interpret is None else interpret
    M = store[opt.weight_keys[0]].shape[0]
    srows, sbags, smsk, swgt = sort_lookups(tgt, valid, M, pooling, weights)
    return _dispatch_row_kernel(opt, store, srows, sbags, smsk, swgt, dY,
                                lr, seed, interpret)


@partial(jax.jit, static_argnames=("opt", "interpret", "shards"))
def fused_row_update_presorted(opt, store, srows, sbags, smsk, swgt, dY,
                               lr, *, seed=0,
                               interpret: bool | None = None,
                               shards: int = 1):
    """:func:`fused_row_update` with the sort done ON THE HOST: the caller
    supplies the ``(sorted_rows, sorted_bags, sorted_msk, sorted_wgt)``
    arrays of ``sort_lookups`` (produced per shard by
    ``repro.data.pipeline.presort_batch`` while the previous step runs on
    device) and the per-step XLA argsort disappears from the hot path.
    Bit-identical to the sorting entry point — a stable sort's permutation
    is unique, so host and device sorts agree exactly.  ``shards``: the
    shards the stream's lookups spread over (``sparse_row_update_pallas``),
    1 for a stream of this shard's own lookups."""
    opt = _coerce_opt(opt)
    interpret = _default_interpret() if interpret is None else interpret
    return _dispatch_row_kernel(opt, store, srows, sbags, smsk, swgt, dY,
                                lr, seed, interpret, shards)


@partial(jax.jit, static_argnames=("interpret",))
def interaction_self_dot(z, interpret: bool | None = None):
    """z [B, F, E] -> [B, F, F] fp32 batched self-dot."""
    interpret = _default_interpret() if interpret is None else interpret
    zp, F = _pad_dim(z, 1, 8)       # sublane-align the F dim
    zp, E = _pad_dim(zp, 2, 128)
    bb = 8
    zb, B = _pad_dim(zp, 0, bb)
    out = interaction_pallas(zb, bb=bb, interpret=interpret)
    return out[:B, :F, :F]


@partial(jax.jit, static_argnames=("interpret",))
def split_sgd_update(hi, lo, g, lr, interpret: bool | None = None):
    """Flat split-SGD step on arbitrary-shaped leaves (raveled + padded)."""
    interpret = _default_interpret() if interpret is None else interpret
    shape = hi.shape
    n = hi.size
    hif, _ = _pad_dim(hi.reshape(-1), 0, 1024)
    lof, _ = _pad_dim(lo.reshape(-1), 0, 1024)
    gf, _ = _pad_dim(g.reshape(-1), 0, 1024)
    block = min(8 * 128 * 64, hif.shape[0])
    nh, nl = split_sgd_pallas(hif, lof, gf, lr, block=block,
                              interpret=interpret)
    return nh[:n].reshape(shape), nl[:n].reshape(shape)


@partial(jax.jit, static_argnames=("causal", "softcap", "window", "scale",
                                   "interpret"))
def flash_attention(q, k, v, causal: bool = True, softcap: float = 0.0,
                    window: int = 0, scale: float | None = None,
                    interpret: bool | None = None):
    """q [B,H,Lq,D], k/v [B,Hkv,Lk,D] (H % Hkv == 0) -> [B,H,Lq,D].

    GQA is handled by repeating KV heads (grid-level index aliasing keeps
    HBM traffic at the Hkv level on TPU; in interpret mode it is a copy)."""
    interpret = _default_interpret() if interpret is None else interpret
    B, H, Lq, D = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(B * H, Lq, D)
    kf = k.reshape(B * H, Lk, D)
    vf = v.reshape(B * H, Lk, D)
    bq = min(128, max(8, Lq))
    bk = min(128, Lk)
    qf, _ = _pad_dim(qf, 1, bq)
    kf, _ = _pad_dim(kf, 1, bk)
    vf, _ = _pad_dim(vf, 1, bk)
    # NOTE: padded queries are garbage rows sliced off below; padded keys are
    # masked inside the kernel via lk_real.
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, softcap=softcap, window=window,
        scale=scale, bq=bq, bk=bk, lq_real=Lq, lk_real=Lk,
        interpret=interpret)
    return out[:, :Lq].reshape(B, H, Lq, D)
