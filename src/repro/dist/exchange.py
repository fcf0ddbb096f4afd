"""Typed communication/precision config for the hybrid step's collectives.

The update path has been compressed since the start (bf16 optimizer state,
Split-SGD bf16 weights) but the pipeline's two dominant collectives — the
dY exchange (``all_gather(dY)`` in row mode, ``all_to_all(dY)`` in table
mode) and the dense-gradient reduce-scatter — historically moved fp32 in
table mode.  This module owns the knob that compresses them, and the API
those knobs hang off:

:class:`ExchangeConfig`
    One frozen dataclass holding the whole comm/precision surface: the
    index-exchange lowering (``impl``), the per-collective wire dtypes,
    the dense bf16-wire error feedback and the RS+AG bucketing
    (``num_buckets``).  Models pass ``exchange=ExchangeConfig(...)``
    (``repro.core.hybrid.StepOptions``); the launcher's
    ``--exchange-dtype D`` is ``ExchangeConfig(dY_dtype=D,
    dense_dtype=D)``.

Wire formats (per collective, ``dY_dtype`` / ``dense_dtype``):

``"fp32"``
    The historical wire — bitwise identical to the pre-config step.  (In
    ROW mode the dY gather has ALWAYS been a round-to-nearest bf16
    payload, matching the bf16 ``psum_scatter`` forward; ``"fp32"`` keeps
    exactly that historical wire rather than inflating it.)
``"bf16"``
    Round-to-nearest truncation on the wire: halves the table-mode dY
    all_to_all and the dense reduce-scatter payloads.  On the dense path
    the fp32 quantization residual of each device's own contribution is
    carried to the next step (error feedback) so the update stays
    unbiased.

    The dY payloads are bitcast to uint16 around the collective so the
    compiled HLO genuinely moves 2 bytes/element (see
    ``sharded_embedding.gather_dY``).  The dense reduce-scatter is a
    REDUCTION — its wire format is the per-contribution quantization
    (each device's bucket is rounded to bf16 before the sum), which is
    the value-level contract; the carrier dtype is backend-dependent
    because jax upcasts sub-fp32 psums to fp32 accumulation, so the
    modeled RS byte saving applies to wire-native collective backends.
``"bf16_sr"``
    Seeded stochastic rounding (repro/optim/stochastic.py): the 16-bit
    dither is a counter-based pure function of ``(sr counter, payload
    tag, element index)``, so every rank computes the same bits for its
    payload and a run resumed from a checkpoint replays the EXACT wire
    dither (the replicated ``state["sr"]`` scalar is part of the
    checkpoint).  Unbiased without carrying an error slab.

Degeneration contract (tests/test_exchange.py): values that are already
representable in bf16 — zeros included, so all-zero gradients — survive
ANY wire format bitwise, because truncation of an exact value is exact
and the SR dither (<= 0xFFFF on the discarded mantissa half) cannot
carry into the kept half.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.optim import stochastic

WIRE_DTYPES = ("fp32", "bf16", "bf16_sr")
EXCHANGE_IMPLS = ("fused", "ring")
# bytes per element actually moved by the collective under each format
WIRE_ITEMSIZE = {"fp32": 4, "bf16": 2, "bf16_sr": 2}

# high-bit stream bases separating the two wire-dither tag namespaces:
# the dY exchange tags payloads by microbatch, the dense reduce-scatter
# by bucket — both additionally mix the sender's rank (wire_tag), so no
# two payloads in a step share a dither stream, and neither collides
# with the row-state dither of repro/optim/stochastic.sr_noise.
TAG_DY = 0xDE100000
TAG_DENSE = 0xD5E00000


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    """Comm/precision config of the hybrid step's collectives.

    ``impl``
        Index-exchange lowering: ``'fused'`` (one all_gather) or
        ``'ring'`` (ppermute-chunked — finer units for the latency-hiding
        scheduler; bit-identical result).
    ``dY_dtype`` / ``dense_dtype``
        Wire format of the dY exchange / the dense gradient
        reduce-scatter (see module docstring).  The all-gather of updated
        dense weights is ALWAYS bf16 (the Split-SGD hi half) and is not
        configurable here.
    ``error_feedback``
        Dense ``'bf16'`` wire only: carry each device's fp32 quantization
        residual to the next step (requires the ``err`` state slab, which
        the state builders materialize iff :attr:`needs_err`).  Ignored
        for ``'fp32'`` (nothing to feed back) and ``'bf16_sr'`` (the
        dither already unbiases the wire).
    ``num_buckets``
        RS+AG bucketing of the flat dense gradient (paper C4): bucket
        k+1's collectives overlap bucket k's shard update.
    """

    impl: str = "fused"
    dY_dtype: str = "fp32"
    dense_dtype: str = "fp32"
    error_feedback: bool = True
    num_buckets: int = 4

    def __post_init__(self):
        if self.impl not in EXCHANGE_IMPLS:
            raise ValueError(
                f"unknown ExchangeConfig.impl {self.impl!r}; expected 'fused' "
                "(one all_gather) or 'ring' (ppermute-chunked)")
        for field, v in (("dY_dtype", self.dY_dtype),
                         ("dense_dtype", self.dense_dtype)):
            if v not in WIRE_DTYPES:
                raise ValueError(f"unknown {field} {v!r}; expected one of "
                                 f"{WIRE_DTYPES}")
        if self.num_buckets < 1:
            raise ValueError(
                f"num_buckets must be >= 1, got {self.num_buckets}")

    @property
    def needs_sr(self) -> bool:
        """Whether any wire format consumes the per-step ``sr`` counter."""
        return "bf16_sr" in (self.dY_dtype, self.dense_dtype)

    @property
    def needs_err(self) -> bool:
        """Whether the dense path carries the error-feedback ``err`` slab."""
        return self.dense_dtype == "bf16" and self.error_feedback


def resolve_exchange(mdef) -> ExchangeConfig:
    """The comm/precision config of a model definition's step."""
    if not isinstance(mdef.exchange, ExchangeConfig):
        raise TypeError(f"exchange must be an ExchangeConfig, got {type(mdef.exchange).__name__}")
    return mdef.exchange


def wire_itemsize(dtype: str) -> int:
    return WIRE_ITEMSIZE[dtype]


def wire_tag(base: int, site: int, rank) -> jax.Array:
    """uint32 stream tag for one wire payload: a static stream base
    (:data:`TAG_DY` / :data:`TAG_DENSE`), a static site within the step
    (microbatch index / bucket index), and the traced sender rank, spread
    onto decorrelating Weyl constants.  Purely positional — no sampler
    state — so the tag (and therefore the dither) of every payload is
    reproducible from the checkpointed ``sr`` counter alone."""
    return (jnp.uint32(base)
            ^ jnp.uint32((site * 0x9E3779B1) & 0xFFFFFFFF)
            ^ jnp.asarray(rank).astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))


def wire_encode(x: jax.Array, dtype: str, seed=None, tag=None) -> jax.Array:
    """fp32 -> on-wire payload under ``dtype``.  ``'fp32'`` is the
    identity; ``'bf16'`` rounds to nearest; ``'bf16_sr'`` adds the seeded
    counter dither (``seed`` = the replicated per-step sr counter,
    ``tag`` from :func:`wire_tag`)."""
    if dtype == "fp32":
        return x
    if dtype == "bf16":
        return x.astype(jnp.bfloat16)
    if dtype != "bf16_sr":
        raise ValueError(f"unknown wire dtype {dtype!r}; expected one of "
                         f"{WIRE_DTYPES}")
    seed = jnp.int32(0) if seed is None else seed
    return stochastic.sr_round_bf16_wire(x, seed, tag)


def wire_decode(x: jax.Array) -> jax.Array:
    """On-wire payload -> fp32 (exact: bf16 -> fp32 widening)."""
    return x.astype(jnp.float32)
