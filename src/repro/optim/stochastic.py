"""Seeded stochastic rounding to bf16-hi storage (compressed optimizer
state).

The Split-SGD trick (paper Sect. VII) keeps fp32 EXACT by bit-partitioning
each weight into a bf16 ``hi`` half and a uint16 ``lo`` carry half.  The
per-row optimizer-state slabs (momentum rows, Adagrad accumulators) do not
need exactness — they need UNBIASEDNESS: storing only the bf16 ``hi`` half
and rounding stochastically halves the state-slab bytes per touched row
while keeping the expected value of the stored state equal to the fp32
value (truncation would bias every row toward zero; round-to-nearest would
bias long accumulations toward the last rounding boundary).

Determinism contract (the reason this module exists instead of a PRNG
call): the dither is a COUNTER-BASED pure function of
``(seed, row id, lane)`` — no sampler state, no traversal order.  The
reference scan, the fused Pallas kernel (device-sorted) and the
host-pre-sorted path therefore add the exact same 16-bit dither to the
exact same fp32 value for every touched row, and the three paths stay
BITWISE identical for a given per-step seed (tests/test_stochastic.py).
``pltpu.prng_random_bits`` could not give this: its stream depends on the
core's sampler state and has no jnp twin for the reference path.

The hash is the 32-bit ``lowbias32`` finalizer (a Murmur3-style avalanche:
xor-shift / multiply rounds) — integer ops only, so the same expression
runs inside the Pallas kernel body (interpret AND compiled) and in plain
``jnp`` reference code with identical results.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# lowbias32 multipliers (Ellis: exact-bias-measured avalanche constants)
_MIX1 = 0x7FEB352D
_MIX2 = 0x846CA68B
# Weyl / stream constants decorrelating the (seed, row, lane) counters
_GOLD = 0x9E3779B1
_ROWC = 0x85EBCA6B
# wire-payload stream constant (PCG multiplier): keeps the collective
# wire dither of repro/dist/exchange.py off the row-state dither streams
# above even when a tag numerically equals a row id
_WIREC = 0xB5297A4D


def mix32(x: jax.Array) -> jax.Array:
    """lowbias32 avalanche on uint32 (xorshift-multiply finalizer)."""
    x = jnp.asarray(x, jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(_MIX1)
    x = (x ^ (x >> 15)) * jnp.uint32(_MIX2)
    return x ^ (x >> 16)


def sr_noise(seed: jax.Array, rows: jax.Array, width: int) -> jax.Array:
    """The dither stream: uint32 noise of shape ``rows.shape + (width,)``.

    Pure function of ``(seed, rows[...], lane)``; ``rows`` are (local) row
    ids of any integer shape/dtype.  Every path that rounds the same row
    under the same seed sees the same bits.
    """
    return sr_noise_col(seed, jnp.asarray(rows)[..., None], width)


def sr_noise_col(seed: jax.Array, rows: jax.Array, width: int) -> jax.Array:
    """:func:`sr_noise` for row ids held as a trailing unit column
    ``rows`` [..., 1]: noise [..., width].  This is the 2-D form a kernel
    body holds (one row id per sublane); the lane counter is a 2-D+
    ``broadcasted_iota`` (TPU-legal in kernel bodies)."""
    seed_u = jnp.asarray(seed).astype(jnp.uint32)
    rows_u = jnp.asarray(rows).astype(jnp.uint32)
    base = mix32(seed_u * jnp.uint32(_GOLD) ^ rows_u * jnp.uint32(_ROWC))
    lane = jax.lax.broadcasted_iota(jnp.uint32, rows_u.shape[:-1] + (width,),
                                    rows_u.ndim - 1)
    return mix32(base ^ (lane * jnp.uint32(_GOLD) + jnp.uint32(1)))


def sr_round_bf16(x: jax.Array, noise_u32: jax.Array) -> jax.Array:
    """fp32 -> bf16 stochastic round: add a uniform 16-bit dither to the
    discarded mantissa half, truncate to the bf16-aliasing hi half.

    The two representable bf16 neighbours of ``x`` are hit with
    probabilities proportional to their distance, so ``E[sr(x)] == x``
    (exactly, over the uniform dither) — the property that keeps long
    state accumulations drift-free where truncation shrinks them ~0.2%
    per rewrite.  The uint32 add carries through the exponent boundary
    (IEEE754 bit patterns are magnitude-ordered), so rounding across a
    binade is handled for free; the sign bit is untouched for any finite
    ``x``.  ``bf16 -> fp32`` decode (``.astype``) is exact, so
    decode(round(x)) differs from ``x`` by at most one bf16 ulp.
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    dithered = bits + (noise_u32 & jnp.uint32(0xFFFF))
    return jax.lax.bitcast_convert_type(
        (dithered >> 16).astype(jnp.uint16), jnp.bfloat16)


def wire_noise(seed: jax.Array, tag: jax.Array, shape: tuple) -> jax.Array:
    """Dither stream for one WIRE payload (a collective operand of
    repro/dist/exchange.py): uint32 noise of ``shape``, a pure function of
    ``(seed, tag, flat element index)``.

    Same determinism contract as :func:`sr_noise` — counter-based, no
    sampler state, no traversal order — so a run resumed from a
    checkpointed ``sr`` counter replays the exact wire dither.  ``tag``
    (see ``exchange.wire_tag``) positions the payload within the step
    (stream base, microbatch/bucket, sender rank); the ``_WIREC``
    multiplier keeps these streams disjoint from the row-state streams
    even when a tag numerically equals a row id.  The flat-iota element
    counter is plain XLA (this path never runs inside a Pallas body, so
    the 1-D iota restriction of kernel code does not apply)."""
    seed_u = jnp.asarray(seed).astype(jnp.uint32)
    tag_u = jnp.asarray(tag).astype(jnp.uint32)
    base = mix32(seed_u * jnp.uint32(_GOLD)
                 ^ (tag_u * jnp.uint32(_WIREC) + jnp.uint32(1)))
    n = 1
    for d in shape:
        n *= int(d)
    ctr = jax.lax.iota(jnp.uint32, n).reshape(shape)
    return mix32(base ^ (ctr * jnp.uint32(_ROWC) + jnp.uint32(_GOLD)))


def sr_round_bf16_wire(x: jax.Array, seed: jax.Array, tag) -> jax.Array:
    """fp32 -> bf16 stochastic round of a wire payload under the seeded
    counter dither.  Exactness guarantee (the degeneration contract of
    the compressed collectives): any value already representable in bf16
    — zeros included — passes through BITWISE, because its discarded
    mantissa half is zero and the <= 0xFFFF dither cannot carry into the
    kept half."""
    x = jnp.asarray(x, jnp.float32)
    return sr_round_bf16(x, wire_noise(seed, tag, x.shape))
