"""MLP stack (paper contribution C2's consumer).

Forward runs in bf16 with fp32 accumulation; XLA fuses the activation
(ReLU) into the GEMM epilogue.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def init_mlp(key: jax.Array, sizes: Sequence[int], dtype=jnp.float32) -> dict:
    """``sizes = [in, h1, ..., out]`` -> {'w': [...], 'b': [...]}."""
    ws, bs = [], []
    for i, (cin, cout) in enumerate(zip(sizes[:-1], sizes[1:])):
        key, k = jax.random.split(key)
        std = (2.0 / (cin + cout)) ** 0.5
        ws.append((jax.random.normal(k, (cin, cout), jnp.float32) * std
                   ).astype(dtype))
        bs.append(jnp.zeros((cout,), dtype))
    return {"w": ws, "b": bs}


def mlp_forward(params: dict, x: jax.Array,
                final_activation: bool = False) -> jax.Array:
    """Apply the stack; ReLU between layers, optionally on the last one."""
    n = len(params["w"])
    h = x
    for i, (w, b) in enumerate(zip(params["w"], params["b"])):
        act = final_activation or i < n - 1
        y = jnp.dot(h.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32) + b.astype(jnp.float32)
        h = jax.nn.relu(y) if act else y
        h = h.astype(jnp.bfloat16) if i < n - 1 else h
    return h  # final layer fp32


def mlp_sizes(params: dict) -> list[int]:
    return [params["w"][0].shape[0]] + [w.shape[1] for w in params["w"]]


def allreduce_bytes(sizes: Sequence[int], bytes_per_elem: int = 4) -> int:
    """Paper Eq. 1: SZ_allreduce = sum_l f_i*f_o + f_o (per rank,
    rank-count-independent — the strong-scaling wall)."""
    total = 0
    for cin, cout in zip(sizes[:-1], sizes[1:]):
        total += cin * cout + cout
    return total * bytes_per_elem
