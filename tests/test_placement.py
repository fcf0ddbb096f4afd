"""Placement-policy unit tests for the perf-log features (EXPERIMENTS.md
section Perf): FSDP/TP/pure-DP param specs, decode cache sharding choice,
windowed-KV slicing equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.kernels import ref
from repro.models.attention import chunked_attention


def _leaf_spec(tree_specs, *keys):
    node = tree_specs
    for k in keys:
        node = node[k]
    return node


def _example_tree():
    return {
        "embed": jnp.zeros((64, 8)),
        "layers": {
            "ln1": jnp.zeros((4, 8)),
            "attn": {"wq": jnp.zeros((4, 8, 16)),
                     "wo": jnp.zeros((4, 16, 8))},
            "mlp": {"wg": jnp.zeros((4, 8, 32)),
                    "wd": jnp.zeros((4, 32, 8))},
            "moe": {"router": jnp.zeros((4, 8, 4)),
                    "wg": jnp.zeros((4, 4, 8, 16)),
                    "wd": jnp.zeros((4, 4, 16, 8))},
        },
        "final_norm": jnp.zeros((8,)),
    }


def test_fsdp_tp_specs():
    specs = shd.lm_param_specs(_example_tree(), fsdp=True, tp=True)
    assert _leaf_spec(specs, "embed") == P("model", "data")
    assert _leaf_spec(specs, "layers", "attn", "wq") == \
        P(None, "data", "model")
    assert _leaf_spec(specs, "layers", "attn", "wo") == \
        P(None, "model", "data")
    assert _leaf_spec(specs, "layers", "moe", "wg") == \
        P(None, "data", None, "model")
    assert _leaf_spec(specs, "layers", "ln1") == P(None, None)


def test_tp_only_specs():
    specs = shd.lm_param_specs(_example_tree(), fsdp=False, tp=True)
    assert _leaf_spec(specs, "layers", "attn", "wq") == \
        P(None, None, "model")
    # EP kept for MoE regardless
    assert _leaf_spec(specs, "layers", "moe", "wg") == \
        P(None, "data", None, "model")


def test_pure_dp_zero3_specs():
    specs = shd.lm_param_specs(_example_tree(), fsdp=True, tp=False)
    # no 'model'-only sharding anywhere outside moe; FSDP spans the mesh
    assert _leaf_spec(specs, "layers", "attn", "wq") == \
        P(None, ("data", "model"), None)
    assert _leaf_spec(specs, "embed") == P(None, ("data", "model"))


def test_decode_cache_sharding_choice():
    """HC2: heads when divisible, else head-dim, never seq for batch_ok."""
    from repro.models import lm_steps
    from repro.models.transformer import TransformerConfig
    # AbstractMesh: sharding decisions are testable without 8 real devices
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    # Hkv=4 % 4 == 0 -> heads sharded
    cfg = TransformerConfig("a", n_layers=2, d_model=32, n_heads=4,
                            n_kv_heads=4, d_head=8, d_ff=64, vocab=64)
    _, spec, _ = lm_steps.cache_structs(cfg, mesh, B=8, Lmax=16)
    assert spec["k"] == P(None, ("data",), "model", None, None)
    # Hkv=2 % 4 != 0, d_head=8 % 4 == 0 -> head-dim sharded
    cfg2 = TransformerConfig("b", n_layers=2, d_model=32, n_heads=4,
                             n_kv_heads=2, d_head=8, d_ff=64, vocab=64)
    _, spec2, _ = lm_steps.cache_structs(cfg2, mesh, B=8, Lmax=16)
    assert spec2["k"] == P(None, ("data",), None, None, "model")
    # B=1 (long-context): sequence sharding over the full mesh
    _, spec3, _ = lm_steps.cache_structs(cfg2, mesh, B=1, Lmax=64)
    assert spec3["k"] == P(None, None, None, ("data", "model"), None)


def test_windowed_slicing_matches_full():
    """Iter. 4: the sliced local-attention path == the masked full path."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 2, 128, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 128, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 128, 16)), jnp.float32)
    # window + bq = 16+16 < Lk=128 -> sliced path active
    out = chunked_attention(q, k, v, causal=True, window=16, bq=16)
    want = ref.flash_attention(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
