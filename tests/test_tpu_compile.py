"""Compiles for a described TPU v5e chip (topology ``v5e:2x2``, no chip
attached): the TPU compiler refuses here what it would refuse on the chip
— block shapes off the tiling, too much VMEM, a program that does not fit
the chip's memory — at no chip time.

* every registered RowOptimizer's fused sparse update, at dlrm-small
  widths (one chip's 8 x 1M rows, E=64, 8192 x 8 x 50 lookups), and the
  entry that sorts the lookups on the device: the kernel is a
  ``tpu_custom_call``, it updates the store in place, and it copies no
  slab;
* one whole dlrm-small train step per placement mode, with the kernel
  compiled (not interpreted), fitting one chip's 16 GiB, and reading
  every slab in place;
* the slab orientation the kernel assumes off the chip against the
  compiler's own default layouts, and the kernel's cache key against the
  checkout path.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time.  Nothing here runs a
program; a compile that passes is not a chip run.
"""

import base64
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.optim import row

ROWS, E, POOLING, BATCH, TABLES = 1_000_000, 64, 50, 8192, 8
M = ROWS * TABLES                      # one chip holds every table
L = BATCH * TABLES * POOLING           # flat lookups per step
HBM_BYTES = 16 * 2**30                 # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _assert_in_place(compiled, store):
    """The kernel is there, every slab aliases its output, and no slab is
    copied, padded or relaid: the temporaries stay below one slab."""
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    sizes = [np.prod(v.shape) * v.dtype.itemsize for v in store.values()]
    assert ma.alias_size_in_bytes == sum(sizes)
    assert ma.temp_size_in_bytes < min(sizes)


def _slab_relayouts(text, slabs) -> list[str]:
    """"dtype opcode" of every compiled-HLO instruction that produces a
    whole slab (either orientation) other than by a parameter, a bitcast
    or a tuple element: each is a copy of the slab."""
    shapes = {tuple(v.shape) for v in slabs} | {
        tuple(v.shape[::-1]) for v in slabs}
    return sorted(
        f"{dt} {op}" for dt, d0, d1, op in re.findall(
            r"= (\w+)\[(\d+),(\d+)\]\{[^}]*\}\S* ([\w-]+)\(", text)
        if (int(d0), int(d1)) in shapes
        and op not in ("parameter", "bitcast", "get-tuple-element"))


def _kernel_modules(text) -> list[bytes]:
    """The serialized Mosaic modules of the kernels in lowered HLO text."""
    return [base64.b64decode(b) for b in
            re.findall(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)", text)]


def test_rows_on_lanes_models_the_compiler_layout(topo):
    """Off the chip the kernel's slab orientation comes from a model of
    XLA:TPU's default layout; on the chip it is that layout.  Both agree
    at every slab shape here, from 17 rows up."""
    from repro.kernels.embedding_update import rows_on_lanes
    dev = topo.devices[0]
    for m in (17, 100, 127, 128, 129, 1000, 4096, 5000, ROWS, ROWS + 8, M,
              M + 8):
        for w in (1, 2, 8, 16, 17, 32, 64, 96, 100, 127, 128, 129, 192,
                  256, 384):
            for dt in (jnp.float32, jnp.int32, jnp.bfloat16, jnp.uint16):
                assert rows_on_lanes((m, w), dt) == rows_on_lanes(
                    (m, w), dt, dev), (m, w, dt)
    # dlrm-small's narrow slabs are column-major, a 128-wide one is not
    assert rows_on_lanes((M, E), jnp.bfloat16, dev)
    assert rows_on_lanes((M, 1), jnp.float32, dev)
    assert not rows_on_lanes((M, 128), jnp.bfloat16, dev)


def test_kernel_cache_key_holds_no_checkout_path(one_chip):
    """The kernel's serialized module carries source locations, and the
    compile cache hashes it whole.  After ``compile_cache.enable()`` the
    locations are relative to the checkout, so one commit finds its
    cache entries from any checkout path."""
    from repro.kernels import ops
    from repro.launch import compile_cache
    opt = row.get("split_sgd")
    n, w, lookups = 1024, E, 512

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    store = {k: sds(v.shape, v.dtype)
             for k, v in opt.store_struct(n, w).items()}

    def modules():
        text = jax.jit(
            lambda st, rows, bags, msk, wgt, dY, lr:
            ops.fused_row_update_presorted(opt, st, rows, bags, msk, wgt,
                                           dY, lr, interpret=False)).lower(
            store, sds((lookups,), jnp.int32), sds((lookups,), jnp.int32),
            sds((lookups,), jnp.int32), sds((lookups,), jnp.float32),
            sds((lookups, w), jnp.float32), sds((), jnp.float32)).as_text()
        return _kernel_modules(text)

    prev = (jax.config.jax_hlo_source_file_canonicalization_regex,
            jax.config.jax_compilation_cache_dir)
    try:
        before = modules()
        compile_cache.enable()
        after = modules()
    finally:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          prev[0])
        jax.config.update("jax_compilation_cache_dir", prev[1])
    root = f"{compile_cache.ROOT}/".encode()
    assert before and all(root in m for m in before)
    assert after and not any(root in m for m in after)
    assert all(b"src/repro/kernels/embedding_update.py" in m for m in after)


@pytest.mark.parametrize("name", row.names())
def test_fused_update_compiles_at_dlrm_small_widths(one_chip, name):
    """Every registered optimizer's kernel, on the pre-sorted stream."""
    from repro.kernels import ops
    opt = row.get(name)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    store = {k: sds(v.shape, v.dtype)
             for k, v in opt.store_struct(M, E).items()}
    update = jax.jit(
        lambda st, rows, bags, msk, wgt, dY, lr:
        ops.fused_row_update_presorted(opt, st, rows, bags, msk, wgt, dY,
                                       lr, seed=3, interpret=False),
        donate_argnums=(0,))
    compiled = update.lower(
        store, sds((L,), jnp.int32), sds((L,), jnp.int32),
        sds((L,), jnp.int32), sds((L,), jnp.float32),
        sds((L // POOLING, E), jnp.float32), sds((), jnp.float32)).compile()
    _assert_in_place(compiled, store)


def test_sorting_entry_compiles_at_dlrm_small_widths(one_chip):
    """The entry that sorts the lookups on the device (table mode)."""
    from repro.kernels import ops

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    store = {k: sds(v.shape, v.dtype) for k, v in
             row.get("split_sgd").store_struct(M, E).items()}
    update = jax.jit(
        lambda st, tgt, dY, lr: ops.fused_row_update(
            "split_sgd", st, tgt, dY, lr, pooling=POOLING, interpret=False),
        donate_argnums=(0,))
    compiled = update.lower(store, sds((L,), jnp.int32),
                            sds((L // POOLING, E), jnp.float32),
                            sds((), jnp.float32)).compile()
    _assert_in_place(compiled, store)


@pytest.mark.parametrize("mode", ["row", "table"])
def test_dlrm_small_train_step_compiles_on_one_chip(topo, monkeypatch, mode):
    from jax.sharding import AxisType, Mesh, NamedSharding
    from repro.configs.dlrm_paper import dlrm_small
    from repro.core import dlrm as D
    from repro.kernels import ops
    # the backend here is the CPU: steer the kernels to their compiled
    # form, as on the chip
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(dlrm_small(mode=mode), fused_update=True)
    assert (cfg.table_rows, cfg.emb_dim, cfg.pooling, cfg.batch) == (
        (ROWS,) * TABLES, E, POOLING, BATCH)
    step, shardings, bspecs, layout = D.make_train_step(cfg, mesh)
    structs, _, _, _ = D.state_struct(cfg, mesh)
    bstructs, _ = D.batch_struct(cfg, mesh, layout)

    def placed(s, sh):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    state = jax.tree.map(placed, structs, shardings)
    batch = jax.tree.map(
        lambda s, spec: placed(s, NamedSharding(mesh, spec)), bstructs,
        bspecs, is_leaf=lambda x: isinstance(x, P))
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _hbm_bytes(compiled) < HBM_BYTES
    # the sparse update reads and writes every slab in place.  The one
    # whole-slab copy in the step is the forward bag gather's: XLA:TPU
    # gathers rows from a row-major slab, so it lays the column-major
    # ``hi`` out row-major once per step
    assert _slab_relayouts(text, structs["emb"].values()) == ["bf16 copy"]
