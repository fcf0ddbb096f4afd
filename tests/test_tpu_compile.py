"""Compiles for a described TPU v5e chip (topology ``v5e:2x2``, no chip
attached): the TPU compiler refuses here what it would refuse on the chip
— block shapes off the tiling, too much VMEM, a program that does not fit
the chip's memory — at no chip time.

* every registered RowOptimizer's fused sparse update, at dlrm-small
  widths (one chip's 8 x 1M rows, E=64, 8192 x 8 x 50 lookups), and the
  entry that sorts the lookups on the device: the kernel is a
  ``tpu_custom_call`` whose grid steps each walk a block of lookups, it
  updates the store in place, and it copies no slab;
* one whole dlrm-small train step per placement mode, with the kernel
  compiled (not interpreted), fitting one chip's 16 GiB, and reading
  every slab in place; its op_names carry the pipeline's stage scopes,
  its kernel is named, and the benchmark's stage rules agree with its
  call-stack rules;
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
import sys
from pathlib import Path

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


def _kernel_grids(jaxpr) -> list[tuple]:
    """The grid of every Pallas call in a (closed) jaxpr, nested ones
    included."""
    from jax.extend import core
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, (core.Jaxpr, core.ClosedJaxpr)):
                    grids += _kernel_grids(sub)
    return grids


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
    """Every registered optimizer's kernel, on the pre-sorted stream.  A
    grid step walks a block of ``BLOCK`` lookups: the grid of a call of
    ``CHUNK`` lookups is ``CHUNK / BLOCK``, not one step per lookup."""
    from repro.kernels import embedding_update as EU
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
    args = (store, sds((L,), jnp.int32), sds((L,), jnp.int32),
            sds((L,), jnp.int32), sds((L,), jnp.float32),
            sds((L // POOLING, E), jnp.float32), sds((), jnp.float32))
    assert _kernel_grids(update.trace(*args).jaxpr) == [
        (EU.CHUNK // EU.BLOCK,)]
    assert EU.CHUNK // EU.BLOCK < EU.CHUNK
    _assert_in_place(update.lower(*args).compile(), store)


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


def _described_step(topo, cfg):
    """``(compiled, state structs, jaxpr)`` of ``cfg``'s train step with
    the kernel compiled (not interpreted), for one described chip."""
    from jax.sharding import AxisType, Mesh, NamedSharding
    from repro.core import dlrm as D
    from repro.core import hybrid as H
    from repro.kernels import ops
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)

    def placed(s, sh):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    cfg = dataclasses.replace(cfg, fused_update=True)
    # the backend here is the CPU: steer the kernels to their compiled
    # form, as on the chip
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_default_interpret", lambda: False)
        step, shardings, bspecs, layout = D.make_train_step(cfg, mesh)
        mdef = D.as_hybrid_def(cfg)
        structs, _, _, _ = H.state_struct(mdef, mesh)
        bstructs, _ = H.batch_struct(mdef, mesh, layout)
        state = jax.tree.map(placed, structs, shardings)
        batch = jax.tree.map(
            lambda s, spec: placed(s, NamedSharding(mesh, spec)),
            bstructs, bspecs, is_leaf=lambda x: isinstance(x, P))
        traced = step.trace(state, batch)
        return traced.lower().compile(), structs, traced.jaxpr


@pytest.fixture(scope="module")
def dlrm_small_step(topo):
    """``mode -> (compiled, state structs)``: one dlrm-small train step per
    placement mode compiled for the described chip, once per module."""
    from repro.configs.dlrm_paper import dlrm_small
    made = {}

    def get(mode):
        if mode not in made:
            cfg = dlrm_small(mode=mode)
            assert (cfg.table_rows, cfg.emb_dim, cfg.pooling, cfg.batch) == (
                (ROWS,) * TABLES, E, POOLING, BATCH)
            made[mode] = _described_step(topo, cfg)[:2]
        return made[mode]

    return get


@pytest.mark.parametrize("mode", ["row", "table"])
def test_dlrm_small_train_step_compiles_on_one_chip(dlrm_small_step, mode):
    compiled, structs = dlrm_small_step(mode)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _hbm_bytes(compiled) < HBM_BYTES
    # the sparse update reads and writes every slab in place.  The one
    # whole-slab copy in the step is the forward bag gather's: XLA:TPU
    # gathers rows from a row-major slab, so it lays the column-major
    # ``hi`` out row-major once per step
    assert _slab_relayouts(text, structs["emb"].values()) == ["bf16 copy"]


_BENCH = Path(__file__).resolve().parents[1] / "bench"


def _bench_trace():
    """The benchmark's HLO reader and layer rules (``bench/harness``)."""
    if str(_BENCH) not in sys.path:
        sys.path.insert(0, str(_BENCH))
    from harness import trace
    return trace


# the stage each layer of the benchmark's older call-stack rules
# (bench/layers/dlrm/) belongs to; ``dense`` is either dense stage
_STAGE_OF_LAYER = {"emb_fwd": "embedding_fwd", "dense": "dense_*",
                   "lookup_sort": "lookup_sort",
                   "sparse_update": "sparse_update",
                   "sparse_update_other": "sparse_update",
                   "dY_exchange": "dY_exchange",
                   "index_exchange": "index_exchange", "other": "other"}


def _named_exceptions(mode):
    """``why -> test(new stage, old layer, instruction)``: the instructions
    on which the two rule sets are known to differ, in either direction."""
    def glue(i):
        # made by JAX's own code around the step's functions (the dense
        # update's slices and converts, the forward weights' convert,
        # reshapes between stages): the call stack starts at whoever
        # lowered the step, ``<module>`` in a benchmark run, so it names
        # no function of the program
        return not i.stack or i.stack[0] in ("<module>", "_described_step")

    ex = {
        "JAX glue, scoped but with no program frame": lambda n, o, i: (
            o == "other" and n != "other" and glue(i)
            and f"/{n}/" in i.op_name),
        # a layout copy goes to its consumer under either rule set: the
        # copies that feed the glue above follow it
        "layout copy of such glue": lambda n, o, i: (
            o == "other" and n != "other" and glue(i)
            and i.opcode in ("copy", "copy-start", "copy-done")),
    }
    if mode == "table":
        # the forward's and the cotangent's slot permutations are one
        # ``jnp.take`` of one shape: JAX lowers it once, so both inlined
        # copies carry the forward's call stack, while each keeps its own
        # scope in op_name
        ex["slot permutation lowered once"] = lambda n, o, i: (
            (n, o) == ("dY_exchange", "emb_fwd")
            and ("/dY_exchange/" in i.op_name or not i.op_name))
        # an index fusion XLA made for the forward's gathers, whose
        # op_name lost the scope ("gather")
        ex["gather index fusion without scope"] = lambda n, o, i: (
            (n, o) == ("other", "emb_fwd") and "/" not in i.op_name)
    return ex


@pytest.mark.parametrize("mode", ["row", "table"])
def test_dlrm_small_step_carries_stage_scopes_and_kernel_name(
        dlrm_small_step, mode):
    """The compiled step names its stages in ``op_name`` and its kernel
    ``sparse_row_update``; the benchmark's stage rules and its older
    call-stack rules put every instruction the device runs in the same
    stage, in both directions, but for the named exceptions."""
    T = _bench_trace()
    text = dlrm_small_step(mode)[0].as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    # on one chip the index exchange issues no op in either mode, nor the
    # dY exchange in row mode (its all-gather spans one chip and its bf16
    # round trip fuses into the sparse update)
    scopes = {"embedding_fwd", "dense_fwd_bwd", "sparse_update",
              "dense_update", "lookup_sort"} | (
        {"dY_exchange"} if mode == "table" else set())
    for scope in scopes:
        assert any(f"/{scope}/" in o for o in op_names), scope
    kernels = re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                         r'"tpu_custom_call"', text)
    assert kernels and all(k.startswith("sparse_row_update.")
                           for k in kernels), kernels
    assert any("/sparse_row_update/" in o for o in op_names)

    instrs = T.parse_hlo(text)
    old = T.classify(instrs, T.load_layers(_BENCH / "layers" / "dlrm"))
    new = T.classify(instrs, T.load_layers(_BENCH / "stages" / "dlrm"))
    # the kernel is the sparse update under both rule sets: a rename of
    # the kernel or of the function that calls it fails here
    for k in kernels:
        assert (old[k], new[k]) == ("sparse_update", "sparse_update"), k
    entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
    runs = {entry} | {i.body for i in instrs.values() if i.body}
    exceptions = _named_exceptions(mode)
    left, seen = [], set()
    for name, ins in instrs.items():
        if ins.comp not in runs or ins.opcode in (
                "parameter", "constant", "get-tuple-element", "tuple",
                "bitcast"):
            continue
        stage = _STAGE_OF_LAYER[old[name]]
        if new[name] == stage or (stage == "dense_*"
                                  and new[name].startswith("dense_")):
            continue
        why = [w for w, ok in exceptions.items()
               if ok(new[name], old[name], ins)]
        seen.update(why)
        if not why:
            left.append((new[name], old[name], name, ins.op_name,
                         ins.stack[:3]))
    assert not left, left
    # every exception listed still occurs: one that no longer does goes
    assert seen == set(exceptions), set(exceptions) - seen


@pytest.fixture(scope="module")
def dlrm_large_share_step(topo):
    """One chip's share of dlrm-large over 64 chips (``--share-of 64``),
    row mode: its train step compiled for the described chip."""
    from repro.configs.dlrm_paper import dlrm_large
    cfg = dlrm_large(mode="row", share_of=64)
    assert (cfg.table_rows, cfg.emb_dim, cfg.pooling, cfg.batch) == (
        (93_750,) * 64, 256, 100, 256)
    return _described_step(topo, cfg)


def _kernel_scratch(jaxpr) -> list[list[tuple]]:
    """The VMEM scratch buffers (shape, dtype) of every Pallas call in a
    (closed) jaxpr, nested ones included."""
    from jax.extend import core
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append([(tuple(a.shape), str(a.dtype)) for a in
                        eqn.params["grid_mapping"].scratch_avals
                        if str(a.dtype) in ("bfloat16", "uint16",
                                            "float32")])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, (core.Jaxpr, core.ClosedJaxpr)):
                    out += _kernel_scratch(sub)
    return out


def test_dlrm_large_share_train_step_compiles_on_one_chip(
        dlrm_large_share_step):
    """The share fits the chip, the kernel is there and reads and writes
    the row-major E=256 slabs in place (no whole-slab copy: the forward
    gathers from them as stored), in row groups of G=128 (its 1.64M
    lookups touch 98.7% of the 16-row tile groups, so wide groups copy
    1.3% more rows for an eighth of the group steps) with a ``pre`` of
    Wp=384 lanes, in 50 calls of 32 grid steps."""
    from repro.kernels import embedding_update as EU
    compiled, structs, jaxpr = dlrm_large_share_step
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _hbm_bytes(compiled) < HBM_BYTES
    slabs = structs["emb"]
    assert [(v.shape[1], v.dtype) for v in slabs.values()] == [
        (256, jnp.bfloat16), (256, jnp.uint16)]
    assert _slab_relayouts(text, slabs.values()) == []
    assert _kernel_scratch(jaxpr) == [[((3, 128, 256), "bfloat16"),
                                       ((3, 128, 256), "uint16"),
                                       ((128, 384), "float32")]]
    assert _kernel_grids(jaxpr) == [(EU.CHUNK // EU.BLOCK,)]


def test_dlrm_large_share_step_carries_stage_scopes(dlrm_large_share_step):
    """The stage scopes and the kernel's name, which the benchmark reads,
    name dlrm-large's ops too."""
    text = dlrm_large_share_step[0].as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("embedding_fwd", "dense_fwd_bwd", "sparse_update",
                  "dense_update", "lookup_sort"):
        assert any(f"/{scope}/" in o for o in op_names), scope
    kernels = re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                         r'"tpu_custom_call"', text)
    assert kernels and all(k.startswith("sparse_row_update.")
                           for k in kernels), kernels


@pytest.mark.parametrize("step", ["dlrm-small-row", "dlrm-small-table",
                                  "dlrm-large-share"])
def test_lookup_sort_is_one_sort_and_no_gather(request, step):
    """The lookup sort takes the sorted keys (and weights) from the stable
    sort itself: the benchmark's ``lookup_sort`` layer holds exactly one
    ``sort``, and no gather, fused bodies included, carries its scope."""
    T = _bench_trace()
    if step == "dlrm-large-share":
        compiled = request.getfixturevalue("dlrm_large_share_step")[0]
    else:
        mode = step.rsplit("-", 1)[1]
        compiled = request.getfixturevalue("dlrm_small_step")(mode)[0]
    instrs = T.parse_hlo(compiled.as_text())
    layer = T.classify(instrs, T.load_layers(_BENCH / "layers" / "dlrm"))
    sorts = [n for n, i in instrs.items()
             if i.opcode == "sort" and layer[n] == "lookup_sort"]
    assert len(sorts) == 1, sorts
    gathers = [(n, i.op_name) for n, i in instrs.items()
               if i.opcode == "gather" and "/lookup_sort/" in i.op_name]
    assert not gathers, gathers
