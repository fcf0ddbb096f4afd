"""The DLRM program of ``dlrm.py``, for configurations whose tables fill
most of a chip: the seeded state is written into the program's store one
table at a time, and read back one table at a time.

``dlrm.py`` builds the whole store from one float32 copy of every table
and reads it back through another.  At a chip's share of dlrm-large the
store is 6.1 GB (a bfloat16 and a 16-bit half), and each float32 copy
6.1 GB more: the build would need 20 GB of the chip's 16.  Here no copy
larger than one table exists beside the store.  The state is the same,
bit for bit, and the norms read back are the same; everything else
(the step, the batches, the counts) is ``dlrm.py``'s, and so are the
layer and stage rules (``bench/layers/dlrm_by_table/`` and
``bench/stages/dlrm_by_table/`` hold the same files as those of
``dlrm``).
"""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from harness import common

_base = common.load_module(Path(__file__).with_name("dlrm.py"),
                           "bench_systems_dlrm")


class DLRMByTable(_base.DLRMSystem):
    """``dlrm.py``'s system with the state built and read per table."""

    def _dense_shapes(self) -> dict:
        """The MLPs' float32 leaves as ``init_dense`` lays them out, as
        shapes (no weights drawn)."""
        def mlp(sizes):
            pairs = list(zip(sizes[:-1], sizes[1:]))
            return {"w": [jax.ShapeDtypeStruct((a, b), jnp.float32)
                          for a, b in pairs],
                    "b": [jax.ShapeDtypeStruct((b,), jnp.float32)
                          for _, b in pairs]}
        return {"bot": mlp(self.sz["bottom"]), "top": mlp(self.sz["top"])}

    def make_state_fn(self):
        """``(key, dense0) -> train state``, the state ``dlrm.py``'s
        ``make_state_fn`` builds, written into zeroed slabs one table at a
        time by a donated update."""
        from repro.dist.exchange import resolve_exchange
        from repro.optim import data_parallel as dp
        from repro.optim import row as row_optim
        structs, _, shardings, _ = self._H.state_struct(self.mdef, self.mesh)
        if set(structs) != {"emb", "dense"} or set(structs["dense"]) != {
                "hi", "lo", "err"}:
            raise SystemExit(f"unexpected train-state layout: "
                             f"{jax.tree.structure(structs)}")
        opt = row_optim.resolve(self.mdef)
        ex = resolve_exchange(self.mdef)
        ns = int(np.prod(list(self.mesh.shape.values())))
        E, ref = self.sz["E"], self.ref
        blocks = self.table_blocks()
        emb_sh = shardings["emb"]

        zeros = jax.jit(lambda: {k: jnp.zeros(s.shape, s.dtype)
                                 for k, s in structs["emb"].items()},
                        out_shardings=emb_sh)

        @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,),
                           out_shardings=emb_sh)
        def put(emb, key, t, rows, start):
            part = opt.init_store(ref.init_table(key, t, rows, E))
            return {k: jax.lax.dynamic_update_slice_in_dim(v, part[k],
                                                           start, 0)
                    for k, v in emb.items()}

        @functools.partial(jax.jit, out_shardings=shardings["dense"])
        def dense(dense0):
            arr = dp.dp_global_arrays(dense0, ns, compress=ex.needs_err,
                                      num_buckets=ex.num_buckets)
            return {"hi": arr["hi"], "lo": arr["lo"], "err": arr["err"]}

        if jax.tree.structure(jax.eval_shape(dense, self._dense_shapes())) \
                != jax.tree.structure(structs["dense"]):
            raise SystemExit("the benchmark's state does not match the "
                             "program's train state")

        def make(key, dense0):
            emb = zeros()
            for t, (start, rows) in enumerate(blocks):
                emb = put(emb, key, t, rows, start)
            return {"emb": emb, "dense": dense(dense0)}

        return make

    def change_norms_fn(self):
        """``dlrm.py``'s ``change_norms_fn``, each table's float32 weights
        made from its own rows of the store."""
        from repro.dist.exchange import resolve_exchange
        from repro.optim import data_parallel as dp
        from repro.optim import row as row_optim
        from repro.optim.split_sgd import combine_split
        opt = row_optim.resolve(self.mdef)
        nb = resolve_exchange(self.mdef).num_buckets
        blocks = self.table_blocks()
        ref, sz = self.ref, self.sz
        ns = int(np.prod(list(self.mesh.shape.values())))

        def norms(state, key, dense0):
            out, rows = [], []
            for t, (s, r) in enumerate(blocks):
                W = opt.materialize_fp32({k: v[s:s + r] for k, v in
                                          state["emb"].items()})
                w0 = ref.init_table(key, t, r, sz["E"])
                out.append(jnp.sqrt(jnp.sum(jnp.square(W - w0))))
                rows.append(jnp.sum(jnp.any(W != w0, axis=1)))
            hi = state["dense"]["hi"]
            flat_hi = jnp.concatenate([x.reshape(-1) for x in
                                       jax.tree.leaves(hi)])
            lo = state["dense"]["lo"]
            n = flat_hi.shape[0]
            # the dense ``lo`` half is bucket-major within each shard
            lo_nat = lo.reshape(ns, nb, -1).transpose(1, 0, 2).reshape(-1)
            w32 = dp.unravel_like(combine_split(flat_hi, lo_nat[:n]), hi)
            out += [jnp.sqrt(jnp.sum(jnp.square(a - b)))
                    for a, b in zip(jax.tree.leaves(w32),
                                    jax.tree.leaves(dense0))]
            return jnp.stack(out), jnp.stack(rows)

        return jax.jit(norms)


flops_per_sample = _base.flops_per_sample
dense_param_count = _base.dense_param_count
update_bytes = _base.update_bytes
step_bytes = _base.step_bytes

SYSTEM = DLRMByTable
