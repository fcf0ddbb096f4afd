"""The program under test for the DLRM configurations: its train step,
built the way ``python -m repro.launch.train --arch <arch> --paper
--emb-mode <placement>`` builds it, fed with weights and batches that the
benchmark makes from the seed.

Everything here goes through the program's own entry points
(``launch.train.dlrm_config``, ``core.dlrm.make_train_step``, the
``RowOptimizer`` store and the data-parallel state arrays); the benchmark
only supplies weights and inputs, and reads the state back to compare it
with the reference.  The layers that a traced run splits the step into
are data: ``bench/layers/dlrm/``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from harness import ids as ids_mod


class DLRMSystem:
    """One configuration of the program on the local devices."""

    def __init__(self, cfg: dict, ref, batch: int | None = None):
        from repro.core import dlrm as D
        from repro.core import hybrid as H
        from repro.launch import train as T
        argv = list(cfg["program_args"])
        if batch is not None:
            argv += ["--batch", str(batch)]
        self.pcfg = T.dlrm_config(T.parse_args(argv))
        self.file = cfg
        self.ref = ref
        self.sz = ref.sizes_of(cfg)
        self.batch = self.pcfg.batch
        want = dict(table_rows=tuple(cfg["table_rows"]),
                    emb_dim=cfg["emb_dim"], pooling=cfg["pooling"],
                    num_dense=cfg["num_dense"], bottom=tuple(cfg["bottom"]),
                    top=tuple(cfg["top"]), lr=cfg["lr"])
        got = {k: getattr(self.pcfg, k) for k in want}
        if batch is None:
            want["batch"], got["batch"] = cfg["batch"], self.pcfg.batch
        if got != want:
            raise SystemExit(f"the program's {' '.join(argv)} is not the "
                             f"configuration file: {got} != {want}")
        self.mesh = T.local_mesh()
        self.mdef = D.as_hybrid_def(self.pcfg)
        self.layout = H.make_layout(self.mdef, self.mesh)
        self._D, self._H = D, H

    # ------------------------------------------------------------ state --
    def table_blocks(self) -> list[tuple[int, int]]:
        """(first layout row, rows) of every table in the program's store;
        each table must sit in one contiguous block."""
        from repro.core import sharded_embedding as se
        _, g2l = se.layout_gid_maps(self.layout)
        offs = self.layout.spec.row_offsets
        out = []
        for t, rows in enumerate(self.sz["table_rows"]):
            pos = g2l[int(offs[t]):int(offs[t]) + rows]
            if not np.array_equal(pos, pos[0] + np.arange(rows)):
                raise SystemExit(f"table {t} is not one block of the store")
            out.append((int(pos[0]), rows))
        return out

    def make_state_fn(self):
        """A jitted ``(key, dense0) -> train state`` that builds the
        program's state from the reference's seeded initial weights (the
        tables made on the device from ``key``, the MLPs ``dense0`` from
        :func:`init_dense`) in one call."""
        from repro.dist.exchange import resolve_exchange
        from repro.optim import data_parallel as dp
        from repro.optim import row as row_optim
        structs, _, shardings, layout = self._H.state_struct(self.mdef,
                                                            self.mesh)
        if set(structs) != {"emb", "dense"} or set(structs["dense"]) != {
                "hi", "lo", "err"}:
            raise SystemExit(f"unexpected train-state layout: "
                             f"{jax.tree.structure(structs)}")
        opt = row_optim.resolve(self.mdef)
        ex = resolve_exchange(self.mdef)
        ns = int(np.prod(list(self.mesh.shape.values())))
        blocks = sorted(enumerate(self.table_blocks()), key=lambda b: b[1][0])
        E, total = self.sz["E"], layout.total_rows
        ref, sz = self.ref, self.sz

        def make(key, dense0):
            parts, at = [], 0
            for t, (start, rows) in blocks:
                if start > at:
                    parts.append(jnp.zeros((start - at, E), jnp.float32))
                parts.append(ref.init_table(key, t, rows, E))
                at = start + rows
            if total > at:
                parts.append(jnp.zeros((total - at, E), jnp.float32))
            emb = opt.init_store(jnp.concatenate(parts, axis=0))
            arr = dp.dp_global_arrays(dense0, ns,
                                      compress=ex.needs_err,
                                      num_buckets=ex.num_buckets)
            return {"emb": emb, "dense": {"hi": arr["hi"], "lo": arr["lo"],
                                          "err": arr["err"]}}

        d0 = ref.init_dense(0, sz)
        if jax.tree.structure(jax.eval_shape(make, jax.random.PRNGKey(0),
                                             d0)) != jax.tree.structure(structs):
            raise SystemExit("the benchmark's state does not match the "
                             "program's train state")
        return jax.jit(make, out_shardings=shardings)

    def change_norms_fn(self):
        """A jitted ``(state, key, dense0) -> ([leaves], [tables])``: the
        norm of every parameter's change since the seeded initial state,
        in the reference's leaf order (tables, then the MLP leaves), and
        the number of rows of each table that differ from it."""
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
            W = opt.materialize_fp32(state["emb"])
            out, rows = [], []
            for t, (s, r) in enumerate(blocks):
                w0 = ref.init_table(key, t, r, sz["E"])
                out.append(jnp.sqrt(jnp.sum(jnp.square(W[s:s + r] - w0))))
                rows.append(jnp.sum(jnp.any(W[s:s + r] != w0, axis=1)))
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

    # ---------------------------------------------------------- batches --
    def batch_fields(self, n: int | None = None) -> dict:
        """The program's batch struct for ``n`` rows (default: the train
        batch): field name -> ShapeDtypeStruct."""
        structs, _ = self._H.batch_struct(self.mdef, self.mesh, self.layout,
                                          n, include_presort=False)
        return structs

    def table_ids(self, traffic: dict, seed: int) -> list:
        """One id generator per table, from the mix's ``ids`` law."""
        g = ids_mod.rng(seed, 1)
        return [ids_mod.TableIds(rows, traffic["ids"], g,
                                 traffic.get("zipf_exponent", 0.0))
                for rows in self.sz["table_rows"]]

    def host_batch(self, g: np.random.Generator, tables: list, n: int
                   ) -> dict:
        """One batch in the reference's form: ``idx`` [n, S, P] int32
        (table ``s`` in slot ``s``), ``dense_x`` [n, D] float32 holding
        bfloat16 values, ``labels`` [n] float32."""
        P = self.sz["P"]
        idx = np.stack([t.draw(g, (n, P)) for t in tables], axis=1)
        x = g.standard_normal((n, self.file["num_dense"]), np.float32)
        x = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        y = g.integers(0, 2, n).astype(np.float32)
        return {"idx": idx, "dense_x": x, "labels": y}

    def program_batch(self, b: dict) -> dict:
        """A reference batch in the program's layout and dtypes."""
        from repro.data.synthetic import to_padded_slots
        fields = self.batch_fields(b["idx"].shape[0])
        idx = b["idx"]
        if self.layout.mode == "table" and self.pcfg.idx_input == "replicated":
            idx = to_padded_slots(self.layout, idx)
        out = {"idx": idx, "dense_x": b["dense_x"], "labels": b["labels"]}
        if set(out) != set(fields):
            raise SystemExit(f"program batch fields {sorted(fields)}")
        return {k: np.asarray(v, _np_dtype(fields[k].dtype))
                for k, v in out.items()}

    # ------------------------------------------------------------ train --
    def train_step(self):
        step, shardings, bspecs, layout = self._D.make_train_step(self.pcfg,
                                                                  self.mesh)
        from repro.dist import sharding
        return step, shardings, sharding.named(self.mesh, bspecs)


def _np_dtype(dt):
    return ml_dtypes.bfloat16 if jnp.dtype(dt) == jnp.bfloat16 else np.dtype(dt)


# ---------------------------------------------------------------- counts --

def _mlp_flops(sizes, B):
    return sum(2.0 * B * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def flops_per_sample(sz: dict, train: bool = True) -> float:
    """Useful FLOPs per sample, as ``benchmarks/model_flops.dlrm_flops``
    counts them: the MLPs and the dot interaction (three passes when
    training), the bag sums (and, training, the row updates)."""
    S, E = len(sz["table_rows"]), sz["E"]
    emb = 2.0 * S * sz["P"] * E
    dense = (_mlp_flops(sz["bottom"], 1) + _mlp_flops(sz["top"], 1)
             + 2.0 * (S + 1) ** 2 * E)
    return 3.0 * dense + 2.0 * emb if train else dense + emb


def dense_param_count(sz: dict) -> int:
    n = 0
    for sizes in (sz["bottom"], sz["top"]):
        n += sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    return n


def update_bytes(idx: np.ndarray, E: int, slab_bytes: int = 4) -> dict:
    """Algorithmic bytes of one step's sparse update on batch ``idx``
    [B, S, P], counted from the batch itself: every distinct row of every
    table read and written once over all slabs (``slab_bytes`` per
    element: bf16 hi + uint16 lo), the bags' float32 cotangents read once,
    and the sorted stream (row, bag, mask, weight: 16 B per lookup) read
    once."""
    B, S, P = idx.shape
    distinct = sum(int(np.unique(idx[:, s]).size) for s in range(S))
    return {"distinct_rows": distinct,
            "bytes": (2 * distinct * E * slab_bytes + B * S * E * 4
                      + 16 * B * S * P)}


def step_bytes(idx: np.ndarray, sz: dict) -> float:
    """Algorithmic bytes of one train step: the forward gather (one bf16
    row per lookup), the sparse update (:func:`update_bytes`), and the
    dense weights read in the forward and backward passes (bf16) and
    read and written by the update (float32 master as two halves)."""
    B, S, P = idx.shape
    fwd = B * S * P * sz["E"] * 2
    dense = dense_param_count(sz) * (2 + 2 + 8)
    return float(fwd + update_bytes(idx, sz["E"])["bytes"] + dense)


SYSTEM = DLRMSystem
