"""Plain reference of DLRM (Naumov et al. 2019) as the Kalamkar et al.
SC'20 Split-SGD training runs it, in straightforward ``jax.numpy`` at
float32 with ``precision=HIGHEST`` matrix products.  It imports nothing of
the program under test.

Model: ``S`` embedding tables (``rows_t x E``), each bag the sum of its
``P`` rows; a bottom MLP on the dense features (ReLU after every layer);
the dot interaction of the bottom output with the ``S`` bags (the strict
lower triangle of ``Z Z^T`` over the ``S + 1`` vectors, after the bottom
output itself); a top MLP (ReLU between layers, none after the last);
binary cross-entropy with logits, mean over the batch.

Initialisation follows the DLRM reference code: table ``t`` uniform in
``+-1/sqrt(rows_t)`` (here the nearest power of two, so that the values
are made from random bits on the device by exact steps, the same bits in
every compiled context); MLP weights normal with std
``sqrt(2 / (in + out))``, biases normal with std ``sqrt(1 / out)``, made
on the host with numpy.

Training: SGD at rate ``lr`` on every parameter.  Split-SGD keeps exact
float32 master weights and runs the forward and backward passes on their
upper 16 bits (a bfloat16 number, the truncation of the master weight);
the reference does the same (``fwd_weights``) and computes everything else
in float32.  ``master="bfloat16"`` is the control: master weights held in
bfloat16 (round to nearest after every update), the precision one step
below the configuration's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def sizes_of(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    S = len(cfg["table_rows"])
    E = cfg["emb_dim"]
    F = S + 1
    return dict(table_rows=tuple(cfg["table_rows"]), E=E, P=cfg["pooling"],
                bottom=[cfg["num_dense"], *cfg["bottom"]],
                top=[E + F * (F - 1) // 2, *cfg["top"], 1])


# --------------------------------------------------------------- params --

def _mlp(g, sizes):
    ws, bs = [], []
    for cin, cout in zip(sizes[:-1], sizes[1:]):
        ws.append((g.standard_normal((cin, cout))
                   * np.sqrt(2.0 / (cin + cout))).astype(np.float32))
        bs.append((g.standard_normal(cout) * np.sqrt(1.0 / cout))
                  .astype(np.float32))
    return {"w": ws, "b": bs}


def init_table(key, t: int, rows: int, E: int):
    """Table ``t``: uniform in ``[-s, s)``, ``s`` the power of two nearest
    ``1/sqrt(rows)``, in 2**23 even levels.  Every step is exact (a float
    in [1, 2) from 23 random bits, minus 1.5, times a power of two), so any
    compiled context, fused or not, gives the same bits."""
    bits = jax.random.bits(jax.random.fold_in(key, t), (rows, E), jnp.uint32)
    one_two = jax.lax.bitcast_convert_type((bits >> 9) | jnp.uint32(0x3F800000),
                                           jnp.float32)
    scale = np.float32(2.0 ** (1 - round(np.log2(np.sqrt(rows)))))
    return (one_two - np.float32(1.5)) * scale


def init_dense(seed: int, sz: dict) -> dict:
    """The MLPs' float32 weights, on the host (numpy), from the seed."""
    g = np.random.default_rng([int(seed) % (1 << 64), 7])
    return {"bot": _mlp(g, sz["bottom"]), "top": _mlp(g, sz["top"])}


# -------------------------------------------------------------- forward --

def fwd_weights(w, master: str = "float32"):
    """What the forward pass reads: the bfloat16 upper half of a float32
    master weight (truncation), or the bfloat16 master itself."""
    if master == "bfloat16":
        return w.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(w, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _mlp_fwd(p, x, last_relu):
    n = len(p["w"])
    for i, (w, b) in enumerate(zip(p["w"], p["b"])):
        x = jnp.dot(x, w, precision=HI) + b
        if last_relu or i < n - 1:
            x = jax.nn.relu(x)
    return x


def logits(dense, bags, dense_x):
    """``dense``: forward weights; ``bags`` [B, S, E]; ``dense_x`` [B, D]."""
    bot = _mlp_fwd(dense["bot"], dense_x, True)
    Z = jnp.concatenate([bot[:, None, :], bags], axis=1)
    F = Z.shape[1]
    ZZ = jnp.einsum("bfe,bge->bfg", Z, Z, precision=HI)
    li, lj = np.tril_indices(F, -1)
    z = jnp.concatenate([bot, ZZ[:, li, lj]], axis=1)
    return _mlp_fwd(dense["top"], z, False)[:, 0]


def bce(x, y):
    return jnp.mean(jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x))))


@jax.jit
def _bag(w, idx):
    """[rows, E] forward weights, [B, P] ids -> [B, E] bag sums."""
    return jnp.sum(jnp.take(w, idx, axis=0), axis=1)


@functools.partial(jax.jit, static_argnames=("master",))
def _dense_step(dense, bags, dense_x, labels, lr, master):
    # the gradient is taken at the forward weights and applied to the
    # master weights, as Split-SGD does
    fw = jax.tree.map(lambda w: fwd_weights(w, master), dense)
    loss, (gd, ge) = jax.value_and_grad(
        lambda d, e: bce(logits(d, e, dense_x), labels),
        argnums=(0, 1))(fw, bags)
    new = jax.tree.map(lambda w, g: _sgd(w, g, lr, master), dense, gd)
    return loss, new, ge


def _sgd(w, g, lr, master):
    out = w.astype(jnp.float32) - lr * g
    return out.astype(jnp.bfloat16) if master == "bfloat16" else out


@functools.partial(jax.jit, static_argnames=("master",), donate_argnums=(0,))
def _table_step(w, idx, d_bag, lr, master):
    """Dense-gradient SGD on one table: every lookup adds its bag's
    cotangent to its row's gradient."""
    P = idx.shape[1]
    g = jnp.zeros(w.shape, jnp.float32).at[idx.reshape(-1)].add(
        jnp.repeat(d_bag, P, axis=0))
    return _sgd(w, g, lr, master)


def train(key, dense0: dict, sz: dict, batches: list, lr: float,
          master="float32", half_batch: bool = False):
    """Run ``len(batches)`` SGD steps from the seeded initial state.

    ``batches``: host dicts ``idx`` [B, S, P] int32 (table ``s`` in slot
    ``s``), ``dense_x`` [B, D], ``labels`` [B]; ``dense0`` the MLPs'
    initial weights (:func:`init_dense`).  Returns the per-step
    losses; after the first and after the last step, the norm of every
    parameter's change from the initial state, leaf by leaf (the tables
    ``table0..``, then the MLP leaves); and after the last step the number
    of rows of each table that differ from the initial state.  ``half_batch`` plants a fault:
    each step sees only the first half of its batch.  Tables are stepped
    one at a time, so at most one table's gradient is live."""
    E = sz["E"]
    dt = jnp.bfloat16 if master == "bfloat16" else jnp.float32
    tables = [init_table(key, t, r, E).astype(dt)
              for t, r in enumerate(sz["table_rows"])]
    dense0 = jax.tree.map(jnp.asarray, dense0)
    dense = jax.tree.map(lambda w: w.astype(dt), dense0)
    losses, after = [], []
    for k, b in enumerate(batches):
        if half_batch:
            b = {n: v[:v.shape[0] // 2] for n, v in b.items()}
        idx = jnp.asarray(b["idx"])
        bags = jnp.stack([_bag(fwd_weights(w, master), idx[:, t])
                          for t, w in enumerate(tables)], axis=1)
        loss, dense, d_bags = _dense_step(
            dense, bags, jnp.asarray(b["dense_x"], jnp.float32),
            jnp.asarray(b["labels"], jnp.float32), jnp.float32(lr), master)
        tables = [_table_step(w, idx[:, t], d_bags[:, t], jnp.float32(lr),
                              master) for t, w in enumerate(tables)]
        losses.append(float(loss))
        if k == 0 or k == len(batches) - 1:
            after.append(change_norms(key, sz, tables, dense, dense0))
    return losses, after[0], after[-1], changed_rows(key, sz, tables)


def change_norms(key, sz, tables, dense, dense0) -> np.ndarray:
    out = [float(_diff_norm(w, init_table(key, t, r, sz["E"])))
           for t, (w, r) in enumerate(zip(tables, sz["table_rows"]))]
    out += [float(_diff_norm(a, b)) for a, b in
            zip(jax.tree.leaves(dense), jax.tree.leaves(dense0))]
    return np.asarray(out, np.float64)


def changed_rows(key, sz, tables) -> np.ndarray:
    return np.asarray([int(_rows_differ(w, init_table(key, t, r, sz["E"])))
                       for t, (w, r) in enumerate(zip(tables, sz["table_rows"]))])


@jax.jit
def _rows_differ(a, b):
    return jnp.sum(jnp.any(a.astype(jnp.float32) != b, axis=1))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))


def leaf_names(sz: dict) -> list[str]:
    names = [f"table{t}" for t in range(len(sz["table_rows"]))]
    tree = {"bot": {"b": list(range(len(sz["bottom"]) - 1)),
                    "w": list(range(len(sz["bottom"]) - 1))},
            "top": {"b": list(range(len(sz["top"]) - 1)),
                    "w": list(range(len(sz["top"]) - 1))}}
    for path, i in jax.tree_util.tree_leaves_with_path(tree):
        names.append(f"{path[0].key}.{path[1].key}{i}")
    return names
