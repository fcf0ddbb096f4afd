"""The paper's own three DLRM configs (Tab. I), as first-class archs.

Each gets TWO train cells: row mode (beyond-paper production placement) and
table mode (the paper's table-wise hybrid parallelism) — the A/B the perf
log builds on.  Batch sizes are the paper's strong-scaling global
minibatches (GN).  ``share_of=N`` gives one chip's share of the config
deployed row-wise over N chips instead (:func:`chip_share`).
"""

import dataclasses

from repro.configs.base import ArchDef, Cell, CellBuild, register
from repro.core import hybrid as H
from repro.core.dlrm import DLRMConfig, as_hybrid_def, make_train_step
from repro.configs.fm_arch import CRITEO_TB


def chip_share(cfg: DLRMConfig, n: int = 1,
               batch: int | None = None) -> DLRMConfig:
    """One chip's share of ``cfg`` deployed row-wise over ``n`` chips:
    every table's rows divided evenly over the chips, this chip holding
    one slice of each (a sliced table is a smaller table: its lookups are
    drawn from the slice), and the dense half data-parallel at the global
    minibatch over ``n``.  Every width stays as published; the exchange
    between the chips is absent.  ``batch`` overrides the batch the share
    trains.  ``n == 1`` is the whole config."""
    if n < 1 or any(r % n for r in cfg.table_rows) or cfg.batch % n:
        raise ValueError(f"{cfg.name}: {n} chips do not divide every "
                         f"table's rows {sorted(set(cfg.table_rows))} and "
                         f"the minibatch {cfg.batch} evenly")
    return dataclasses.replace(
        cfg, table_rows=tuple(r // n for r in cfg.table_rows),
        batch=batch or cfg.batch // n, deployment_chips=n)


def dlrm_small(mode="row", batch=None, share_of=1):
    return chip_share(DLRMConfig(
        name="dlrm-small", num_dense=512, bottom=(512, 512, 64),
        top=(1024, 1024, 1024, 1024), table_rows=(1_000_000,) * 8,
        emb_dim=64, pooling=50, batch=8192, emb_mode=mode), share_of, batch)


def dlrm_large(mode="row", batch=None, share_of=1):
    return chip_share(DLRMConfig(
        name="dlrm-large", num_dense=2048,
        bottom=(2048,) * 7 + (256,), top=(4096,) * 16,
        table_rows=(6_000_000,) * 64, emb_dim=256, pooling=100,
        batch=16384, emb_mode=mode), share_of, batch)


def dlrm_mlperf(mode="row", batch=None, share_of=1):
    return chip_share(DLRMConfig(
        name="dlrm-mlperf", num_dense=13, bottom=(512, 256, 128),
        top=(512, 512, 256), table_rows=CRITEO_TB, emb_dim=128,
        pooling=1, batch=16384, emb_mode=mode), share_of, batch)


def _archdef(name, cfg_fn):
    cells = [Cell("train", "train"), Cell("train_tablewise", "train")]

    def build(shape: str, mesh, batch: int | None = None,
              n_layers: int | None = None,
              cost_mode: bool = False) -> CellBuild:
        mode = "table" if shape == "train_tablewise" else "row"
        cfg = cfg_fn(mode=mode, batch=batch)
        fn, shardings, bspecs, layout = make_train_step(cfg, mesh)
        mdef = as_hybrid_def(cfg)
        sstructs, _, _, _ = H.state_struct(mdef, mesh)
        bstructs, _ = H.batch_struct(mdef, mesh, layout)
        meta = dict(arch=name, shape=shape, kind="train", family="dlrm",
                    batch=cfg.batch, slots=len(cfg.table_rows),
                    pooling=cfg.pooling, emb_dim=cfg.emb_dim,
                    emb_rows=cfg.spec.total_rows,
                    bottom=cfg.bottom_sizes, top=cfg.top_sizes,
                    deployment_chips=cfg.deployment_chips,
                    scan_unit=1, scan_outside=0, n_layers=1)
        return CellBuild(fn, (sstructs, bstructs), meta)

    return register(ArchDef(name, "dlrm", cells, build,
                            notes="paper Tab. I config"))


ARCH_SMALL = _archdef("dlrm-small", dlrm_small)
ARCH_LARGE = _archdef("dlrm-large", dlrm_large)
ARCH_MLPERF = _archdef("dlrm-mlperf", dlrm_mlperf)
