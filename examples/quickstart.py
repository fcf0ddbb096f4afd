"""Quickstart: hybrid-parallel DLRM training end-to-end in ~30 seconds.

Run with a simulated 8-device mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py

What this shows:
  * the paper's hybrid parallelism (model-parallel unified embedding +
    data-parallel MLPs, reduce-scatter layout switch) on a (2, 4) mesh,
  * Split-SGD-BF16 (C5) as the optimizer for both sparse and dense params,
  * checkpoint -> crash -> restore.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.core import dlrm as D
from repro.core import hybrid as H
from repro.data.synthetic import dlrm_stream
from repro.launch.mesh import make_mesh
from repro.train import TrainLoop, TrainLoopConfig


def main():
    n = len(jax.devices())
    mesh = make_mesh((max(1, n // 4), min(4, n)), ("data", "model"))
    print(f"devices={n}, mesh={dict(mesh.shape)}")

    cfg = D.DLRMConfig(
        name="quickstart", num_dense=64, bottom=(128, 32), top=(128, 64),
        table_rows=(40_000, 10_000, 5_000, 2_000, 1_000, 500, 200, 100),
        emb_dim=32, pooling=8, batch=512, lr=0.05)
    state, layout = D.init_state(jax.random.PRNGKey(0), cfg, mesh)
    step, shardings, bspecs, _ = D.make_train_step(cfg, mesh)
    stream = ({k: jnp.asarray(v) for k, v in b.items()}
              for b in dlrm_stream(0, cfg, alpha=0.6))

    with tempfile.TemporaryDirectory() as ckdir:
        loop = TrainLoop(TrainLoopConfig(steps=60, ckpt_dir=ckdir,
                                         ckpt_every=20, log_every=20),
                         step, state, stream, state_shardings=shardings)
        state = loop.run()
        print(f"loss: {loop.losses[0]:.4f} -> {loop.losses[-1]:.4f}")

        # simulate a restart: a fresh loop restores from the checkpoint
        loop2 = TrainLoop(TrainLoopConfig(steps=80, ckpt_dir=ckdir,
                                          ckpt_every=20, log_every=20),
                          step, state, stream, state_shardings=shardings)
        assert loop2.start_step >= 60, loop2.start_step
        loop2.run()
        print(f"restored at step {loop2.start_step}, continued to 80 OK")

    ev, _, _, _ = H.make_score_step(D.as_hybrid_def(cfg), mesh)
    batch = next(stream)
    scores = ev(state, batch)
    print(f"eval scores: shape {scores.shape}, "
          f"mean {float(scores.mean()):.4f}")


if __name__ == "__main__":
    main()
