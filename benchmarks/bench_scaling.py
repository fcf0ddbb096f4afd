"""Strong/weak scaling experiment (paper Fig. 9-14 analogue, on compiled
artifacts).

For rank counts 2..32 we lower the paper-faithful TABLE-mode DLRM on a 1D
mesh (one rank = one paper socket) in a SUBPROCESS (the device-count flag
must precede jax init) and record per-rank compute FLOPs and collective
bytes.  Expectations from the paper:

  strong scaling: alltoall bytes/rank shrink ~1/R (Eq. 2 at fixed GN);
                  allreduce bytes/rank stay CONSTANT (Eq. 1) -> efficiency
                  decays exactly the way Fig. 9 shows.
  weak scaling:   alltoall bytes/rank stay ~constant (volume grows with R).

``--microbatches M`` lowers the staged microbatch pipeline
(repro/core/pipeline.py) instead of the monolithic step — the collective
bytes must match the M=1 step (same exchange volume, chunked), which is
the pipeline's lowering regression check.  ``--smoke`` runs a reduced,
cache-less sweep (CI); results also land in ``BENCH_scaling.json``.
"""

import argparse
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
RESULTS = ROOT / "results"
SRC = ROOT / "src"

SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ranks}"
import json, jax
from repro.configs.dlrm_paper import dlrm_small
from repro.core import hybrid as H
from repro.core.dlrm import as_hybrid_def, make_train_step
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import parse_collective_bytes
import dataclasses

mesh = make_mesh((1, {ranks}), ("data", "model"))
cfg = dataclasses.replace(dlrm_small(mode="table", batch={batch}),
                          microbatches={mb})
step, shardings, bspecs, layout = make_train_step(cfg, mesh)
sstructs, _, _, _ = H.state_struct(as_hybrid_def(cfg), mesh)
bstructs, _ = H.batch_struct(as_hybrid_def(cfg), mesh, layout)
# no jax.set_mesh here: the shard_mapped step carries its mesh explicitly
compiled = step.lower(sstructs, bstructs).compile()
ca = compiled.cost_analysis() or {{}}
coll = parse_collective_bytes(compiled.as_text())
print(json.dumps(dict(ranks={ranks}, batch={batch}, microbatches={mb},
                      flops=float(ca.get("flops", 0)),
                      coll=coll["bytes_by_op"])))
"""


def run_point(ranks: int, batch: int, microbatches: int = 1) -> dict:
    # forced host devices: the child stays on the CPU, off any chip
    # the parent process may hold
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    code = textwrap.dedent(SUB.format(ranks=ranks, batch=batch,
                                      mb=microbatches))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def rows(ranks=(2, 4, 8), gn=8192, ln=1024, cache=True, microbatches=1,
         json_path: Path | None = None, tag: str = ""):
    mb_tag = (f"_mb{microbatches}" if microbatches != 1 else "") + tag
    out_path = RESULTS / f"scaling{mb_tag}.json"
    if cache and out_path.exists():
        data = json.loads(out_path.read_text())
    else:
        data = {"strong": [run_point(r, gn, microbatches) for r in ranks],
                "weak": [run_point(r, ln * r, microbatches) for r in ranks]}
        out_path.parent.mkdir(exist_ok=True)
        out_path.write_text(json.dumps(data, indent=2))
    if json_path is not None:
        json_path.write_text(json.dumps(
            {"microbatches": microbatches, "gn": gn, "ln": ln, **data},
            indent=2))
    out = []
    for kind in ("strong", "weak"):
        for rec in data[kind]:
            a2a = rec["coll"].get("all-to-all", 0) / 2**20
            ar = (rec["coll"].get("all-reduce", 0)
                  + rec["coll"].get("reduce-scatter", 0)
                  + rec["coll"].get("all-gather", 0)) / 2**20
            out.append((f"scaling_{kind}_{rec['ranks']}r{mb_tag}"
                        f"_a2a_MBperdev", a2a, f"GN={rec['batch']}"))
            out.append((f"scaling_{kind}_{rec['ranks']}r{mb_tag}"
                        f"_dense_MBperdev", ar,
                        "Eq.1 term (const under strong scaling)"))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced cache-less sweep (CI): 2 rank points, "
                         "small batches")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="lower the staged pipeline at this M")
    ap.add_argument("--json", default=str(ROOT / "BENCH_scaling.json"))
    args = ap.parse_args(argv)
    kw = dict(microbatches=args.microbatches, json_path=Path(args.json))
    if args.smoke:
        # own cache filename so the reduced sweep never shadows the full
        # sweep's results/scaling.json
        kw.update(ranks=(2, 4), gn=256, ln=64, cache=False, tag="_smoke")
    for name, val, derived in rows(**kw):
        print(f"{name},{val:.3f},{derived}")


if __name__ == "__main__":
    main()
