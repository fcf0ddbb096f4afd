"""Train cells: the program's jitted train step driven through its own
``TrainLoop`` (with the mix's host prefetch) over a pool of distinct
batches made from the seed.

Set-up builds the state from the seed on the device, compiles the step,
and runs the first three steps through the same loop, on three different
batches; the norms of the parameters' change after the first and the
third step are read there.  Then the window: whole steps for ``seconds``;
the rate is the samples of the steps completed in the window over the
window's length (from the end of step 3 to the end of the first step that
completes at or after the deadline).  Afterwards the program's state is
freed and the reference runs the same three steps from the same seed.

Two faults are planted here for the harness's tests and the readings
that set the limits (``bench/tools/readings.py``), never in a timed run:
``state_unchanged``, a step that hands back its input state, and
``control``, the reference one precision lower (bfloat16 master weights)
put in the program's place in the comparison.
"""

from __future__ import annotations

import math
import shutil
import tempfile
import time
import types

import numpy as np

from harness import common, ids
from harness import trace as trace_mod

CHECK_STEPS = 3


class _Loss:
    """The step's loss as ``TrainLoop`` fetches it, with the fetch as a
    host span."""

    __slots__ = ("x",)

    def __init__(self, x):
        self.x = x

    def __float__(self):
        import jax
        with jax.profiler.TraceAnnotation("bench/loss_fetch"):
            return float(self.x)


def _listen(events: list):
    """Record every JAX event with a duration (compiles, cache reads) as
    (name, seconds, host time at its end): a compile inside the window
    shows in the run's record."""
    import jax

    def on(name, secs, **_):
        events.append((name, float(secs), time.perf_counter()))
    jax.monitoring.register_event_duration_secs_listener(on)
    return on


def run(ctx) -> types.SimpleNamespace:
    import jax
    from repro.train import TrainLoop, TrainLoopConfig

    traffic, cfg = ctx.cell.traffic, ctx.cell.config
    sysm = ctx.system
    events: list = []
    listener = _listen(events)
    phases = {"start": ctx.t0, "built": time.perf_counter()}
    key = common.seed_key(ctx.seed)
    tables = sysm.table_ids(traffic, ctx.seed)
    g = ids.rng(ctx.seed, 2)
    n_pool = int(traffic["pool"])
    if n_pool < CHECK_STEPS:
        raise SystemExit(f"a train mix needs a pool of {CHECK_STEPS} or more")
    ref_batches = [sysm.host_batch(g, tables, cfg["batch"])
                   for _ in range(n_pool)]
    # the program's batch is the configuration's, unless a test has built
    # it smaller to plant a fault (half of every batch left out)
    pool = [{k: v[:sysm.batch] for k, v in sysm.program_batch(b).items()}
            for b in ref_batches]
    phases["batches"] = time.perf_counter()

    dense0 = ctx.reference.init_dense(ctx.seed, sysm.sz)
    dense0_d = jax.device_put(dense0)
    step, _, bsh = sysm.train_step()
    state = sysm.make_state_fn()(key, dense0_d)
    compiled = step.lower(state, jax.device_put(pool[0], bsh)).compile()
    norms = sysm.change_norms_fn()
    start_norms, _ = jax.device_get(norms(state, key, dense0_d))
    phases["compiled"] = time.perf_counter()

    win = types.SimpleNamespace(start=None, close=None, done=0, n1=None,
                                n3=None, rows=None, tdir=None, ann=None,
                                times=[])

    def source():
        i = 0
        while win.close is None:
            yield pool[i % n_pool]
            i += 1

    def step_fn(st, batch):
        # a planted fault (tests only): the step hands back its input state
        kept = (jax.tree.map(jax.numpy.copy, st)
                if ctx.fault == "state_unchanged" else None)
        with jax.profiler.TraceAnnotation("bench/step_call"):
            new, loss = compiled(st, batch)
        return (kept if ctx.fault == "state_unchanged" else new), _Loss(loss)

    def hook(completed, st):
        if completed == 1:
            win.n1, _ = jax.device_get(norms(st, key, dense0_d))
        elif completed == CHECK_STEPS:
            win.n3, win.rows = jax.device_get(norms(st, key, dense0_d))
            if ctx.trace:
                win.tdir = tempfile.mkdtemp(prefix="bench_trace_")
                trace_mod.start(win.tdir)
                win.ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
                win.ann.__enter__()
            win.start = time.perf_counter()
        elif win.start is not None and win.close is None:
            t = time.perf_counter()
            win.times.append(t)
            win.done = completed - CHECK_STEPS
            if t - win.start >= ctx.seconds:
                win.close = t
                if ctx.trace:
                    win.ann.__exit__(None, None, None)
                    jax.profiler.stop_trace()

    loop = TrainLoop(TrainLoopConfig(steps=1 << 40, prefetch=traffic["prefetch"],
                                     log_every=1 << 40),
                     step_fn, state, source(), batch_shardings=bsh,
                     step_hook=hook)
    state = None
    loop.run()
    if win.close is None:
        raise SystemExit("the window did not close")
    jax.monitoring.unregister_event_duration_listener(listener)
    phases["window"], phases["close"] = win.start, win.close
    setup_s = win.start - ctx.t0
    window_s = win.close - win.start
    samples_per_s = win.done * sysm.batch / window_s
    losses = list(loop.losses)
    failed = sum(not math.isfinite(x) for x in losses[CHECK_STEPS:])
    device = common.device_record(ctx.cell.chips)
    hlo = compiled.as_text() if ctx.trace else ""
    del loop, pool, compiled, dense0_d
    jax.clear_caches()

    out = types.SimpleNamespace(
        e2e={"setup_s": setup_s, "train_samples_per_s": samples_per_s},
        attempted=win.done, failed=failed, device=device, trace=None,
        breakdown=None)
    read = types.SimpleNamespace(
        window_s=window_s, steps=win.done, batch=sysm.batch,
        samples_per_s=samples_per_s, chips=ctx.cell.chips,
        flops_per_sample=ctx.system_mod.flops_per_sample(sysm.sz),
        peak=ctx.peak, trace=None, layers={})
    if ctx.trace:
        tr = trace_mod.Trace.from_dir(win.tdir)
        shutil.rmtree(win.tdir, ignore_errors=True)
        read.trace = tr
        read.layers = trace_mod.classify(
            trace_mod.parse_hlo(hlo),
            trace_mod.load_layers(ctx.cell.bench / "layers" / cfg["system"]))
        read.step_bytes = float(np.mean([ctx.system_mod.step_bytes(b["idx"], sysm.sz)
                                         for b in ref_batches]))
        read.update_bytes = float(np.mean([
            ctx.system_mod.update_bytes(b["idx"], sysm.sz["E"])["bytes"]
            for b in ref_batches]))
        out.trace = tr
        out.breakdown = {"device_ops": tr.top_ops(10, read.layers),
                         "idle_gaps": tr.idle_gaps(10)}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
    out.readings = read

    # -- the reference, after the window, with the program's state freed --
    ref = ctx.reference
    r_losses, r1, r3, r_rows = ref.train(key, dense0, sysm.sz,
                                         ref_batches[:CHECK_STEPS], cfg["lr"])
    p_losses, n1, n3, rows = losses[:CHECK_STEPS], win.n1, win.n3, win.rows
    if ctx.fault == "control":
        p_losses, n1, n3, rows = ref.train(key, dense0, sysm.sz,
                                           ref_batches[:CHECK_STEPS],
                                           cfg["lr"], master="bfloat16")
    lim = cfg["limits"]["train"]
    keep = r1 >= 1e-3 * np.median(r1)
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(p_losses, r_losses))
    grad_gap = _gap(n1, r1, keep)
    change_gap = _gap(n3, r3, keep)
    rows_gap = rows_changed_gap(rows, r_rows)
    out.checks = [
        common.Check("start_state_norm", float(np.max(start_norms)), 0.0),
        common.Check("grad_gap", grad_gap, lim["grad_gap"]),
        common.Check("change_gap", change_gap, lim["change_gap"]),
        common.Check("rows_gap", rows_gap, lim["rows_gap"])]
    if "loss_gap" in lim:
        out.checks.insert(1, common.Check("loss_gap", loss_gap, lim["loss_gap"]))
    out.detail = {"losses": p_losses, "ref_losses": r_losses,
                  "loss_gap": loss_gap, "n1": np.asarray(n1).tolist(),
                  "r1": r1.tolist(), "n3": np.asarray(n3).tolist(),
                  "r3": r3.tolist(), "rows": np.asarray(rows).tolist(),
                  "ref_rows": r_rows.tolist(),
                  "leaves": ref.leaf_names(sysm.sz),
                  "step_s": np.diff([win.start] + win.times).tolist(),
                  "setup_phases_s": {k: v - ctx.t0 for k, v in phases.items()},
                  "window_events": [[n, d, t - win.start] for n, d, t in events
                                    if win.start <= t <= win.close]}
    return out


def rows_changed_gap(prog, ref) -> float:
    """Worst table: rows the program changed against rows the reference
    changed, as a share of the reference's."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, 1.0)))


def _gap(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(prog)):
        return float("inf")
    med = float(np.median(ref[keep]))
    return float(np.max(np.where(keep, np.abs(prog - ref)
                                 / np.maximum(ref, med), 0.0)))
