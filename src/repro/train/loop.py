"""Fault-tolerant training loop.

Scale features (designed for 1000+ node SPMD jobs, exercised here on the
local device set):

* checkpoint/restart — periodic async checkpoints (atomic commit, verified
  on restore: ``repro/checkpoint/manager.py``), restore on startup from the
  newest VALID checkpoint (corrupt ones are skipped), final checkpoint on
  SIGTERM / KeyboardInterrupt / any in-loop failure (the save lives in a
  ``finally``, so preemption safety is not lost to an exception) — except a
  simulated process death (:class:`repro.faults.InjectedCrash`), which dies
  checkpoint-less like a real ``kill -9``;
* straggler mitigation — a per-step timing ring buffer flags steps slower
  than ``threshold x`` the running median; in synchronous SPMD you cannot
  drop a worker, so the mitigation hook rebalances DATA: the elastic
  sampler shrinks the slow host's shard (callback-based so deployments can
  plug in their own telemetry);
* loader fault containment — a counted skip-batch budget
  (``TrainLoopConfig.skip_batch_budget``) absorbs transient loader
  exceptions: each one is logged and the pull retried, up to the budget;
  beyond it the failure propagates (and the final checkpoint still
  commits).  A source that ends (``StopIteration``) ends the run cleanly
  at the last completed step;
* elastic restart — on device-count change, states are restored through
  CheckpointManager with the NEW mesh's shardings (global-array format),
  embeddings re-laid-out via ``reshard_embedding`` / ``reshard_store``;
* host-side prefetch — :func:`prefetch_to_device` runs a worker thread
  keeping ``size`` batches submitted to the devices (``jax.device_put``
  is async), so the loader's host work AND the H2D transfer of batch n+1
  overlap step n's device compute.  Worker failures poison the queue and
  re-raise at the consumer — a dead loader fails the loop instead of
  hanging it.

SIGTERM handling degrades gracefully off the main thread (Python only
allows signal handlers there): preemption is then requested via the
``_stop`` flag — ``FaultPlan`` preemption drills use exactly that path.
Fault-injection hook point: ``train.step`` (inside the timed window, so
injected stalls register as stragglers).  Recovery actions record
structured events on the optional :class:`repro.faults.FailureLog`.

Tracer spans per step (docs/telemetry.md): ``train/next_batch`` (the wait
for the next batch), then ``train/step`` holding ``train/dispatch`` (the
step call) and ``train/loss_fetch`` (the loss's device-to-host copy),
each with ``step=``.  Every backend compile while :meth:`TrainLoop.run`
lasts, on any thread of the process (JAX's monitoring listeners are
process-wide, so a publisher's or a server's compiles count too), is a
``train/compile`` instant and counts in the heartbeat's ``compiles``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import sys
import threading
import time
import warnings
from collections import deque
from pathlib import Path
from typing import Any, Callable, Iterator, Optional

import numpy as np

from repro import telemetry
from repro.checkpoint import CheckpointManager
from repro.data.pipeline import ThreadedIterator
from repro.faults.plan import NO_FAULTS, InjectedCrash

_EXHAUSTED = object()
# the JAX monitoring event of one backend compile (``jax._src.dispatch``)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class PrefetchIterator:
    """The iterator :func:`prefetch_to_device` returns: forwards one
    :class:`ThreadedIterator` and exposes its ``stats``/``close`` (the
    train-loop heartbeat reads ``stats``; a bare generator would hide
    them).  Dropping it closes the worker, same as the generator did."""

    def __init__(self, tit: ThreadedIterator):
        self._tit = tit

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        return next(self._tit)

    @property
    def stats(self) -> dict:
        return self._tit.stats

    def close(self) -> None:
        self._tit.close()

    def __del__(self):
        try:
            self._tit.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def prefetch_to_device(batches: Iterator[Any], size: int = 2, shardings: Any = None,
                       faults=None) -> Iterator[Any]:
    """Wrap a host batch iterator so the next ``size`` batches are already
    submitted to the devices (``jax.device_put`` returns immediately with
    the transfer in flight) while the current step runs.

    A :class:`repro.data.pipeline.ThreadedIterator` worker pulls from
    ``batches`` and device_puts into a bounded queue, so the HOST-side
    cost of ``next(batches)`` (shard decode, pre-sort) also overlaps
    device compute, not just the H2D transfer.  The worker stays at most
    ``size`` batches ahead of the consumer (bounded-queue backpressure);
    order is preserved exactly.  If the source iterator raises, the
    exception is delivered through the queue as a poison sentinel and
    re-raised to the consumer promptly — a loader failure fails the
    training loop, it does not hang it.  Dropping the iterator (consumer
    stops early, e.g. a step-bounded loop over an infinite stream)
    closes the worker and releases its queued batches instead of leaking
    a blocked thread.

    ``shardings``: optional pytree of shardings matching each batch (the
    ``bspecs``-derived NamedShardings of the step factory); None keeps the
    default placement.  ``faults``: optional
    :class:`repro.faults.FaultPlan` — the worker fires ``loader.next``
    per pull (drills inject loader deaths and stalls here)."""
    import jax

    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")

    def put(b):
        return jax.device_put(b, shardings) if shardings is not None else jax.device_put(b)

    tit = ThreadedIterator(batches, transform=put, depth=size,
                           name="prefetch_to_device", faults=faults)
    return PrefetchIterator(tit)


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    straggler_threshold: float = 2.0  # step > thr x median -> straggler
    straggler_window: int = 50
    prefetch: int = 0  # >0: device_put-ahead window
    skip_batch_budget: int = 0  # transient loader errors absorbed per run
    # heartbeat: one JSONL record per ``heartbeat_every``-step window
    # (step-time percentiles, straggler snapshot, ingest stats, cache hit
    # rate, checkpoint save durations); None = off
    heartbeat_path: Optional[str] = None
    heartbeat_every: int = 10
    # in-graph metrics drain cadence (steps): how often state["metrics"]
    # is copied to host and emitted as a trace counter.  Only meaningful
    # when the model def set step_metrics=True.
    metrics_every: int = 10
    # with heartbeats on: a jax.profiler trace of the steps between the
    # first and the second heartbeat is written here (device ops, stage
    # scopes and the tracer's spans on one clock); None = off
    device_trace_dir: Optional[str] = None


class StragglerMonitor:
    """Ring-buffer step timer; flags outliers vs the running median."""

    def __init__(self, window: int = 50, threshold: float = 2.0,
                 on_straggler: Optional[Callable[[int, float, float], None]] = None):
        self.times: deque[float] = deque(maxlen=window)
        self.threshold = threshold
        self.events: list[tuple[int, float, float]] = []
        self.on_straggler = on_straggler

    def record(self, step: int, dt: float) -> bool:
        is_straggler = False
        if len(self.times) >= 10:
            med = float(np.median(self.times))
            if dt > self.threshold * med:
                is_straggler = True
                self.events.append((step, dt, med))
                if self.on_straggler:
                    self.on_straggler(step, dt, med)
        self.times.append(dt)
        return is_straggler

    def snapshot(self) -> dict:
        """Summary over the current ring-buffer window: {n, median_ms,
        p99_ms, max_ms, outliers} (outliers = flagged stragglers over the
        whole run, not just the window)."""
        if not self.times:
            return {"n": 0, "outliers": len(self.events)}
        a = np.asarray(self.times, np.float64) * 1e3
        return {"n": int(a.size), "median_ms": float(np.median(a)),
                "p99_ms": float(np.percentile(a, 99)),
                "max_ms": float(a.max()), "outliers": len(self.events)}


class DataRebalancer:
    """Elastic per-host batch shares.  Synchronous SPMD keeps the global
    batch fixed; when host h straggles we shift a fraction of its rows to
    the fastest hosts (the sampler consults ``shares`` when building the
    next global batch).  ``min_share`` floors every host's share (as a
    fraction of the uniform 1/n share) so repeated penalties never starve
    a host to zero."""

    def __init__(self, n_hosts: int, min_share: float = 0.5):
        self.shares = np.ones(n_hosts) / n_hosts
        self.min_share = min_share / n_hosts

    def penalize(self, host: int, factor: float = 0.9):
        moved = self.shares[host] * (1 - factor)
        floor = self.min_share
        if self.shares[host] - moved < floor:
            moved = max(0.0, self.shares[host] - floor)
        self.shares[host] -= moved
        others = [i for i in range(len(self.shares)) if i != host]
        self.shares[others] += moved / len(others)

    def rows_per_host(self, global_batch: int) -> np.ndarray:
        raw = np.floor(self.shares * global_batch).astype(int)
        raw[0] += global_batch - raw.sum()
        return raw


class TrainLoop:
    def __init__(self, cfg: TrainLoopConfig, step_fn: Callable, state: Any,
                 batches: Iterator[Any], state_shardings: Any = None,
                 batch_shardings: Any = None, faults=None, event_log=None,
                 step_hook: Optional[Callable[[int, Any], Any]] = None,
                 serve_stats: Optional[Callable[[], dict]] = None):
        # step_hook(completed_step, state) runs after every completed step
        # (the serve snapshot publisher: repro/serve/publish.py);
        # serve_stats() is folded into each heartbeat record as rec["serve"]
        # (per-bucket latency percentiles, queue depth, snapshot freshness)
        self.cfg = cfg
        self.step_fn = step_fn
        self.state = state
        self.step_hook = step_hook
        self.serve_stats = serve_stats
        self.faults = faults if faults is not None else NO_FAULTS
        self.events = event_log
        if cfg.prefetch > 0:
            batches = prefetch_to_device(batches, size=cfg.prefetch,
                                         shardings=batch_shardings, faults=faults)
        self.batches = batches
        self.monitor = StragglerMonitor(cfg.straggler_window, cfg.straggler_threshold)
        self.ckpt = (CheckpointManager(cfg.ckpt_dir, cfg.keep, faults=self.faults,
                                       event_log=event_log)
                     if cfg.ckpt_dir else None)
        self.state_shardings = state_shardings
        self.start_step = 0
        self.losses: list[float] = []
        self.skipped_batches = 0
        self._stop = False
        self._owns_batches = cfg.prefetch > 0
        self._metrics_prev: Optional[dict] = None
        self._metrics_window: Optional[dict] = None
        self._compiles = 0              # backend compiles since the heartbeat
        self._compiles_lock = threading.Lock()   # listeners run on any thread
        self._device_trace = "off" if cfg.device_trace_dir is None else "armed"
        if self.ckpt and self.ckpt.latest_valid_step() is not None:
            self.start_step, self.state = self.ckpt.restore(
                self.state, shardings=state_shardings)
            print(f"[train] restored checkpoint at step {self.start_step}")

    def _record(self, kind: str, **fields) -> None:
        if self.events is not None:
            self.events.record(kind, **fields)

    def _sigterm(self, *_):
        self._stop = True

    def _next_batch(self):
        """Pull the next batch; transient loader exceptions consume the
        skip-batch budget (each one logged) before propagating.  A source
        that ends — including a loader that died and went sticky-dead —
        returns the exhaustion sentinel so the loop can finish cleanly."""
        while True:
            try:
                return next(self.batches)
            except StopIteration:
                return _EXHAUSTED
            except InjectedCrash:
                raise  # simulated process death: never absorbed
            except Exception as e:  # noqa: BLE001 — budgeted containment
                if self.skipped_batches < self.cfg.skip_batch_budget:
                    self.skipped_batches += 1
                    self._record("batch_skipped", error=repr(e),
                                 skipped=self.skipped_batches,
                                 budget=self.cfg.skip_batch_budget)
                    print(f"[train] skipping failed batch "
                          f"({self.skipped_batches}/{self.cfg.skip_batch_budget}): {e!r}")
                    continue
                raise

    def _drain_metrics(self) -> Optional[dict]:
        """Copy the cumulative in-graph metrics vector to host (one small
        device->host transfer), emit it as a trace counter, and remember
        the per-window delta for the next heartbeat.  No-op (None) when the
        model def did not enable ``step_metrics``."""
        from repro.telemetry import metrics as step_mx

        cur = step_mx.drain(self.state)
        if cur is None:
            return None
        self._metrics_window = step_mx.window(cur, self._metrics_prev)
        self._metrics_prev = cur
        step_mx.emit(telemetry.get_tracer(), cur)
        return self._metrics_window

    def _heartbeat(self, step: int, window: list[float]) -> dict:
        """One JSONL record summarizing the window since the last
        heartbeat: step-time percentiles, straggler snapshot, ingest
        stats, drained metrics (+ cache hit rate), checkpoint save
        durations.  Appended + flushed per record so a dying process
        leaves the tail on disk."""
        from repro.telemetry import metrics as step_mx

        with self._compiles_lock:
            compiles, self._compiles = self._compiles, 0
        rec: dict = {"step": step, "t": time.time(),
                     "skipped_batches": self.skipped_batches,
                     "compiles": compiles}
        if window:
            a = np.asarray(window, np.float64) * 1e3
            rec["window_steps"] = int(a.size)
            rec["step_ms_p50"] = float(np.percentile(a, 50))
            rec["step_ms_p99"] = float(np.percentile(a, 99))
            rec["step_ms_mean"] = float(a.mean())
        rec["straggler"] = self.monitor.snapshot()
        ingest = getattr(self.batches, "stats", None)
        if ingest is not None:
            rec["ingest"] = dict(ingest)
        if self._metrics_window is not None:
            rec["metrics_window"] = self._metrics_window
            rec["cache_hit_rate"] = step_mx.hit_rate(self._metrics_window)
        if self.ckpt is not None and self.ckpt.save_durations:
            rec["ckpt_save_s"] = [round(d, 6) for d in self.ckpt.save_durations[-8:]]
        if self.serve_stats is not None:
            try:
                rec["serve"] = self.serve_stats()
            except Exception as e:  # noqa: BLE001 — telemetry must not kill the run
                rec["serve"] = {"error": repr(e)}
        path = Path(self.cfg.heartbeat_path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with path.open("a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
        telemetry.instant("train/heartbeat", cat="train", step=step)
        return rec

    def _on_duration(self, event: str, secs: float, **_) -> None:
        """JAX monitoring listener: count each backend compile (a compile
        or a compile-cache load), on whatever thread compiled, and mark it
        on the trace."""
        if event == _BACKEND_COMPILE:
            with self._compiles_lock:
                self._compiles += 1
            telemetry.instant("train/compile", cat="train", seconds=secs)

    def _step_device_trace(self) -> None:
        """At a heartbeat: start the profiler at the first, stop it at
        the second (``cfg.device_trace_dir``; a run that ends before the
        second stops it on its way out).  A profiler that fails, such as
        one another session already holds, is recorded as an event and the
        run goes on without the trace."""
        if self._device_trace not in ("armed", "on"):
            return
        import jax
        state, self._device_trace = self._device_trace, "done"
        try:
            if state == "armed":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # it would slow the host path
                jax.profiler.start_trace(self.cfg.device_trace_dir,
                                         profiler_options=opts)
                self._device_trace = "on"
            elif state == "on":
                jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — telemetry must not mask the run
            self._record("device_trace_failed", error=repr(e))

    def run(self) -> Any:
        """Run to ``cfg.steps``, checkpointing every ``cfg.ckpt_every``
        completed steps.  The FINAL checkpoint is written in a ``finally``:
        SIGTERM preemption, KeyboardInterrupt, a dead loader or a failing
        step all leave the last completed state on disk (only a simulated
        hard crash skips it).  Off the main thread, SIGTERM installation is
        skipped with a warning and preemption degrades to the ``_stop``
        flag."""
        on_main = threading.current_thread() is threading.main_thread()
        old = None
        if on_main:
            old = signal.signal(signal.SIGTERM, self._sigterm)
        else:
            warnings.warn(
                "TrainLoop.run outside the main thread: SIGTERM handler not "
                "installed (Python restricts signal handling to the main "
                "thread); preemption degrades to the _stop flag",
                RuntimeWarning, stacklevel=2)
        import jax
        tr = telemetry.get_tracer()
        tr.set_track("train_loop")
        hb_on = self.cfg.heartbeat_path is not None
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        window: list[float] = []
        completed = self.start_step
        crashed = False
        try:
            for step in range(self.start_step, self.cfg.steps):
                if self._stop:
                    print(f"[train] preemption at step {step}; checkpointing")
                    self._record("preempted", step=step)
                    break
                with tr.span("train/next_batch", cat="train", step=step):
                    batch = self._next_batch()
                if batch is _EXHAUSTED:
                    print(f"[train] batch stream ended at step {step}")
                    self._record("stream_exhausted", step=step)
                    break
                t0 = time.perf_counter()
                fault = self.faults.fire("train.step", step=step)
                if fault is not None and fault.action in ("preempt", "sigterm"):
                    if fault.action == "sigterm" and on_main:
                        os.kill(os.getpid(), signal.SIGTERM)  # handler sets _stop
                    else:
                        self._stop = True
                with tr.span("train/step", cat="train", step=step):
                    with tr.span("train/dispatch", cat="train", step=step):
                        self.state, loss = self.step_fn(self.state, batch)
                    with tr.span("train/loss_fetch", cat="train", step=step):
                        loss = float(loss)
                dt = time.perf_counter() - t0
                self.losses.append(loss)
                window.append(dt)
                completed = step + 1
                if self.monitor.record(step, dt):
                    print(f"[train] straggler step {step}: {dt * 1e3:.1f} ms")
                if self.step_hook is not None:
                    self.step_hook(completed, self.state)
                if step % self.cfg.log_every == 0:
                    print(f"[train] step {step} loss {loss:.4f} {dt * 1e3:.1f} ms")
                if self.ckpt and completed % self.cfg.ckpt_every == 0:
                    self.ckpt.save(completed, self.state)
                if completed % self.cfg.metrics_every == 0:
                    self._drain_metrics()
                if hb_on and completed % self.cfg.heartbeat_every == 0:
                    self._heartbeat(completed, window)
                    window.clear()
                    self._step_device_trace()
        except InjectedCrash:
            crashed = True  # simulated kill -9: no final checkpoint
            raise
        finally:
            unwinding = sys.exc_info()[1] is not None
            try:
                if self.ckpt and not crashed:
                    self.ckpt.save(completed, self.state, blocking=True)
            except Exception as e:  # noqa: BLE001 — don't mask the in-flight error
                self._record("final_checkpoint_failed", step=completed, error=repr(e))
                if not unwinding:
                    raise
            finally:
                try:
                    if not crashed:
                        self._drain_metrics()
                        if hb_on:
                            self._heartbeat(completed, window)
                except Exception:  # noqa: BLE001 — telemetry must not mask the run
                    pass
                jax.monitoring.unregister_event_duration_listener(
                    self._on_duration)
                if self._device_trace == "on":
                    self._step_device_trace()
                if self._owns_batches:
                    try:
                        self.batches.close()
                    except Exception:  # noqa: BLE001 — worker already dead is fine
                        pass
                if old is not None:
                    signal.signal(signal.SIGTERM, old)
        return self.state
