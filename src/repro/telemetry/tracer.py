"""Host-side tracer: nestable spans, instants, counters -> Chrome trace JSON.

One :class:`Tracer` collects timing events from every thread of the
process — the train loop, the ``HostPipeline`` / ``ThreadedIterator``
ingestion workers, the async checkpoint writer — and exports them as
Chrome trace-event JSON (the ``{"traceEvents": [...]}`` format), loadable
in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.  Each
thread gets its own track (named after the thread, overridable with
:meth:`Tracer.set_track`); spans emitted with an explicit ``track=`` land
on a named VIRTUAL track instead (the fault log and the blocking
checkpoint save, which run on the caller's thread but read as their own
timeline).

Design constraints, in order:

1. **Near-zero cost when disabled.**  The hot path (one span per train
   step, one per loader pull) must survive being compiled in permanently.
   ``span()`` on a disabled tracer returns a shared no-op context manager
   after a single attribute check; nothing is allocated, no clock is read.
2. **Thread-safe.**  Events append to one list under a lock; spans carry
   their own start time on the stack frame (the context-manager object),
   so nesting needs no per-thread state.
3. **Stdlib only.**  This module is imported by the loader, the
   checkpoint writer and the failure log — it must not pull jax.

Timestamps are microseconds on the ``perf_counter`` clock, zeroed at
tracer construction (Chrome trace viewers only care about relative time).
The wall-clock epoch is recorded in the exported metadata for
cross-referencing heartbeat / failure-log records.

While enabled, every span is also a ``jax.profiler.TraceAnnotation`` of
the same name and args (an instant a zero-length one), once the process
has loaded JAX: a profiler trace then holds the program's spans on the
calling thread, on the clock it shares with the device ops.  Whether a
profiler is recording decides only whether the annotation is kept.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path
from typing import Optional


class _NoopSpan:
    """Shared do-nothing context manager for the disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


def _annotation(name: str, args: dict):
    """The profiler annotation of one span, or None while JAX is not
    loaded (never imported from here: the module stays stdlib-only)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    return profiler.TraceAnnotation(name, **args)


class _Span:
    """One live span: records its own start, emits a complete ('X') event
    on exit.  Created only when the tracer is enabled."""

    __slots__ = ("_tracer", "name", "cat", "args", "_tid", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, tid: int, args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._tid = tid
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        self._ann = _annotation(self.name, self.args)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        tr = self._tracer
        ev = {
            "name": self.name,
            "ph": "X",
            "ts": (self._t0 - tr._epoch) * 1e6,
            "dur": (t1 - self._t0) * 1e6,
            "pid": tr._pid,
            "tid": self._tid,
        }
        if self.cat:
            ev["cat"] = self.cat
        if self.args:
            ev["args"] = self.args
        with tr._lock:
            tr._events.append(ev)
        return False


class Tracer:
    """Collects spans/instants/counters; exports Chrome trace JSON.

    ``enabled=False`` (the default) makes every emit call a cheap no-op;
    flip with :meth:`enable` / :meth:`disable`.  ``trace_dir`` (optional)
    is where :meth:`export` writes ``trace.json`` when called without an
    explicit path.
    """

    def __init__(self, enabled: bool = False, trace_dir: Optional[str] = None):
        self.enabled = enabled
        self.trace_dir = Path(trace_dir) if trace_dir else None
        self._epoch = time.perf_counter()
        self._epoch_unix = time.time()
        self._pid = os.getpid()
        self._lock = threading.Lock()
        self._events: list[dict] = []
        # thread ident -> track name override; virtual track name -> tid
        self._thread_tracks: dict[int, str] = {}
        self._virtual_tids: dict[str, int] = {}
        self._named_tids: set[int] = set()

    # ------------------------------------------------------------ config
    def enable(self, trace_dir: Optional[str] = None) -> "Tracer":
        if trace_dir is not None:
            self.trace_dir = Path(trace_dir)
        self.enabled = True
        return self

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        """Drop all collected events (tests / reuse across runs)."""
        with self._lock:
            self._events = []
            self._named_tids = set()
            self._virtual_tids = {}

    # ------------------------------------------------------------ tracks
    def set_track(self, name: str) -> None:
        """Name the CURRENT thread's track (overrides the thread name)."""
        if not self.enabled:
            return
        tid = threading.get_ident()
        self._thread_tracks[tid] = name
        with self._lock:
            self._named_tids.discard(tid)  # re-emit metadata with new name

    def _tid_for(self, track: Optional[str]) -> int:
        if track is not None:
            with self._lock:
                tid = self._virtual_tids.get(track)
                if tid is None:
                    # virtual tracks get small negative-range ids well away
                    # from real thread idents
                    tid = 1_000_000 + len(self._virtual_tids)
                    self._virtual_tids[track] = tid
                    self._events.append(_thread_name(self._pid, tid, track))
                    self._named_tids.add(tid)
            return tid
        tid = threading.get_ident()
        if tid not in self._named_tids:
            name = self._thread_tracks.get(tid) or threading.current_thread().name
            with self._lock:
                if tid not in self._named_tids:
                    self._events.append(_thread_name(self._pid, tid, name))
                    self._named_tids.add(tid)
        return tid

    # ------------------------------------------------------------- emits
    def span(self, name: str, cat: str = "", track: Optional[str] = None, **args):
        """Context manager timing the enclosed block.  ``args`` are
        attached to the event (visible in the Perfetto side panel);
        ``track`` places the span on a named virtual track instead of the
        calling thread's."""
        if not self.enabled:
            return _NOOP_SPAN
        return _Span(self, name, cat, self._tid_for(track), args)

    def instant(self, name: str, cat: str = "", track: Optional[str] = None, **args) -> None:
        """Zero-duration marker (failure-log events, preemptions, ...)."""
        if not self.enabled:
            return
        ann = _annotation(name, args)
        if ann is not None:
            with ann:
                pass
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",
            "ts": (time.perf_counter() - self._epoch) * 1e6,
            "pid": self._pid,
            "tid": self._tid_for(track),
        }
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def counter(self, name: str, values: dict, track: Optional[str] = None) -> None:
        """Counter sample: ``values`` is a dict of series -> number.  The
        drained in-graph metrics vector lands here (one event per drain,
        cumulative values; see repro/telemetry/metrics.py)."""
        if not self.enabled:
            return
        ev = {
            "name": name,
            "ph": "C",
            "ts": (time.perf_counter() - self._epoch) * 1e6,
            "pid": self._pid,
            "tid": self._tid_for(track),
            "args": {k: float(v) for k, v in values.items()},
        }
        with self._lock:
            self._events.append(ev)

    # ------------------------------------------------------------ export
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def export(self, path: Optional[str] = None) -> Optional[Path]:
        """Write ``{"traceEvents": [...]}`` JSON.  ``path`` overrides the
        configured ``trace_dir/trace.json``.  Returns the written path,
        or None when there is nowhere to write."""
        if path is None:
            if self.trace_dir is None:
                return None
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            path = self.trace_dir / "trace.json"
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        doc = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"epoch_unix_s": self._epoch_unix, "pid": self._pid},
        }
        p.write_text(json.dumps(doc))
        return p


def _thread_name(pid: int, tid: int, name: str) -> dict:
    return {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid, "args": {"name": name}}


# ---------------------------------------------------------------------------
# Process-global tracer: the integration points (train loop, loader
# workers, checkpoint writer, failure log, serve loop) all emit here, so
# enabling tracing is one configure() call — no tracer threading through
# every constructor.
# ---------------------------------------------------------------------------

_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    return _GLOBAL


def configure(enabled: bool = True, trace_dir: Optional[str] = None) -> Tracer:
    """Enable (or disable) the process-global tracer.  With ``trace_dir``
    set, :func:`export` writes ``<trace_dir>/trace.json``."""
    if enabled:
        _GLOBAL.enable(trace_dir)
    else:
        _GLOBAL.disable()
    return _GLOBAL


def span(name: str, cat: str = "", track: Optional[str] = None, **args):
    return _GLOBAL.span(name, cat, track, **args)


def instant(name: str, cat: str = "", track: Optional[str] = None, **args) -> None:
    _GLOBAL.instant(name, cat, track, **args)


def counter(name: str, values: dict, track: Optional[str] = None) -> None:
    _GLOBAL.counter(name, values, track)


def set_track(name: str) -> None:
    _GLOBAL.set_track(name)


def export(path: Optional[str] = None) -> Optional[Path]:
    return _GLOBAL.export(path)
