"""Telemetry stack: tracer, histogram, in-graph metrics, summarize,
heartbeat.  The load-bearing contracts:

* disabled tracer = shared no-op span, zero events (safe to leave wired
  into every hot path);
* an enabled tracer's spans are host events of a profiler trace, on the
  clock of the device ops;
* ``step_metrics=True`` is bitwise invisible to training and its drained
  window reproduces the cache bench's hit-rate arithmetic exactly;
* the train-loop heartbeat JSONL carries step percentiles, the straggler
  snapshot, ingest stats, the metrics window and the compile count; the
  loop spans the wait for a batch, the step call and the loss fetch.
"""

import json
import threading
import time

import numpy as np
import pytest

from repro import telemetry
from repro.telemetry import LatencyHistogram, Tracer
from repro.telemetry import metrics as step_mx
from repro.telemetry.summarize import summarize
from repro.telemetry.tracer import _NOOP_SPAN


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s1 = tr.span("a", step=1)
    s2 = tr.span("b")
    assert s1 is _NOOP_SPAN and s2 is _NOOP_SPAN  # shared singleton
    with s1:
        pass
    tr.instant("x")
    tr.counter("c", {"v": 1.0})
    assert tr.events() == []


def test_span_events_and_nesting():
    tr = Tracer(enabled=True)
    with tr.span("outer", cat="t", step=3):
        with tr.span("inner"):
            time.sleep(0.002)
    evs = [e for e in tr.events() if e["ph"] == "X"]
    by = {e["name"]: e for e in evs}
    assert set(by) == {"outer", "inner"}
    assert by["outer"]["args"] == {"step": 3}
    assert by["outer"]["dur"] >= by["inner"]["dur"] > 0
    # inner nests inside outer on the same track
    assert by["inner"]["tid"] == by["outer"]["tid"]
    assert by["outer"]["ts"] <= by["inner"]["ts"]
    assert (by["inner"]["ts"] + by["inner"]["dur"]
            <= by["outer"]["ts"] + by["outer"]["dur"] + 1.0)


def test_tracks_thread_names_and_virtual(tmp_path):
    tr = Tracer(enabled=True, trace_dir=str(tmp_path))
    tr.set_track("train_loop")
    with tr.span("step"):
        pass
    with tr.span("stage/x", track="pipeline_stages"):
        pass

    def worker():
        with tr.span("pull"):
            pass

    t = threading.Thread(target=worker, name="ingest_worker")
    t.start()
    t.join()
    tr.instant("fault/test", track="faults")
    path = tr.export()
    doc = json.loads(path.read_text())
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M"}
    assert {"train_loop", "pipeline_stages", "ingest_worker",
            "faults"} <= names
    assert "epoch_unix_s" in doc["otherData"]


def test_tracer_thread_safety():
    tr = Tracer(enabled=True)

    def emit(i):
        for j in range(200):
            with tr.span(f"t{i}", j=j):
                pass

    ts = [threading.Thread(target=emit, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    spans = [e for e in tr.events() if e["ph"] == "X"]
    assert len(spans) == 8 * 200


def test_global_configure_round_trip(tmp_path):
    tr = telemetry.configure(enabled=True, trace_dir=str(tmp_path))
    try:
        with telemetry.span("g"):
            pass
        assert any(e.get("name") == "g" for e in tr.events())
    finally:
        telemetry.configure(enabled=False)
        tr.reset()
    assert telemetry.span("after") is _NOOP_SPAN


def test_enabled_tracer_spans_are_profiler_host_events(tmp_path):
    """While enabled, a span is also a profiler annotation of the same name
    and args (an instant a zero-length one), so a device trace holds the
    program's spans on its own clock; a disabled tracer adds nothing."""
    import glob

    import jax
    from jax.profiler import ProfileData

    on, off = Tracer(enabled=True), Tracer(enabled=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with on.span("train/step", cat="train", step=7):
            with off.span("quiet/step", step=7):
                jax.numpy.ones(8).block_until_ready()
        on.instant("train/compile", seconds=0.25)
        off.instant("quiet/compile")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    host: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(e)
    (step,) = host["train/step"]
    assert dict(step.stats)["step"] == 7 and step.duration_ns > 0
    (mark,) = host["train/compile"]
    assert dict(mark.stats)["seconds"] == 0.25
    assert not any(name.startswith("quiet/") for name in host)
    # the Chrome JSON export keeps the same span
    assert [e["name"] for e in on.events() if e["ph"] == "X"] == ["train/step"]
    assert off.events() == []


# ---------------------------------------------------------------------------
# LatencyHistogram
# ---------------------------------------------------------------------------


def test_latency_histogram_quantiles():
    h = LatencyHistogram()
    assert h.summary() == {}
    vals = np.linspace(1.0, 100.0, 1000)
    for v in vals:
        h.record(float(v))
    s = h.summary()
    assert s["n"] == 1000
    # log-bucketed: 2% relative resolution
    assert s["p50"] == pytest.approx(np.percentile(vals, 50), rel=0.05)
    assert s["p99"] == pytest.approx(np.percentile(vals, 99), rel=0.05)
    assert s["min"] <= s["p50"] <= s["p99"] <= s["max"]
    assert s["mean"] == pytest.approx(vals.mean(), rel=0.05)


def test_latency_histogram_out_of_range_samples():
    """Samples outside [lo, hi) are clamped into the edge buckets but stay
    EXACT in min/max/mean — recording them must never raise or be lost."""
    h = LatencyHistogram(lo=1.0, hi=100.0, growth=1.02)
    h.record(0.001)     # far below lo -> first bucket
    h.record(0.5)
    h.record(10.0)
    h.record(5000.0)    # far above hi -> last bucket
    s = h.summary()
    assert s["n"] == 4
    assert s["min"] == 0.001 and s["max"] == 5000.0
    assert s["mean"] == pytest.approx((0.001 + 0.5 + 10.0 + 5000.0) / 4)
    # quantiles stay inside the observed range; an above-hi sample
    # saturates at the last bucket, so its quantile caps near hi (the
    # exact value survives only in min/max/mean)
    assert s["min"] <= h.quantile(0.0) <= h.quantile(1.0) <= s["max"]
    assert 100.0 <= h.quantile(1.0) <= 105.0


def test_latency_histogram_single_sample():
    h = LatencyHistogram()
    h.record(42.0)
    s = h.summary()
    assert s["n"] == 1
    assert s["min"] == s["max"] == s["mean"] == 42.0
    # with one sample every quantile is that sample (clamping to the
    # exact min/max beats the bucket midpoint)
    assert s["p50"] == s["p99"] == 42.0


def test_latency_histogram_relative_error_bound():
    """Documented accuracy contract: with growth=1.02 any quantile of a
    known distribution is within 2% relative error (bucket width + the
    'lower' rank convention's one-sample slack)."""
    rng = np.random.default_rng(7)
    vals = rng.lognormal(mean=1.0, sigma=1.5, size=20_000)
    h = LatencyHistogram(lo=1e-3, hi=1e5, growth=1.02)
    for v in vals:
        h.record(float(v))
    svals = np.sort(vals)
    for q in (0.01, 0.25, 0.5, 0.9, 0.99):
        exact = svals[int(q * len(svals))]      # 'lower' rank convention
        assert h.quantile(q) == pytest.approx(exact, rel=0.02), q


def test_serve_loop_uses_histogram():
    from repro.serve import BatchingServer

    server = BatchingServer(lambda b: np.zeros(4), batch_size=4,
                            pad_batch=lambda reqs: {"n": len(reqs)})
    assert server.percentiles() == {}
    for i in range(10):
        server.submit(i)
    list(server.drain())
    p = server.percentiles()
    assert p["n"] == 10
    assert 0 < p["p50_ms"] <= p["p99_ms"]


# ---------------------------------------------------------------------------
# Metrics: host-side helpers
# ---------------------------------------------------------------------------


def test_metrics_pack_window_hit_rate():
    import jax.numpy as jnp

    v = step_mx.pack(steps=1.0, bags=4.0, skipped_bags=3.0)
    assert v.shape == (step_mx.NUM_METRICS,)
    assert float(v[step_mx.METRIC_NAMES.index("bags")]) == 4.0
    with pytest.raises(ValueError):
        step_mx.pack(nope=1.0)
    cur = dict(zip(step_mx.METRIC_NAMES, [2.0, 0.0, 6.0, 8.0, 10.0, 64.0]))
    prev = dict(zip(step_mx.METRIC_NAMES, [1.0, 0.0, 3.0, 4.0, 5.0, 32.0]))
    win = step_mx.window(cur, prev)
    assert win["bags"] == 4.0 and win["skipped_bags"] == 3.0
    # f32 arithmetic, same as jnp.mean over the hit mask
    assert step_mx.hit_rate(win) == float(jnp.float32(3.0) / jnp.float32(4.0))
    assert step_mx.hit_rate({"bags": 0.0}) == 0.0
    assert step_mx.drain({"no": 1}) is None
    assert step_mx.drain(object()) is None


def _small_cfg(**kw):
    from repro.core.dlrm import DLRMConfig

    base = dict(name="t", num_dense=8, bottom=(16, 8), top=(16,),
                table_rows=(64, 48, 32), emb_dim=8, pooling=3, batch=8,
                emb_mode="table", idx_input="sharded")
    base.update(kw)
    return DLRMConfig(**base)


def _draw_idx(rng, cfg, zipf=None):
    if zipf is not None:
        from repro.data.synthetic import zipf_indices

        cols = [zipf_indices(rng, m, (cfg.batch, cfg.pooling), zipf)
                for m in cfg.table_rows]
    else:
        cols = [rng.integers(0, m, (cfg.batch, cfg.pooling))
                for m in cfg.table_rows]
    return np.stack(cols, 1).astype(np.int32)


def _run_steps(cfg, n, seed=0, zipf=None):
    import jax
    import jax.numpy as jnp

    from repro.core.dlrm import init_state, make_train_step
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    step, _, _, layout = make_train_step(cfg, mesh)
    state, _ = init_state(jax.random.PRNGKey(0), cfg, mesh)
    rng = np.random.default_rng(seed)
    losses = []
    batches = []
    for _ in range(n):
        idx = _draw_idx(rng, cfg, zipf)
        b = {"idx": jnp.asarray(idx),
             "dense_x": jnp.asarray(
                 rng.standard_normal((cfg.batch, cfg.num_dense)),
                 jnp.bfloat16),
             "labels": jnp.asarray(rng.integers(0, 2, cfg.batch),
                                   jnp.float32)}
        batches.append(b)
        state, loss = step(state, b)
        losses.append(np.asarray(loss))
    return state, losses, layout, batches


def test_step_metrics_bitwise_invisible_and_exact_counts():
    off_state, off_losses, _, _ = _run_steps(_small_cfg(), 3)
    on_state, on_losses, _, _ = _run_steps(_small_cfg(step_metrics=True), 3)
    assert "metrics" not in off_state and "metrics" in on_state
    for a, b in zip(off_losses, on_losses):
        assert a.tobytes() == b.tobytes()  # bitwise, not approx
    import jax

    for k in off_state:
        la = jax.tree_util.tree_leaves(off_state[k])
        lb = jax.tree_util.tree_leaves(on_state[k])
        for a, b in zip(la, lb):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), k
    m = step_mx.drain(on_state)
    assert m["steps"] == 3.0
    # every index was drawn in-range: rows = batch * slots * pooling / step
    assert m["rows_touched"] == 3 * 8 * 3 * 3
    assert m["bags"] == 3 * 8 * 3
    assert m["skipped_bags"] == 0.0  # no cache in this config
    assert m["exchange_payload_bytes"] == m["bags"] * 8 * 4


def test_cache_hit_metrics_match_hot_bag_local():
    import jax.numpy as jnp

    from repro.core import cache as hot_cache

    # 8 x 4 = 32 bags: a power of two, so the f32 divide in hit_rate and
    # jnp.mean's multiply-by-reciprocal are BOTH exact and must agree
    # bitwise (same reason the bench's 64 x 8 = 512 window is exact)
    cfg = _small_cfg(step_metrics=True, hot_rows=16, promote_every=2,
                     table_rows=(64, 48, 32, 32))
    state, _, layout, _ = _run_steps(cfg, 4, zipf=1.5)
    before = step_mx.drain(state)

    import jax

    from repro.core.dlrm import make_train_step
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    step, _, _, _ = make_train_step(cfg, mesh)
    rng = np.random.default_rng(123)
    idx = _draw_idx(rng, cfg, zipf=1.5)
    b = {"idx": jnp.asarray(idx),
         "dense_x": jnp.asarray(
             rng.standard_normal((cfg.batch, cfg.num_dense)), jnp.bfloat16),
         "labels": jnp.asarray(rng.integers(0, 2, cfg.batch), jnp.float32)}
    # the bench measurement: all-hot-bag fraction on this batch against
    # the PRE-step hot set
    hit, _ = hot_cache.hot_bag_local(layout, state["cache"]["hot_w"],
                                     state["cache"]["hot_pos"], b["idx"])
    bench_rate = float(jnp.mean(hit))
    state, _ = step(state, b)
    jax.block_until_ready(state["metrics"])
    win = step_mx.window(step_mx.drain(state), before)
    assert win["steps"] == 1.0
    assert win["bags"] == cfg.batch * len(cfg.table_rows)
    assert step_mx.hit_rate(win) == bench_rate  # exact, not approx
    # zipf(1.5) + hot 16 of <=64 rows: a real hit rate, not trivially 0/1
    assert 0 < win["skipped_bags"] < win["bags"]


# ---------------------------------------------------------------------------
# Summarize
# ---------------------------------------------------------------------------


def test_build_marks_the_deployment_on_the_trace(tmp_path):
    """``build_dlrm`` leaves a ``train/config`` instant: the deployment a
    chip's share stands for, the rows it holds of each table, its batch,
    its lookups a step, and the sparse-update kernel's row groups (E=64
    slabs are held on lanes: groups of 128 of the 8 x 15,632 padded
    rows)."""
    import jax
    from repro.launch import train as T
    from repro.launch.mesh import make_mesh
    args = T.parse_args(["--arch", "dlrm-small", "--paper", "--emb-mode",
                         "row", "--share-of", "64"])
    tr = telemetry.configure(enabled=True, trace_dir=str(tmp_path))
    try:
        T.build_dlrm(args, make_mesh((1, 1), ("data", "model")),
                     jax.random.PRNGKey(0))
        path = tr.export()
    finally:
        telemetry.configure(enabled=False)
        tr.reset()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    marks = [e for e in events if e.get("name") == "train/config"]
    assert len(marks) == 1 and marks[0]["ph"] == "i"
    assert marks[0]["args"] == {
        "arch": "dlrm-small", "deployment_chips": 64,
        "rows_per_table": [15_625] * 8, "batch": 128,
        "lookups_per_step": 128 * 8 * 50, "sparse_group_rows": 128,
        "sparse_groups": 8 * 15_632 // 128}
    assert summarize(path)["instants"]["train/config"] == 1


def test_summarize_round_trip(tmp_path):
    tr = Tracer(enabled=True, trace_dir=str(tmp_path))
    tr.set_track("train_loop")
    for i in range(3):
        with tr.span("train/step", step=i):
            time.sleep(0.001)
    tr.instant("fault/skip", track="faults")
    step_mx.emit(tr, dict(zip(step_mx.METRIC_NAMES,
                              [1.0, 0.0, 0.0, 4.0, 8.0, 128.0])))
    step_mx.emit(tr, dict(zip(step_mx.METRIC_NAMES,
                              [2.0, 0.0, 3.0, 8.0, 16.0, 160.0])))
    s = summarize(tr.export())
    row = s["tracks"]["train_loop"]["train/step"]
    assert row["count"] == 3 and row["total_ms"] >= 3.0
    assert s["instants"] == {"fault/skip": 1}
    m = s["metrics"]
    assert m["drains"] == 2
    assert m["last_window"]["bags"] == 4.0
    assert m["last_window"]["skipped_bags"] == 3.0
    assert m["last_window_hit_rate"] == step_mx.hit_rate(m["last_window"])


def test_summarize_aggregates_serve_spans(tmp_path):
    """`summarize` folds serve/* spans into a serve section with a
    per-bucket breakdown (batches, requests, wall time)."""
    tr = Tracer(enabled=True, trace_dir=str(tmp_path))
    tr.set_track("serve_worker")
    for bucket, n in ((8, 5), (8, 8), (32, 20)):
        with tr.span("serve/batch", cat="serve", bucket=bucket, n=n,
                     queue_depth=0):
            time.sleep(0.001)
    tr.instant("serve/publish", cat="serve", step=4, version=2)
    s = summarize(tr.export())
    row = s["serve"]["serve/batch"]
    assert row["count"] == 3 and row["requests"] == 33
    assert row["by_bucket"]["8"] == pytest.approx(
        {"count": 2, "requests": 13,
         "total_ms": row["by_bucket"]["8"]["total_ms"],
         "mean_ms": row["by_bucket"]["8"]["total_ms"] / 2})
    assert row["by_bucket"]["32"]["requests"] == 20
    assert s["instants"]["serve/publish"] == 1
    # non-serving traces keep an empty section
    assert summarize(Tracer(enabled=True,
                            trace_dir=str(tmp_path)).export())["serve"] == {}


def test_summarize_cli(tmp_path, capsys):
    from repro.telemetry.summarize import main

    tr = Tracer(enabled=True, trace_dir=str(tmp_path))
    with tr.span("x"):
        pass
    p = tr.export()
    assert main(["summarize", str(p)]) == 0
    out = capsys.readouterr().out
    assert "x" in out and "track:" in out
    assert main(["summarize", str(p), "--json"]) == 0
    json.loads(capsys.readouterr().out)


# ---------------------------------------------------------------------------
# StragglerMonitor snapshot + heartbeat
# ---------------------------------------------------------------------------


def test_straggler_snapshot_flags_synthetic_slow_step():
    from repro.train import StragglerMonitor

    mon = StragglerMonitor(window=50, threshold=2.0)
    assert mon.snapshot() == {"n": 0, "outliers": 0}
    for i in range(20):
        mon.record(i, 0.010)
    assert mon.record(20, 0.100)  # 10x median -> straggler
    snap = mon.snapshot()
    assert snap["n"] == 21 and snap["outliers"] == 1
    assert snap["median_ms"] == pytest.approx(10.0)
    assert snap["max_ms"] == pytest.approx(100.0)
    assert snap["p99_ms"] > snap["median_ms"]


def test_trainloop_heartbeat_jsonl(tmp_path):
    from repro.data.pipeline import ThreadedIterator
    from repro.train import TrainLoop, TrainLoopConfig

    def step(state, batch):
        time.sleep(0.001)
        return state + batch, float(batch)

    hb = tmp_path / "heartbeat.jsonl"
    stream = ThreadedIterator(iter(range(100)), depth=2)
    loop = TrainLoop(
        TrainLoopConfig(steps=7, heartbeat_path=str(hb), heartbeat_every=3,
                        log_every=100),
        step, 0, stream)
    loop.run()
    stream.close()
    recs = [json.loads(line) for line in hb.read_text().splitlines()]
    # windows at steps 3 and 6, plus the final flush at 7
    assert [r["step"] for r in recs] == [3, 6, 7]
    for r in recs[:2]:
        assert r["window_steps"] == 3
        assert 0 < r["step_ms_p50"] <= r["step_ms_p99"]
        assert r["straggler"]["n"] >= 3
        assert r["ingest"]["batches"] >= 3  # reads the iterator's stats
        assert r["skipped_batches"] == 0
    assert recs[-1]["window_steps"] == 1


def test_trainloop_emits_step_spans_and_closes_prefetch(tmp_path):
    from repro.train import TrainLoop, TrainLoopConfig

    tr = telemetry.configure(enabled=True)
    try:
        def step(state, batch):
            return state, 0.5

        loop = TrainLoop(
            TrainLoopConfig(steps=4, prefetch=2, log_every=100),
            step, 0, iter(np.arange(50.0)))
        loop.run()
        spans = [e for e in tr.events()
                 if e.get("ph") == "X" and e["name"] == "train/step"]
        assert len(spans) == 4
        # the loop owns the prefetch wrapper it created and closed it
        assert loop._owns_batches
        assert not loop.batches._tit._thread.is_alive()
    finally:
        telemetry.configure(enabled=False)
        tr.reset()


def test_trainloop_spans_each_leg_of_a_step_and_counts_compiles(tmp_path):
    """``train/next_batch``, then ``train/dispatch`` and ``train/loss_fetch``
    inside ``train/step``, each with ``step=``; a recompile forced by a
    new batch shape counts in its heartbeat window's ``compiles``."""
    import jax
    import jax.numpy as jnp

    from repro.train import TrainLoop, TrainLoopConfig

    step = jax.jit(lambda s, b: (s + b.sum(), b.sum()))
    # the batch widens at step 2: a compile in the second window too
    batches = iter([np.ones(4, np.float32)] * 2 + [np.ones(5, np.float32)] * 4)
    hb = tmp_path / "heartbeat.jsonl"
    tr = telemetry.configure(enabled=True)
    try:
        TrainLoop(TrainLoopConfig(steps=6, prefetch=2, log_every=100,
                                  heartbeat_path=str(hb), heartbeat_every=2),
                  step, jnp.float32(0), batches).run()
        evs = tr.events()
    finally:
        telemetry.configure(enabled=False)
        tr.reset()
    spans = [e for e in evs if e.get("ph") == "X"]
    by_step = {e["args"]["step"]: e for e in spans if e["name"] == "train/step"}
    for name in ("train/next_batch", "train/dispatch", "train/loss_fetch"):
        got = [e for e in spans if e["name"] == name]
        assert [e["args"]["step"] for e in got] == list(range(6)), name
        if name != "train/next_batch":
            for e in got:
                outer = by_step[e["args"]["step"]]
                assert outer["ts"] <= e["ts"]
                assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1.0
    recs = [json.loads(line) for line in hb.read_text().splitlines()]
    assert [r["step"] for r in recs] == [2, 4, 6, 6]
    assert recs[0]["compiles"] >= 1 and recs[1]["compiles"] >= 1
    assert recs[2]["compiles"] == 0 and recs[3]["compiles"] == 0
    marks = [e for e in evs if e["name"] == "train/compile"]
    assert len(marks) == sum(r["compiles"] for r in recs)
    assert all(e["args"]["seconds"] > 0 for e in marks)


def test_trainloop_device_trace_holds_the_second_heartbeat_window(tmp_path):
    """``device_trace_dir``: a profiler trace of the steps between the first
    and the second heartbeat, with the loop's spans in it."""
    import glob

    from jax.profiler import ProfileData

    from repro.train import TrainLoop, TrainLoopConfig

    tr = telemetry.configure(enabled=True)
    try:
        TrainLoop(TrainLoopConfig(steps=7, log_every=100, heartbeat_every=2,
                                  heartbeat_path=str(tmp_path / "hb.jsonl"),
                                  device_trace_dir=str(tmp_path / "device")),
                  lambda s, b: (s + b, float(b)), 0, iter(range(10))).run()
    finally:
        telemetry.configure(enabled=False)
        tr.reset()
    (path,) = glob.glob(f"{tmp_path}/device/**/*.xplane.pb", recursive=True)
    steps = sorted(dict(e.stats)["step"]
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name == "train/dispatch")
    assert steps == [2, 3]


def test_trainloop_device_trace_failure_is_recorded_and_the_run_goes_on(
        tmp_path):
    """A profiler session that is already running makes the loop's own
    ``start_trace`` fail: the failure is an event and every step runs."""
    import jax

    from repro.faults import FailureLog
    from repro.train import TrainLoop, TrainLoopConfig

    log = FailureLog()
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        loop = TrainLoop(
            TrainLoopConfig(steps=6, log_every=100, heartbeat_every=2,
                            heartbeat_path=str(tmp_path / "hb.jsonl"),
                            device_trace_dir=str(tmp_path / "device")),
            lambda s, b: (s + b, float(b)), 0, iter(range(10)),
            event_log=log)
        assert loop.run() == sum(range(6))
    finally:
        jax.profiler.stop_trace()
    (failed,) = [e for e in log.events if e["kind"] == "device_trace_failed"]
    assert failed["error"]
    assert len(loop.losses) == 6


def test_trainloop_counts_a_compile_on_another_thread(tmp_path):
    """The compile count is process-wide: a compile that another thread
    makes while the loop runs counts in that heartbeat window."""
    import jax
    import jax.numpy as jnp

    from repro.train import TrainLoop, TrainLoopConfig

    def step(state, batch):
        if batch == 2:                 # a fresh function: a new compile
            t = threading.Thread(
                target=lambda: jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)))
            t.start()
            t.join()
        return state + batch, float(batch)

    hb = tmp_path / "heartbeat.jsonl"
    TrainLoop(TrainLoopConfig(steps=4, log_every=100, heartbeat_every=2,
                              heartbeat_path=str(hb)),
              step, 0, iter(range(10))).run()
    recs = [json.loads(line) for line in hb.read_text().splitlines()]
    assert [r["step"] for r in recs] == [2, 4, 4]
    assert recs[0]["compiles"] == 0 and recs[1]["compiles"] >= 1
