"""The benchmark's yardstick on the CPU: the generators, the FLOP and byte
counts, the trace reducer, and the correctness controls at a size a test
run can hold."""

from __future__ import annotations

import json

import numpy as np
import pytest

import bench_testlib as lib
from harness import ids
from harness import trace as T

SMALL = {"table_rows": [1_000_000] * 8, "emb_dim": 64, "pooling": 50,
         "num_dense": 512, "bottom": [512, 512, 64],
         "top": [1024, 1024, 1024, 1024]}


def _ref():
    return lib.common.load_module(lib.BENCH / "configs" / "dlrm_reference.py")


def _system_mod():
    return lib.common.load_module(lib.BENCH / "systems" / "dlrm.py")


# ------------------------------------------------------------ generators --

@pytest.mark.parametrize("dist", ["uniform", "zipf"])
def test_ids_are_the_seeds(dist):
    def draw(seed):
        g = ids.rng(seed, 1)
        t = ids.TableIds(100_000, dist, g, 1.05)
        return t.draw(g, (64, 50))
    a, b, c = draw(2**40 + 3), draw(2**40 + 3), draw(2**40 + 4)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 100_000


def test_zipf_head_share_is_the_law():
    cdf = ids.zipf_cdf(1_000_000, 1.05)
    assert 0.44 < cdf[99] < 0.46            # the top 100 ranks
    g = ids.rng(7, 1)
    t = ids.TableIds(1_000_000, "zipf", g, 1.05)
    x = t.draw(g, 409_600)
    hot = t.perm[:100]
    share = np.isin(x, hot).mean()
    assert abs(share - cdf[99]) < 0.01
    # about 91k distinct rows among 409,600 draws (not 336k as uniform)
    assert 80_000 < np.unique(x).size < 100_000
    # rank 1 takes H(N, s)^-1 of the lookups, about 1 in 11, not half
    assert (x == t.perm[0]).mean() < 0.1


def test_zipf_hot_rows_are_spread_over_row_groups():
    g = ids.rng(11, 1)
    t = ids.TableIds(1_000_000, "zipf", g, 1.05)
    hot = t.perm[:1000]
    groups = np.unique(hot // 128).size
    # 1000 rows scattered over 7813 groups of 128: nearly all apart
    assert groups > 900
    assert hot.max() - hot.min() > 900_000


# ----------------------------------------------------------------- counts --

def test_flops_per_sample_of_dlrm_small_by_hand():
    sz = _ref().sizes_of(SMALL)
    assert sz["top"] == [100, 1024, 1024, 1024, 1024, 1]
    bottom = 2 * (512 * 512 + 512 * 512 + 512 * 64)              # 1,114,112
    top = 2 * (100 * 1024 + 3 * 1024 * 1024 + 1024)              # 6,498,304
    inter = 2 * 9 * 9 * 64                                       # 10,368
    emb = 2 * 8 * 50 * 64                                        # 51,200
    want = 3 * (bottom + top + inter) + 2 * emb                  # 22,970,752
    got = _system_mod().flops_per_sample(sz)
    assert got == want == 22_970_752


def test_flops_match_the_repo_model_flops():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "repo_model_flops", lib.REPO / "benchmarks" / "model_flops.py")
    mf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mf)
    sz = _ref().sizes_of(SMALL)
    meta = dict(batch=8192, slots=8, pooling=50, emb_dim=64, kind="train",
                bottom=sz["bottom"], top=sz["top"])
    assert mf.dlrm_flops(meta) == 8192 * _system_mod().flops_per_sample(sz)


def test_update_bytes_count_distinct_rows_by_hand():
    idx = np.array([[[1, 1, 2], [0, 0, 0]],
                    [[2, 3, 3], [5, 0, 7]]], np.int32)     # [B=2, S=2, P=3]
    got = _system_mod().update_bytes(idx, E=4)
    # table 0 rows {1, 2, 3}, table 1 rows {0, 5, 7}
    assert got["distinct_rows"] == 6
    assert got["bytes"] == 2 * 6 * 4 * 4 + 2 * 2 * 4 * 4 + 16 * 12


def test_step_bytes_of_dlrm_small_by_hand():
    sm = _system_mod()
    sz = _ref().sizes_of(SMALL)
    bottom = (512 * 512 + 512) * 2 + 512 * 64 + 64                 # 558,144
    top = 100 * 1024 + 1024 + 3 * (1024 * 1024 + 1024) + 1024 + 1  # 3,253,249
    assert sm.dense_param_count(sz) == bottom + top == 3_811_393
    idx = np.zeros((2, 8, 50), np.int32)          # one distinct row per table
    idx[1] = 1                                    # two per table
    fwd = 2 * 8 * 50 * 64 * 2                     # one bf16 row per lookup
    update = 2 * 16 * 64 * 4 + 2 * 8 * 64 * 4 + 16 * 2 * 8 * 50
    dense = 3_811_393 * (2 + 2 + 8)
    assert sm.step_bytes(idx, sz) == fwd + update + dense


# ------------------------------------------------------------------ trace --

HLO = """HloModule jit_step

FileNames
1 "src/repro/core/pipeline.py"

FunctionNames
1 "make_pipelined_train_step.<locals>.step_local"
2 "build_stages.<locals>.embedding_fwd"
3 "_row_sorted_streams"
4 "build_stages.<locals>.sparse_update"
5 "sparse_row_update_pallas"
6 "gather_rows_pallas"

FileLocations
1 {file_name_id=1 function_name_id=1 line=1 end_line=1 column=1 end_column=2}
2 {file_name_id=1 function_name_id=2 line=2 end_line=2 column=1 end_column=2}
3 {file_name_id=1 function_name_id=3 line=3 end_line=3 column=1 end_column=2}
4 {file_name_id=1 function_name_id=4 line=4 end_line=4 column=1 end_column=2}
5 {file_name_id=1 function_name_id=5 line=5 end_line=5 column=1 end_column=2}
6 {file_name_id=1 function_name_id=6 line=6 end_line=6 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=0}
2 {file_location_id=2 parent_frame_id=2}
3 {file_location_id=4 parent_frame_id=2}
4 {file_location_id=3 parent_frame_id=4}
5 {file_location_id=5 parent_frame_id=4}
6 {file_location_id=6 parent_frame_id=3}

%body.1 (p: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %gather.1 = bf16[8,64]{1,0} gather(%p), metadata={op_name="x" stack_frame_id=2}
}

ENTRY %main.1 (a: bf16[100,64], b: s32[32]) -> bf16[100,64] {
  %copy.1 = bf16[100,64]{1,0} copy(%a), metadata={op_name="jit(step)/while" stack_frame_id=1}
  %tuple.1 = (s32[], bf16[100,64]) tuple(%c, %copy.1)
  %while.1 = (s32[], bf16[8,64]) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/while" stack_frame_id=1}
  %sort.0 = (s32[32]{0}, s32[32]{0}) sort(%b), dimensions={0}, metadata={op_name="jit(step)/sort" stack_frame_id=1}
  %fusion.2 = s32[32]{0} fusion(%sort.0), kind=kCustom, metadata={op_name="jit(step)/gather" stack_frame_id=4}
  %closed_call.3 = bf16[100,64]{1,0} custom-call(%a, %fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call" stack_frame_id=5}
  %closed_call.7 = bf16[8,64]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call" stack_frame_id=6}
  %convolution_fusion.4 = f32[8,8]{1,0} fusion(%a), kind=kOutput, metadata={op_name="jit(step)/transpose(jvp())/dot_general" stack_frame_id=1}
}
"""


def _dlrm_layers():
    return T.load_layers(lib.BENCH / "layers" / "dlrm")


def test_hlo_layers_from_stacks_op_names_and_consumers():
    layers = T.classify(T.parse_hlo(HLO), _dlrm_layers())
    assert layers["closed_call.3"] == "sparse_update"          # the kernel
    assert layers["sort.0"] == "lookup_sort"                   # opcode
    assert layers["fusion.2"] == "lookup_sort"                 # stack
    assert layers["gather.1"] == "emb_fwd"                     # stack
    assert layers["while.1"] == "emb_fwd"                      # its body
    assert layers["copy.1"] == "emb_fwd"                       # consumer
    assert layers["convolution_fusion.4"] == "dense"           # derivative
    # another Pallas kernel, made inside the embedding forward, is not
    # taken for the sparse update: it goes with where it was made
    assert layers["closed_call.7"] == "emb_fwd"


def test_every_layer_rule_file_is_sound():
    rules = _dlrm_layers()
    assert rules[0] == ("sparse_update", {"target": ("tpu_custom_call",),
                                          "stack": ("sparse_row_update_pallas",)})
    names = {layer for layer, _ in rules}
    # each per-layer metric that reads a layer names one that a rule makes
    for f in (lib.BENCH / "metrics").glob("*.ms_per_step.py"):
        assert f.name.split(".")[0] in names


def test_a_new_layer_is_a_new_rule_file(tmp_path):
    """A later PR gives a new kernel a layer of its own by adding a rule
    file whose name sorts it before the rule that would take it."""
    import shutil
    d = tmp_path / "dlrm"
    shutil.copytree(lib.BENCH / "layers" / "dlrm", d)
    (d / "15-fwd_gather.json").write_text(json.dumps(
        {"layer": "fwd_gather", "why": "test",
         "match": {"target": ["tpu_custom_call"],
                   "stack": ["gather_rows_pallas"]}}))
    layers = T.classify(T.parse_hlo(HLO), T.load_layers(d))
    assert layers["closed_call.7"] == "fwd_gather"
    assert layers["closed_call.3"] == "sparse_update"
    (d / "99-bad.json").write_text(json.dumps({"layer": "x", "match": {
        "opcod": ["sort"]}}))
    with pytest.raises(ValueError):
        T.load_layers(d)


def _op(name, a, b):
    return T.Op(name, f"%{name} = f32[8]{{0}} fusion()", a, b)


def test_busy_idle_self_time_and_gap_names():
    ops = [_op("while.1", 100, 400), _op("closed_call.3", 150, 250),
           _op("closed_call.3", 260, 390), _op("sort.0", 500, 600),
           _op("fusion.9", 900, 1100)]
    tr = T.Trace([ops], [("bench/loss_fetch", 390, 520),
                         ("bench/step_call", 610, 880)], (0, 1000))
    assert tr.window_s == pytest.approx(1000e-9)
    # busy: [100, 400] + [500, 600] + [900, 1000]
    assert tr.busy_s == pytest.approx(500e-9)
    layers = {"while.1": "sparse_update_other",
              "closed_call.3": "sparse_update", "sort.0": "lookup_sort"}
    secs = tr.layer_seconds(layers)
    assert secs["sparse_update"] == pytest.approx(230e-9)
    assert secs["sparse_update_other"] == pytest.approx(70e-9)   # self time
    assert secs["lookup_sort"] == pytest.approx(100e-9)
    # only the part inside the window counts
    assert "fusion.9" not in layers and secs["other"] == pytest.approx(100e-9)
    gaps = tr.idle_gaps(10)
    assert gaps[0] == ["bench/step_call", pytest.approx(300e-9)]
    assert gaps[1] == ["no bench span", pytest.approx(100e-9)]
    assert gaps[2] == ["bench/loss_fetch", pytest.approx(100e-9)]
    top = tr.top_ops(2, layers)
    assert top[0][0].startswith("sparse_update: closed_call.3")
    again = T.Trace.from_json(tr.to_json())
    assert again.busy_s == pytest.approx(tr.busy_s)


def test_reducer_on_a_recorded_trace():
    """The opening 82 ms of a traced ``dlrm-small.train-uniform`` window on
    a TPU v5e: the step call, the embedding forward, the lookup sort and
    the start of the sparse update's loop, which the slice's end cuts off
    from its kernel calls.  ``layers`` is what the run's reducer made of
    the compiled step's HLO."""
    text = (lib.REPO / "tests" / "bench" / "data"
            / "train-uniform-window-start.json").read_text()
    layers = json.loads(text)["layers"]
    tr = T.Trace.from_json(text)
    secs = tr.layer_seconds(layers)
    # self times partition the busy time, the cut-off loop included
    assert sum(secs.values()) == pytest.approx(tr.busy_s, rel=1e-9)
    assert secs["sparse_update_other"] < 2e-3
    assert 0.01 < 1 - tr.busy_s / tr.window_s < 0.02
    # the device waits for the host to dispatch the step
    gap, seconds = tr.idle_gaps(1)[0]
    assert gap == "bench/step_call" and seconds == pytest.approx(1.237683e-3)
    assert (layers["fusion.130"], layers["copy.27"], layers["sort.0"],
            layers["fusion.2"]) == ("emb_fwd", "emb_fwd", "lookup_sort",
                                     "lookup_sort")
    assert secs["emb_fwd"] > secs["lookup_sort"] > secs["dense"]
    top = tr.top_ops(1, layers)[0]
    assert top[0].startswith("emb_fwd: fusion.130")


# --------------------------------------------------------------- controls --

def _tiny_batches(ref, sz, seed, n=3, B=64):
    g = ids.rng(seed, 2)
    tables = [ids.TableIds(r, "uniform", g) for r in sz["table_rows"]]
    out = []
    for _ in range(n):
        idx = np.stack([t.draw(g, (B, sz["P"])) for t in tables], axis=1)
        x = g.standard_normal((B, sz["bottom"][0]), np.float32)
        out.append({"idx": idx, "dense_x": x,
                    "labels": g.integers(0, 2, B).astype(np.float32)})
    return out


def _train_gaps(ref, sz, seed, **kw):
    """The train cells' numbers for the reference with ``kw`` in the
    program's place."""
    key = lib.common.seed_key(seed)
    d0 = ref.init_dense(seed, sz)
    batches = _tiny_batches(ref, sz, seed)
    train = lib.common.load_module(lib.BENCH / "drivers" / "train.py")
    _, r1, r3, rrows = ref.train(key, d0, sz, batches, 0.05)
    _, c1, c3, crows = ref.train(key, d0, sz, batches, 0.05, **kw)
    keep = r1 >= 1e-3 * np.median(r1)
    return {"grad_gap": train._gap(c1, r1, keep),
            "change_gap": train._gap(c3, r3, keep),
            "rows_gap": train.rows_changed_gap(crows, rrows)}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_fails_the_limit(seed):
    """The reference at bfloat16 master weights in the program's place,
    and the reference that leaves out half of every batch, are each not
    correct by the train cells' limits."""
    ref = _ref()
    sz = ref.sizes_of(dict(SMALL, **lib.TINY))
    lim = json.loads((lib.BENCH / "configs" / "dlrm-small.json")
                     .read_text())["limits"]["train"]
    for kw in ({"master": "bfloat16"}, {"half_batch": True}):
        got = _train_gaps(ref, sz, seed, **kw)
        assert any(got[k] > lim[k] for k in got), (kw, got)
