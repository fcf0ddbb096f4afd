"""The stage rules (``bench/stages/dlrm/``): one rule per scope that the
program writes into the compiled step's ``op_name``s, in the format of
the layer rules, so a device trace can be split by pipeline stage."""

from __future__ import annotations

import dataclasses

import bench_testlib as lib
from harness import trace as T


def test_every_stage_rule_names_a_scope_of_the_program():
    """The six pipeline stages, the lookup sort and the two epilogues, each
    matched by its scope alone; the nested scope sorts before the stage it
    sits in."""
    from repro.core.pipeline import PipelineStages
    scopes = {f.name for f in dataclasses.fields(PipelineStages)} | {
        "lookup_sort", "cache_epilogue", "metrics_epilogue"}
    rules = T.load_layers(lib.BENCH / "stages" / "dlrm")
    assert {layer for layer, _ in rules} == scopes
    assert all(m == {"op_name": (f"/{layer}/",)} for layer, m in rules)
    assert rules[0][0] == "lookup_sort"


HLO = """HloModule jit_step

ENTRY %main.1 (a: bf16[100,64], b: s32[32]) -> bf16[100,64] {
  %copy.1 = bf16[100,64]{1,0} copy(%a)
  %fusion.1 = bf16[8,64]{1,0} fusion(%copy.1), kind=kLoop, metadata={op_name="jit(step_local)/embedding_fwd/jit(_take)/gather"}
  %sort.0 = (s32[32]{0}, s32[32]{0}) sort(%b), dimensions={0}, metadata={op_name="jit(step_local)/sparse_update/jit(fused_row_update)/lookup_sort/sort"}
  %sparse_row_update.3 = bf16[100,64]{1,0} custom-call(%a, %sort.0), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_local)/sparse_update/jit(fused_row_update_presorted)/while/body/closed_call/sparse_row_update/pallas_call"}
  %convolution_fusion.4 = f32[8,8]{1,0} fusion(%a), kind=kOutput, metadata={op_name="jit(step_local)/dense_fwd_bwd/transpose(jvp())/dot_general"}
  %fusion.5 = f32[8,8]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(step_local)/dense_update/convert_element_type"}
  %pad_clamp_fusion.1 = s32[8]{0} fusion(%b), kind=kLoop, metadata={op_name="gather"}
}
"""


def test_stage_rules_read_the_scopes():
    stages = T.classify(T.parse_hlo(HLO),
                        T.load_layers(lib.BENCH / "stages" / "dlrm"))
    assert stages == {
        "copy.1": "embedding_fwd",               # a copy: its consumer's
        "fusion.1": "embedding_fwd",
        "sort.0": "lookup_sort",                 # nested in sparse_update
        "sparse_row_update.3": "sparse_update",  # the named kernel
        "convolution_fusion.4": "dense_fwd_bwd",  # the derivative too
        "fusion.5": "dense_update",
        "pad_clamp_fusion.1": "other"}           # no scope left


def test_stage_metrics_patch_applies_to_the_tree():
    """docs/bench-stage-metrics.patch, the benchmark's reading of the stage
    scopes and the train-loop spans, still applies to the harness as it
    stands: every file of it is checked, none skipped."""
    import os
    import re
    import shutil
    import subprocess
    git = shutil.which("git")
    assert git, "git is needed to check the patch"
    patch = lib.REPO / "docs" / "bench-stage-metrics.patch"
    files = re.findall(r"^diff --git a/(\S+) ", patch.read_text(), re.M)
    # a checkout inside another repository would have git skip every path
    env = dict(os.environ, GIT_CEILING_DIRECTORIES=str(lib.REPO.parent))
    r = subprocess.run([git, "apply", "--check", "--verbose", str(patch)],
                       cwd=lib.REPO, env=env, capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr
    checked = re.findall(r"^Checking patch (\S+)\.\.\.$", r.stderr, re.M)
    assert files and checked == files, r.stderr
