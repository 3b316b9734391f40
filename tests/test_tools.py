"""Ops-layer tooling: parse_log, flakiness_checker, bandwidth
(reference ``tools/`` — SURVEY.md §2 layer 12 / §6 benchmark-harness row)."""
import os
import subprocess
import sys

import numpy as np

import mxnet_tpu as mx

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
TOOLS = os.path.join(REPO, "tools")


def test_parse_log_markdown_table(tmp_path):
    sys.path.insert(0, TOOLS)
    try:
        import parse_log
    finally:
        sys.path.pop(0)
    log = tmp_path / "train.log"
    log.write_text(
        "INFO:root:Epoch[0] Train-accuracy=0.5\n"
        "INFO:root:Epoch[0] Validation-accuracy=0.45\n"
        "INFO:root:Epoch[0] Time cost=12.5\n"
        "INFO:root:Epoch[1] Train-accuracy=0.75\n"
        "INFO:root:Epoch[1] Time cost=11.0\n")
    data = parse_log.parse(log.read_text().splitlines(), ["accuracy"])
    table = parse_log.render(data, ["accuracy"])
    assert "| epoch |" in table and "0.750000" in table and "12.5" in table
    assert "0.450000" in table


def test_flakiness_checker_runs_target(tmp_path):
    test_file = tmp_path / "test_tiny_flake.py"
    test_file.write_text(
        "def test_always_passes():\n    assert 1 + 1 == 2\n")
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "flakiness_checker.py"),
         str(test_file) + "::test_always_passes", "-n", "2"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0/2 trials failed" in out.stdout


def test_flakiness_checker_uses_tier1_invocation():
    sys.path.insert(0, TOOLS)
    try:
        import flakiness_checker as fc
    finally:
        sys.path.pop(0)
    # trials run the tier-1 pytest flags (not the legacy nose runner)
    cmd = fc.tier1_command("tests/")
    assert "pytest" in " ".join(cmd)
    assert "not slow" in cmd
    assert "--continue-on-collection-errors" in cmd
    cmd_all = fc.tier1_command("tests/", include_slow=True)
    assert "not slow" not in cmd_all
    assert "--continue-on-collection-errors" in cmd_all
    # the interpreter's own "-m pytest" must survive the filter strip
    assert cmd_all[1:3] == ["-m", "pytest"]
    # an explicitly named test is never deselected by the marker filter
    assert "not slow" not in fc.tier1_command("tests/t.py::test_x")
    # no target = the whole tier-1 suite; dotted reference spelling maps
    assert fc.parse_args([]).test == "tests/"
    assert fc.parse_args(["test_operator.test_abs"]).test == \
        "test_operator.py::test_abs"


def test_bandwidth_measure_reduces_correctly():
    sys.path.insert(0, os.path.join(TOOLS, "bandwidth"))
    try:
        import measure
    finally:
        sys.path.pop(0)
    res = measure.run(network="squeezenet1.0", kv_store="device",
                      num_batches=2, num_classes=10, log=False)
    assert len(res) == 2
    assert all(bw > 0 and np.isfinite(t) for _b, t, bw in res)


def test_word_lm_example_learns():
    out = subprocess.run(
        [sys.executable, "example/rnn/word_lm.py", "--epochs", "3",
         "--sentences", "200"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    assert "final train perplexity" in out.stderr or \
        "final train perplexity" in out.stdout


_HLO = '''HloModule jit_step

%fused_computation.1 (param_0: f32[64,128], param_1: f32[128,256], param_2: u32[]) -> f32[64,256] {
  %param_0 = f32[64,128]{1,0} parameter(0)
  %param_2 = u32[] parameter(2)
  %shift-right-logical.1 = u32[] shift-right-logical(%param_2, %param_2)
  %broadcast.1 = u32[64,128]{1,0} broadcast(%shift-right-logical.1), dimensions={}
  %convert.1 = f32[64,128]{1,0} convert(%broadcast.1)
  %multiply.1 = f32[64,128]{1,0} multiply(%param_0, %convert.1)
  %param_1 = f32[128,256]{1,0} parameter(1)
  %convolution.1 = f32[64,256]{1,0} convolution(%multiply.1, %param_1), dim_labels=bf_io->bf
  ROOT %erf.1 = f32[64,256]{1,0} erf(%convolution.1)
}

%fused_computation.2 (param_0.1: f32[64,256], param_1.1: f32[256,128]) -> f32[64,128] {
  %param_0.1 = f32[64,256]{1,0} parameter(0)
  %param_1.1 = f32[256,128]{1,0} parameter(1)
  ROOT %convolution.2 = f32[64,128]{1,0} convolution(%param_0.1, %param_1.1), dim_labels=bf_io->bf
}

ENTRY %main (a: f32[64,128], w: f32[128,256], v: f32[256,128], k: u32[]) -> f32[64,128] {
  %a = f32[64,128]{1,0} parameter(0)
  %w = f32[128,256]{1,0} parameter(1)
  %v = f32[256,128]{1,0} parameter(2)
  %k = u32[] parameter(3)
  %fusion.7 = f32[64,256]{1,0} fusion(%a, %w, %k), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/dot_general"}, backend_config={"window_config":{"estimated_cycles":"900","iteration_bounds":["2","3"]}}
  ROOT %fusion.8 = f32[64,128]{1,0} fusion(%fusion.7, %v), kind=kOutput, calls=%fused_computation.2, backend_config={"window_config":{"estimated_cycles":"400","iteration_bounds":["1","1"]}}
}
'''


def test_fusion_audit_tells_producers_from_epilogue():
    """``tools/fusion_audit.py``: a recipe that FEEDS a convolution (run
    again for every output tile) is told from one applied to its result."""
    sys.path.insert(0, TOOLS)
    try:
        import fusion_audit
    finally:
        sys.path.pop(0)
    big, small = fusion_audit.audit(_HLO)
    assert (big["fusion"], big["estimated_cycles"]) == ("fusion.7", 900)
    assert big["iteration_bounds"] == "2,3"
    assert list(big["producer_recipes"]) == ["threefry"]
    assert list(big["epilogue_recipes"]) == ["erf"]
    assert big["producers"]["multiply"] == 1 and "erf" in big["epilogue"]
    assert big["convolution_operands"][1].startswith("f32[128,256]")
    assert (small["fusion"], small["estimated_cycles"]) == ("fusion.8", 400)
    assert not small["producer_recipes"] and not small["epilogue_recipes"]
    table = fusion_audit.format_rows([big, small])
    assert "threefry FEEDS the convolution" in table
    assert "erf in the epilogue" in table
