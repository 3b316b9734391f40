"""The A.X-K1 cell (``axk1_ep16.assist_steady``) rehearsed at a tiny size on
the CPU through the benchmark's own driver: the last line is well-formed, the
sound run passes its limits, the lower-precision controls fail them, the new
per-layer metrics are read where the CPU can read them, the entries are
appended as data, the configuration keeps every published width, and the
operations-and-bytes function gives the figures ``PERF.md`` reasons with."""
import argparse
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.harness import flops, flops_latent_moe, xplane_scopes  # noqa: E402
from perf.harness.spec import Cell  # noqa: E402

CELL = "axk1_ep16.assist_steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("step_roofline.serve_moe", "experts_share_of_step",
       "latent_attention_share_of_step", "experts_hit_per_step",
       "held_assignment_share")
TINY = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 6,
        "held_experts": [0, 1, 2, 3, 8, 9], "num_experts_per_tok": 4,
        "n_group": 4, "topk_group": 2, "vocab_size": 97, "n_layer": 3,
        # logits of order 1: at 0.02 and 64 wide every gap is rounding-sized
        "initializer_range": 0.2,
        "published": {"n_routed_experts": 16}}
# set as the real cell's are (PERF.md section 2), from readings at THIS size
# (seeds 1-7 and 2**31 + 5, on the CPU): sound runs read a mean gap of 0 to
# 0.022 and a widest of 0 to 1.09 (64 wide, bfloat16's own noise is large
# and one flipped expert choice moves a token's logits by their scale); the
# e4m3 controls read a mean of 0.10 to 0.21 (weights) and 0.031 to 0.128
# (latent rows).  The mean separates, the widest gap does not and is held
# loosely; the tests below use seeds well inside both.
TINY_LIMITS = {"logit_gap_mean": 0.04, "logit_gap_max": 1.5}


@pytest.fixture
def tiny_root(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perf", "metrics"),
                    os.path.join(root, "perf", "metrics"))
    os.makedirs(os.path.join(root, "perf", "traffic"))
    os.makedirs(os.path.join(root, "perf", "configs"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(ROOT, "perf", "configs", "axk1_ep16.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    with open(os.path.join(root, "perf", "configs", "axk1_ep16.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "perf", "traffic",
                           "assist_steady.json")) as f:
        tr = json.load(f)
    tr["arrivals"]["rate_rps"] = 4.0
    tr["lengths"] = {
        "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
    tr["session"].update(batch_buckets=[1, 2, 4], seq_buckets=[8, 16],
                         page_size=8, context_tokens=32)
    tr.update(check={"pad_to": 32}, drain_limit_s=60,
              trace_window_s=[0.2, 0.5], client_threads=16,
              limits=TINY_LIMITS)
    with open(os.path.join(root, "perf", "traffic", "assist_steady.json"),
              "w") as f:
        json.dump(tr, f)
    return root


def _run(root, seed, trace=0, control=0):
    import importlib
    import time
    import jax
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    import run as perf_run
    cell = Cell(CELL, root=root)
    args = argparse.Namespace(seed=seed, seconds=2.0, trace=trace,
                              control=control, workload=None)
    clock = perf_run.Clock(time.perf_counter(), root)
    devices = jax.devices()[:1]
    driver = importlib.import_module(
        "perf.drivers." + cell.traffic["driver"])
    out = driver.run(cell, args, devices, clock)
    line = perf_run.result_line(cell, args, out, clock, devices)
    return cell, out, json.loads(json.dumps(line))


def test_rehearsal_last_line_is_well_formed_and_sound(tiny_root):
    cell, out, line = _run(tiny_root, seed=2**31 + 5)
    assert line["attempted"] == 8 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    checks = {n: ok for n, _v, _l, ok, _w in out["checks"]}
    assert checks == {"logit_gap_mean": True, "logit_gap_max": True,
                      "compiles_in_window": True}
    # a CPU run is never a correct device measurement
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert cell.config["held_experts"] == [0, 1, 2, 3, 8, 9]


def test_traced_rehearsal_reads_the_new_counters(tiny_root):
    _cell, _out, line = _run(tiny_root, seed=7, trace=1)
    got = line["metrics"]
    # the program's counters are read on the CPU too; what needs a device
    # plane is left out, not zero and not an error
    assert {"experts_hit_per_step", "held_assignment_share", "rows_per_step",
            "prefill_p50_ms", "step_span_p50_ms", "loop_host_p50_ms",
            "kv_pages_live_share", "compiles_in_window.serve"} <= set(got)
    assert not {"step_roofline.serve_moe", "experts_share_of_step",
                "latent_attention_share_of_step", "step_roofline.serve",
                "steps_below_bucket8_share", "decode_step_p50_ms"} & set(got)
    # 6 of 16 experts held: about 37.5% of the assignments, whatever the
    # seed; far from it, the router's width or choice was changed
    assert 25 < got["held_assignment_share"]["value"] < 50
    assert 0 < got["experts_hit_per_step"]["value"] <= 6
    assert got["compiles_in_window.serve"]["value"] == 0


def test_controls_fail_where_the_sound_run_passes(tiny_root, capsys):
    _cell, out, _line = _run(tiny_root, seed=3, control=1)
    assert all(ok for _n, _v, _l, ok, _w in out["checks"])
    printed = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("control ")]
    assert len(printed) == 2
    assert all("-> fails" in ln for ln in printed), printed


def test_broken_expert_layer_is_not_correct(tiny_root, monkeypatch):
    """The routed experts' part dropped from the program (what leaving the
    expert layer out would give): the served tokens are no longer the
    reference's and the run is not correct."""
    from mxnet_tpu.parallel import moe
    real = moe.routed_expert_share

    def no_experts(x, *a, **kw):
        y, rows, n = real(x, *a, **kw)
        return y * 0.0, rows, n

    monkeypatch.setattr(moe, "routed_expert_share", no_experts)
    _cell, out, _line = _run(tiny_root, seed=4)
    checks = {n: ok for n, _v, _l, ok, _w in out["checks"]}
    assert not (checks["logit_gap_mean"] and checks["logit_gap_max"])


def test_route_flips_tool_counts_choices_against_the_reference(tiny_root):
    """``perf/tools/route_flips.py`` at the tiny size: in float32 the
    block's choice of experts is the reference's at every position; in
    bfloat16 (64 wide, logits of order 1) some differ, and every count is
    of the positions that score an answer token."""
    import jax
    from perf.systems import latent_moe_gateway as system_mod
    from perf.tools import route_flips
    cell = Cell(CELL, root=tiny_root)
    got = {}
    for dtype in ("float32", "bfloat16"):
        cfg = dict(cell.config, precision=dict(cell.config["precision"],
                                               weights=dtype))
        got[dtype] = route_flips.count_flips(
            cfg, cell.traffic, system_mod, seed=11, seconds=2.0, sequences=6,
            pad=32, device=jax.devices()[0])
    exact, served = got["float32"], got["bfloat16"]
    assert exact["sequences"] == served["sequences"] == 6
    assert exact["layer_choices"] == 2 * exact["answer_positions"] > 0
    assert exact["layer_choices_flipped"] == exact["positions_flipped"] == 0
    assert 0 < served["positions_flipped"] <= served["layer_choices_flipped"]
    assert served["positions_flipped_at_a_held_expert"] \
        <= served["positions_flipped"] < served["answer_positions"]


def test_cell_offers_load_at_the_asked_share_of_the_knee():
    """ISSUE 26: 0.7 of the rate the server sustains, moved within 0.6-0.8
    so that the occupancy sits inside one batch bucket; the knee is the
    sweep's (``PERF.md`` section 4) and the traffic file states it."""
    tr = Cell(CELL).traffic
    share = tr["arrivals"]["rate_rps"] / tr["knee_rps"]
    assert 0.6 <= share <= 0.8
    assert tr["client_threads"] > 32 and tr["arrivals"]["kind"] == "paced"
    # batch buckets up to 32, and no edge between 2 and 32 rows for the
    # occupancy to wander over: a step costs its bucket, and on a finer
    # ladder ``tpot_p50_ms`` spread by 6-11% between runs of one commit
    # (``PERF.md`` section 4); 1 is the one-prompt prefill's bucket
    assert tr["session"]["batch_buckets"] == [1, 32]


def test_entries_are_appended_as_data():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert tuple(m["name"] for m in b["per_layer"][-len(NEW):]) == NEW
    for m in b["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
    assert b["workloads"][-1]["name"] == CELL
    assert b["workloads"][-1]["chips"] == 1
    assert b["configs"][-1]["name"] == "axk1_ep16"
    cell = Cell(CELL)
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= mine and "setup_s" in {
        m["name"] for m in cell.end_to_end()}
    # GPT-2's step count and the two metrics waiting to be retired are
    # not this cell's
    assert not {"step_roofline.serve", "steps_below_bucket8_share",
                "decode_step_p50_ms"} & mine
    # the older cells report nothing new
    for other in ("gpt2_medium.chat_paced", "bert_base.pretrain_s512"):
        assert not set(NEW) & {m["name"] for m in Cell(other).per_layer()}


def test_configuration_keeps_every_published_width():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = [json.loads(ln) for ln in f if '"A.X-K1"' in ln][0]
    cfg = Cell(CELL).config
    assert cfg["source"].startswith(row["source_url"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"n_routed_experts", "vocab_size"}
    assert set(cfg["reduced"]) == differs | {"n_layer"}
    assert cfg["published"]["n_routed_experts"] == 192
    assert cfg["published"]["vocab_size"] == row["config"]["vocab_size"]
    # the floors: four layers after the dense one, 8 experts, an eighth
    assert cfg["n_layer"] - cfg["first_k_dense_replace"] >= 4
    assert len(cfg["held_experts"]) == cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]
    for key in ("topk_method", "rotary_pairing", "initializer_range",
                "weights", "eos"):
        assert key in cfg["assumed"]


def test_step_cost_is_the_arithmetic_of_the_issue():
    cfg = Cell(CELL).config
    n = flops_latent_moe.param_counts(cfg)
    assert n["attention"] == 5 * 101_122_048
    assert n["dense_ffn"] == 396_361_728 and n["expert"] == 44_040_192
    total = n["attention"] + n["dense_ffn"] + n["router"] + n["shared"] \
        + 4 * 12 * n["expert"] + 2 * n["head"]
    assert round(total / 1e6) == 3491
    cost = flops_latent_moe.decode_step_cost(cfg, rows=32,
                                             context_tokens=700,
                                             experts_hit_per_layer=9,
                                             held_assignments_per_step=64)
    assert cost["always_read_bytes"] / 1e9 == pytest.approx(2.472, abs=0.005)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.least_seconds(cost, peaks)
    assert bound == "memory" and 7.0e-3 < least < 7.6e-3
    # an expert that no row reached is not read
    idle = flops_latent_moe.decode_step_cost(cfg, 1, 700, 0, 0)
    assert idle["bytes"] == pytest.approx(
        cost["always_read_bytes"] + 7168 * 2 + 701 * 5 * 576 * 2)


def test_scope_reader_on_the_recorded_tpu_trace():
    """``perf/testdata/toy_phases_v5e.xplane.pb`` has no named scopes, but
    its operations carry their framework name (``jit(step)/while:``), which
    ``ProfileData`` does not hand out: the raw reader finds it."""
    path = os.path.join(ROOT, "perf", "testdata", "toy_phases_v5e.xplane.pb")
    modules, ops = xplane_scopes.device_ops(path)
    assert len(modules) == 4 and len(ops) == 88
    assert all(name.startswith("jit_step(") for name, _s, _e in modules)
    assert sum(op == "jit(step)/while/body/closed_call/dot_general:"
               for op, _hlo, _s, _e in ops) == 32
    found = xplane_scopes.scope_seconds(path, r"^jit_step\(",
                                        ["while", "mla.attend"])
    assert 0 < found["while"] <= found["_programs"]
    assert found["mla.attend"] == 0 and found["_named"] > 0
    # what falls under no scope is listed by name, largest first (here the
    # loop's own instruction, which has no framework name, and the
    # asynchronous copies): a kernel the compiler renamed away from an
    # alias would turn up here
    assert [n for n, _s in found["_unscoped"]][:2] == ["while", "copy-done"]
    # an alias is a prefix of the operation's own name and beats the scopes
    aliased = xplane_scopes.scope_seconds(
        path, r"^jit_step\(", ["while", "moe.experts"],
        aliases=(("dot_gen", "moe.experts"),))
    assert 0 < aliased["moe.experts"] < found["while"]
    assert aliased["while"] < found["while"]
    assert xplane_scopes.scope_seconds(path, r"^jit_other\(", ["x"]) is None

    class _Cell:
        root = "/nowhere"

    # a run without a trace, as the parent gives: nothing, and no error
    spec = {"step_module": r"^jit_step\(", "scopes": ["mla.attend"]}
    assert xplane_scopes.share_of_programs(
        {"trace": None, "cell": _Cell()}, spec) is None
