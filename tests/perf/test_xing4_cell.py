"""The Xing4.0 cell (``xing4_29b_ep8.agent_turns``) rehearsed at a tiny size
on the CPU through the benchmark's own driver, ONE run with the trace, the
telemetry and the controls on: the last lines are well-formed, the sound run
passes its limits, the lower-precision weights and the two broken mixings (no
Sinkhorn round, coefficients that ignore the token) fail them, the program's
counters are read where the CPU can read them; the new per-layer metrics
read numbers in [0, 100] from a scripted device trace; the configuration
keeps every published width; the traffic file's design is replayed from
``--seconds`` and the seed; the entries are in the benchmark (looked up BY
NAME, never by position); and the operations-and-bytes functions give the
figures ``PERF.md`` reasons with."""
import argparse
import json
import math
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.harness import (flops, flops_hyper_latent_moe,  # noqa: E402
                          flops_latent_moe)
from perf.harness import traffic as traffic_mod  # noqa: E402
from perf.harness.spec import Cell  # noqa: E402

CELL = "xing4_29b_ep8.agent_turns"
CONFIG = "xing4_29b_ep8"
SOURCE = ("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
          "config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("step_roofline.serve_hyper_moe", "hc_share_of_step", "hc_roofline",
       "hc_share_of_prefill")
TINY = {"hidden_size": 64, "n_layer": 3, "num_attention_heads": 4,
        "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 6,
        "held_experts": [0, 1, 2, 3, 8, 9], "vocab_size": 97,
        "first_k_dense_replace": 1,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 16,
                         "type": "yarn"},
        # logits of order 1: at 0.02 and 64 wide every gap is rounding-sized;
        # phi as wide as the published widths' 0.02 x sqrt(14336) = 2.4
        "initializer_range": 0.2,
        "hc_init": {"phi_std": 0.15, "alpha": [0.3, 0.3, 0.3],
                    "res_diagonal": 1.5},
        "published": {"n_routed_experts": 16}}
# set as the real cell's are (PERF.md section 2), from readings at THIS size
# on the CPU (seeds 3, 11 and 2**31 + 5): sound runs read a mean gap of
# 0.0006 to 0.0014 and a widest of 0.02 to 0.06; no Sinkhorn round a mean of
# 0.010 to 0.024 (three layers: the real 40 compound it), the e4m3 weights
# 0.07 to 0.18, static coefficients 0.09 to 0.19
TINY_LIMITS = {"logit_gap_mean": 0.005, "logit_gap_max": 0.5}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One run of the driver at the tiny size: ``(cell, out, printed lines,
    the untraced line, the traced line)``."""
    import contextlib
    import importlib
    import io
    import time
    import jax
    root = str(tmp_path_factory.mktemp("xing4"))
    shutil.copytree(os.path.join(ROOT, "perf", "metrics"),
                    os.path.join(root, "perf", "metrics"))
    os.makedirs(os.path.join(root, "perf", "traffic"))
    os.makedirs(os.path.join(root, "perf", "configs"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(ROOT, "perf", "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    with open(os.path.join(root, "perf", "configs", CONFIG + ".json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "perf", "traffic", "agent_turns.json")) as f:
        tr = json.load(f)
    tr["arrivals"]["rate_rps"] = 4.0
    tr["lengths"] = {
        "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 24},
        "output": {"median": 5, "sigma": 0.5, "min": 3, "max": 8}}
    tr["session"].update(batch_buckets=[1, 4], seq_buckets=[16, 32],
                         page_size=8, context_tokens=32, num_pages=33)
    # every sequence padded to 32 for the reference: one shape to compile
    tr.update(check={"pad_to": 128}, drain_limit_s=60,
              trace_window_s=[0.2, 0.5], client_threads=16,
              limits=TINY_LIMITS)
    with open(os.path.join(root, "perf", "traffic", "agent_turns.json"),
              "w") as f:
        json.dump(tr, f)
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    import run as perf_run
    cell = Cell(CELL, root=root)
    args = argparse.Namespace(seed=2**31 + 5, seconds=2.0, trace=1,
                              control=1, workload=None)
    clock = perf_run.Clock(time.perf_counter(), root)
    devices = jax.devices()[:1]
    driver = importlib.import_module("perf.drivers." + tr["driver"])
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        out = driver.run(cell, args, devices, clock)
        traced = perf_run.result_line(cell, args, out, clock, devices)
        args.trace = 0
        plain = perf_run.result_line(cell, args, out, clock, devices)
    return (cell, out, printed.getvalue().splitlines(),
            json.loads(json.dumps(plain)), json.loads(json.dumps(traced)))


def test_rehearsal_last_line_is_well_formed_and_sound(rehearsal):
    cell, out, _printed, line, _traced = rehearsal
    assert line["attempted"] == 8 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    checks = {n: ok for n, _v, _l, ok, _w in out["checks"]}
    assert checks == {"logit_gap_mean": True, "logit_gap_max": True,
                      "compiles_in_window": True}
    # a CPU run is never a correct device measurement
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert cell.config["held_experts"] == [0, 1, 2, 3, 8, 9]


def test_traced_rehearsal_reads_the_counters_and_leaves_the_device_out(
        rehearsal):
    _cell, _out, _printed, _line, traced = rehearsal
    got = traced["metrics"]
    # the program's counters are read on the CPU too; what needs a device
    # plane is left out, not zero and not an error
    assert {"experts_hit_per_step", "held_assignment_share", "rows_per_step",
            "prefill_p50_ms", "step_span_p50_ms", "loop_host_p50_ms",
            "kv_pages_live_share", "compiles_in_window.serve",
            "steps_ahead_share", "prefill_share_of_loop"} <= set(got)
    assert not (set(NEW) | {
        "experts_share_of_step", "latent_attention_share_of_step",
        "step_roofline.serve_moe", "step_roofline.serve"}) & set(got)
    # 6 of 16 experts held: about 37.5% of the assignments
    assert 25 < got["held_assignment_share"]["value"] < 50
    assert 0 < got["experts_hit_per_step"]["value"] <= 6
    assert got["compiles_in_window.serve"]["value"] == 0
    # the step's extras rode the fetch: the streams' gauges are the bus's
    from mxnet_tpu.telemetry import bus
    gauges = bus.snapshot()["gauges"]
    assert gauges["decode.hc.streams"] == 4
    assert 0 <= gauges["decode.hc.sinkhorn_residual"] < 1e-3


def test_each_control_fails_where_the_sound_run_passes(rehearsal):
    """``--control 1``: the reference with every matrix through e4m3, with
    no Sinkhorn round and with coefficients that ignore the token, each put
    in the program's place, fails ``logit_gap_mean`` where the sound run
    passes it."""
    _cell, _out, printed, _line, _traced = rehearsal
    lines = {ln.split()[1].rstrip(":"): ln for ln in printed
             if ln.startswith("control ")}
    assert list(lines) == ["weights_fp8", "sinkhorn_off", "hc_static"]
    for line in lines.values():
        assert "-> fails logit_gap_mean" in line, line


def test_cell_offers_load_at_the_asked_share_of_the_knee():
    """ISSUE 39: 0.70 of the rate the finished change sustains, on the
    ladder (1, 32), prompt-heavy turns; the design is replayed from
    ``--seconds`` and the seed alone."""
    cell = Cell(CELL)
    tr = cell.traffic
    assert 0.65 <= tr["arrivals"]["rate_rps"] / tr["knee_rps"] <= 0.75
    assert tr["client_threads"] == 128 and tr["arrivals"]["kind"] == "paced"
    assert tr["driver"] == "serve_open_loop"
    assert tr["system"] == "hyper_latent_moe_gateway"
    s = tr["session"]
    assert s["batch_buckets"] == [1, 32]
    assert s["seq_buckets"] == [512, 1024, 1536, 2048]
    assert s["page_size"] == 16 and s["prefix_sharing"] is True
    # pages for 16 full contexts, and the trash page
    assert s["num_pages"] == 16 * s["context_tokens"] // s["page_size"] + 1
    assert tr["lengths"]["prompt"] == {"median": 1024, "sigma": 0.6,
                                       "min": 256, "max": 2048}
    assert tr["lengths"]["output"] == {"median": 48, "sigma": 0.7,
                                       "min": 16, "max": 192}
    assert tr["drain_limit_s"] == 75 and tr["trace_window_s"] == [15, 5]
    assert tr["lengths"]["prompt"]["max"] + tr["lengths"]["output"]["max"] \
        == 2240 <= s["context_tokens"] == tr["check"]["pad_to"] == 2304
    assert set(tr["limits"]) == {"logit_gap_mean", "logit_gap_max"}
    assert tr["controls"] == ["weights_fp8", "sinkhorn_off", "hc_static"]
    # the design: the count and the lengths follow from the file and
    # --seconds, the seed moves ids and slots only
    a = traffic_mod.design(tr, 50.0, 3, cell.config["vocab_size"])
    b = traffic_mod.design(tr, 50.0, 2**31 + 7, cell.config["vocab_size"])
    assert len(a) == len(b) == math.ceil(50 * tr["arrivals"]["rate_rps"])
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    pairs = sorted((len(r["prompt"]), r["max_new_tokens"]) for r in a)
    assert pairs == sorted((len(r["prompt"]), r["max_new_tokens"])
                           for r in b)
    assert a != b
    assert max(p + o for p, o in pairs) <= 2240
    assert min(p for p, _o in pairs) >= 256
    assert all(0 <= t < 16384 for r in a[:5] for t in r["prompt"])
    assert a == traffic_mod.design(tr, 50.0, 3, cell.config["vocab_size"])


def test_entries_are_in_the_benchmark():
    """Membership, not equality or position: a later cell may share a
    metric and a later PR appends behind these."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == (
            "ttft_mean_ms" if name == "hc_share_of_prefill"
            else "tpot_p50_ms")
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["layer"] == "kernels"
        for ext in (".json", ".py"):
            assert os.path.exists(os.path.join(ROOT, "perf", "metrics",
                                               name + ext))
    row = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(row) == 1 and row[0]["chips"] == 1
    assert "8 times" in row[0]["why"] and len(row[0]["why"]) <= 200
    config = [c for c in b["configs"] if c["name"] == CONFIG]
    assert [c["file"] for c in config] == ["perf/configs/" + CONFIG + ".json"]
    assert config[0]["source"] == SOURCE
    assert set(config[0]["reduced"]) == set(Cell(CELL).config["reduced"])
    assert len(config[0]["why"]) <= 200
    cell = Cell(CELL)
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW) | {"experts_share_of_step", "experts_hit_per_step",
                       "held_assignment_share", "kv_pages_live_share",
                       "latent_attention_share_of_step", "rows_per_step",
                       "steps_ahead_share", "step_span_p50_ms",
                       "prefill_share_of_loop", "device_idle_share.serve",
                       "hbm_peak_gb.serve"} <= mine
    assert {m["name"] for m in cell.end_to_end()} == {
        "ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    # the other blocks' own metrics are not this cell's, nor the hand-over's
    # three (no bracket in any serving cell since PR 36: PERF.md section 7)
    assert not {"step_roofline.serve", "step_roofline.serve_moe",
                "step_roofline.serve_hybrid", "ssm_share_of_step",
                "step_roofline.serve_window_moe", "kda_share_of_step",
                "step_roofline.serve_linear_moe", "state_slots_live_share",
                "step_handover_p50_ms", "launch_p50_ms",
                "wake_p50_ms"} & mine
    # the older cells report nothing new
    for other in b["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m["name"] for m in
                                   Cell(other["name"]).per_layer()}
    assert len(b["workloads"]) <= 24


def test_configuration_keeps_every_published_width():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = [json.loads(ln) for ln in f
               if '"name": "Xing4.0-29B-A4B"' in ln][0]
    assert row["source_url"] == SOURCE
    cfg = Cell(CELL).config
    assert cfg["source"].startswith(SOURCE)
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == {"n_routed_experts", "vocab_size"}
    # ``n_layer`` joins ``reduced`` only under the depth rule (ISSUE 39)
    full_depth = cfg["n_layer"] == cfg["num_hidden_layers"] == 40
    assert set(cfg["reduced"]) == differs | (set() if full_depth
                                             else {"n_layer"})
    assert full_depth or cfg["n_layer"] == 20
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (40, 64, 131072)
    # the floors: the two dense layers and at least four that follow, 8
    # experts, an eighth of the vocabulary
    assert cfg["n_layer"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["held_experts"] == list(range(8))
    assert len(cfg["held_experts"]) == cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert "how_near" in cfg["deployment"]
    for key in ("hc_norm_gain", "hc_eps", "sinkhorn_order", "res_clamp",
                "streams", "hc_draws", "selection_bias", "weights", "eos"):
        assert key in cfg["assumed"]
    assert "num_nextn_predict_layers" in cfg["left_out"]
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"]) == (4, 20)
    assert "streams" in cfg["precision"]["note"]


def test_step_cost_is_the_arithmetic_of_the_issue():
    import numpy as np
    from perf.reference import xing4
    cfg = Cell(CELL).config
    L = cfg["n_layer"]
    n = flops_latent_moe.param_counts(cfg)
    # ISSUE 39: 28.41M a layer's attention, 99.09M a dense FFN, 11.01M an
    # expert and the shared one, 0.23M a router
    assert n["attention"] == L * 28_409_856
    assert n["dense_ffn"] == 2 * 99_090_432
    assert n["expert"] == 11_010_048 and n["shared"] == (L - 2) * 11_010_048
    assert n["router"] == (L - 2) * 3584 * 64
    # every parameter of the share is in the reference's table
    shapes = xing4.shapes(cfg)
    total = sum(int(np.prod(shape)) for shape, _k, _d in shapes.values())
    hc = sum(int(np.prod(shape)) for name, (shape, _k, _d) in shapes.items()
             if ".hc_" in name)
    assert hc == L * 2 * (4 * 3584 * 24 + 24 + 3)          # 0.69M a layer
    if L == 40:
        assert total == 5_254_039_536                      # 10.58 GB stored
    # the hyper-connections of a step by hand, at 5 rows: 80 sublayers, phi
    # once in float32, a row's 57 KB stream four times
    cost = flops_hyper_latent_moe.hc_step_cost(cfg, 5)
    assert cost["param_bytes"] == 2 * L * (14336 * 24 + 27) * 4
    assert cost["stream_bytes"] == 2 * L * 5 * 4 * 14336 * 4
    assert cost["flops"] == 2 * L * 5 * (2 * 14336 * 24 + 4 * 14336
                                         + 2 * 14336 * 5 + 4 * 16 * 20)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.least_seconds(cost, peaks)
    assert bound == "memory"
    if L == 40:
        assert least * 1e3 == pytest.approx(0.246, abs=0.005)
    # the whole step adds them to the family's count and nothing else
    base = flops_latent_moe.decode_step_cost(cfg, 5, 1200, 2.2, 2.5)
    whole = flops_hyper_latent_moe.decode_step_cost(cfg, 5, 1200, 2.2, 2.5)
    assert whole["bytes"] == base["bytes"] + cost["bytes"]
    assert whole["flops"] == base["flops"] + cost["flops"]
    assert whole["hc_bytes"] == cost["bytes"]
    # a step of no live row still reads every phi
    idle = flops_hyper_latent_moe.hc_step_cost(cfg, 0)
    assert idle["bytes"] == idle["param_bytes"] > 0


def _trace_obs(scopes, rows=5.0, module_s=0.020, steps=10):
    """What a traced run hands a reader, with the device's part scripted:
    ``steps`` programs of ``module_s`` seconds and ``scopes`` seconds under
    each named scope in all, for the step's and the prefill's pattern."""
    from perf.harness import hc_scopes

    class Reduced:
        def module_seconds(self, pattern):
            return steps, steps * module_s

    cell = Cell(CELL)
    found = dict({s: 0.0 for s in hc_scopes.SCOPES}, **scopes)
    found["_programs"] = steps * module_s
    obs = {"cell": cell, "trace": Reduced(),
           "flight": [(0.0, "decode.step", None, rows)] * steps,
           "samples": {"live_tokens_per_row": [1200.0]},
           "counters": {"decode.steps": steps,
                        "decode.moe.layer_steps": 38 * steps,
                        "decode.moe.experts_hit": 38 * steps * 2.2,
                        "decode.moe.assignments_held": steps * 2.5 * 38},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    for name in NEW:
        obs["hc_scopes:" + cell.metric_file(name)["reader"]["module"]] = found
    return obs


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_a_share_in_0_to_100(name):
    """Each new reader on a scripted device trace of the cell's own sizes (5
    rows, a 20 ms program of which 8 under ``hc.*``): a percentage; the
    roofline shares below 100."""
    from perf.harness import readers
    obs = _trace_obs({"hc.coef": 0.010, "hc.sinkhorn": 0.060,
                      "hc.mix": 0.010, "mla.attend": 0.050})
    got = readers.read_metric(name, obs)
    assert 0 < got < 100
    if name.startswith("hc_share_of"):
        assert got == pytest.approx(40.0)
    # a program without the hyper-connections' scopes (the parent) or a run
    # without a trace: nothing, not zero and not an error
    bare = _trace_obs({"mla.attend": 0.050})
    assert readers.read_metric(name, bare) is None
    untraced = {k: v for k, v in bare.items()
                if not k.startswith("hc_scopes:")}
    untraced["trace"] = None
    assert readers.read_metric(name, untraced) is None
