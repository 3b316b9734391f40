"""The MiMo-V2.5 cell (``mimo_v2_5_ep16.mixed_lengths``) rehearsed at a tiny
size on the CPU through the benchmark's own driver: the last line is
well-formed, the sound run passes its limits, the lower-precision weights
and the two broken mechanisms (window layers that read everything, a
missing sink) fail them, a served step that ignores the window is not
correct, the new per-layer metrics are read where the CPU can read them, the
configuration keeps every published width, the entries are in the benchmark
(membership, never position), and the operations-and-bytes functions give
the figures ``PERF.md`` reasons with."""
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

from perf.harness import flops, flops_window_moe  # noqa: E402
from perf.harness.spec import Cell  # noqa: E402

CELL = "mimo_v2_5_ep16.mixed_lengths"
CONFIG = "mimo_v2_5_ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("step_roofline.serve_window_moe", "window_attention_share_of_step",
       "global_attention_share_of_step")
TINY = {"hidden_size": 64, "n_layer": 5,
        "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1],
        "moe_layer_freq": [0, 1, 1, 1, 1, 1],
        "num_attention_heads": 4, "swa_num_attention_heads": 4,
        "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
        "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
        "swa_v_head_dim": 16, "sliding_window": 8, "sliding_window_size": 8,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 6, "held_experts": [0, 1, 2, 3, 8, 9],
        "num_experts_per_tok": 4, "vocab_size": 97,
        # logits of order 1: at 0.02 and 64 wide every gap is rounding-sized
        "initializer_range": 0.2,
        # a sink near a row's largest scores at this width, a selection bias
        # as wide as the gaps between router scores
        "sink_bias": {"mean": 1.0, "std": 1.0}, "selection_bias_std": 0.1,
        "published": {"n_routed_experts": 16}}
# set as the real cell's are (PERF.md section 2), from readings at THIS size
# on the CPU: sound runs read a mean gap of 0.002 to 0.010 and a widest of
# 0.2 to 0.9 (seeds 1-8 and 2**31 + 5); the e4m3 weights a mean of 0.12 to
# 0.30, window layers that read everything 0.25 to 0.6, no sink 0.05 to 0.2
TINY_LIMITS = {"logit_gap_mean": 0.03, "logit_gap_max": 1.5}


@pytest.fixture
def tiny_root(tmp_path):
    root = str(tmp_path)
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
    with open(os.path.join(ROOT, "perf", "traffic",
                           "mixed_lengths.json")) as f:
        tr = json.load(f)
    tr["arrivals"]["rate_rps"] = 4.0
    # contexts of up to five windows: a ring wraps, and a step that reads
    # past the window reads other tokens
    tr["lengths"] = {
        "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 30},
        "output": {"median": 8, "sigma": 0.5, "min": 3, "max": 14}}
    tr["session"].update(batch_buckets=[1, 2, 4], seq_buckets=[8, 16, 32],
                         page_size=8, context_tokens=48)
    tr.update(check={"pad_to": 36}, drain_limit_s=60,
              trace_window_s=[0.2, 0.5], client_threads=16,
              limits=TINY_LIMITS)
    with open(os.path.join(root, "perf", "traffic", "mixed_lengths.json"),
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


def test_traced_rehearsal_reads_the_new_metrics(tiny_root):
    _cell, _out, line = _run(tiny_root, seed=7, trace=1)
    got = line["metrics"]
    # the program's counters are read on the CPU too; what needs a device
    # plane is left out, not zero and not an error
    assert {"experts_hit_per_step", "held_assignment_share", "rows_per_step", "prefill_p50_ms",
            "step_span_p50_ms", "loop_host_p50_ms", "kv_pages_live_share",
            "compiles_in_window.serve", "gateway_queue_wait_p50_ms",
            "decode_step_p50_ms", "steps_below_bucket8_share"} <= set(got)
    assert not {"step_roofline.serve_window_moe",
                "window_attention_share_of_step",
                "global_attention_share_of_step", "experts_share_of_step",
                "step_roofline.serve_hybrid", "ssm_share_of_step",
                "ssm_roofline", "gqa_attention_share_of_step",
                "step_roofline.serve_moe", "latent_attention_share_of_step",
                "step_roofline.serve"} & set(got)
    # (the rings' slots are in ``sess.stats()``; ``state_slots_live_share``
    # stays the hybrid cell's alone: its own test pins that list)
    assert "state_slots_live_share" not in got
    # 6 of 16 experts held: about 37.5% of the assignments, whatever the
    # seed; far from it, the router's width or choice was changed
    assert 25 < got["held_assignment_share"]["value"] < 50
    assert 0 < got["experts_hit_per_step"]["value"] <= 6
    assert got["compiles_in_window.serve"]["value"] == 0


def test_the_control_and_both_probes_fail_where_the_sound_run_passes(
        tiny_root, capsys):
    """``--control 1``: the reference with every matrix through e4m3, the
    cell's control, fails ``logit_gap_mean`` where the sound run passes it;
    so do the two probes that break the mechanism in the reference's place
    (window layers that read the whole context, no sink), run here beside
    it."""
    path = os.path.join(tiny_root, "perf", "traffic", "mixed_lengths.json")
    with open(path) as f:
        tr = json.load(f)
    assert "weights_fp8" in tr["controls"]
    tr["controls"] = ["weights_fp8", "window_off", "sink_off"]
    with open(path, "w") as f:
        json.dump(tr, f)
    _cell, out, _line = _run(tiny_root, seed=3, control=1)
    assert all(ok for _n, _v, _l, ok, _w in out["checks"])
    printed = {ln.split()[1].rstrip(":"): ln
               for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("control ")}
    assert list(printed) == ["weights_fp8", "window_off", "sink_off"]
    for name, line in printed.items():
        assert "-> fails logit_gap_mean" in line, line


def test_a_served_step_that_ignores_the_window_is_not_correct(
        tiny_root, monkeypatch):
    """A step whose window layers keep every token (the ring as long as the
    context, so nothing is ever overwritten or masked): requests whose
    context passes the window are served other tokens than the reference's,
    and the run is not correct."""
    from perf.systems import window_moe_gateway as system_mod
    real = system_mod.WindowMoELM

    def forgetful(*a, **k):
        # the block as built for a window of 48: rings that hold the whole
        # context of this tiny cell
        k["sliding_window"] = 48
        return real(*a, **k)

    monkeypatch.setattr(system_mod, "WindowMoELM", forgetful)
    _cell, out, _line = _run(tiny_root, seed=4)
    checks = {n: ok for n, _v, _l, ok, _w in out["checks"]}
    assert not (checks["logit_gap_mean"] and checks["logit_gap_max"])


def test_cell_offers_load_at_the_asked_share_of_the_knee():
    """ISSUE 32: 0.70 of the rate the finished change sustains (the sweep is
    in ``PERF.md`` section 4 and the traffic file states the knee), on the
    ladder (1, 32), short and long prompts in one queue."""
    tr = Cell(CELL).traffic
    share = tr["arrivals"]["rate_rps"] / tr["knee_rps"]
    assert 0.6 <= share <= 0.8
    assert tr["client_threads"] == 128 and tr["arrivals"]["kind"] == "paced"
    assert tr["session"]["batch_buckets"] == [1, 32]
    assert tr["session"]["seq_buckets"] == [256, 512, 1024, 2048, 4096]
    assert tr["session"]["page_size"] == 16
    assert tr["session"]["prefix_sharing"] is True      # asked for, skipped
    assert tr["lengths"]["prompt"] == {"median": 1024, "sigma": 0.9,
                                       "min": 128, "max": 4096}
    assert tr["lengths"]["output"] == {"median": 128, "sigma": 0.7,
                                       "min": 32, "max": 512}
    # the lower precision, then the two probes that break the mechanism:
    # each fails logit_gap_mean by a margin on the chip (PERF.md section 2)
    assert tr["controls"] == ["weights_fp8", "window_off", "sink_off"]
    # no context passes the cache's: the longest prompt and answer fit
    assert tr["lengths"]["prompt"]["max"] + tr["lengths"]["output"]["max"] \
        <= tr["session"]["context_tokens"] == tr["check"]["pad_to"] == 4608


def test_entries_are_in_the_benchmark():
    """Membership, not position: a later PR appends behind these."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tpot_p50_ms"
        for ext in (".json", ".py"):
            assert os.path.exists(os.path.join(ROOT, "perf", "metrics",
                                               name + ext))
    row = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(row) == 1 and row[0]["chips"] == 1
    assert "16 times" in row[0]["why"] and len(row[0]["why"]) <= 200
    config = [c for c in b["configs"] if c["name"] == CONFIG]
    assert [c["file"] for c in config] == ["perf/configs/" + CONFIG + ".json"]
    assert config[0]["source"] == \
        "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    assert config[0]["reduced"] == ["n_layer", "n_routed_experts",
                                    "vocab_size"]
    cell = Cell(CELL)
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW) | {"experts_share_of_step", "experts_hit_per_step",
                       "held_assignment_share", "kv_pages_live_share",
                       "rows_per_step",
                       "step_span_p50_ms", "gateway_queue_wait_p50_ms",
                       "decode_step_p50_ms", "steps_below_bucket8_share",
                       "device_idle_share.serve", "hbm_peak_gb.serve"} <= mine
    assert {m["name"] for m in cell.end_to_end()} == {
        "ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    # the other blocks' own metrics are not this cell's
    assert not {"step_roofline.serve", "step_roofline.serve_moe",
                "latent_attention_share_of_step",
                "step_roofline.serve_hybrid", "ssm_share_of_step",
                "ssm_roofline", "gqa_attention_share_of_step",
                "state_slots_live_share"} & mine
    # the older cells report nothing new
    for other in ("gpt2_medium.chat_paced", "bert_base.pretrain_s512",
                  "axk1_ep16.assist_steady",
                  "nemotron3_nano_ep8.chat_steady"):
        assert not set(NEW) & {m["name"] for m in Cell(other).per_layer()}


def test_configuration_keeps_every_published_width():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = [json.loads(ln) for ln in f if '"name": "MiMo-V2.5"' in ln][0]
    cfg = Cell(CELL).config
    assert cfg["source"].startswith(row["source_url"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    # the depth is cut under a key of its own, as ``axk1_ep16``'s is:
    # ``num_hidden_layers`` stays the published 48 beside the patterns
    assert differs == {"n_routed_experts", "vocab_size"}
    assert set(cfg["reduced"]) == differs | {"n_layer"}
    assert cfg["n_layer"] == 7
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (48, 256, 152576)
    assert len(cfg["hybrid_layer_pattern"]) == 48 == \
        len(cfg["moe_layer_freq"])
    # the floors: a whole period and four layers behind the dense one, 8
    # experts, an eighth of the vocabulary
    from perf.reference import mimo_v2
    kinds = mimo_v2.layers(cfg)
    assert kinds == [(0, 0), (1, 1), (1, 1), (1, 1), (1, 1), (0, 1), (1, 1)]
    assert cfg["held_experts"] == list(range(16))
    assert len(cfg["held_experts"]) == cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert "how_near" in cfg["deployment"]
    for key in ("qk_norm", "attention_chunk_size", "rotary", "sink_bias",
                "selection_bias", "router", "initializer_range", "residual",
                "eos", "text_only"):
        assert key in cfg["assumed"]
    assert mimo_v2.rotary_dim(cfg) == 64


def test_step_cost_is_the_arithmetic_of_the_issue():
    import numpy as np
    from perf.reference import mimo_v2
    cfg = Cell(CELL).config
    n = flops_window_moe.param_counts(cfg)
    # ISSUE 32: 89.13M a global layer's attention, 94.37M a window layer's,
    # 201.33M the dense MLP, 25.17M an expert, 1.05M a router
    assert n["attention_global"] == 89_128_960
    assert n["attention_window"] == 94_371_840
    assert n["dense_mlp"] == 201_326_592 and n["expert"] == 25_165_824
    assert n["router"] == 4096 * 256 + 256
    assert (n["global_layers"], n["window_layers"], n["dense_layers"],
            n["expert_layers"]) == (2, 5, 1, 6)
    assert flops_window_moe.kv_bytes_per_token(cfg, mimo_v2.GLOBAL) == 2560
    assert flops_window_moe.kv_bytes_per_token(cfg, mimo_v2.WINDOW) == 5120
    # every parameter of the share is in the reference's table: 3,430M
    total = sum(int(np.prod(shape)) for shape, _k, _d
                in mimo_v2.shapes(cfg).values())
    assert round(total / 1e6) == 3430
    # a step of 14 rows at 1,500 tokens: 1.69 GB always read (attention
    # 1.30, the dense MLP 0.40... the head 0.16), 1.8 GB of 6 experts hit a
    # layer, the global layers' live K/V 0.11 GB, the rings 0.05
    cost = flops_window_moe.decode_step_cost(
        cfg, rows=14, context_tokens=1500, experts_hit_per_layer=6.0,
        held_assignments_per_step=14 * 8 * 6 / 16)
    assert cost["always_read_bytes"] / 1e9 == pytest.approx(1.87, abs=0.03)
    assert cost["expert_bytes"] == 6 * 6 * 25_165_824 * 2
    assert cost["kv_bytes"] == 14 * 1501 * 2 * 2560
    assert cost["ring_bytes"] == 14 * 129 * 5 * 5120
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.least_seconds(cost, peaks)
    assert bound == "memory" and 4.5e-3 < least < 5.0e-3
    # a ring does not grow with the context, the paged K/V does
    longer = flops_window_moe.decode_step_cost(cfg, 14, 4000, 6.0, 42)
    assert longer["ring_bytes"] == cost["ring_bytes"]
    assert longer["kv_bytes"] > 2.6 * cost["kv_bytes"]
    # a step of no live row moves no K/V and hits no expert
    idle = flops_window_moe.decode_step_cost(cfg, 0, 0, 0, 0)
    assert idle["bytes"] == idle["always_read_bytes"]
