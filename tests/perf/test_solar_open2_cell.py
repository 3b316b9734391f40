"""The Solar-Open2 cell (``solar_open2_ep16.reason_long``) rehearsed at a
tiny size on the CPU through the benchmark's own driver: the last line is
well-formed, the sound run passes its limits, the lower-precision weights
and the two broken mechanisms (a state that never decays, ``beta`` not
doubled) fail them, a served step whose state forgets nothing is not
correct, the new per-layer metrics are read where the CPU can read them (and
read numbers in [0, 100] from a recorded device trace), the configuration
keeps every published width, the entries are in the benchmark (membership,
never position), and the operations-and-bytes functions give the figures
``PERF.md`` reasons with."""
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

from perf.harness import flops, flops_linear_moe  # noqa: E402
from perf.harness.spec import Cell  # noqa: E402

CELL = "solar_open2_ep16.reason_long"
CONFIG = "solar_open2_ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("step_roofline.serve_linear_moe", "kda_share_of_step", "kda_roofline",
       "gated_attention_share_of_step")
TINY = {"hidden_size": 64, "n_layer": 5, "gqa_layers": [0, 4, 8],
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 32,
                               "num_heads": 4, "num_kv_heads": None},
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "kda_gate_rank": 16, "kda_chunk_size": 8,
        "moe_intermediate_size": 32, "n_routed_experts": 6,
        "held_experts": [0, 1, 2, 3, 8, 9], "num_experts_per_tok": 4,
        "vocab_size": 97,
        # logits of order 1: at 0.02 and 64 wide every gap is rounding-sized;
        # steps large enough that a state forgets within these sequences
        "initializer_range": 0.2, "time_step_min": 0.01,
        "time_step_max": 0.5, "published": {"n_routed_experts": 16}}
# set as the real cell's are (PERF.md section 2), from readings at THIS size
# on the CPU (seeds 1-7 and 2**31 + 5): sound runs read a mean gap of 0.007
# to 0.050 and a widest of 0.25 to 1.75 (at 64 wide a bfloat16 router flips
# a choice every few tokens); beta not doubled a mean of 0.14 and 0.17 (seeds
# 3 and 5), the e4m3 weights 0.38 and 0.48, a state that never decays 1.9
TINY_LIMITS = {"logit_gap_mean": 0.08, "logit_gap_max": 2.5}


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
    with open(os.path.join(ROOT, "perf", "traffic", "reason_long.json")) as f:
        tr = json.load(f)
    tr["arrivals"]["rate_rps"] = 4.0
    # answers longer than their questions, contexts of several chunks of 8
    tr["lengths"] = {
        "prompt": {"median": 8, "sigma": 0.6, "min": 3, "max": 20},
        "output": {"median": 12, "sigma": 0.5, "min": 5, "max": 24}}
    tr["session"].update(batch_buckets=[1, 2, 4], seq_buckets=[8, 16, 32],
                         page_size=8, context_tokens=48)
    # every sequence padded to 48 for the reference: one shape to compile
    tr.update(check={"pad_to": 240}, drain_limit_s=60,
              trace_window_s=[0.2, 0.5], client_threads=16,
              limits=TINY_LIMITS)
    with open(os.path.join(root, "perf", "traffic", "reason_long.json"),
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


def test_traced_rehearsal_reads_the_counters_and_leaves_the_device_out(
        tiny_root):
    _cell, _out, line = _run(tiny_root, seed=7, trace=1)
    got = line["metrics"]
    # the program's counters are read on the CPU too; what needs a device
    # plane is left out, not zero and not an error
    assert {"experts_hit_per_step", "held_assignment_share", "rows_per_step",
            "prefill_p50_ms", "step_span_p50_ms", "loop_host_p50_ms",
            "kv_pages_live_share", "compiles_in_window.serve",
            "gateway_queue_wait_p50_ms", "decode_step_p50_ms",
            "steps_below_bucket8_share", "steps_ahead_share"} <= set(got)
    assert not (set(NEW) | {
        "experts_share_of_step", "step_roofline.serve_hybrid",
        "ssm_share_of_step", "ssm_roofline", "gqa_attention_share_of_step",
        "step_roofline.serve_window_moe", "step_roofline.serve_moe",
        "step_roofline.serve", "state_slots_live_share"}) & set(got)
    # 6 of 16 experts held: about 37.5% of the assignments, whatever the
    # seed; far from it, the router's width or choice was changed
    assert 25 < got["held_assignment_share"]["value"] < 50
    assert 0 < got["experts_hit_per_step"]["value"] <= 6
    assert got["compiles_in_window.serve"]["value"] == 0


def test_each_control_fails_where_the_sound_run_passes(tiny_root, capsys):
    """``--control 1``: the reference with every matrix through e4m3, with
    ``g = 0`` (a state that never forgets) and with ``beta`` not doubled,
    each put in the program's place, fails ``logit_gap_mean`` where the
    sound run passes it."""
    _cell, out, _line = _run(tiny_root, seed=3, control=1)
    assert all(ok for _n, _v, _l, ok, _w in out["checks"])
    printed = {ln.split()[1].rstrip(":"): ln
               for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("control ")}
    assert list(printed) == ["weights_fp8", "decay_off", "neg_eig_off"]
    for _name, line in printed.items():
        assert "-> fails logit_gap_mean" in line, line


def test_a_served_step_whose_state_never_decays_is_not_correct(
        tiny_root, monkeypatch):
    """A block whose decay is switched off in the program alone (``A_log``
    so low that ``g`` is 0 to float32; the reference makes its own weights
    again): its states keep everything, requests are served other tokens
    than the reference's, and the run is not correct."""
    import jax.numpy as jnp
    from perf.systems import linear_moe_gateway as system_mod
    real = system_mod.block

    def forgetful(cfg, context_tokens, weights, device):
        for name in weights:
            if name.endswith("A_log"):
                weights[name] = jnp.full_like(weights[name], -80.0)
        return real(cfg, context_tokens, weights, device)

    monkeypatch.setattr(system_mod, "block", forgetful)
    _cell, out, _line = _run(tiny_root, seed=4)
    checks = {n: ok for n, _v, _l, ok, _w in out["checks"]}
    assert not (checks["logit_gap_mean"] and checks["logit_gap_max"])


def test_cell_offers_load_at_the_asked_share_of_the_knee():
    """ISSUE 37: 0.70 of the rate the finished change sustains (the sweep is
    in ``PERF.md`` section 4 and the traffic file states the knee), on the
    ladder (1, 32), short questions and long answers."""
    tr = Cell(CELL).traffic
    share = tr["arrivals"]["rate_rps"] / tr["knee_rps"]
    assert 0.65 <= share <= 0.75
    assert tr["client_threads"] == 128 and tr["arrivals"]["kind"] == "paced"
    assert tr["driver"] == "serve_open_loop"
    assert tr["session"]["batch_buckets"] == [1, 32]
    assert tr["session"]["seq_buckets"] == [256, 512, 1024, 2048]
    assert tr["session"]["page_size"] == 16
    assert tr["session"]["prefix_sharing"] is True      # asked for, skipped
    assert tr["lengths"]["prompt"] == {"median": 512, "sigma": 0.8,
                                       "min": 64, "max": 2048}
    assert tr["lengths"]["output"] == {"median": 1024, "sigma": 0.6,
                                       "min": 256, "max": 3072}
    assert tr["drain_limit_s"] == 75 and tr["trace_window_s"] == [15, 5]
    # no context passes the cache's: the longest prompt and answer fit
    assert tr["lengths"]["prompt"]["max"] + tr["lengths"]["output"]["max"] \
        <= tr["session"]["context_tokens"] == tr["check"]["pad_to"] == 5120
    assert set(tr["limits"]) == {"logit_gap_mean", "logit_gap_max"}
    assert set(tr["controls"]) <= {"weights_fp8", "decay_off", "neg_eig_off"}
    assert "weights_fp8" in tr["controls"]


def test_entries_are_in_the_benchmark():
    """Membership, not equality or position: a later cell may share a
    metric and a later PR appends behind these."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "tpot_p50_ms"
        assert by_name[name]["source"] == "device_trace"
        for ext in (".json", ".py"):
            assert os.path.exists(os.path.join(ROOT, "perf", "metrics",
                                               name + ext))
    row = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(row) == 1 and row[0]["chips"] == 1
    assert "16 times" in row[0]["why"] and len(row[0]["why"]) <= 200
    config = [c for c in b["configs"] if c["name"] == CONFIG]
    assert [c["file"] for c in config] == ["perf/configs/" + CONFIG + ".json"]
    assert config[0]["source"] == \
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
    assert config[0]["reduced"] == ["n_layer", "n_routed_experts",
                                    "vocab_size"]
    assert len(config[0]["why"]) <= 200
    cell = Cell(CELL)
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW) | {"experts_share_of_step", "experts_hit_per_step",
                       "held_assignment_share", "kv_pages_live_share",
                       "rows_per_step", "steps_ahead_share",
                       "step_span_p50_ms", "gateway_queue_wait_p50_ms",
                       "decode_step_p50_ms", "device_idle_share.serve",
                       "hbm_peak_gb.serve"} <= mine
    assert {m["name"] for m in cell.end_to_end()} == {
        "ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    # the other blocks' own metrics are not this cell's, nor the slot share
    # (its list is the hybrid cell's alone) nor the hand-over's three (no
    # bracket in any serving cell since PR 36: PERF.md section 7)
    assert not {"step_roofline.serve", "step_roofline.serve_moe",
                "latent_attention_share_of_step",
                "step_roofline.serve_hybrid", "ssm_share_of_step",
                "ssm_roofline", "gqa_attention_share_of_step",
                "step_roofline.serve_window_moe",
                "window_attention_share_of_step",
                "global_attention_share_of_step", "state_slots_live_share",
                "step_handover_p50_ms", "launch_p50_ms",
                "wake_p50_ms"} & mine
    # the older cells report nothing new
    for other in ("gpt2_medium.chat_paced", "bert_base.pretrain_s512",
                  "axk1_ep16.assist_steady", "nemotron3_nano_ep8.chat_steady",
                  "mimo_v2_5_ep16.mixed_lengths"):
        assert not set(NEW) & {m["name"] for m in Cell(other).per_layer()}


def test_configuration_keeps_every_published_width():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = [json.loads(ln) for ln in f
               if '"name": "Solar-Open2-250B"' in ln][0]
    cfg = Cell(CELL).config
    assert cfg["source"].startswith(row["source_url"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    # the depth is cut under a key of its own, as ``axk1_ep16``'s is:
    # ``num_hidden_layers`` stays the published 48 beside ``gqa_layers``
    assert differs == {"n_routed_experts", "vocab_size"}
    assert set(cfg["reduced"]) == differs | {"n_layer"}
    assert cfg["n_layer"] == 8
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (48, 320, 196608)
    # the floors: two whole periods (one would do), 20 >= 8 experts, an
    # eighth of the vocabulary
    from perf.reference import solar_open2
    assert solar_open2.gqa_layers(cfg) == (0, 4)
    assert cfg["held_experts"] == list(range(20))
    assert len(cfg["held_experts"]) == cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert "how_near" in cfg["deployment"]
    for key in ("kda_gate_rank", "decay_parameters", "conv1d", "qk_norm",
                "gated_norm", "gqa_gate", "router", "experts",
                "gqa_interval", "initializer_range", "residual", "eos"):
        assert key in cfg["assumed"]
    z = solar_open2.sizes(cfg)
    assert (z["H"], z["dk"], z["K"], z["rank"]) == (64, 128, 4, 128)
    assert "state" in cfg["precision"]


def test_step_cost_is_the_arithmetic_of_the_issue():
    import numpy as np
    from perf.reference import solar_open2
    cfg = Cell(CELL).config
    n = flops_linear_moe.param_counts(cfg)
    # ISSUE 37: 137.7M a KDA mixer, 109.1M a grouped-query mixer, 15.73M an
    # expert, 1.31M a router
    assert n["kda"] + n["kda_small"] == 137_740_480
    assert n["attention"] == 109_051_904
    assert n["expert"] == n["shared"] == 15_728_640
    assert n["router"] == 4096 * 320
    assert (n["kda_layers"], n["attention_layers"], n["expert_layers"]) == \
        (6, 2, 8)
    # 4.19 MB of matrix state + 0.15 MB of tails a layer a slot
    assert flops_linear_moe.state_bytes_per_row(cfg) == \
        64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2 == 4_341_760
    # every parameter of the share is in the reference's table: 3,899M
    total = sum(int(np.prod(shape)) for shape, _k, _d
                in solar_open2.shapes(cfg).values())
    assert total == 3_898_840_192
    # a step of 22 rows at 1,200 tokens: 2.59 GB always read (1.65 of it the
    # six KDA mixers', 0.44 the two grouped-query mixers', 0.25 the shared
    # experts, 0.20 the head), 2.2 GB of 8.6 experts hit a layer, 1.15 GB
    # of live state there and back, 0.2 GB of K/V
    cost = flops_linear_moe.decode_step_cost(
        cfg, rows=22, context_tokens=1200, experts_hit_per_layer=8.6,
        held_assignments_per_step=22 * 8 * 8 * 20 / 320)
    kda = flops_linear_moe.kda_step_cost(cfg, 22)
    assert kda["weight_bytes"] / 1e9 == pytest.approx(1.65, abs=0.01)
    assert cost["always_read_bytes"] / 1e9 == pytest.approx(2.59, abs=0.02)
    assert cost["expert_bytes"] == 8.6 * 8 * 15_728_640 * 2
    assert cost["state_bytes"] == kda["state_bytes"] == \
        22 * 6 * 2 * 4_341_760
    assert cost["kv_bytes"] == 22 * 1201 * 2 * 2 * 1024 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.least_seconds(cost, peaks)
    assert bound == "memory" and 6.5e-3 < least < 8.0e-3
    # the KDA layers are the largest single share of the bytes
    assert kda["bytes"] > cost["expert_bytes"] > cost["kv_bytes"]
    assert 0.4 < kda["bytes"] / cost["bytes"] < 0.55
    # the state does not grow with the context, the paged K/V does
    longer = flops_linear_moe.decode_step_cost(cfg, 22, 4000, 8.6, 88)
    assert longer["state_bytes"] == cost["state_bytes"]
    assert longer["kv_bytes"] > 3 * cost["kv_bytes"]
    # a step of no live row moves no state and hits no expert
    idle = flops_linear_moe.decode_step_cost(cfg, 0, 0, 0, 0)
    assert idle["bytes"] == idle["always_read_bytes"]


def _trace_obs(scopes, rows=20.0, module_s=0.012, steps=10):
    """What a traced run hands a reader, with the device's part scripted:
    ``steps`` step programs of ``module_s`` seconds and ``scopes`` seconds
    under each named scope in all."""
    class Reduced:
        def module_seconds(self, pattern):
            return steps, steps * module_s

    cell = Cell(CELL)
    found = dict({"kda.mix": 0.0, "kda.conv": 0.0, "kda.recur": 0.0,
                  "attn.gqa": 0.0}, **scopes)
    found["_programs"] = steps * module_s
    return {"cell": cell, "trace": Reduced(),
            "kda_scopes:" + cell.metric_file(NEW[1])["reader"]["step_module"]:
            found,
            "flight": [(0.0, "decode.step", None, rows)] * steps,
            "samples": {"live_tokens_per_row": [1200.0]},
            "counters": {"decode.steps": steps,
                         "decode.moe.layer_steps": 8 * steps,
                         "decode.moe.experts_hit": 8 * steps * 8.6,
                         "decode.moe.assignments_held": steps * 88,
                         "decode.kda.layer_steps": 6 * steps},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_a_share_in_0_to_100(name):
    """Each new reader on a scripted device trace of the cell's own sizes
    (20 rows, a 12 ms step of which 6 under ``kda.*`` and 0.5 under
    ``attn.gqa``): a percentage, and the roofline shares below 100 because
    the state's bytes are the LIVE rows'."""
    from perf.harness import readers
    obs = _trace_obs({"kda.mix": 0.030, "kda.conv": 0.005,
                      "kda.recur": 0.025, "attn.gqa": 0.005})
    got = readers.read_metric(name, obs)
    assert 0 < got < 100
    if name == "kda_share_of_step":
        assert got == pytest.approx(50.0)
    if name == "gated_attention_share_of_step":
        assert got == pytest.approx(100 * 0.005 / 0.12)
    # a program without the KDA scopes (the parent) or a run without a
    # trace: nothing, not zero and not an error
    bare = _trace_obs({})
    bare["counters"].pop("decode.kda.layer_steps")
    assert readers.read_metric(name, bare) is None
    untraced = dict(bare, trace=None)
    untraced.pop([k for k in untraced if k.startswith("kda_scopes:")][0])
    assert readers.read_metric(name, untraced) is None
