"""The EvaByte cell (``evabyte_pp2.doc_bytes``) rehearsed at a tiny size on
the CPU through the benchmark's own driver, ONE run with the trace, the
telemetry and the controls on: the last lines are well-formed, the sound run
passes its limits, the lower-precision weights and the two broken mechanisms
(no summary column, uniform pooling) fail them, the program's counters are
read where the CPU can read them; the new per-layer metrics read numbers in
[0, 100] from a scripted device trace; the configuration keeps every
published width; the traffic file's design is replayed from ``--seconds`` and
the seed; the entries are in the benchmark (looked up BY NAME, never by
position); and the operations-and-bytes functions give the figures ISSUE 43
and ``PERF.md`` reason with."""
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

from perf.harness import flops, flops_eva  # noqa: E402
from perf.harness import traffic as traffic_mod  # noqa: E402
from perf.harness.spec import Cell  # noqa: E402

CELL = "evabyte_pp2.doc_bytes"
CONFIG = "evabyte_pp2"
SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("step_roofline.serve_eva", "eva_share_of_step",
       "eva_share_of_prefill", "eva_roofline")
TINY = {"hidden_size": 64, "n_layer": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "vocab_size": 97, "window_size": 32, "chunk_size": 4,
        # logits of order 1: at 0.02 and 64 wide every gap is rounding-sized
        "initializer_range": 0.2}
# set as the real cell's are (PERF.md section 2), from readings at THIS size
# on the CPU (seeds 3, 11 and 2**31 + 5): sound runs read a mean gap of
# 0.0004 to 0.0008 and a widest of 0.01 to 0.04; the e4m3 weights a mean of
# 0.03 to 0.05, no summary column 0.45 to 0.75, uniform pooling 0.35 to 0.60
TINY_LIMITS = {"logit_gap_mean": 0.005, "logit_gap_max": 0.3}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One run of the driver at the tiny size: ``(cell, out, printed lines,
    the untraced line, the traced line)``."""
    import contextlib
    import importlib
    import io
    import time
    import jax
    root = str(tmp_path_factory.mktemp("evabyte"))
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
    with open(os.path.join(ROOT, "perf", "traffic", "doc_bytes.json")) as f:
        tr = json.load(f)
    tr["arrivals"]["rate_rps"] = 4.0
    # as the real cell: every prompt at least one window, contexts to 3.5
    tr["lengths"] = {
        "prompt": {"median": 60, "sigma": 0.5, "min": 32, "max": 96},
        "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12}}
    tr["session"].update(batch_buckets=[1, 4], seq_buckets=[32, 64, 96],
                         page_size=8, context_tokens=128, num_pages=17,
                         max_slots=4)
    # every sequence padded to 128 for the reference: one shape to compile
    tr.update(check={"pad_to": 1536}, drain_limit_s=60,
              trace_window_s=[0.2, 0.5], client_threads=16,
              limits=TINY_LIMITS)
    with open(os.path.join(root, "perf", "traffic", "doc_bytes.json"),
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
    _cell, out, _printed, line, _traced = rehearsal
    assert line["attempted"] == 8 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    checks = {n: ok for n, _v, _l, ok, _w in out["checks"]}
    assert checks == {"logit_gap_mean": True, "logit_gap_max": True,
                      "compiles_in_window": True}
    # a CPU run is never a correct device measurement
    assert line["correct"] is False and line["device"]["platform"] == "cpu"


def test_traced_rehearsal_reads_the_counters_and_leaves_the_device_out(
        rehearsal):
    _cell, _out, _printed, _line, traced = rehearsal
    got = traced["metrics"]
    # the program's counters are read on the CPU too; what needs a device
    # plane is left out, not zero and not an error
    assert {"rows_per_step", "prefill_p50_ms", "step_span_p50_ms",
            "loop_host_p50_ms", "kv_pages_live_share",
            "compiles_in_window.serve",
            "steps_ahead_share", "prefill_share_of_loop"} <= set(got)
    assert not (set(NEW) | {"experts_share_of_step", "hc_share_of_step",
                            "step_roofline.serve"}) & set(got)
    assert got["compiles_in_window.serve"]["value"] == 0
    # every per-layer metric that lists the cell is in the line, but those
    # that read the device's trace or its memory
    by_name = {m["name"]: m for m in _cell.per_layer()}
    assert {n for n in set(by_name) - set(got)
            if by_name[n]["source"] != "device_trace"} <= {"hbm_peak_gb.serve"}
    # two samples a second of a window of eight short requests: the shares
    # are read, and may have met no live request
    assert 0 <= got["kv_pages_live_share"]["value"] <= 100
    # the step's counts rode the fetch
    from mxnet_tpu.telemetry import bus
    snap = bus.snapshot()
    c = snap["counters"]
    assert c["decode.eva.layer_steps"] == 2 * c["decode.steps"] > 0
    assert c["decode.eva.ring_rows"] > 0 and c["decode.eva.summary_rows"] > 0
    # a layer and decoded byte (the first byte of each of the 8 requests is
    # its prefill's)
    assert c["decode.eva.summary_rows_written"] == \
        2 * (c["decode.tokens"] - 8)
    assert c["decode.eva.windows_closed"] >= 1
    assert "decode.eva.live_ring_bytes" in snap["gauges"]


def test_each_control_fails_where_the_sound_run_passes(rehearsal):
    """``--control 1``: the reference with every matrix through e4m3, with no
    summary column and with uniform pooling, each put in the program's place,
    fails ``logit_gap_mean`` where the sound run passes it."""
    _cell, _out, printed, _line, _traced = rehearsal
    lines = {ln.split()[1].rstrip(":"): ln for ln in printed
             if ln.startswith("control ")}
    assert list(lines) == ["weights_fp8", "summaries_off", "pool_uniform"]
    for line in lines.values():
        assert "-> fails logit_gap_mean" in line, line


def test_cell_offers_load_at_the_asked_share_of_the_knee():
    """ISSUE 43: 0.70 of the rate the finished change sustains, on the ladder
    (1, 8) with 8 slots, prompts of one to five windows; the design is
    replayed from ``--seconds`` and the seed alone."""
    cell = Cell(CELL)
    tr = cell.traffic
    assert 0.65 <= tr["arrivals"]["rate_rps"] / tr["knee_rps"] <= 0.75
    assert tr["client_threads"] == 128 and tr["arrivals"]["kind"] == "paced"
    assert tr["driver"] == "serve_open_loop"
    assert tr["system"] == "eva_gateway"
    s = tr["session"]
    assert s["batch_buckets"] == [1, 8] and s["max_slots"] == 8
    assert s["seq_buckets"] == [2048, 4096, 6144, 8192, 10240]
    assert s["page_size"] == 16 and s["prefix_sharing"] is True
    # a row stands for chunk_size = 16 tokens: 48 pages a full context, for
    # 8 slots, and the trash page
    tokens_a_page = s["page_size"] * cell.config["chunk_size"]
    assert s["context_tokens"] // tokens_a_page == 48
    assert s["num_pages"] == 8 * 48 + 1
    assert tr["lengths"]["prompt"] == {"median": 5120, "sigma": 0.5,
                                       "min": 2048, "max": 10240}
    assert tr["lengths"]["output"] == {"median": 192, "sigma": 0.7,
                                       "min": 64, "max": 768}
    assert tr["drain_limit_s"] == 75 and tr["trace_window_s"] == [15, 5]
    assert tr["lengths"]["prompt"]["max"] + tr["lengths"]["output"]["max"] \
        == 11008 <= s["context_tokens"] == tr["check"]["pad_to"] == 12288
    assert tr["lengths"]["prompt"]["min"] >= cell.config["window_size"]
    assert set(tr["limits"]) == {"logit_gap_mean", "logit_gap_max"}
    assert tr["controls"] == ["weights_fp8", "summaries_off", "pool_uniform"]
    assert [r[0] for r in tr["sweep"]["rows"]] == sorted(
        r[0] for r in tr["sweep"]["rows"])
    # the design: the count and the lengths follow from the file and
    # --seconds, the seed moves ids and slots only
    a = traffic_mod.design(tr, 50.0, 3, cell.config["vocab_size"])
    b = traffic_mod.design(tr, 50.0, 2**31 + 7, cell.config["vocab_size"])
    assert len(a) == len(b) == 28 == math.ceil(
        50 * tr["arrivals"]["rate_rps"] - 1e-9)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    pairs = sorted((len(r["prompt"]), r["max_new_tokens"]) for r in a)
    assert pairs == sorted((len(r["prompt"]), r["max_new_tokens"])
                           for r in b)
    assert a != b
    assert max(p + o for p, o in pairs) <= 11008
    assert min(p for p, _o in pairs) >= 2048
    assert all(0 <= t < 320 for r in a[:3] for t in r["prompt"])
    assert a == traffic_mod.design(tr, 50.0, 3, cell.config["vocab_size"])


def test_entries_are_in_the_benchmark():
    """Membership, not equality or position: a later cell may share a metric
    and a later PR appends behind these."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == (
            "ttft_mean_ms" if name == "eva_share_of_prefill"
            else "tpot_p50_ms")
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["layer"] == "kernels"
        assert by_name[name]["unit"] == "%"
        for ext in (".json", ".py"):
            assert os.path.exists(os.path.join(ROOT, "perf", "metrics",
                                               name + ext))
    row = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(row) == 1 and row[0]["chips"] == 1
    assert "knee" in row[0]["why"] and len(row[0]["why"]) <= 200
    config = [c for c in b["configs"] if c["name"] == CONFIG]
    assert [c["file"] for c in config] == ["perf/configs/" + CONFIG + ".json"]
    assert config[0]["source"] == SOURCE
    assert config[0]["reduced"] == Cell(CELL).config["reduced"] == ["n_layer"]
    assert len(config[0]["why"]) <= 200
    cell = Cell(CELL)
    mine = {m["name"] for m in cell.per_layer()}
    assert set(NEW) | {"kv_pages_live_share",
                       "prefill_p50_ms", "rows_per_step",
                       "steps_ahead_share", "step_span_p50_ms",
                       "prefill_share_of_loop", "device_idle_share.serve",
                       "hbm_peak_gb.serve"} <= mine
    assert {m["name"] for m in cell.end_to_end()} == {
        "ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    # the other blocks' own metrics are not this cell's: it has no expert
    # layer and no kernel of its own (and ``state_slots_live_share`` is
    # pinned to its first cell by that cell's accepted test)
    assert not {"step_roofline.serve", "state_slots_live_share", "step_roofline.serve_moe",
                "step_roofline.serve_hybrid", "ssm_share_of_step",
                "step_roofline.serve_window_moe", "kda_share_of_step",
                "step_roofline.serve_linear_moe", "hc_share_of_step",
                "experts_share_of_step", "experts_hit_per_step",
                "held_assignment_share", "step_handover_p50_ms",
                "launch_p50_ms", "wake_p50_ms"} & mine
    # the older cells report nothing new
    for other in b["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m["name"] for m in
                                   Cell(other["name"]).per_layer()}
    assert len(b["workloads"]) <= 24 and len(b["configs"]) == 8


def test_configuration_keeps_every_published_width():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = [json.loads(ln) for ln in f if '"name": "EvaByte"' in ln][0]
    assert row["source_url"] == SOURCE
    cfg = Cell(CELL).config
    assert cfg["source"].startswith(SOURCE)
    # every key of the catalog's config, under the same key, unchanged: the
    # cut is the depth the chip runs, which has a key of its own
    assert {k for k, v in row["config"].items() if cfg.get(k) != v} == set()
    assert cfg["reduced"] == ["n_layer"]
    assert cfg["n_layer"] == 16 and cfg["num_hidden_layers"] == 32 == \
        cfg["published"]["num_hidden_layers"]
    assert cfg["n_layer"] >= 4                      # the floor: four periods
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["window_size"],
            cfg["chunk_size"], cfg["num_pred_heads"]) == \
        (4096, 32, 11008, 320, 2048, 16, 8)
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 1 and dep["stage"] == 0
    assert "two-stage pipeline" in dep["what"]
    for key in ("pooling_vectors", "pooling_scale", "pooled_keys_rotated",
                "closed_windows_only", "prediction_heads", "byte_ids"):
        assert cfg["assumed"][key].startswith("INFERENCE")
    for key in ("draws", "eos", "rope", "rms_norm"):
        assert key in cfg["assumed"]
    assert "multibyte_speculation" in cfg["left_out"]
    assert cfg["precision"]["weights"] == cfg["precision"]["cache"] == \
        "bfloat16"
    # a 12,288-byte context reserves 48 pages, not 768
    from mxnet_tpu.serving.decode import pages_needed
    assert pages_needed(12288 - 767, 768, 16 * cfg["chunk_size"]) == 48
    assert pages_needed(12288 - 767, 768, 16) == 768


def test_step_cost_is_the_arithmetic_of_the_issue():
    import numpy as np
    from perf.reference import evabyte
    cfg = Cell(CELL).config
    n = flops_eva.param_counts(cfg)
    # ISSUE 43: 67.11M a layer's four matrices, 135.27M its SwiGLU, 16K of
    # norms and learned vectors: 202.39M; the head 10.49M, the embedding 1.31M
    assert n["attention"] == 67_108_864 and n["mlp"] == 135_266_304
    assert n["layer_float32"] == 16_384
    assert n["head"] == 10_485_760 and n["embedding"] == 1_310_720
    # every parameter of the share is in the reference's table: 3,250M
    total = sum(int(np.prod(shape))
                for shape, _k, _d in evabyte.shapes(cfg).values())
    assert total == 16 * 202_391_552 + 10_485_760 + 1_310_720 + 4096 \
        == 3_250_065_408
    # a ring: 2048 x 4096 x 2 x 2 B = 33.55 MB a sequence and layer; a
    # summary row 16 KiB: 1 KiB a context byte
    per = flops_eva.entry_bytes(cfg)
    assert per == 16_384 and cfg["window_size"] * per == 33_554_432
    assert per // cfg["chunk_size"] == 1024
    # rings 9 x 16 x 33.55 MB = 4.83 GB; summaries 8 x 768 x 16 KiB x 16
    assert 9 * 16 * cfg["window_size"] * per == 4_831_838_208
    assert 8 * 768 * per * 16 == 1_610_612_736
    # a step of 6 rows, each half way through its window behind two closed
    # ones: 6.5 GB of weights, 1.61 GB of live ring entries, 0.40 GB of
    # live summaries; memory-bound, 10.4 ms at 819 GB/s
    cost = flops_eva.decode_step_cost(cfg, 6, 6 * 1024, 6 * 256)
    assert cost["always_read_bytes"] == pytest.approx(6.498e9, rel=1e-3)
    assert cost["ring_bytes"] == 16 * 6 * 1024 * per
    assert cost["summary_bytes"] == 16 * 6 * 256 * per
    eva = flops_eva.eva_step_cost(cfg, 6, 6 * 1024, 6 * 256)
    assert cost["eva_bytes"] == eva["bytes"] == \
        16 * per * (6 * 1024 + 6 * 256 + 6 * 18)
    assert cost["bytes"] == cost["always_read_bytes"] + eva["bytes"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.least_seconds(cost, peaks)
    assert bound == "memory"
    assert least * 1e3 == pytest.approx(10.43, abs=0.05)
    # a step of no live row still reads every weight and nothing of EVA's
    idle = flops_eva.decode_step_cost(cfg, 0, 0, 0)
    assert idle["eva_bytes"] == 0 and idle["bytes"] > 6.49e9


def _trace_obs(scopes, rows=6.0, module_s=0.020, steps=10):
    """What a traced run hands a reader, with the device's part scripted:
    ``steps`` programs of ``module_s`` seconds and ``scopes`` seconds under
    each named scope in all, for the step's and the prefill's pattern."""
    from perf.harness import eva_scopes

    class Reduced:
        def module_seconds(self, pattern):
            return steps, steps * module_s

    cell = Cell(CELL)
    found = dict({s: 0.0 for s in eva_scopes.SCOPES}, **scopes)
    found["_programs"] = steps * module_s
    obs = {"cell": cell, "trace": Reduced(),
           "flight": [(0.0, "decode.step", None, rows)] * steps,
           "counters": {"decode.steps": steps,
                        "decode.eva.layer_steps": 16 * steps,
                        "decode.eva.ring_rows": 16 * steps * rows * 1024,
                        "decode.eva.summary_rows": 16 * steps * rows * 256},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    for name in NEW:
        obs["eva_scopes:" + cell.metric_file(name)["reader"]["module"]] = \
            found
    return obs


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_a_share_in_0_to_100(name):
    """Each new reader on a scripted device trace of the cell's own sizes (6
    rows, a 20 ms program of which 9 under EVA's scopes): a percentage; the
    roofline shares below 100."""
    from perf.harness import readers
    obs = _trace_obs({"attn.eva": 0.085, "eva.pool": 0.005,
                      "ffn.dense": 0.070})
    got = readers.read_metric(name, obs)
    assert 0 < got < 100
    if name.startswith("eva_share_of"):
        assert got == pytest.approx(45.0)
    if name == "step_roofline.serve_eva":
        assert got == pytest.approx(100 * 10.43 / 20.0, abs=0.5)
    # a program without EVA's scopes (the parent) or a run without a trace:
    # nothing, not zero and not an error
    bare = _trace_obs({"ffn.dense": 0.050})
    assert readers.read_metric(name, bare) is None
    untraced = {k: v for k, v in bare.items()
                if not k.startswith("eva_scopes:")}
    untraced["trace"] = None
    assert readers.read_metric(name, untraced) is None
