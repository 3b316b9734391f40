"""The benchmark's own tests (CPU only; nothing here loads libtpu):
the schema of ``BENCHMARK.json``, the trace reducer on one small recorded
TPU trace, the traffic design, a tiny-size rehearsal of the serving and the
training driver, the lower-precision controls and the broken-path runs that
must come out as not correct, and the proof that a cell, a configuration, a
traffic mix and a per-layer metric are added by files and entries alone."""
import argparse
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.harness import readers, stats, trace_reduce, traffic  # noqa: E402
from perf.harness.spec import Cell  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ schema
def test_benchmark_json_schema():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    for n in names:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in b[k]}) == len(b[k])
    metric_names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]["file"]))
        assert os.path.exists(os.path.join(
            ROOT, "perf", "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    assert {c["name"] for c in b["configs"]} == {
        w["config"] for w in b["workloads"]}

    def reports(metric):
        return set(metric.get("workloads") or cells)

    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e, m
        # every cell that reports the layer metric reports what it moves
        assert reports(m) <= reports(e2e[m["moves"]]), m["name"]
        f = os.path.join(ROOT, "perf", "metrics", m["name"] + ".json")
        with open(f) as fh:
            own = json.load(fh)
        assert (own["layer"], own["moves"], own["unit"]) == (
            m["layer"], m["moves"], m["unit"])
        kind = own["reader"]["kind"]
        assert kind in readers.KINDS or (kind == "python" and os.path.exists(
            f[:-5] + ".py"))
    for w in cells:
        mine = [m for m in b["per_layer"] if w in reports(m)]
        assert mine and any(w in reports(m) and m["name"] != "setup_s"
                            for m in b["end_to_end"])
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in b["command"]:
        assert not word.startswith("/") and ".." not in word


def test_config_files_state_source_and_cuts():
    b = _bench()
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"].startswith(c["source"])
        assert cfg["reduced"] == c["reduced"]
        assert "assumed" in cfg and "precision" in cfg
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head)",
                                 key)


# ----------------------------------------------------------- trace reducer
def test_trace_reducer_on_recorded_tpu_trace():
    """perf/testdata/toy_v5e.xplane.pb: three executions of one jitted toy
    step on a v5e, each under a host annotation, with a 10 ms host sleep
    between them (perf/tools/trace_probe.py wrote it)."""
    r = trace_reduce.reduce_trace(
        os.path.join(ROOT, "perf", "testdata", "toy_v5e.xplane.pb"))
    assert list(r.devices) == ["/device:TPU:0"]
    n, total = r.module_seconds(r"^jit_step\(")
    assert n == 3 and 5.6e-5 < total < 5.8e-5
    ops = r.op_seconds()
    assert set(ops) == {"copy-start", "copy-done", "reduce", "fusion"}
    assert ops["fusion"] == pytest.approx(3.8929e-5, rel=1e-3)
    # busy is the union of op intervals: at most the module time, and idle
    # nearly all of a 23 ms window that holds 57 us of work
    assert 0 < r.busy_s <= total
    assert 0.02 < r.window_s < 0.03
    assert 1 - r.busy_s / r.window_s > 0.99
    gaps = r.idle_gaps(top=2, annotations=["toy."])
    assert [g[0] for g in gaps] == ["host:toy.host_sleep"] * 2
    assert all(0.010 < g[1] < 0.013 for g in gaps)
    assert r.matching_seconds([r"reduce\("]) == pytest.approx(
        ops["reduce"], rel=1e-6)
    d = r.devices["/device:TPU:0"]
    assert d["collective_s"] == 0 and d["collective_exposed_s"] == 0


def test_interval_arithmetic():
    total, merged = trace_reduce._union([(0, 2), (1, 3), (5, 6)])
    assert total == 4 and merged == [[0, 3], [5, 6]]
    # a collective from 2 to 7, compute from 0 to 3 and 5 to 6: 3 exposed
    assert trace_reduce._subtract([[2, 7]], merged) == 3
    assert trace_reduce.op_kind("%multiply_reduce_fusion.12 = f32[8]{0} "
                                "fusion(...)") == "multiply_reduce_fusion"


def test_percentile_is_numpy_linear():
    rng = np.random.default_rng(0)
    v = rng.random(20).tolist()
    for q in (50, 75, 90, 99):
        assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


# ------------------------------------------------------------ traffic design
def test_design_is_the_same_for_every_seed():
    tr = Cell("gpt2_medium.chat_paced").traffic
    a = traffic.design(tr, 50, 1, 50257)
    b = traffic.design(tr, 50, 2**31 + 12345, 50257)
    assert len(a) == len(b) == round(50 * tr["arrivals"]["rate_rps"])
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert a[0]["due_s"] == 0.0

    def pairs(d):
        return sorted((len(r["prompt"]), r["max_new_tokens"]) for r in d)

    assert pairs(a) == pairs(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    lens = tr["lengths"]
    for r in a:
        assert lens["prompt"]["min"] <= len(r["prompt"]) <= lens["prompt"]["max"]
        assert lens["output"]["min"] <= r["max_new_tokens"] <= lens["output"]["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= tr["check"]["pad_to"]
    # the seed does move the order
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert traffic.design(tr, 50, 7, 50257) == traffic.design(tr, 50, 7, 50257)


def test_paced_arrivals_are_evenly_spaced_inside_the_window():
    due = traffic.due_times({"kind": "paced", "rate_rps": 2.0}, 10)
    assert len(due) == 20 and due[0] == 0.0 and max(due) < 10
    assert {round(b - a, 9) for a, b in zip(due, due[1:])} == {0.5}
    # a window that is not a whole number of periods still holds every
    # request due inside it
    assert len(traffic.due_times({"kind": "paced", "rate_rps": 0.5}, 51)) == 26
    with pytest.raises(ValueError):
        traffic.due_times({"kind": "poisson", "rate_rps": 1.0}, 10)


def test_share_at_most_reader():
    flight = [(0.0, "decode.step", None, rows) for rows in (1, 2, 4, 4, 5, 8)]
    flight += [(0.0, "decode.prefill", None, 3), (0.0, "decode.step", None, 0)]
    spec = {"kind": "flight_share_at_most", "event": "decode.step",
            "at_most": 4}
    assert readers.flight_share_at_most({"flight": flight}, spec) == \
        pytest.approx(100.0 * 4 / 6)
    assert readers.flight_share_at_most({"flight": []}, spec) is None


# ------------------------------------------------- tiny cells in a temp root
TINY_GPT2 = {"n_embd": 32, "n_head": 2, "n_layer": 2, "n_positions": 64,
             "n_inner": None, "vocab_size": 97, "layer_norm_epsilon": 1e-5,
             "initializer_range": 0.02}
TINY_BERT = {"hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 2, "intermediate_size": 64,
             "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1,
             "max_position_embeddings": 16, "type_vocab_size": 2,
             "vocab_size": 101, "initializer_range": 0.02,
             "layer_norm_eps": 1e-5, "max_predictions_per_seq": 3}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout in miniature: the real BENCHMARK.json, metric files and
    traffic files, with the two configurations and the mixes cut to a size
    the CPU runs in seconds."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perf", "metrics"),
                    os.path.join(root, "perf", "metrics"))
    os.makedirs(os.path.join(root, "perf", "traffic"))
    os.makedirs(os.path.join(root, "perf", "configs"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name, tiny in (("gpt2_medium", TINY_GPT2), ("bert_base", TINY_BERT)):
        with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
            cfg = json.load(f)
        cfg.update(tiny)
        with open(os.path.join(root, "perf", "configs", name + ".json"),
                  "w") as f:
            json.dump(cfg, f)
    for name in ("chat_paced", "pretrain_s512"):
        with open(os.path.join(ROOT, "perf", "traffic", name + ".json")) as f:
            tr = json.load(f)
        if name == "chat_paced":
            tr["arrivals"]["rate_rps"] = 4.0
            tr["lengths"] = {
                "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
            tr["session"].update(batch_buckets=[1, 2], seq_buckets=[8, 16],
                                 page_size=8)
            tr["check"] = {"pad_to": 32}
            tr["drain_limit_s"] = 60
            tr["trace_window_s"] = [0.2, 0.5]
        else:
            tr.update(seq_len=16, per_chip_batch=4, reference_block_rows=2,
                      trace_steps=2)
        with open(os.path.join(root, "perf", "traffic", name + ".json"),
                  "w") as f:
            json.dump(tr, f)
    return root


def _args(seed=5, seconds=2.0, trace=0, control=0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              control=control, workload=None)


def _run(root, workload, args, n_devices=1):
    import jax
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    import run as perf_run
    cell = Cell(workload, root=root)
    clock = perf_run.Clock(__import__("time").perf_counter(), root)
    devices = jax.devices()[:n_devices]
    driver = __import__("importlib").import_module(
        "perf.drivers." + cell.traffic["driver"])
    out = driver.run(cell, args, devices, clock)
    line = perf_run.result_line(cell, args, out, clock, devices)
    return cell, out, json.loads(json.dumps(line))


def _checks(out):
    return {n: (v, lim, ok) for n, v, lim, ok, _w in out["checks"]}


# ------------------------------------------------------- serving rehearsal
def test_serve_rehearsal_last_line_and_design(tiny_root):
    cell, out, line = _run(tiny_root, "gpt2_medium.chat_paced", _args())
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] == 8 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    c = _checks(out)
    # float32 program against the float32 reference: the served token is
    # the reference's own choice, up to a rounding-sized near tie
    assert c["logit_gap_max"][2] and c["logit_gap_max"][0] < 1e-4
    assert c["logit_gap_mean"][2]
    assert c["compiles_in_window"][2]
    # a CPU run is never a correct device measurement
    assert line["correct"] is False and line["device"]["platform"] == "cpu"


def test_serve_traced_run_reads_per_layer_metrics(tiny_root):
    cell, out, line = _run(tiny_root, "gpt2_medium.chat_paced",
                           _args(seed=6, trace=1))
    got = set(line["metrics"])
    # what needs no device trace is read on the CPU too
    assert {"gen_late_p99_ms", "gateway_queue_wait_p50_ms", "ttft_p90_ms",
            "prefill_p50_ms", "rows_per_step", "steps_below_bucket8_share",
            "prefill_share_of_loop", "gap_p99_ms", "kv_pages_live_share",
            "decode_step_p50_ms", "compiles_in_window.serve"} <= got
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
    assert 1 <= line["metrics"]["rows_per_step"]["value"] <= 2
    assert not (got & {"train_tokens_per_s", "setup_s"})


def test_serve_broken_path_is_not_correct(tiny_root, monkeypatch):
    """A token altered where it is produced: the reference's logit of the
    served token falls far below its best."""
    from perf.drivers import serve_open_loop as drv
    send = drv.send_design

    def tampering_send(*a, **kw):
        results = send(*a, **kw)
        for r in results:
            if r.error is None:
                r.tokens[len(r.tokens) // 2] = (r.tokens[0] + 17) % 97
        return results

    monkeypatch.setattr(drv, "send_design", tampering_send)
    _cell, out, line = _run(tiny_root, "gpt2_medium.chat_paced",
                            _args(seed=8))
    c = _checks(out)
    assert not c["logit_gap_max"][2] and c["logit_gap_max"][0] > 0.01
    assert line["correct"] is False


def test_serve_control_lower_precision_fails(tiny_root):
    """The reference with its keys and values stored in 8 bits, put in the
    program's place on three seeds: where it puts another token first, the
    float32 reference's logit of that token lies well below its best; the
    float32 reading of the same tokens is exactly 0."""
    import jax
    from perf.reference import gpt2 as ref
    from perf.systems import decode_gateway
    cell = Cell("gpt2_medium.chat_paced", root=tiny_root)
    cfg = dict(cell.config, initializer_range=0.2)   # peaked logits
    sound, low = [], []
    for seed in (1, 2, 3):
        w = decode_gateway.weights(cfg, seed)
        rng = np.random.default_rng(seed)
        prompt = rng.integers(0, 97, 12).tolist()
        served = []
        for _ in range(16):       # greedy by the float32 reference itself
            seq = np.zeros(32, "int32")
            seq[:len(prompt) + len(served)] = prompt + served
            logits = ref.forward(w, jax.numpy.asarray(seq), n_layer=2,
                                 n_head=2, eps=1e-5)
            served.append(int(np.argmax(
                logits[len(prompt) + len(served) - 1])))
        sound.append(float(ref.served_token_gaps(
            w, cfg, [prompt], [served], 32, chunk=2).max()))
        low.append(float(ref.served_token_gaps(
            w, cfg, [prompt], [served], 32, precision="kv_fp8",
            chunk=2).max()))
    assert max(sound) == 0.0
    # a widest gap swings by its nature (0 where no token flips): at this
    # size two of the three seeds flip, by 0.02 and 0.04
    assert max(low) > 1e-3 and sum(x > 1e-3 for x in low) >= 2


# ------------------------------------------------------ training rehearsal
def test_train_rehearsal_matches_reference(tiny_root):
    cell, out, line = _run(tiny_root, "bert_base.pretrain_s512",
                           _args(seed=11, seconds=1.0))
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    c = _checks(out)
    # same float32 arithmetic on the CPU: the program and the plain
    # reference, with the same dropout masks, agree to rounding
    assert c["loss_rel_gap_max"][0] < 1e-5
    assert c["grad_projection_gap"][0] < 1e-3
    assert c["grad_norm_gap_worst_leaf"][0] < 1e-3
    assert c["param_delta_gap_worst_leaf"][0] < 1e-3
    assert c["compiles_in_window"][2]
    assert line["correct"] is False        # CPU


def test_memory_peak_is_the_programs_own(tiny_root, capsys):
    """The training cell's memory is read before the plain reference runs:
    the compiled step's footprint (arguments + temporaries + outputs that
    alias nothing) or the allocator's peak, whichever is larger — never the
    two added up."""
    _cell, out, line = _run(tiny_root, "bert_base.pretrain_s512",
                            _args(seed=14, seconds=0.5))
    printed = capsys.readouterr().out
    mem = json.loads([ln for ln in printed.splitlines()
                      if ln.startswith("memory ")][0][7:])
    assert mem["step_bytes"] == mem["arguments"] + mem["temporaries"] \
        + mem["outputs_not_aliased"] > 0
    assert out["memory_peak_bytes"] == max(mem["step_bytes"],
                                           mem["allocator_peak_bytes"])
    assert line["device"]["memory_peak_bytes"] == out["memory_peak_bytes"]
    # the reference ran after the window and after that reading
    assert printed.index("memory ") < printed.index("reference: ")
    assert printed.index("setup ") < printed.index("memory ")


def test_train_driver_takes_another_family_as_a_system_module(
        tiny_root, monkeypatch):
    """The drivers hold the window only: a system module of another family
    (here least squares by gradient descent, in numpy) brings its own
    weights, batches, reference and step, and the training driver runs it
    with no edit."""
    import types

    toy = types.ModuleType("perf.systems.toy_lsq")
    lr = 0.05

    def weights(cfg, seed, device=None):
        return {"w": np.random.default_rng(seed).normal(size=4)}

    def batches(cfg, traffic, seed, rows):
        rng = np.random.default_rng(seed + 1)
        return [(rng.normal(size=(rows, 4)), rng.normal(size=rows))
                for _ in range(traffic["batch_pool"])]

    def grad(w, batch):
        x, y = batch
        r = x @ w - y
        return float(np.mean(r * r)), 2 * x.T @ r / len(y)

    def reference_numbers(cfg, traffic, seed, batches, precision="float32"):
        w0 = weights(cfg, seed)["w"]
        w, losses, first = w0.copy(), [], None
        for b in batches:
            loss, g = grad(w, b)
            first = g if first is None else first
            losses.append(loss)
            w = w - lr * g
        return {"losses": losses,
                "grad_norms": {"w": float(np.linalg.norm(first))},
                "grad_projections": first.tolist(),
                "delta_norms": {"w": float(np.linalg.norm(w - w0))}}

    class Toy:
        def __init__(self, w):
            self.w, self.first = w["w"].copy(), None

        def place(self, batch):
            return batch

        def step(self, placed):
            loss, g = grad(self.w, placed)
            self.first = g if self.first is None else self.first
            self.w = self.w - lr * g
            return loss

        def state_norms(self):
            return {"w": float(np.linalg.norm(self.first))}

        def grad_projections(self):
            return self.first.tolist()

        def delta_norms(self, start):
            return {"w": float(np.linalg.norm(self.w - start["w"]))}

        def exhaust_step_keys(self):
            pass

        def step_memory_bytes(self, placed):
            return 64, {"arguments": 64}

    toy.weights, toy.batches = weights, batches
    toy.reference_numbers = reference_numbers
    toy.build = lambda cfg, traffic, seed, w, devices: Toy(w)
    monkeypatch.setitem(sys.modules, "perf.systems.toy_lsq", toy)
    path = os.path.join(tiny_root, "perf", "traffic", "pretrain_s512.json")
    with open(path) as f:
        tr = json.load(f)
    tr["system"] = "toy_lsq"
    with open(path, "w") as f:
        json.dump(tr, f)
    _cell, out, line = _run(tiny_root, "bert_base.pretrain_s512",
                            _args(seed=15, seconds=0.2))
    c = _checks(out)
    assert all(c[n][0] < 1e-12 for n in (
        "loss_rel_gap_max", "grad_projection_gap",
        "grad_norm_gap_worst_leaf", "param_delta_gap_worst_leaf"))
    assert c["window_loss_fall"][2] and line["attempted"] > 3
    assert line["device"]["memory_peak_bytes"] == 64


def test_train_broken_path_is_not_correct(tiny_root, monkeypatch):
    """A step that returns its state unchanged: the parameters' change
    after three steps is zero where the reference's is not."""
    from perf.systems import bert_pretrain as sysmod
    real = sysmod.BertPretrain.step

    def frozen(self, placed):
        state = self.trainer._state
        import jax
        keep = jax.tree_util.tree_map(lambda a: a.copy(), state)
        loss = real(self, placed)
        self.trainer._state = keep
        return loss

    monkeypatch.setattr(sysmod.BertPretrain, "step", frozen)
    _cell, out, line = _run(tiny_root, "bert_base.pretrain_s512",
                            _args(seed=13, seconds=0.5))
    c = _checks(out)
    assert not c["param_delta_gap_worst_leaf"][2]
    assert c["param_delta_gap_worst_leaf"][0] > 0.5
    assert line["correct"] is False


def test_train_control_lower_precision_fails(tiny_root):
    """The reference computed in fp8 (the step below the bfloat16 products
    the configuration states), put in the program's place on three seeds:
    its gradient norms leave the float32 reference's by far more than the
    program's do."""
    from perf.drivers import train_steps as drv
    from perf.systems import bert_pretrain as sysmod
    cell = Cell("bert_base.pretrain_s512", root=tiny_root)
    cfg, tr = cell.config, dict(cell.traffic, batch_pool=3)
    for seed in (21, 22, 23):
        batches = sysmod.batches(cfg, tr, seed, 4)
        ref = sysmod.reference_numbers(cfg, tr, seed, batches)
        low = sysmod.reference_numbers(cfg, tr, seed, batches,
                                       precision="fp8")
        rows = {n: (v, ok) for n, v, _l, ok, _w in drv.compare(
            low, ref, cell.traffic["limits"])}
        # the projections see the error itself: a few percent in fp8,
        # against 1e-6 for the float32 program on this backend
        assert rows["grad_projection_gap"][0] > 0.03
        assert not rows["grad_projection_gap"][1]


# ------------------------------------- added by files and entries alone
def test_cell_config_traffic_and_metric_are_added_as_data(tiny_root):
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(os.path.join(tiny_root, "perf", "configs",
                           "gpt2_medium.json")) as f:
        cfg = json.load(f)
    cfg.update(n_layer=1)
    with open(os.path.join(tiny_root, "perf", "configs", "gpt2_one.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(tiny_root, "perf", "traffic",
                           "chat_paced.json")) as f:
        tr = json.load(f)
    tr["arrivals"] = {"kind": "paced", "rate_rps": 3.0}
    tr["lengths"]["prompt"].update(median=12)
    with open(os.path.join(tiny_root, "perf", "traffic", "chat_longer.json"),
              "w") as f:
        json.dump(tr, f)
    with open(os.path.join(tiny_root, "perf", "metrics",
                           "ttft_p50_ms.json"), "w") as f:
        json.dump({"name": "ttft_p50_ms", "unit": "ms",
                   "layer": "HTTP door serving/gateway",
                   "moves": "ttft_mean_ms",
                   "reader": {"kind": "sample_percentile",
                              "sample": "ttft_ms", "q": 50}}, f)
    b["configs"].append({"name": "gpt2_one", "source": "test",
                         "file": "perf/configs/gpt2_one.json",
                         "reduced": ["n_layer"], "why": "test"})
    b["workloads"].append({"name": "gpt2_one.chat_longer",
                           "config": "gpt2_one", "traffic": "chat_longer",
                           "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if "workloads" in m and m["name"] in ("ttft_mean_ms", "tpot_p50_ms"):
            m["workloads"].append("gpt2_one.chat_longer")
    b["per_layer"].append({"name": "ttft_p50_ms", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "HTTP door serving/gateway",
                           "moves": "ttft_mean_ms",
                           "workloads": ["gpt2_one.chat_longer"]})
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    cell, out, line = _run(tiny_root, "gpt2_one.chat_longer",
                           _args(seed=31, trace=1))
    assert cell.config["n_layer"] == 1
    assert line["attempted"] == 6 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p50_ms"}
    assert line["metrics"]["ttft_p50_ms"]["value"] > 0
