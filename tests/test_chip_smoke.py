"""The CPU rehearsal of ``chip_smoke.py``: its phase functions at a tiny size
on the virtual CPU mesh (Pallas in interpret mode), its refusal where JAX
finds no TPU, the exact shape of its last line, and ``--chips 4`` on four
virtual devices.  The chip itself is reached only through the chip tool."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = {
    "bert": {"model": "bert_tiny", "vocab": 200, "batch": 4, "seq": 16,
             "masked": 4, "steps": 3},
    "resnet": {"model": "resnet18_v1", "classes": 10, "batch": 2,
               "image": 32, "steps": 3},
    # the second shape is not a multiple of the block: the kernel masks the
    # padded keys by length where it used to hand back the dense reference
    "flash_shapes": [(2, 2, 128, 32), (1, 2, 200, 32)],
    "decode": {"model": "decode_tiny", "vocab": 96, "max_length": 32,
               "batch_buckets": (1, 4), "seq_buckets": (8,), "page_size": 8,
               "spec_k": 2, "new_tokens": 8},
    "owner_spec": "tests.test_chip_smoke:build_owner",
    "attention": {"heads": 4, "seq": 64, "dim": 8},
    "multichip_steps": 3,
}


def build_owner(aot_cache=None):
    """Builder spec of the fleet rehearsal's device-owner child."""
    return chip_smoke._build_owner(TINY, aot_cache)


def test_train_phase_tiny():
    out = chip_smoke.phase_train(TINY, "cpu")
    assert out["bert"]["losses"][-1] < out["bert"]["losses"][0]
    assert out["bert"]["compiles_per_step"][1:] == [0, 0]
    assert out["resnet"]["compiles_per_step"][-1] == 0
    assert all("cpu" in d.lower() for d in out["resnet"]["params_on"])


def test_flash_phase_tiny():
    out = chip_smoke.phase_flash(TINY, "cpu")
    assert len(out["kernel"]) == 4
    assert not any(k["tpu_custom_call"] for k in out["kernel"])
    # bert_tiny has two layers: each dispatched the kernel op once in the
    # forward without a mask and once in the one with it
    assert out["bert_forward"]["flash_attention_dispatches"] == 4
    assert set(out["bert_forward"]["max_abs_err_vs_dense_path"]) == {
        "mask_none", "mask_ones"}


def test_serve_and_fleet_phases_tiny(tmp_path):
    served = chip_smoke.phase_serve(TINY, "cpu")
    assert served["stats"]["platform"] == "cpu"
    assert served["stats"]["pages_in_use"] == 0
    fleet = chip_smoke.phase_fleet(TINY, "cpu", workdir=str(tmp_path))
    assert fleet["owner"]["pid"] != os.getpid()
    assert fleet["owner_device"]["platform"] == "cpu"
    # same seed, same programs: the owner answers what the in-process
    # gateway answered
    assert fleet["tokens"] == served["tokens"]
    assert os.listdir(tmp_path / ".aot_cache" / "chip_smoke")


def test_multichip_phase_on_four_virtual_devices():
    out = chip_smoke.phase_multichip(TINY, "cpu")
    assert len(out["bert_dp2_tp2"]["param_bytes_per_device"]) == 4
    assert out["bert_dp2_tp2"]["collectives"]["collective_ops"] > 0
    assert len(out["sp4_attention"]["ring"]["on"]) == 4
    assert len(out["peak_bytes_in_use"]) == 4


def test_phase_refuses_an_array_on_the_wrong_platform():
    import jax.numpy as jnp
    with pytest.raises(AssertionError, match="expected every array on a "
                                             "'tpu' device"):
        chip_smoke._require_on("tpu", "probe", [jnp.zeros(2)])


def test_last_line_shape():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.final_line(True, device)
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    assert json.loads(chip_smoke.final_line(False, None)) == \
        {"ok": False, "device": None}


def test_refuses_without_a_tpu(tmp_path):
    """No accelerator: a non-zero exit and no result on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
    # and alone, without the program beside it
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    proc = subprocess.run([sys.executable, str(lone)], capture_output=True,
                          text=True, timeout=60, env=env)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no mxnet_tpu package" in proc.stderr
