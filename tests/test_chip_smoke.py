"""CPU rehearsal of ``chip_smoke.py``'s control flow at tiny sizes.

The script's phases are plain functions; what keeps them off a CPU is
one device gate. The rehearsal steers the gate — it calls ``run`` with
the CPU devices the suite already has — and hands the phases tiny
sizes, so a later PR cannot break the script's paths, arguments or
checks unnoticed. Mosaic-only proofs (``tpu_custom_call`` in the
lowered text, per-chip ``memory_stats``) are the script's to make on
the chip; everything else runs here exactly as it does there.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from singa_tpu.models.resnet import resnet_conf


@pytest.fixture()
def tiny(tmp_path):
    """The real confs where they are small already, a generated
    ResNet-18 on 32x32 records where the shipped one is not."""
    conf = tmp_path / "resnet18.conf"
    conf.write_text(resnet_conf(
        18, classes=10, batchsize=4, size=32, compute_dtype="bfloat16"
    ))
    return chip_smoke.Sizes(
        resnet_conf=str(conf), resnet_batch=4, resnet_image=36,
        lm_seq=128, lm_samples=4,
        serve_d_model=32, serve_heads=2, serve_d_ff=64, serve_max_len=128,
        serve_new_tokens=6,
        mlp_batch=16, ring_seq=16, ring_samples=32,
    )


def test_gate_refuses_a_cpu():
    """Under JAX_PLATFORMS=cpu the script exits non-zero, says why in
    one line, and never prints the result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(chip_smoke.REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.require_tpu(1)


def test_one_chip_phases_rehearse_on_cpu(tiny, tmp_path):
    out = str(tmp_path / "out")
    summary = chip_smoke.run(
        jax.devices()[:1], chip_smoke.ONE_CHIP_PHASES, 0, tiny, out=out
    )
    assert [s for s, _ in summary["train_resnet50"]["displayed"]] == [1, 3, 5]
    assert [s for s, _ in summary["train_lm_kernel"]["displayed"]] == [0, 2, 4]
    # on a CPU both attention modes are the dense reference
    assert summary["train_lm_kernel"]["max_abs_diff"] == 0.0
    assert summary["serve"]["streams"] == "identical"
    # bulky work files are gone; the summary stays
    assert os.listdir(out) == ["summary.json"]
    with open(os.path.join(out, "summary.json")) as f:
        assert json.load(f)["seed"] == 0


def test_cross_chip_phase_rehearses_on_four_virtual_devices(tiny, tmp_path):
    summary = chip_smoke.run(
        jax.devices()[:4], chip_smoke.FOUR_CHIP_PHASES, 0, tiny,
        out=str(tmp_path / "out"),
    )
    got = summary["cross_chip"]
    assert set(got) == {"mlp data=4", "mlp data=2 x model=2", "ring data=4",
                        "cache"}
