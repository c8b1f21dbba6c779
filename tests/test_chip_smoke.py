"""chip_smoke.py off the chip: it refuses to run, and its phases pass at a tiny size.

The phases run here with a cut serpytor-demo-100m and the qwen3 smoke
variant, with the kernels in the Pallas interpreter, so a later change that
breaks the smoke's logic (crash, resume, serving, the data=4 comparison)
fails on the CPU before it costs a chip run.
"""

import dataclasses
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from repro.configs import get_config, smoke_variant  # noqa: E402

TINY_TRAIN = dataclasses.replace(
    get_config("serpytor-demo-100m"),
    num_layers=2,
    d_model=128,
    num_heads=4,
    num_kv_heads=2,
    head_dim=32,
    d_ff=256,
    vocab_size=512,
    attn_impl="interpret",
)


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    return env


def test_refuses_to_run_without_a_tpu(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=_env(),
        cwd=tmp_path,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_train_phase_resumes_bit_identical(tmp_path):
    r = chip_smoke.train_phase(TINY_TRAIN, str(tmp_path), batch=2, seq=64)
    assert r["step0_loss"] > 0
    assert not r["kernel_in_step"]  # interpreter: no Mosaic call in the step


def test_serve_phase_answers_through_the_gateway():
    cfg = dataclasses.replace(smoke_variant(get_config("qwen3-1.7b")), attn_impl="interpret")
    r = chip_smoke.serve_phase(cfg, prompt_len=16, new_tokens=3)
    assert r["requests"] == chip_smoke.SERVE_REQUESTS
    assert r["tokens_generated"] == 3 * chip_smoke.SERVE_REQUESTS


def test_four_chip_phase_on_four_host_devices(tmp_path):
    code = (
        "import sys, chip_smoke, test_chip_smoke as t\n"
        "r = chip_smoke.four_chip_phase(t.TINY_TRAIN, sys.argv[1], batch=8, seq=64)\n"
        "print('rel', r['max_rel_loss_diff'])\n"
    )
    env = _env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] += os.pathsep + os.path.join(ROOT, "tests")
    out = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path)],
        env=env,
        cwd=tmp_path,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "rel " in out.stdout


def test_check_raises_even_without_asserts():
    with pytest.raises(chip_smoke.SmokeFailure, match="a failed check"):
        chip_smoke.check(False, "a failed check")
    chip_smoke.check(True, "a failed check")
