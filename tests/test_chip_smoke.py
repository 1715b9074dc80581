"""CPU rehearsal of ``chip_smoke.py``: its phases at a small width, in
interpret-mode Pallas, and its refusal to run without a TPU."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_phase_image_rehearsal():
    """Phase A at width 32: the searched 4-node resnet101 plan on the
    pallas backend matches ``run_reference``; only POOL runs on XLA."""
    rec = chip_smoke.phase_image(32)
    assert rec["ok"], rec
    assert rec["rel_err"] <= rec["tol"]
    assert rec["pallas_records"] > 0 and not rec["unexpected_xla"]
    assert all("POOL" in r for r in rec["xla_reasons"]), rec["xla_reasons"]


def test_phase_decode_rehearsal():
    """Phase B with the tiny decode spec: pallas paged attention through
    ``DecodeSession`` matches ``reference_decode``."""
    rec = chip_smoke.phase_decode("tiny")
    assert rec["ok"], rec
    assert rec["tokens_match"]


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                        *args], capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
