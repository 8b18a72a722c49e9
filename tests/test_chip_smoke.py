"""The chip smoke script's phases at a tiny size on CPU.

``chip_smoke.py`` runs ann-glove on a TPU.  Here its one-chip and
four-chip phases run on a few thousand rows with the Pallas kernels in
interpret mode, so a change that breaks the script's path or its checks
fails on CPU first.  Without a TPU the script itself must refuse to run.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_one_chip_phase_on_cpu():
    # 5000 rows: below the 6144-row bucket, so the NRT cycle's 32 added
    # rows stay in the bucket exactly like ann-glove's 1,193,472 do.
    out = chip_smoke.one_chip(n_docs=5000, n_queries=64, batch=32, kernel=True)
    assert out["id_agreement"] >= chip_smoke.MIN_ID_AGREEMENT
    assert out["serving_path"] == "packed single launch"


def test_four_chip_phase_on_virtual_devices():
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "chip_smoke.four_chips(n_docs=4096, n_queries=64, batch=32, "
        "kernel=True)" % ROOT
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    assert "match only" in r.stdout


def test_script_refuses_to_run_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
