"""Multi-device integration tests.  Each scenario runs in a subprocess so the
forced host-device count never leaks into this process (see conftest note)."""
import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(__file__)
_SRC = os.path.abspath(os.path.join(_HERE, "..", "src"))


def _run(name: str, timeout: int = 600):
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    # the scenarios measure on virtual CPU devices by design: pin the
    # platform so a child never competes with its parent for a chip
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(_HERE, "multidev_scenarios.py"), name],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"{name} failed:\n{out.stdout}\n{out.stderr}"
    assert "SCENARIO OK" in out.stdout


def test_lower_all_smoke_shapes():
    _run("lower_all_smoke_shapes")


def test_ddp_compressed_training():
    _run("ddp_compressed_training")


def test_elastic_checkpoint_restore():
    _run("elastic_checkpoint_restore")


def test_gspmd_vs_single_device_numerics():
    _run("gspmd_vs_single_device_numerics")


def test_seq_sharded_decode_numerics():
    _run("seq_sharded_decode_numerics")


def test_sharded_paged_decode_parity():
    _run("sharded_paged_decode_parity")


def test_disagg_vs_monolithic_parity():
    _run("disagg_vs_monolithic_parity")


def test_disagg_smoke():
    _run("disagg_smoke")


def test_chip_smoke_four_chips():
    _run("chip_smoke_four_chips")
