"""chip_smoke.py: its refusal rules, and its phases rehearsed on the CPU
at a small size (the platform check is the only thing that differs on
the chip)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _ok_line(out: str) -> bool:
    return any(json.loads(ln).get("ok") for ln in out.splitlines()
               if ln.startswith("{"))


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    assert not _ok_line(capsys.readouterr().out)


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"],
                       cwd=str(tmp_path), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert not _ok_line(p.stdout)


def test_phase_b_device_equals_serial_oracle():
    rec = chip_smoke.phase_b("tgen_100.yaml", stop_s=4.0,
                             platform="cpu")
    assert rec["failed"] == [], rec
    assert rec["runner"] == "DeviceRunner"
    assert rec["hosts_compared"] == 100
    assert rec["packets_routed"] > 0


@pytest.mark.parametrize("n", [4])
def test_phase_mesh_sharded_equals_one_device(n):
    rec = chip_smoke.phase_mesh(n, "tgen_100.yaml", stop_s=4.0,
                                platform="cpu")
    assert rec["failed"] == [], rec
    assert rec[f"chips_{n}"]["mesh_devices"] == n
    assert rec["chips_1"]["mesh_devices"] == 1


def test_check_device_run_names_each_failure():
    facts = {"runner": "DeviceRunner", "platform": "cpu", "ok": False,
             "packets_routed": 0, "packets_delivered": 0,
             "overflow": 3, "x_overflow": 0}
    bad = chip_smoke.check_device_run(facts)
    assert len(bad) == 4, bad
    assert chip_smoke.check_device_run({"runner": "Manager"})
