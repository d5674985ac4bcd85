"""bench.py's backend rule, and BENCH_SMOKE=1 bench.py as a slow-marked
test: bench regressions (like the r5 zero-division on a zero-packet
rung) must fail here before chip time is spent discovering them. CPU
platform, tiny ladder — this validates the bench MECHANICS (ladder,
ratio guards, JSON contract, occupancy record), not the numbers."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_smoke_emits_valid_json(tmp_path):
    env = dict(os.environ,
               BENCH_SMOKE="1",
               JAX_PLATFORMS="cpu",
               SHADOW_TPU_OCC_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    # the contract: exactly one JSON line on stdout, always
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, p.stdout + p.stderr
    result = json.loads(lines[0])
    assert result["metric"] == "packets_routed_per_sec_per_chip"
    assert p.returncode == 0, (result, p.stderr[-2000:])
    assert "error" not in result, result
    assert result["value"] > 0
    assert result["ladder"]["tgen_100"]["speedup"] > 0
    assert result["platform"] == "cpu"
    # the multichip rung ran on the virtual 8-device mesh (conftest's
    # XLA_FLAGS reach the subprocess) and recorded ICI volume next to
    # throughput
    mc = result["multichip"]
    assert "error" not in mc, mc
    if "skipped" not in mc:
        assert mc["n_chips"] > 1
        assert mc["pkts_per_s"] > 0
        assert mc["ici_rows_per_flush"] > 0
        assert mc["ici_rows_per_round"] > 0
        assert mc["exchange"] in ("all_to_all", "all_gather",
                                  "two_phase")
    # the topology rung stamped the hierarchical-vs-dense table cost
    # (1M point skipped under smoke) and met the reduction floor
    topo = result["topology"]
    assert "error" not in topo, topo
    pts = {pt["label"]: pt for pt in topo["points"]}
    assert pts["100k"]["reduction"] >= 100
    assert pts["1k"]["hier_table_bytes"] < pts["1k"]["dense_table_bytes"]
    assert "1M" not in pts
    # the run's measured occupancy landed for tune_10k.py to reuse
    occ_path = result["occupancy_record"]
    with open(occ_path) as f:
        occ = json.load(f)
    assert occ["measured"]["outbox_rows_max"] > 0
    assert occ["workload"]["n_hosts"] == 100


def _bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_without_tpu_fails_unless_cpu_was_asked_for(
        monkeypatch, capsys):
    """No chip and no JAX_PLATFORMS=cpu: the bench fails with a
    non-zero exit and an error record; it never falls back."""
    bench = _bench()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="no TPU"):
        bench.init_backend()
    assert bench.main() == 1
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "no TPU" in result["error"] and result["value"] == 0.0
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.init_backend()[0].platform == "cpu"
