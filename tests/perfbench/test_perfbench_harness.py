"""The benchmark harness on the CPU, on tiny cells: cells, mixes and
metrics found by name, the result line's keys, no recompile across
seeds, the horizon guard, and `correct` false when the timed path is
broken underneath."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import yaml

from perfbench_testlib import REPO, add_config, edit_benchmark, recorded_trace
from perfbench import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(root, cell, seed=2**31 + 5, seconds=0.5, trace=False,
         patch=None):
    out = harness.run(root, cell, seed, seconds, trace, platform=None,
                      patch=patch)
    info = out.pop("_info")
    return out, info


def test_every_cell_and_metric_of_the_benchmark_resolves():
    bench = harness.load_benchmark(REPO)
    for wl in bench["workloads"]:
        cell = harness.resolve_cell(REPO, wl["name"])
        assert os.path.exists(cell.config_path)
        assert os.path.exists(os.path.join(
            REPO, "perfbench", "references",
            cell.meta["reference"] + ".py"))
        assert cell.end_to_end and cell.per_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        mod = harness.load_module(os.path.join(
            REPO, "perfbench", "metrics", m["name"] + ".py"), m["name"])
        assert callable(mod.read)


def test_added_config_mix_and_metric_are_found_by_name(tiny_root,
                                                       tmp_path):
    """A later PR adds a cell and a metric with new files and new
    BENCHMARK.json entries only."""
    import shutil

    root = str(tmp_path / "root")
    shutil.copytree(tiny_root, root)
    cell = harness.resolve_cell(root, "phold_tiny.uniform")
    with open(cell.config_path) as f:
        add_config(root, "phold_fixture", yaml.safe_load(f), cell.meta)
    with open(os.path.join(root, "perfbench", "traffic",
                           "fixture_mix.json"), "w") as f:
        json.dump({"apps": {"phold": {"msgload": 2}}, "horizon_s": 50,
                   "warmup_s": 1, "segment_s": 0.5}, f)
    with open(os.path.join(root, "perfbench", "metrics",
                           "fixture.segments.py"), "w") as f:
        f.write("def read(rec):\n    return len(rec['window']"
                "['segments'])\n")
    edit_benchmark(
        root,
        configs=[{"name": "phold_fixture", "source": "test",
                  "file": "perfbench/configs/phold_fixture.yaml",
                  "reduced": [], "why": "test"}],
        workloads=[{"name": "phold_fixture.fixture_mix",
                    "config": "phold_fixture", "traffic": "fixture_mix",
                    "chips": 1, "why": "test"}],
        end_to_end=[{"name": "fixture.segments", "unit": "segments",
                     "better": "higher", "bound": 0.1,
                     "source": "host_clock",
                     "workloads": ["phold_fixture.fixture_mix"]}])
    new = harness.resolve_cell(root, "phold_fixture.fixture_mix")
    assert new.mix["apps"]["phold"]["msgload"] == 2
    raw = harness.raw_config(new, 7)
    assert all("msgload=2" in g["processes"][0]["args"]
               for g in raw["hosts"].values())
    assert raw["general"]["stop_time"] == "50 s"
    assert "fixture.segments" in [m["name"] for m in new.end_to_end]
    # the new metric is this cell's alone
    assert "fixture.segments" not in [
        m["name"] for m in harness.resolve_cell(
            root, "phold_tiny.uniform").end_to_end]
    got = harness.read_metrics(root, new.end_to_end, {
        "chips": 1, "window": {"segments": [{}, {}, {}], "sim_s": 1.5,
                               "wall_s": 2.0, "packets": 10},
        "setup": {"total_s": 3.0}, "memory": {"peak_bytes": 0}})
    assert got["fixture.segments"] == {"value": 3.0, "unit": "segments"}
    assert "peak_hbm_bytes" not in got     # nothing to read: left out


def test_mix_sets_app_args_and_horizon():
    raw = {"general": {}, "hosts": {
        "c": {"processes": [{"path": "model:tgen_client",
                             "args": "server=s size=1KiB count=4"}]},
        "s": {"processes": [{"path": "model:tgen_server"}]}}}
    harness.apply_mix(raw, {"apps": {"tgen_client": {"count": 9,
                                                     "retry": "200ms"}},
                            "horizon_s": 12})
    assert raw["hosts"]["c"]["processes"][0]["args"] == \
        "server=s size=1KiB count=9 retry=200ms"
    assert "args" not in raw["hosts"]["s"]["processes"][0]
    assert raw["general"]["stop_time"] == "12 s"


@pytest.mark.parametrize("cell", ["phold_tiny.uniform", "tgen_tiny.bulk"])
def test_result_has_exactly_the_contract_keys(tiny_root, cell):
    out, info = _run(tiny_root, cell)
    assert list(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == info["segments"] >= 1
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    names = {m["name"] for m in harness.resolve_cell(
        tiny_root, cell).end_to_end}
    # no peak memory on the CPU backend; every other metric is read
    assert set(out["metrics"]) == names - {"peak_hbm_bytes"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert info["compiles_in_window"] == 0
    # the last round may pass the segment's end (final_stop is the
    # horizon); the reference recomputes to the clock reached
    assert info["t_reached_ns"] >= info["t_end_ns"]
    assert out["checks"]["clock_short_ns"]["value"] == 0


def test_traced_result_adds_breakdown_and_per_layer_metrics(tiny_root,
                                                           monkeypatch):
    """The CPU backend records no device plane: the reduction is fed
    the committed v5e trace in its place."""
    from perfbench import xplane

    monkeypatch.setattr(xplane, "reduce_dir",
                        lambda _: xplane.reduce_file(recorded_trace()))
    out, _ = _run(tiny_root, "phold_tiny.uniform", trace=True,
                  seconds=0.3)
    assert list(out) == KEYS[:5] + ["breakdown", "checks"]
    assert out["correct"] is True
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 1 <= len(out["breakdown"]["device_ops"]) <= 10
    names = {m["name"] for m in harness.resolve_cell(
        tiny_root, "phold_tiny.uniform").per_layer}
    assert set(out["metrics"]) == names


def test_a_new_seed_does_not_recompile(tiny_root):
    _run(tiny_root, "phold_tiny.uniform", seed=11)
    out, info = _run(tiny_root, "phold_tiny.uniform", seed=2**31 + 77)
    assert out["correct"] is True
    assert info["cache_misses"] == 0 and info["cache_hits"] >= 1
    assert info["compiles_in_window"] == 0


def test_window_fails_when_traffic_stops(tiny_root):
    def silent(runner):
        """From the window's third segment on, nothing happens."""
        real, calls = runner.engine.run, []

        def run(state, stop=None, final_stop=None):
            calls.append(stop)
            if len(calls) > 3:
                return state, np.int64(0)
            return real(state, stop=stop, final_stop=final_stop)
        runner.engine.run = run

    with pytest.raises(harness.CellError, match="traffic stopped"):
        _run(tiny_root, "phold_tiny.uniform", seconds=30, patch=silent)


def test_window_fails_at_the_horizon(tiny_root, tmp_path):
    import shutil

    root = str(tmp_path / "root")
    shutil.copytree(tiny_root, root)
    path = os.path.join(root, "perfbench", "traffic", "uniform.json")
    with open(path) as f:
        mix = json.load(f)
    mix["horizon_s"] = mix["warmup_s"] + 2 * mix["segment_s"]
    with open(path, "w") as f:
        json.dump(mix, f)
    with pytest.raises(harness.CellError, match="phold_tiny.uniform"):
        _run(root, "phold_tiny.uniform", seconds=30)


def _unchanged(runner, n_warm):
    """The window's segment returns its state unchanged (an earlier
    one would only delay work that the next segment catches up)."""
    real, calls = runner.engine.run, []

    def run(state, stop=None, final_stop=None):
        calls.append(stop)
        if len(calls) == n_warm + 1:    # the warm-up's calls come first
            return state, np.int64(0)
        return real(state, stop=stop, final_stop=final_stop)
    runner.engine.run = run


def _half_left_out(runner, n_warm):
    """The second half of the hosts keep their state: their events of
    the segment are dropped."""
    real = runner.engine.run

    def run(state, stop=None, final_stop=None):
        new, rounds = real(state, stop=stop, final_stop=final_stop)
        half = next(iter(state.values())).shape[0] // 2

        def keep(a, b):
            if a.ndim and a.shape[0] == 2 * half:
                return b.at[half:].set(a[half:])
            return b
        return {k: keep(state[k], new[k]) for k in new}, rounds
    runner.engine.run = run


def _altered(runner, n_warm):
    """One host's checksum is altered where it is produced."""
    real = runner.engine.run

    def run(state, stop=None, final_stop=None):
        new, rounds = real(state, stop=stop, final_stop=final_stop)
        new["chk"] = new["chk"].at[3].add(1)
        return new, rounds
    runner.engine.run = run


@pytest.mark.parametrize("cell", ["phold_tiny.uniform", "tgen_tiny.bulk"])
@pytest.mark.parametrize("fault", [_unchanged, _half_left_out, _altered],
                         ids=["state_unchanged", "half_left_out",
                              "answer_altered"])
def test_correct_is_false_when_the_timed_path_is_broken(tiny_root, fault,
                                                        cell):
    mix = harness.resolve_cell(tiny_root, cell).mix
    n_warm = round(mix["warmup_s"] / mix["segment_s"])
    # a window of one segment
    out, info = _run(tiny_root, cell, patch=lambda r: fault(r, n_warm),
                     seconds=1e-4)
    assert info["segments"] == 1
    assert out["correct"] is False
    over = {k for k, v in out["checks"].items() if v["value"] > v["limit"]}
    # a segment that did not run leaves the clock short of its end,
    # and the reference, run to that clock, agrees with every host
    assert ("clock_short_ns" if fault is _unchanged
            else "hosts_differing") in over


def test_cli_refuses_without_a_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "tgen_10000.bulk", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert "correct" not in p.stdout


def test_cli_fails_outside_a_checkout(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    tmp_path / "perfbench")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "tgen_10000.bulk", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
