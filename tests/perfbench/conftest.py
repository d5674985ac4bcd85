"""Fixtures for the benchmark's own tests: a copy of the benchmark in a
temporary checkout, with tiny cells of both configurations added as
new files and new BENCHMARK.json entries (the way a later PR adds a
cell), run on the CPU."""

from __future__ import annotations

import json
import os
import shutil

import pytest
import yaml

from perfbench_testlib import REPO, TINY, _shrink, add_config, edit_benchmark


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout-like root: BENCHMARK.json and perfbench/ copied from
    the repo, plus tiny cells `tgen_tiny.bulk` and
    `phold_tiny.uniform`."""
    root = str(tmp_path_factory.mktemp("bench"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    configs, cells = [], []
    for name, (src, sizes, compare, traffic) in TINY.items():
        conf = os.path.join(REPO, "perfbench", "configs", src)
        with open(conf + ".yaml") as f:
            raw = _shrink(yaml.safe_load(f), sizes)
        with open(conf + ".json") as f:
            meta = json.load(f)
        meta["compare"] = compare
        add_config(root, name, raw, meta)
        configs.append({"name": name, "source": "test",
                        "file": f"perfbench/configs/{name}.yaml",
                        "reduced": [], "why": "test"})
        cells.append({"name": f"{name}.{traffic}", "config": name,
                      "traffic": traffic, "chips": 1, "why": "test"})
    edit_benchmark(root, configs=configs, workloads=cells)
    return root
