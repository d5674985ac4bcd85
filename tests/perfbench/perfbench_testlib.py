"""Helpers for the benchmark's tests: the repo root, and adding a
configuration or BENCHMARK.json entries to a copied checkout."""

from __future__ import annotations

import glob
import json
import os

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# tiny copies of each configuration: (config, group sizes by prefix,
# the reference's compare args, traffic mix)
# (tgen's tiny copy compares both servers of each group: every host)
TINY = {"tgen_tiny": ("tgen_10000", {"server": 2, "client": 8},
                      {"per_group": 2}, "bulk"),
        "phold_tiny": ("phold_10000", {"": 16}, {}, "uniform")}


def recorded_trace() -> str:
    """The committed slice of a profiler trace recorded on a v5e."""
    paths = glob.glob(os.path.join(REPO, "perfbench", "fixtures",
                                   "*.xplane.pb"))
    assert paths, "the recorded trace is missing"
    return paths[0]


def _shrink(raw: dict, sizes: dict) -> dict:
    for gname, g in raw["hosts"].items():
        for prefix, q in sizes.items():
            if gname.startswith(prefix):
                g["quantity"] = q
    return raw


def add_config(root: str, name: str, raw: dict, meta: dict) -> None:
    base = os.path.join(root, "perfbench", "configs", name)
    with open(base + ".yaml", "w") as f:
        yaml.safe_dump(raw, f, sort_keys=False)
    with open(base + ".json", "w") as f:
        json.dump(meta, f)


def edit_benchmark(root: str, **append) -> None:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for key, entries in append.items():
        bench[key].extend(entries)
    with open(path, "w") as f:
        json.dump(bench, f)
