"""The plain references against the program's serial policy (a second
witness at small sizes), the control that has to fail, and the
independence of tgen's server components that the sampled comparison
rests on."""

from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest
import yaml

from perfbench.harness import apply_mix, compare
from perfbench.references import phold, tgen
from perfbench_testlib import REPO, TINY, _shrink

STOP = 4 * 10**9


def _raw(name, seed):
    src, sizes, _, traffic = TINY[name]
    with open(os.path.join(REPO, "perfbench", "configs",
                           src + ".yaml")) as f:
        raw = _shrink(yaml.safe_load(f), sizes)
    with open(os.path.join(REPO, "perfbench", "traffic",
                           traffic + ".json")) as f:
        apply_mix(raw, json.load(f))
    raw["general"]["seed"] = seed
    return raw


def _serial(raw, stop):
    from shadow_tpu.config.loader import load_config_str
    from shadow_tpu.core.controller import Controller

    raw = copy.deepcopy(raw)
    raw["general"]["stop_time"] = f"{stop} ns"
    raw["experimental"] = {"scheduler_policy": "serial"}
    c = Controller(load_config_str(yaml.safe_dump(raw, sort_keys=False)))
    c.run()
    attrs = ("events_executed", "packets_sent", "packets_dropped",
             "packets_delivered", "trace_checksum")
    return {col: np.array([getattr(h, a) for h in c.sim.hosts], np.uint64)
            for col, a in zip(("n_exec", "n_sent", "n_drop", "n_deliv",
                               "chk"), attrs)}


def _lossy(raw):
    """PHOLD's deployment is lossless; the witness also covers loss."""
    g = raw["network"]["graph"]
    g["inline"] = g["inline"].replace("packet_loss 0.0 ", "packet_loss 0.05 ")
    return raw


@pytest.mark.parametrize("name,ref,lossy", [
    ("tgen_tiny", tgen, False), ("phold_tiny", phold, False),
    ("phold_tiny", phold, True)], ids=["tgen", "phold", "phold_lossy"])
def test_reference_equals_the_serial_policy(name, ref, lossy):
    raw = _raw(name, 2**31 + 99)
    if lossy:
        raw = _lossy(raw)
    ids, got = ref.run(raw, STOP, pick=3, **TINY[name][2])
    want = _serial(raw, STOP)
    assert ids.size >= 16
    assert got["n_exec"].sum() > 100
    for col in got:
        np.testing.assert_array_equal(got[col].astype(np.uint64),
                                      want[col][ids], err_msg=col)


@pytest.mark.parametrize("name,ref", [("tgen_tiny", tgen),
                                      ("phold_tiny", phold)],
                         ids=["tgen", "phold"])
@pytest.mark.parametrize("seed", [5, 2**31 + 1, 3_000_000_017])
def test_control_is_not_correct(name, ref, seed):
    """The control runs events of one instant in reverse source order:
    it breaks the order guarantee, and the comparison sees it."""
    raw = _raw(name, seed)
    ids, good = ref.run(raw, STOP, pick=seed, **TINY[name][2])
    _, bad = ref.run(raw, STOP, pick=seed, control=True,
                     **TINY[name][2])
    full = {c: np.zeros(int(ids.max()) + 1, np.uint64) for c in bad}
    for c in bad:
        full[c][ids] = bad[c]
    checks = compare(full, ids, good)
    assert checks["hosts_differing"]["value"] > checks[
        "hosts_differing"]["limit"]


def test_tgen_components_do_not_depend_on_each_other():
    raw = _raw("tgen_tiny", 17)
    ids_all, every = tgen.run(raw, STOP, pick=0, per_group=100)
    ids, some = tgen.run(raw, STOP, pick=17, per_group=1)
    assert 0 < ids.size < ids_all.size
    where = np.searchsorted(ids_all, ids)
    for col in some:
        np.testing.assert_array_equal(some[col], every[col][where])
