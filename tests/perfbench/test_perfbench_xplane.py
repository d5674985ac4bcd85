"""The trace reduction is the yardstick's: pinned on a synthetic trace
whose answer is known by hand, and on a small trace recorded on a v5e
chip, against a plain recount."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from perfbench import xplane
from perfbench_testlib import recorded_trace


def _ev(name, a, b, **stats):
    return NS(name=name, start_ns=float(a), end_ns=float(b),
              stats=list(stats.items()))


def _line(name, events):
    return NS(name=name, events=events)


def test_synthetic_trace_reduces_to_known_numbers():
    device = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [_ev("jit_run", 0, 100)]),
        _line("XLA Ops", [_ev("while", 0, 100), _ev("sort", 10, 40),
                          _ev("fusion", 50, 60), _ev("copy", 120, 130),
                          _ev("late", 250, 260)])])
    host = NS(name="/host:CPU", lines=[_line("python", [
        _ev("perfbench.window", 0, 200), _ev("perfbench.segment", 0, 110),
        _ev("perfbench.counter_read", 110, 200)])])
    out = xplane.reduce_profile(NS(planes=[host, device]))
    assert out["window_s"] == pytest.approx(200e-9)
    assert out["busy_s"] == pytest.approx(110e-9)
    assert dict((k, v) for k, v in out["device_ops"]) == pytest.approx(
        {"while": 60e-9, "sort": 30e-9, "fusion": 10e-9, "copy": 10e-9})
    assert out["idle_gaps"] == [["perfbench.counter_read", 70e-9],
                                ["perfbench.counter_read", 20e-9]]


def test_recorded_chip_trace_matches_a_plain_recount():
    from jax.profiler import ProfileData

    path = recorded_trace()
    out = xplane.reduce_file(path)
    pd = ProfileData.from_file(path)
    host = [e for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name == "perfbench.window"]
    w0, w1 = host[0].start_ns, host[0].end_ns
    devices = [p for p in pd.planes if p.name.startswith("/device:")
               and any(ln.name == "XLA Ops" for ln in p.lines)]
    assert out["n_devices"] == len(devices) >= 1
    ops = [(max(e.start_ns, w0), min(e.end_ns, w1))
           for ln in devices[0].lines if ln.name == "XLA Ops"
           for e in ln.events if e.end_ns > w0 and e.start_ns < w1]
    # busy time and gaps by a depth sweep over every boundary
    edges = sorted([(a, 1) for a, _ in ops] + [(b, -1) for _, b in ops])
    busy, gaps, depth, last = 0.0, [], 0, w0
    for t, step in edges:
        if depth > 0:
            busy += t - last
        elif t > last:
            gaps.append(t - last)
        depth += step
        last = t
    if w1 > last:
        gaps.append(w1 - last)
    assert out["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert out["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    assert [g for _, g in out["idle_gaps"]] == pytest.approx(
        [x / 1e9 for x in sorted(gaps, reverse=True)[:10]])
    # each op's self time: nested ops are not counted twice
    assert sum(v for _, v in out["device_ops"]) <= \
        out["busy_s"] * (1 + 1e-9)
