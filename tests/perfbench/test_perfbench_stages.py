"""The stage reduction (perfbench/stages.py) on a synthetic trace and
synthetic HLO text whose per-stage self times are known by hand."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from perfbench import stages

RUN = 'op_name="jit(_run_shard)/while'
HLO = f"""HloModule jit__run_shard

%fused_computation (param_0: s64[8]) -> s64[8] {{
  %param_0 = s64[8]{{0}} parameter(0)
  ROOT %add.1 = s64[8]{{0}} add(%param_0, %param_0), metadata={{{RUN}/body/cond/branch_1_fun/engine.flush/engine.merge/add"}}
}}

%pop_body (p.1: (s64[8])) -> (s64[8]) {{
  %p.1 = (s64[8]{{0}}) parameter(0)
  %gte.1 = s64[8]{{0}} get-tuple-element(%p.1), index=0
  %copy.3 = s64[8]{{0}} copy(%gte.1)
  %sort.2 = s64[8]{{0}} sort(%copy.3), dimensions={{0}}, metadata={{{RUN}/body/engine.pop/while/body/sort"}}
  ROOT %tuple.1 = (s64[8]{{0}}) tuple(%sort.2)
}}

%round_body (p.2: (s64[8])) -> (s64[8]) {{
  %p.2 = (s64[8]{{0}}) parameter(0)
  %while.2 = (s64[8]{{0}}) while(%p.2), condition=%pop_cond, body=%pop_body, metadata={{{RUN}/body/engine.pop/while"}}
  %gte.2 = s64[8]{{0}} get-tuple-element(%while.2), index=0
  %fusion.7 = s64[8]{{0}} fusion(%gte.2), kind=kLoop, calls=%fused_computation
  %fusion.9 = s64[8]{{0}} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1, metadata={{{RUN}/body/cond/branch_1_fun/engine.flush/engine.judge/mul"}}
  %sort.5 = s64[8]{{0}} sort(%fusion.9), dimensions={{0}}, metadata={{{RUN}/body/cond/branch_1_fun/engine.flush/sort"}}
  ROOT %tuple.2 = (s64[8]{{0}}) tuple(%sort.5)
}}

ENTRY %main.1 (Arg_0.1: s64[8]) -> s64[8] {{
  %Arg_0.1 = s64[8]{{0}} parameter(0)
  %while.1 = (s64[8]{{0}}) while(%Arg_0.1), condition=%round_cond, body=%round_body, metadata={{{RUN}"}}
  ROOT %gte.3 = s64[8]{{0}} get-tuple-element(%while.1), index=0
}}
"""


def _ev(instr, a, b):
    return NS(name=f"%{instr} = s64[8]{{0}} op()", start_ns=float(a),
              end_ns=float(b), stats=[])


def _line(name, events):
    return NS(name=name, events=events)


def _device(name="/device:TPU:0"):
    return NS(name=name, lines=[
        _line("XLA Modules", [
            NS(name="jit__run_shard(3002135639146332032)", start_ns=0.0,
               end_ns=100.0, stats=[]),
            NS(name="jit_clock_of(77)", start_ns=120.0, end_ns=140.0,
               stats=[])]),
        _line("XLA Ops", [
            _ev("while.1", 0, 100),         # the round loop: unscoped
            _ev("while.2", 5, 40),          # the pop loop
            _ev("sort.2", 10, 30),
            _ev("copy.3", 30, 35),          # no op_name, no root
            _ev("fusion.7", 40, 60),        # no op_name: its root's
            _ev("fusion.9", 60, 70),
            _ev("sort.5", 70, 90),
            _ev("fusion.7", 120, 130),      # another program's op
            _ev("sort.5", 160, 170)])])     # outside the window


def _host():
    return NS(name="/host:CPU", lines=[_line("python", [
        NS(name="perfbench.window", start_ns=0.0, end_ns=150.0,
           stats=[])])])


def test_stage_map_follows_scopes_and_fusion_roots():
    m = stages.stage_map(HLO)
    assert m["sort.2"] == m["while.2"] == "engine.pop"
    assert m["fusion.7"] == "engine.merge"       # through its root
    assert m["fusion.9"] == "engine.judge"       # innermost scope
    assert m["sort.5"] == "engine.flush"
    assert m["while.1"] == m["copy.3"] == stages.UNSCOPED


def test_synthetic_trace_reduces_to_hand_counted_stages():
    out = stages.reduce_profile(NS(planes=[_host(), _device()]), HLO)
    # self times in ns: while.1 100 - 35 - 20 - 10 - 20, and copy.3 5;
    # the pop loop 35 - 20 - 5 of its own, and sort.2 20
    want = {"unscoped": 20, "engine.pop": 30, "engine.merge": 20,
            "engine.judge": 10, "engine.flush": 20}
    assert out == pytest.approx({k: v / 1e9 for k, v in want.items()})
    # two devices with the same ops average to the same split
    two = stages.reduce_profile(NS(planes=[
        _host(), _device(), _device("/device:TPU:1")]), HLO)
    assert two == pytest.approx(out)


def test_nothing_to_read_gives_none():
    # no device plane
    assert stages.reduce_profile(NS(planes=[_host()]), HLO) is None
    # no text, or a text that names none of the trace's ops
    trace = NS(planes=[_host(), _device()])
    assert stages.reduce_profile(trace, None) is None
    assert stages.reduce_profile(
        trace, HLO.replace("engine.", "other.")) is None
