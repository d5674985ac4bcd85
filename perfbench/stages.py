"""Device time of the engine round split by stage, from a profiler
trace (``*.xplane.pb``) and the optimized HLO text of the round
program (``engine.program_text("run")``).

* An HLO instruction's stage is the innermost ``engine.*`` component
  of its metadata ``op_name``: the ``jax.named_scope`` stages of
  ``shadow_tpu/device/engine.py`` (pop, judge, flush, exchange, merge,
  audit). A fusion with no ``op_name`` takes the stage of its fused
  computation's root. Anything else is ``unscoped``: round control,
  and any op the text does not name, so a stale text shows there.
* Device ops are each ``/device:*`` plane's ``XLA Ops`` line. Only ops
  inside an event of the round program on the plane's ``XLA Modules``
  line count (the harness's own small programs are left out), clipped
  to the window, which is the host span ``perfbench.window`` as in
  ``xplane.py``.
* A stage's time is the self time of its ops (nested events on a line
  are not counted twice), averaged over the devices.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from perfbench import xplane

RUN_MODULE = "jit__run_shard"
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")


def _stage_of(op_name: str) -> str:
    scopes = [p for p in op_name.split("/") if p.startswith("engine.")]
    return scopes[-1] if scopes else UNSCOPED


def stage_map(hlo_text: str) -> dict:
    """{instruction name: stage} for every instruction of the text."""
    own, fused, roots = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head and " = " not in line:
            computation = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = _stage_of(op.group(1)) if op else None
        called = _CALLS.search(line)
        if called and op is None:
            fused[name] = called.group(1)
        if line.lstrip().startswith("ROOT ") and computation:
            roots[computation] = name

    def resolve(name, depth=0):
        stage = own.get(name)
        if stage is None and fused.get(name) in roots and depth < 16:
            return resolve(roots[fused[name]], depth + 1)
        return stage

    return {name: resolve(name) or UNSCOPED for name in own}


def _instruction(event_name: str) -> str:
    """'%fusion.12 = s32[...] fusion(...)' -> 'fusion.12'."""
    if event_name.startswith("%") and " = " in event_name:
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def reduce_profile(pd, hlo_text: str) -> dict | None:
    """{stage: seconds} of self time in the window, averaged over the
    devices; None when the trace has no device ops of the round
    program or no op maps to a stage."""
    stages = stage_map(hlo_text or "")
    host_spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines and "XLA Modules" in lines:
                runs = [(a, b) for name, a, b, _ in
                        xplane._events(lines["XLA Modules"])
                        if name.startswith(RUN_MODULE)]
                devices.append((runs, xplane._events(lines["XLA Ops"])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(e for e in xplane._events(line)
                                  if e[0] == xplane.WINDOW_SPAN)
    if not devices:
        return None
    if host_spans:
        w0, w1 = host_spans[0][1], host_spans[0][2]
    else:
        w0, w1 = float("-inf"), float("inf")
    per_op = defaultdict(float)
    for runs, ops in devices:
        inside = [e for e in ops if e[2] > w0 and e[1] < w1
                  and any(a <= e[1] < b for a, b in runs)]
        xplane._self_times(inside, w0, w1, per_op)
    out = defaultdict(float)
    mapped = False
    for name, t in per_op.items():
        stage = stages.get(_instruction(name))
        mapped |= stage not in (None, UNSCOPED)
        out[stage or UNSCOPED] += t / len(devices) / 1e9
    return dict(out) if mapped else None


def reduce_dir(directory: str, hlo_text: str) -> dict | None:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    return reduce_profile(ProfileData.from_file(paths[-1]), hlo_text)
