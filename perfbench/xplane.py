"""The yardstick's reduction of a profiler trace (``*.xplane.pb``) to
device busy time, the ops that took it, and the idle gaps with what
the host was doing in each.

* The window is the host span ``perfbench.window`` the harness opens
  around the traced segments (the whole trace when it is missing).
* A device is a ``/device:*`` plane with a line of XLA ops (``XLA
  Ops``, else ``XLA Modules``). A trace with none holds no device
  time, and is refused.
* Busy time is the union of op intervals inside the window, averaged
  over the devices. An op's time is its self time: nested events on
  one line (a loop and its body) are not counted twice.
* An idle gap is a stretch of the window in which no op ran on the
  first device; it is named by the innermost host span open at its
  middle.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "perfbench.window"
TOP = 10


def reduce_dir(directory: str) -> dict:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(paths[-1])


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_name(text: str) -> str:
    """An XLA op's name and result shape from its HLO text on TPU
    ('%sort.2 = (u32[620000]{0:T(1024)}, ...) sort(...)' -> 'sort.2
    u32[620000]'); other names pass through."""
    if not text.startswith("%") or " = " not in text:
        return text
    head, rest = text[1:].split(" = ", 1)
    shape = _SHAPE.search(rest)
    return f"{head} {shape.group(0)}" if shape else head


def _events(line):
    return [(e.name, float(e.start_ns), float(e.end_ns), e)
            for e in line.events]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events, w0, w1, acc):
    """Add each event's self time (its clipped duration less that of
    the events nested in it on the same line) to acc[name]."""
    stack = []                      # [end, name, child time, own]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, child, own = stack.pop()
            acc[name] += own - child
            if stack:
                stack[-1][2] += own
    for name, a, b, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        own = max(0.0, min(b, w1) - max(a, w0))
        stack.append([b, name, 0.0, own])
    close(float("inf"))


def reduce_profile(pd) -> dict:
    host_spans, device_lines = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is not None:
                device_lines.append(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(ev for ev in _events(line)
                                  if ev[2] > ev[1])
    if not device_lines:
        raise ValueError("the trace holds no device ops")
    win = [e for e in host_spans if e[0] == WINDOW_SPAN]
    if win:
        w0, w1 = win[0][1], win[0][2]
    else:
        w0 = min(e[1] for d in device_lines for e in d)
        w1 = max(e[2] for d in device_lines for e in d)
    busy, per_op = [], defaultdict(float)
    merged0 = None
    for events in device_lines:
        inside = [e for e in events if e[2] > w0 and e[1] < w1]
        merged = _union([(max(a, w0), min(b, w1))
                         for _, a, b, _ in inside])
        busy.append(sum(b - a for a, b in merged))
        _self_times(inside, w0, w1, per_op)
        if merged0 is None:
            merged0 = merged
    n = len(device_lines)
    gaps, prev = [], w0
    for a, b in merged0 + [[w1, w1]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) / 2
        around = [e for e in host_spans if e[1] <= mid < e[2]]
        name = min(around, key=lambda e: e[2] - e[1])[0] if around \
            else "host:no span"
        named.append([name[:80], (b - a) / 1e9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "n_devices": n,
            "device_ops": [[op_name(k)[:80], v / n / 1e9] for k, v in ops],
            "idle_gaps": named}
