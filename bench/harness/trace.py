"""Reduction of a profiler trace to device busy time, per-layer device
time and named idle gaps.

The trace is read into plain records, ``{"plane", "line", "events":
[[name, start_ns, duration_ns], ...]}``, so that the reduction can be
checked on a small recorded trace without the profiler.

* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane.
* Host spans are the ``TraceAnnotation`` events the harness writes on the
  host plane: ``window`` around the measured loop, ``request.<query>``
  around each request, ``collect`` around the copy of each answer to the
  host.
* Busy time is the union of a device's operation intervals inside the
  window; the idle share is one less busy time over the window.
* An operation's event name is its HLO text; it is read as
  ``<opcode>:<instruction> <shape>`` (``custom-call:group_cumulant_sums.2
  f32[8,168448]``), and its self time is its duration less that of the
  operations nested in it (a ``while`` encloses its body's operations).
* A matcher (``bench/matchers/<name>.json``) assigns operations to a
  layer by regular expressions on that short name; a layer's device time
  is the summed self time of its operations inside the window.
* Each idle gap of a device is named by the innermost host span that
  covers its midpoint (``between`` where none does).
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_NAMES = re.compile(r"^(window|collect|request\.[\w.-]+)$")


def load(trace_dir: str) -> list:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain records:
    every device plane's op line and the host spans the harness wrote."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.append(dict(plane=plane.name, line=line.name,
                                    events=[[e.name, e.start_ns,
                                             e.duration_ns]
                                            for e in line.events]))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events if SPAN_NAMES.match(e.name)]
                if evs:
                    out.append(dict(plane=plane.name, line=line.name,
                                    events=evs))
    return out


_HLO = re.compile(r"%([\w.-]+) = (\(.*?\)|\S+) ([\w-]+)\(")


def short_name(name: str) -> str:
    """``<opcode>:<instruction> <shape>`` of an HLO op event (the shape's
    layout dropped); other names unchanged."""
    m = _HLO.match(name)
    if not m:
        return name
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))[:48]
    return f"{m.group(3)}:{m.group(1)} {shape}"


def self_times(ops: list) -> list:
    """[(name, start, end, self duration)] of op events: each event's
    duration less that of the events wholly inside it."""
    out, stack = [], []
    for name, s, d in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, s + d, d]
        if stack and s + d <= stack[-1][2]:
            stack[-1][3] -= d
        out.append(rec)
        stack.append(rec)
    return out


def _union(intervals: list) -> list:
    """Disjoint sorted union of [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


class Reduced:
    """The reduction of one traced window.

    ``window_s``: length of the ``window`` span; ``busy_s``: union of
    device-op intervals inside it, averaged over the devices;
    ``op_s``: {op short name: self seconds, summed over devices};
    ``layer_s``: {layer: seconds of its matched ops, summed over devices};
    ``gaps``: [(span name, seconds)] of every idle gap of every device.
    """

    def __init__(self, records: list, devices: list, matchers: dict):
        spans = [(n, s, s + d) for r in records if r["plane"] == HOST_PLANE
                 for n, s, d in r["events"]]
        wins = [(s, e) for n, s, e in spans if n == "window"]
        if not wins:
            raise ValueError("the trace holds no 'window' span")
        lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
        self.window_s = (hi - lo) * 1e-9
        inner = sorted(((n, s, e) for n, s, e in spans if n != "window"),
                       key=lambda x: x[1])
        pats = {layer: [re.compile(p) for p in ps]
                for layer, ps in matchers.items()}
        self.op_s, self.layer_s, self.gaps = {}, {}, []
        self.devices_found = 0
        busy = []
        for dev in devices:
            ops = [ev for r in records
                   if r["plane"] == f"/device:TPU:{dev}"
                   and r["line"] == OPS_LINE for ev in r["events"]]
            if not ops:
                continue
            self.devices_found += 1
            ivs = []
            for name, s, e, own in self_times(ops):
                a, b = _clip(s, e, lo, hi)
                if b <= a:
                    continue
                ivs.append((a, b))
                sec = max(own, 0) * (b - a) / (e - s) * 1e-9
                short = short_name(name)
                self.op_s[short] = self.op_s.get(short, 0.0) + sec
                for layer, ps in pats.items():
                    if any(p.search(short) for p in ps):
                        self.layer_s[layer] = self.layer_s.get(layer,
                                                               0.0) + sec
            u = _union(ivs)
            busy.append(sum(b - a for a, b in u) * 1e-9)
            prev = lo
            for a, b in u + [[hi, hi]]:
                if a > prev:
                    self.gaps.append((_span_at(inner, (prev + a) / 2),
                                      (a - prev) * 1e-9))
                prev = max(prev, b)
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time and the host spans under
        which the device stood idle longest, summed over devices."""
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        by_span: dict = {}
        for name, sec in self.gaps:
            by_span[name] = by_span.get(name, 0.0) + sec
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return dict(device_ops=[[n, s] for n, s in ops],
                    idle_gaps=[[n, s] for n, s in gaps])


def _span_at(spans: list, t) -> str:
    """The innermost (latest-starting) span covering time t."""
    best = "between"
    for name, s, e in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best
