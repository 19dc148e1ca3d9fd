"""Reduction of a profiler trace to device busy time, program time and
idle gaps.

The trace is read into plain event tuples ``(plane, line, name,
start_ns, dur_ns)``: the device planes' op and program (module) lines,
and the benchmark's own host spans (``bench.*``), which share the
trace's clock.  :func:`reduce` works on those tuples only, so a
synthetic trace tests it.

Busy time of a device is the union of its op intervals inside the
window span (``bench.window``); its idle share is one minus busy over
the window.  Every device the cell uses counts, a silent one as busy 0,
so a chip left idle raises the mean.  Idle gaps are the holes in that
union, each named by the innermost benchmark span around its midpoint.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    """A module event's program name without its run id suffix."""
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """An op event's HLO instruction name, without its text."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str) -> list:
    """Event tuples of the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith("bench."):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals):
    """Sorted, disjoint ``[start, end]`` lists covering ``intervals``
    (pairs); touching intervals merge."""
    a = np.asarray(intervals, float).reshape(-1, 2)
    return union_of(a[:, 0], a[:, 1])


def union_of(s0, s1) -> list:
    """:func:`_union` of the intervals ``[s0, s1]`` given as arrays."""
    if not s0.size:
        return []
    order = np.lexsort((s1, s0))
    start, end = s0[order], np.maximum.accumulate(s1[order])
    first = np.flatnonzero(np.concatenate(([True], start[1:] > end[:-1])))
    last = np.concatenate((first[1:] - 1, [start.size - 1]))
    return [[a, b] for a, b in zip(start[first].tolist(),
                                   end[last].tolist())]


def plane_name(device) -> str:
    """The trace's plane name of a JAX device."""
    return f"/device:{device.platform.upper()}:{device.id}"


def reduce(events: list, planes=None) -> dict | None:
    """Per-device busy and idle share, device seconds per op and per
    program, and the longest idle gaps; ``None`` when the trace holds
    no device op inside the window.  ``planes`` names the devices the
    cell uses (default: every device plane with an event); each of them
    has an entry, and only their events count."""
    spans = [(s, s + d, n) for p, l, n, s, d in events
             if p.startswith("/host:")]
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    dev_ev = [ev for ev in events if ev[0].startswith("/device:")
              and (planes is None or ev[0] in planes)]
    if not dev_ev:
        return None
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(s for _, _, _, s, _ in dev_ev)
        w1 = max(s + d for _, _, _, s, d in dev_ev)
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    devices = {}
    ops = defaultdict(float)
    programs = defaultdict(float)
    gaps = []
    by_plane = defaultdict(list)
    for ev in dev_ev:
        by_plane[ev[0]].append(ev)
    op_names: dict = {}        # an event's name parsed once per name
    for plane in sorted(set(by_plane) | set(planes or ())):
        iv = []
        for _, line, name, s, d in by_plane[plane]:
            s0 = s if s > w0 else w0
            s1 = s + d
            if s1 > w1:
                s1 = w1
            if s1 <= s0:
                continue
            if line == OP_LINE:
                iv.append((s0, s1))
                key = op_names.get(name)
                if key is None:
                    key = op_names[name] = op_name(name)
                ops[key] += (s1 - s0) * 1e-9
            else:
                programs[program_name(name)] += (s1 - s0) * 1e-9
        merged = _union(iv)
        busy = sum(e - s for s, e in merged)
        devices[plane] = {"busy_s": busy * 1e-9,
                          "idle_share": 1.0 - busy / (w1 - w0)}
        edges = [w0] + [x for m in merged for x in m] + [w1]
        gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    if not ops:
        return None
    n = len(devices)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) / 2
        around = [sp for sp in inner if sp[0] <= mid <= sp[1]]
        named.append((min(around, key=lambda sp: sp[1] - sp[0])[2]
                      if around else "outside any span", (e - s) * 1e-9))
    gaps = named
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    top_prog = sorted(programs.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
        "idle_share": sum(d["idle_share"] for d in devices.values()) / n,
        "devices": devices,
        "program_s": dict(programs),
        "device_ops": ([[f"program {k}", v] for k, v in top_prog[:4]]
                       + [[k, v] for k, v in top[:10 - min(4,
                                                           len(top_prog))]]),
        "idle_gaps": [[g[0], g[1]] for g in gaps[:10]],
    }
