"""The program's spans and named scopes in a profiler trace.

``trace.py`` reduces a trace to device busy time, program time and idle
gaps, and names each gap by the benchmark span around it.  This module
adds what the program's own spans (``repro.*``, from
``repro.runtime.spans``) and the step's named scopes say, and changes
none of ``trace.py``'s numbers: :func:`reduce` calls it and keeps every
value it returns except the labels of ``idle_gaps``.

Events are tuples ``(plane, line, name, start_ns, dur_ns[, meta[,
thread]])``: ``meta`` is a span's metadata (its ticket, step, queue
wait) or an op's program, ``thread`` tells apart host lines of one
name.  A 5-tuple is valid, with no metadata and its line as its thread.

A TPU trace's op events carry no op-name metadata (their name is the
instruction's HLO text without it, and their stats are timing only), so
the scopes come from the compiled step's HLO text: :func:`hlo_op_paths`
maps each instruction of a module to its op-name path, and
:func:`reduce` looks an op up by its program and name.

* ``idle_by_span``: each idle interval of each device is cut at span
  edges, and each piece is charged to the innermost ``repro.*`` span
  open then on any thread, those of the service worker's thread (the
  one that runs ``repro.service.execute``) first; else to the innermost
  ``bench.*`` span; else to ``outside any span``.  A gap of
  ``idle_gaps`` is named by its largest charged piece.
* ``scope_s``: device self time of the chunk step's ops (an op's
  duration less the ops nested in it, so a loop does not count its
  body twice), by the innermost of :data:`SCOPES` in the op's op-name
  path, summed over devices; ``unscoped`` holds the rest
  (``scope_leaf_s``: the same over ops with none nested in them).
* ``span_s``, ``queued_s``, ``executor_idle_s``: what the readers of
  :func:`readings` read.
* ``program_spans``: per-name count and total of the ``repro.*`` spans
  in the window, and the ten longest with their thread and ticket.

``run.py --trace 1`` loads the trace with :func:`load`, logs these
outputs before its result line, and reads the per-layer metrics of
:func:`readings` from them (``metrics/<name>.py``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
import warnings
from collections import defaultdict
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace as T  # noqa: E402
from stats import percentile  # noqa: E402

#: The chunk step's named scopes (``core/backend.py``), innermost last
#: where they nest.
SCOPES = ("decode_gather", "eq1_11", "chunk_partials", "dominance_filter",
          "fold_chunk")
#: The chunk step's programs, as ``metrics/step_device_s_per_gconfig``.
STEP = ("jit_step", "jit_one", "jit_call_wrapped")
OUTSIDE = "outside any span"
UNSCOPED = "unscoped"
WORKER_SPAN = "repro.service.execute"
_NO_META: dict = {}
_SCOPE_RE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
_HLO_OP = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', re.M)


def _fields(ev):
    plane, line, name, s, d = ev[:5]
    meta = ev[5] if len(ev) > 5 else {}
    thread = ev[6] if len(ev) > 6 else line
    return plane, line, name, s, d, meta, thread


def load(log_dir: str) -> list:
    """Events of the newest ``*.xplane.pb`` under ``log_dir``: what
    ``trace.load`` keeps, plus the ``repro.*`` host spans, with their
    metadata and thread, and each device op's program (the module event
    around it)."""
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
        lines = list(plane.lines)
        if device:
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           T.program_name(e.name))
                          for ln in lines if ln.name == T.MODULE_LINE
                          for e in ln.events)
            starts = [m[0] for m in mods]
            progs: dict = {}
        for i, line in enumerate(lines):
            if device and line.name not in (T.OP_LINE, T.MODULE_LINE):
                continue
            thread = f"{line.name}#{i}"
            for e in line.events:
                s, d = float(e.start_ns), float(e.duration_ns)
                if not device:
                    if e.name.startswith(("bench.", "repro.")):
                        with warnings.catch_warnings():
                            warnings.simplefilter("ignore",
                                                  DeprecationWarning)
                            meta = dict(e.stats)
                        out.append((plane.name, line.name, e.name, s, d,
                                    meta, thread))
                    continue
                if line.name == T.MODULE_LINE:
                    out.append((plane.name, line.name, e.name, s, d))
                    continue
                k = bisect.bisect_right(starts, s) - 1
                prog = mods[k][2] if k >= 0 and s < mods[k][1] else ""
                meta = progs.get(prog)
                if meta is None:
                    meta = progs[prog] = {"program": prog}
                out.append((plane.name, line.name, e.name, s, d, meta,
                            thread))
    return out


def hlo_op_paths(text: str) -> dict:
    """``{(program, op): op-name path}`` of the instructions of a
    compiled module's HLO text (``Compiled.as_text()``)."""
    m = re.search(r"^HloModule ([^\s,]+)", text, re.M)
    prog = m.group(1) if m else ""
    return {(prog, op): path for op, path in _HLO_OP.findall(text)}


def trace_events(events: list) -> list:
    """The events ``trace.load`` keeps, as it gives them."""
    return [ev[:5] for ev in events
            if ev[0].startswith("/device:") or ev[2].startswith("bench.")]


def scope_of(op_path: str) -> str:
    """The innermost of :data:`SCOPES` on an op-name path, else
    ``unscoped``."""
    found = _SCOPE_RE.findall(op_path or "")
    return found[-1] if found else UNSCOPED


def _self_times(s0, s1):
    """``(order, own, leaf)`` of one device's ops, from their starts and
    ends: the order that sorts them by start, the longest first on a
    tie, and in that order each op's duration less the parts of it that
    ops nested in it cover, and whether none is.  An op's parent is the
    latest earlier op, in that order, still running when it starts."""
    order = np.lexsort((-s1, s0))
    S0, S1 = s0[order], s1[order]
    n = S0.size
    own = S1 - S0
    # top[k][p]: the latest end among the 2^k ops before position p.
    top = [np.concatenate(([-np.inf], S1))]
    while (1 << len(top)) <= n:
        h = 1 << (len(top) - 1)
        t = top[-1].copy()
        t[h:] = np.maximum(t[h:], top[-1][:-h])
        top.append(t)
    # Step back over every block of ops that ended by the op's start.
    pos = np.arange(n)
    for k in range(len(top) - 1, -1, -1):
        back = pos - (1 << k)
        ok = back >= 0
        ok[ok] = top[k][pos[ok]] <= S0[ok]
        pos = np.where(ok, back, pos)
    child = np.flatnonzero(pos > 0)
    parent = pos[child] - 1
    np.subtract.at(own, parent, np.minimum(S1[child], S1[parent])
                   - S0[child])
    leaf = np.ones(n, bool)
    leaf[parent] = False
    return order, own, leaf


def _overlap(merged, starts, s, e):
    """Length of ``[s, e)`` covered by sorted disjoint intervals."""
    k = max(0, bisect.bisect_right(starts, s) - 1)
    cov = 0.0
    while k < len(merged) and merged[k][0] < e:
        cov += max(0.0, min(e, merged[k][1]) - max(s, merged[k][0]))
        k += 1
    return cov


def reduce(events: list, planes=None, op_paths=None,
           base=None) -> dict | None:
    """``trace.reduce`` of the same trace, with the idle gaps named by
    the spans the idle time is charged to, and the outputs the module
    docstring lists; ``None`` where ``trace.reduce`` gives ``None``.
    ``op_paths`` (:func:`hlo_op_paths` of the step programs) gives the
    ops' op-name paths; without it every step op is unscoped.  ``base``
    is ``trace.reduce``'s result where the caller has it already (it
    costs seconds on a long trace)."""
    if base is None:
        base = T.reduce([ev[:5] for ev in events], planes=planes)
    if base is None:
        return None
    host = [_fields(e) for e in events if e[0].startswith("/host:")]
    by_plane = defaultdict(list)        # each device's op line
    for e in events:
        if (e[0].startswith("/device:") and e[1] == T.OP_LINE
                and (planes is None or e[0] in planes)):
            by_plane[e[0]].append(e)
    win = [(s, s + d) for _, _, n, s, d, _, _ in host
           if n == T.WINDOW_SPAN]
    if win:
        w0, w1 = win[0]
    else:
        dev = [e for e in events if e[0].startswith("/device:")
               and (planes is None or e[0] in planes)]
        w0 = min(e[3] for e in dev)
        w1 = max(e[3] + e[4] for e in dev)
    spans = sorted(((s, s + d, n, th, m) for _, _, n, s, d, m, th in host
                    if n != T.WINDOW_SPAN
                    and n.startswith(("bench.", "repro."))),
                   key=lambda sp: sp[0])
    workers = {sp[3] for sp in spans if sp[2] == WORKER_SPAN}

    busy = {}
    gaps = []                  # (start, end, plane), as trace.reduce
    names = SCOPES + (UNSCOPED,)
    scopes: dict = {}
    codes, owns, leaves = [], [], []
    for plane in sorted(set(by_plane) | set(planes or ())):
        evs = by_plane[plane]
        n = len(evs)
        start = np.fromiter((e[3] for e in evs), float, n)
        s1 = np.minimum(start + np.fromiter((e[4] for e in evs), float, n),
                        w1)
        s0 = np.maximum(start, w0)
        kept = np.flatnonzero(s1 > s0)
        s0, s1 = s0[kept], s1[kept]
        merged = T.union_of(s0, s1)
        busy[plane] = (merged, [m[0] for m in merged])
        edges = [w0] + [x for m in merged for x in m] + [w1]
        gaps += [(s, e, plane) for s, e in zip(edges[::2], edges[1::2])
                 if e > s]
        code = np.empty(kept.size, int)
        for i, k in enumerate(kept.tolist()):
            e = evs[k]
            meta = e[5] if len(e) > 5 else _NO_META
            c = scopes.get((id(meta), e[2]))
            if c is None:           # once per op, not once per event
                prog = meta.get("program")
                c = scopes[id(meta), e[2]] = -1 if prog not in STEP \
                    else names.index(scope_of((op_paths or {}).get(
                        (prog, T.op_name(e[2])), "")))
            code[i] = c
        order, own, leaf = _self_times(s0, s1)
        codes.append(code[order])
        owns.append(own)
        leaves.append(leaf)
    # Summed over devices, in the order the ops ran on each.
    code = np.concatenate(codes) if codes else np.empty(0, int)
    own = np.concatenate(owns) if owns else np.empty(0)
    leaf = np.concatenate(leaves) if leaves else np.empty(0, bool)
    step = code >= 0
    scope_s, leaf_s = {}, {}
    for out, sel in ((scope_s, step), (leaf_s, step & leaf)):
        n = np.bincount(code[sel], minlength=len(names))
        total = np.bincount(code[sel], weights=own[sel] * 1e-9,
                            minlength=len(names))
        out.update({names[i]: float(total[i]) for i in np.flatnonzero(n)})

    # Cut every idle interval at span edges; charge each piece.
    cuts = sorted({x for sp in spans for x in sp[:2]})
    pieces = []
    for gi, (s, e, _) in enumerate(gaps):
        pts = [s] + cuts[bisect.bisect_right(cuts, s):
                         bisect.bisect_left(cuts, e)] + [e]
        pieces += [(a, b, gi) for a, b in zip(pts, pts[1:]) if b > a]
    pieces.sort(key=lambda p: p[0] + p[1])
    charged = defaultdict(float)
    per_gap = defaultdict(lambda: defaultdict(float))
    labelled = []
    active, nxt = [], 0
    for a, b, gi in pieces:
        mid = (a + b) / 2
        while nxt < len(spans) and spans[nxt][0] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] > mid]
        rep = [sp for sp in active if sp[2].startswith("repro.")]
        pick = ([sp for sp in rep if sp[3] in workers] or rep
                or [sp for sp in active if sp[2].startswith("bench.")])
        label = max(pick, key=lambda sp: sp[0])[2] if pick else OUTSIDE
        charged[label] += (b - a) * 1e-9
        per_gap[gi][label] += (b - a) * 1e-9
        labelled.append((a, b, label))

    top = sorted(range(len(gaps)), key=lambda i: gaps[i][0] - gaps[i][1])
    idle_gaps = []
    for gi in top[:10]:
        s, e, _ = gaps[gi]
        lab = per_gap[gi]
        idle_gaps.append([max(lab, key=lab.get), (e - s) * 1e-9])

    inwin = [sp for sp in spans if w0 <= sp[0] <= w1
             and sp[2].startswith("repro.")]
    span_s = defaultdict(float)
    count = defaultdict(int)
    for s, e, n, _, _ in inwin:
        span_s[n] += (e - s) * 1e-9
        count[n] += 1
    queued = [float(m["queued_s"]) for _, _, n, _, m in inwin
              if n == "repro.service.start" and "queued_s" in m]
    exec_idle = []
    for s, e, n, _, m in inwin:
        if n == "repro.stream.grid" and "ticket" in m:
            idle = [(e - s) - _overlap(mg, st, s, e)
                    for mg, st in busy.values()]
            exec_idle.append(sum(idle) / len(idle) * 1e-9)
    longest = sorted(inwin, key=lambda sp: sp[0] - sp[1])[:10]
    return dict(
        base,
        idle_gaps=idle_gaps,
        idle_by_span=dict(sorted(charged.items(), key=lambda kv: -kv[1])),
        ticket_idle=_ticket_idle(spans, labelled),
        scope_s=scope_s,
        scope_leaf_s=leaf_s,
        span_s=dict(span_s),
        queued_s=queued,
        executor_idle_s=exec_idle,
        program_spans={
            "count": len(inwin),
            "by_name": {n: [count[n], span_s[n]] for n in sorted(span_s)},
            "longest": [[n, th, m.get("ticket"), (s - w0) * 1e-9,
                         (e - s) * 1e-9] for s, e, n, th, m in longest]},
    )


def _ticket_idle(spans, labelled) -> dict:
    """Device idle seconds inside the tickets' intervals, from a
    ticket's first admission to the end of its final frame's encoding,
    and the part of them charged to a ``repro.*`` span.  A ticket's id
    comes from the journal span inside its admission."""
    journals = defaultdict(list)
    for sp in spans:
        if sp[2] == "repro.service.journal" and "ticket" in sp[4]:
            journals[sp[3]].append(sp)
    admit_at = {}
    for s, e, n, th, m in spans:
        if n != "repro.service.admit":
            continue
        for s2, e2, _, _, m2 in journals[th]:
            if s <= s2 and e2 <= e:
                admit_at.setdefault(m2["ticket"], s)
                break
    iv = [(admit_at[m["ticket"]], e) for s, e, n, _, m in spans
          if n == "repro.wire.encode" and m.get("ticket") in admit_at]
    merged = T._union(iv)
    starts = [m[0] for m in merged]
    idle = named = 0.0
    for a, b, label in labelled:
        x = _overlap(merged, starts, a, b) * 1e-9
        idle += x
        if label.startswith("repro."):
            named += x
    return {"tickets": len(iv), "idle_s": idle, "repro_s": named}


def readings(red: dict | None, run: dict) -> dict:
    """The per-layer numbers the spans and scopes give, by name, where
    the trace holds something to read: the queue wait, journal and
    codec time per ticket, the device's idle time inside a ticket's
    executor, and the dominance filter's and Eq. 1-11's device time per
    10^9 configurations."""
    if red is None:
        return {}
    out = {}
    span_s, scope_s = red["span_s"], red["scope_s"]
    done = run.get("completed") or 0
    if red["queued_s"]:
        out["queue_wait_p50_s"] = percentile(red["queued_s"], 50)
    if done and "repro.service.journal" in span_s:
        out["journal_s_per_ticket"] = span_s["repro.service.journal"] / done
    codec = [span_s[n] for n in ("repro.wire.encode", "repro.wire.decode")
             if n in span_s]
    if done and codec:
        out["codec_s_per_ticket"] = sum(codec) / done
    if red["executor_idle_s"]:
        out["executor_idle_p50_s"] = percentile(red["executor_idle_s"], 50)
    gconfigs = sum(s["n_configs"] for s in run.get("studies", ())) / 1e9
    for name, scope in (("filter_device_s_per_gconfig", "dominance_filter"),
                        ("eval_device_s_per_gconfig", "eq1_11")):
        if gconfigs and scope_s.get(scope):
            out[name] = scope_s[scope] / gconfigs
    return out
