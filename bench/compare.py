"""The comparison that decides ``correct``: one study's deliverables from
the program against the plain reference of :mod:`reference`.

Two numbers, each with its limit (see ``PERF.md`` for the readings the
limits were set from):

``rel_err``
    The largest relative gap over every reported value and every choice.
    Values: each argmin, top-k and Pareto-front value the program reports
    against the reference's value of the same configuration, and the
    channel bounds against the reference's.  Choices: how much worse, in
    the reference's own values, the program's argmin and each top-k rank
    are than the reference's; how far the program's front falls short of
    covering each reference front member; by how much a reference front
    member dominates a member of the program's front; by how much a
    chosen configuration breaks a constraint.  A missing answer reads
    ``inf``.
``count_err``
    The largest difference of a feasibility count, an exact comparison.
"""

from __future__ import annotations

import types

import numpy as np

import reference as R

#: Limits per compared number, set from the readings in PERF.md.
LIMITS = {"rel_err": 1e-10, "count_err": 0}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    if not np.array_equal(np.isfinite(a), np.isfinite(b)):
        return np.inf
    fin = np.isfinite(b)
    d = np.abs(a[fin] - b[fin]) / np.maximum(np.abs(b[fin]), 1e-300)
    return float(d.max()) if d.size else 0.0


def _front_gaps(P, Rf):
    """(shortfall, excess) between a program front ``P`` and the
    reference front ``Rf``, both as reference values ``(n, d)``.  A
    channel can be 0 (no on-sensor work, no uTSV): gaps are relative to
    a floor of 1e-300 there, as in :func:`_rel`, so a tie at 0 reads 0
    and a gap over 0 reads far over any limit, where 0/0 would read NaN
    and drop out of the maxima."""
    if P.shape[0] == 0 or Rf.shape[0] == 0:
        return (np.inf if Rf.shape[0] else 0.0,
                np.inf if P.shape[0] else 0.0)
    short, excess = 0.0, 0.0
    with np.errstate(over="ignore"):
        for lo in range(0, Rf.shape[0], 256):
            r = Rf[lo:lo + 256]
            worse = np.maximum(0.0, (P[None] - r[:, None])
                               / np.maximum(np.abs(r[:, None]), 1e-300))
            short = max(short, float(worse.max(-1).min(-1).max()))
        for lo in range(0, P.shape[0], 256):
            p = P[lo:lo + 256]
            beat = (p[:, None] - Rf[None]) / np.maximum(
                np.abs(p[:, None]), 1e-300)
            dom = (beat >= 0).all(-1) & (beat > 0).any(-1)
            margin = np.where(dom, beat.min(-1), 0.0)
            excess = max(excess, float(margin.max()))
    return short, excess


def compare(prog, ref: dict, study: dict, constraints: dict,
            objectives) -> dict:
    """Compared numbers of one study: ``{"rel_err": x, "count_err": n}``.

    ``prog`` has the deliverable attributes of a ``StreamResult``;
    ``ref`` is :func:`reference.solve`'s answer for the same study."""
    n_total = ref["n_total"]
    errs = [0.0]
    fields = list(ref["min_val"])

    def ref_at(idx):
        idx = np.asarray(idx, np.int64).ravel()
        if idx.size and (idx.min() < 0 or idx.max() >= n_total):
            return None
        return R.at(study, idx)

    def check_picks(idx, vals, ref_vals, field):
        """Reported values, choices against the reference's, and the
        constraints, for configurations the program picked."""
        got = ref_at(idx)
        if got is None:
            return np.inf
        v = got[field]
        slack = [0.0]
        for f, bound in constraints.items():
            slack.append(float(np.max((got[f] - bound) / abs(bound),
                                      initial=0.0)))
        if not np.isfinite(v).all():
            return np.inf
        return max(_rel(vals, v), _rel(v, ref_vals), max(slack))

    for f in fields:
        if ref["finite_counts"][f] == 0:
            if prog.finite_counts[f] != 0:
                errs.append(np.inf)
            continue
        errs.append(check_picks([prog.min_idx[f]], [prog.min_val[f]],
                                [ref["min_val"][f]], f))
        errs.append(_rel([prog.channel_min[f], prog.channel_max[f]],
                         [ref["channel_min"][f], ref["channel_max"][f]]))
    for oi, o in enumerate(objectives):
        rv = ref["topk_val"][oi]
        fin = np.isfinite(rv)
        if not np.array_equal(fin, np.isfinite(prog.topk_val[oi])):
            errs.append(np.inf)
            continue
        errs.append(check_picks(prog.topk_idx[oi][fin],
                                prog.topk_val[oi][fin], rv[fin], o))
    fi = np.asarray(prog.front_indices, np.int64)
    got = ref_at(fi)
    if got is None:
        errs.append(np.inf)
    else:
        P = np.stack([got[o] for o in objectives], axis=1).reshape(-1, len(
            objectives))
        errs.append(_rel(np.asarray(prog.front_values).reshape(P.shape), P))
        slack = [float(np.max((got[f] - b) / abs(b), initial=0.0))
                 for f, b in constraints.items()]
        errs.append(max(slack, default=0.0))
        if not np.isfinite(P).all():
            errs.append(np.inf)
        else:
            errs.extend(_front_gaps(P, np.asarray(ref["front_values"])))
    count = max(abs(int(prog.finite_counts.get(f, -1))
                    - int(ref["finite_counts"][f])) for f in fields)
    return {"rel_err": float(max(errs)), "count_err": count}


def worst(readings: list) -> dict:
    """Largest reading of each number over several compared answers."""
    return {k: max(r[k] for r in readings) for k in LIMITS} if readings \
        else {k: np.inf for k in LIMITS}


def within(numbers: dict) -> bool:
    return all(np.isfinite(numbers[k]) and numbers[k] <= LIMITS[k]
               for k in LIMITS)


def as_control(ref: dict):
    """A reference answer in the shape of a program result."""
    return types.SimpleNamespace(**ref)
