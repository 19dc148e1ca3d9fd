"""Streaming, sharded sweep executor: memory-bounded giant design spaces.

:func:`repro.core.sweep.evaluate_grid` materializes the full cartesian
product — host coordinate meshes on the way in, eleven dense channel
grids on the way out — so memory is O(grid) and a 9-axis space at
realistic resolution (10⁷–10⁹ configurations) is unreachable.  This
module replaces that with a **streaming executor** over the *same*
compiled Eq. 1-11 kernel, with every per-chunk reduction fused into the
device step so the host never sees a full channel array:

* **Device-side coordinate decoding** — each chunk starts from a flat
  index range; the mixed-radix decode of
  :func:`repro.core.sweep.decode_flat_index` runs on-device, so no
  coordinate arrays are ever materialized anywhere.
* **Fused on-device reductions** — one cached, jit-compiled step decodes
  and evaluates a chunk and folds it into a donated running device carry:
  argmin, feasibility counts and channel bounds per tracked channel,
  per-objective **top-k** (chunk ``lax.top_k`` merged against the running
  ``(n_obj, k)`` table with an exact two-key sort), optional histograms,
  and the Pareto **dominance pre-filter**
  (:func:`repro.core.pareto.dominance_filter_mask`, traced on-device).
  Each step returns only a *compacted survivor set* — the few candidate
  front rows (flat indices + objective values) the filter could not
  discard — instead of ``(n_fields, chunk)`` channel arrays, so
  device→host traffic is O(survivors) per chunk.
* **Compiled constraint predicates** — ``constraints=`` (e.g. a latency
  budget or a MIPI link cap, see
  :func:`repro.core.sweep.parse_constraints`) are masked inside the chunk
  step before any reduction: every result — argmin, top-k, counts,
  bounds, histograms, front — is over the *feasible* set, identical to
  host post-filtering the dense grid (``SweepResult.constrain``).
* **Async double-buffered pipeline** — a producer thread drives the
  chunk chain (XLA releases the GIL while a step executes) with
  ``prefetch=`` chunk results in flight, so the host-side exact front
  merges (filter pre-cull + :func:`_merge_into_front`) hide under
  device compute.  Host memory stays O(chunk + front) for any grid
  size, and argmin/top-k/front are *exactly* the dense-path results.
* **Sharding** — with more than one device the chunk stream is split
  across devices via ``jax.pmap`` (one carry per device, merged once at
  the end), with the same prefetch pipeline, so kernel throughput scales
  with the device count.
* **Unified backend layer** — the chunk step is assembled by
  :mod:`repro.core.backend` from the same decode→evaluate→fold contract
  the dense engine runs: ``backend=`` picks the evaluation backend
  (``"xla"`` default; ``"pallas"`` fuses decode + Eq. 1-11 + block
  reductions into one ``pallas_call``, :mod:`repro.kernels.sweep_grid`)
  and ``scan_chunks=`` fuses K chunk folds per device dispatch via
  ``lax.scan`` — cutting per-step dispatch overhead at 10⁷–10⁸ configs
  with bitwise-identical results.
* **Fault tolerance** — the executor is resumable and self-healing:
  ``checkpoint_dir=`` periodically snapshots the merged running carry,
  the exact Pareto-front buffer and the next flat-index cursor through
  :class:`repro.checkpoint.CheckpointManager` (atomic tmp-dir +
  rename), keyed by a content hash of the sweep specification
  (:func:`repro.core.backend.job_signature`) so a stale snapshot from a
  different spec is rejected loudly; a re-run with the same arguments
  resumes from the newest snapshot with **bitwise-identical** results.
  ``retry_policy=`` bounds in-place retries of transiently failed chunk
  dispatches and full pipeline restarts from the last snapshot; on the
  pmap path a dead device shard triggers an elastic replan
  (:func:`repro.runtime.elastic.drop_worker`) that re-issues only the
  unfinished chunk ranges on the survivors, degrading gracefully to
  single-device execution.  ``fault_injector=``
  (:class:`repro.runtime.fault_injection.FaultInjector`) exercises
  every one of those recovery paths deterministically in CI.
* **Cooperative cancellation & partial snapshots** — ``should_stop=``
  is polled between chunk dispatches (so deadlines and client cancels
  take effect within one chunk); when it fires, the executor folds
  everything already dispatched and returns the consistent prefix
  snapshot as a ``partial=True`` result (``fraction_complete`` in
  ``stats``), still checkpointed for later resume.  This — plus
  :func:`plan_stream`, which splits the reusable job definition
  (:class:`StreamPlan`) out of the executor so compiled chunk steps
  stay cached across calls — is the contract the persistent sweep
  service (:mod:`repro.core.service`) is built on.
* **Batched workload axis** — ``models=`` stacks architecture variants
  (see :func:`repro.core.arrays.stacked_model_arrays`) into a leading
  grid axis evaluated inside the same kernel, for SplitNets-style
  architecture × partition co-design sweeps.

The dense path remains the right tool for small grids where the full
per-channel arrays are wanted (heatmaps, reporting); the two paths are
pinned exactly equal — argmin, top-k, and Pareto front, with and without
constraints, across prefetch depths — by ``tests/test_stream.py`` and
the ``benchmarks/run.py --smoke`` CI gate.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import time
from collections import OrderedDict
from queue import Empty as _Empty
from queue import Full as _Full
from queue import Queue as _Queue
from typing import Iterable, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..checkpoint import CheckpointManager
from ..runtime.elastic import drop_worker
from ..runtime.fault_injection import DeviceLostError, TransientDeviceError
from ..runtime.fault_tolerance import RetryPolicy, StragglerDetector
from ..runtime import spans as SP
from . import arrays as A
from . import backend as B
from . import pareto as P
from . import sweep as SW
from .constants import (CAMERA_FPS, DETNET_FPS, KEYNET_FPS, NUM_CAMERAS,
                        TechNode)
from .workloads import NNWorkload

#: Default flat-index chunk evaluated per device per step.  The executor
#: clamps the chunk to the (quantized) grid size so small grids never pay
#: for padded lanes.  2¹⁷ keeps the chunk's working set inside CPU
#: caches — the same fused step runs ~1.6× more configs/s than at 2¹⁸
#: (measured), and the finer chunking pipelines better.
DEFAULT_CHUNK = 1 << 17

#: Default number of chunks kept in flight ahead of the host merges.
DEFAULT_PREFETCH = 2

_FILTER_ROWS = 24      # explicit front rows in the dominance pre-filter
_FILTER_BINS = 256     # quantile bins of the prefix-min dominance table
_SURVIVOR_CAP = 16384  # per-chunk compacted-survivor capacity
_PROBE = 4096          # strided probe (front seed + histogram ranges)
_MERGE_EVERY = 4096    # candidate-buffer size that triggers an exact merge
_CHUNK_QUANTUM = 4096  # chunk sizes are clamped to multiples of this
_SCAN_MAX = 8          # auto scan fusion: at most this many chunks/dispatch
_SCAN_PER = 16         # ... one fused chunk per this many raw steps

#: Default seconds between checkpoint snapshots when ``checkpoint_dir``
#: is set (wall-clock cadence; ``checkpoint_every_steps`` overrides it
#: with a deterministic step-count cadence).
DEFAULT_CHECKPOINT_EVERY_S = 30.0

#: Failures that trigger a pipeline restart from the last consistent
#: snapshot (vs the in-place retry of pre-dispatch transient faults and
#: the elastic replan of device loss).
_RESTARTABLE = (TransientDeviceError, jax.errors.JaxRuntimeError)


# ---------------------------------------------------------------------------
# Result container
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """Reductions of one streamed sweep (never the dense grid itself).

    Holds O(front + k + axes) state: per-channel argmin winners, top-k
    tables for the tracked objectives, feasibility counts, channel
    bounds, optional histograms, and the exact Pareto front.  ``axes``
    matches :class:`~repro.core.sweep.SweepResult` (including the
    optional leading ``model`` axis), and flat indices are
    interchangeable with the dense path, so :meth:`config_at` decodes
    identically.  When the sweep ran with ``constraints=``, every
    reduction is over the *feasible* subset only.
    """

    axes: "OrderedDict[str, tuple]"
    objectives: tuple[str, ...]
    maximize: tuple[str, ...]
    chunk_size: int
    n_devices: int

    min_val: Mapping[str, float]          # per tracked channel: lowest value
    min_idx: Mapping[str, int]            # ... and its flat index
    finite_counts: Mapping[str, int]      # feasible-config counts (exact)
    channel_min: Mapping[str, float]      # feasible min / max per channel
    channel_max: Mapping[str, float]
    #: Valid-config counts per axis value from the strided probe pass —
    #: diagnostics for the all-invalid error messages, not exact tallies.
    axis_valid: "OrderedDict[str, np.ndarray]"

    topk_idx: np.ndarray                  # (n_objectives, k) flat indices
    topk_val: np.ndarray                  # natural-orientation values

    front_indices: np.ndarray             # (f,) flat indices, exact front
    front_values: np.ndarray              # (f, d) natural-orientation values

    hist: Optional[Mapping[str, tuple[np.ndarray, np.ndarray]]]
    stats: Mapping[str, float]
    #: Canonical ``(field, op, bound)`` predicates compiled into the chunk
    #: step (empty when the sweep was unconstrained).
    constraints: tuple[tuple[str, str, float], ...] = ()
    #: ``True`` when the stream halted early (a ``should_stop=`` hook —
    #: deadline or client cancel — fired before the grid was exhausted):
    #: every reduction is then exact over the contiguous flat-index
    #: prefix ``[0, stats["fraction_complete"] * n_configs)`` — the same
    #: consistent snapshot a checkpoint would persist — never a torn or
    #: interleaved subset.
    partial: bool = False

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(len(v) for v in self.axes.values())

    @property
    def n_configs(self) -> int:
        return int(np.prod(self.shape))

    def config_at(self, flat_index: int) -> dict:
        """Axis values of one flat grid index (the shared
        :func:`~repro.core.sweep.config_from_flat` decode — identical to
        the dense ``SweepResult.config_at``)."""
        return SW.config_from_flat(self.shape, self.axes, flat_index)

    def _invalid_notes(self) -> list[str]:
        return [f"{name}={vals[i]!r}"
                for (name, vals), counts in zip(self.axes.items(),
                                                self.axis_valid.values())
                for i in np.flatnonzero(counts == 0)]

    def _all_invalid_error(self, field: str) -> ValueError:
        if self.constraints:
            preds = ", ".join(f"{f} {op} {v:g}"
                              for f, op, v in self.constraints)
            return ValueError(
                f"no grid configuration is feasible in channel {field!r} "
                f"under constraints ({preds}) — loosen the constraints or "
                f"widen the grid axes")
        return ValueError(SW.invalid_message(field, self._invalid_notes()))

    def argmin(self, field: str = "avg_power") -> dict:
        """Best (lowest-``field``) feasible configuration.

        Exactly the dense-path ``SweepResult.argmin``: the *first*
        minimum wins, i.e. ties break toward the lower flat grid index
        (matching ``np.nanargmin`` on the dense channel array).  Raises
        :class:`ValueError` when every configuration is invalid
        (all-NaN) in ``field`` — naming the fully-invalid axis values —
        or, under ``constraints=``, when no configuration is feasible.
        """
        if field not in self.min_val:
            raise ValueError(
                f"channel {field!r} was not tracked; this stream reduced "
                f"{sorted(self.min_val)} — re-run stream_grid with "
                f"track=({field!r},) or track='all'")
        if self.finite_counts[field] == 0:
            raise self._all_invalid_error(field)
        out = self.config_at(self.min_idx[field])
        out[field] = self.min_val[field]
        return out

    def top_k(self, field: str) -> list[dict]:
        """The k best feasible configurations of one tracked objective,
        best first (k was fixed at :func:`stream_grid` time).

        Tie-breaking matches the dense ``SweepResult.top_k`` exactly:
        equal objective values order by ascending flat grid index (a
        stable sort over (value, flat index)).  Invalid (NaN) and
        constraint-infeasible configurations never appear; fewer than k
        entries come back when the feasible set is smaller than k.
        """
        if field not in self.objectives:
            raise ValueError(f"top-k tracks only {self.objectives}; "
                             f"re-run stream_grid with {field!r} in "
                             f"objectives=")
        oi = self.objectives.index(field)
        out = []
        for flat, val in zip(self.topk_idx[oi], self.topk_val[oi]):
            if not np.isfinite(val):
                break
            cfg = self.config_at(int(flat))
            cfg[field] = float(val)
            out.append(cfg)
        return out

    def channel_bounds(self, field: str) -> tuple[float, float]:
        """(min, max) of the feasible entries of one channel (the
        protocol :meth:`repro.core.pareto.ParetoFront.hypervolume` prices
        against).  Raises :class:`ValueError` on all-invalid (or
        all-infeasible) channels, like :meth:`argmin`."""
        if self.finite_counts[field] == 0:
            raise self._all_invalid_error(field)
        return self.channel_min[field], self.channel_max[field]

    def pareto_front(self) -> P.ParetoFront:
        """The exact non-dominated set as a regular
        :class:`~repro.core.pareto.ParetoFront` (identical — indices and
        values — to ``pareto.pareto_front`` on the dense grid, post
        ``SweepResult.constrain`` when constraints were given)."""
        sign0 = -1.0 if self.objectives[0] in self.maximize else 1.0
        order = np.argsort(self.front_values[:, 0] * sign0, kind="stable")
        return P.ParetoFront(
            result=self, objectives=self.objectives, maximize=self.maximize,
            indices=self.front_indices[order],
            values=self.front_values[order])


# ---------------------------------------------------------------------------
# Exact JSON codec + progress snapshots (the wire format of the
# networked service)
# ---------------------------------------------------------------------------


def _jsonable_scalar(v):
    return v.item() if isinstance(v, np.generic) else v


def result_to_json(res: StreamResult) -> dict:
    """Exact JSON-able encoding of a :class:`StreamResult`.

    Floats round-trip bitwise (Python's ``repr`` emits the shortest
    exact decimal, and non-finite values use the Python-extended JSON
    ``NaN``/``Infinity`` tokens), integer index tables stay int64 —
    :func:`result_from_json` reconstructs a result whose every
    reduction compares bitwise-equal to the original.  This is both
    the service's journal format for finished requests and the
    transport's result frame payload.
    """
    hist = None
    if res.hist is not None:
        hist = {f: [c.tolist(), e.tolist()]
                for f, (c, e) in res.hist.items()}
    return {
        "schema": "stream-result/v1",
        "axes": [[k, [_jsonable_scalar(v) for v in vals]]
                 for k, vals in res.axes.items()],
        "objectives": list(res.objectives),
        "maximize": list(res.maximize),
        "chunk_size": int(res.chunk_size),
        "n_devices": int(res.n_devices),
        "min_val": {k: float(v) for k, v in res.min_val.items()},
        "min_idx": {k: int(v) for k, v in res.min_idx.items()},
        "finite_counts": {k: int(v)
                          for k, v in res.finite_counts.items()},
        "channel_min": {k: float(v) for k, v in res.channel_min.items()},
        "channel_max": {k: float(v) for k, v in res.channel_max.items()},
        "axis_valid": [[k, np.asarray(v).tolist()]
                       for k, v in res.axis_valid.items()],
        "topk_idx": res.topk_idx.tolist(),
        "topk_val": res.topk_val.tolist(),
        "front_indices": res.front_indices.tolist(),
        "front_values": res.front_values.tolist(),
        "hist": hist,
        "stats": {k: float(v) for k, v in res.stats.items()},
        "constraints": [list(c) for c in res.constraints],
        "partial": bool(res.partial),
    }


def result_from_json(d: Mapping) -> StreamResult:
    """Inverse of :func:`result_to_json` (bitwise-exact round-trip)."""
    n_obj = len(d["objectives"])
    hist = None
    if d.get("hist") is not None:
        hist = {f: (np.asarray(c), np.asarray(e, np.float64))
                for f, (c, e) in d["hist"].items()}
    front_v = np.asarray(d["front_values"], np.float64)
    return StreamResult(
        axes=OrderedDict((k, tuple(vals)) for k, vals in d["axes"]),
        objectives=tuple(d["objectives"]),
        maximize=tuple(d["maximize"]),
        chunk_size=int(d["chunk_size"]),
        n_devices=int(d["n_devices"]),
        min_val=dict(d["min_val"]),
        min_idx={k: int(v) for k, v in d["min_idx"].items()},
        finite_counts={k: int(v)
                       for k, v in d["finite_counts"].items()},
        channel_min=dict(d["channel_min"]),
        channel_max=dict(d["channel_max"]),
        axis_valid=OrderedDict((k, np.asarray(v))
                               for k, v in d["axis_valid"]),
        topk_idx=np.asarray(d["topk_idx"], np.int64).reshape(n_obj, -1),
        topk_val=np.asarray(d["topk_val"],
                            np.float64).reshape(n_obj, -1),
        front_indices=np.asarray(d["front_indices"], np.int64),
        front_values=front_v.reshape(-1, n_obj),
        hist=hist,
        stats=dict(d["stats"]),
        constraints=tuple((f, op, v) for f, op, v in d["constraints"]),
        partial=bool(d["partial"]),
    )


def _progress_snapshot(folded: int, n_total: int, front_vals, front_idx,
                       objectives, sign) -> dict:
    """JSON-able progress snapshot over the folded contiguous prefix
    ``[0, folded)``: fraction complete, running per-objective best
    (value + flat index, read off the running front — the single-
    objective optimum is always a non-dominated point) and front size.
    The running front is conservatively pre-filtered against probe
    witnesses from the whole grid, so a mid-run ``best`` can only be
    *pessimistic* relative to the prefix; the final result (and any
    cooperative-stop partial) is exact."""
    best = {}
    for oi, f in enumerate(objectives):
        if front_vals.shape[0]:
            j = int(np.argmin(front_vals[:, oi] * sign[oi]))
            best[f] = {"value": float(front_vals[j, oi]),
                       "index": int(front_idx[j])}
    return {"fraction_complete": (folded / n_total if n_total else 1.0),
            "front_size": int(front_vals.shape[0]),
            "partial": True,
            "best": best,
            # Running-front membership, rows sorted by flat index (the
            # merge invariant).  A flat index's objective vector never
            # changes, so the delta codec below can key front changes
            # purely by index (entrant / evict records).
            "front": {"i": [int(i) for i in front_idx],
                      "v": [[float(x) for x in row]
                            for row in front_vals]}}


def result_delta_to_json(prev: Optional[Mapping],
                         cur: Mapping) -> dict:
    """Per-chunk *delta* between two consecutive progress snapshots.

    The networked ``watch`` stream sends one full snapshot (the
    baseline) and then only deltas: changed top-level scalars, changed
    per-objective running-best records, and front entrant/evict
    records keyed by flat index (a config's objective vector is
    immutable, so membership changes are the whole story).  With
    ``prev=None`` the delta is the full snapshot.
    :func:`apply_result_delta` reconstructs ``cur`` exactly — the
    round trip is pinned value-equal in the tests, and the *final*
    result always travels through :func:`result_to_json`, so delta
    streaming can never touch result exactness.
    """
    if prev is None:
        return dict(cur)
    out: dict = {}
    for k in ("fraction_complete", "front_size", "partial"):
        if prev.get(k) != cur.get(k):
            out[k] = cur[k]
    pb, cb = prev.get("best", {}), cur.get("best", {})
    changed = {f: v for f, v in cb.items() if pb.get(f) != v}
    if changed:
        out["best"] = changed
    gone = [f for f in pb if f not in cb]
    if gone:
        out["best_del"] = gone
    pf = prev.get("front") or {"i": [], "v": []}
    cf = cur.get("front") or {"i": [], "v": []}
    pset = set(pf["i"])
    add_i = [i for i in cf["i"] if i not in pset]
    if add_i:
        vmap = dict(zip(cf["i"], cf["v"]))
        out["front_add"] = {"i": add_i, "v": [vmap[i] for i in add_i]}
    dels = sorted(pset - set(cf["i"]))
    if dels:
        out["front_del"] = dels
    return out


def apply_result_delta(prev: Optional[Mapping],
                       delta: Mapping) -> dict:
    """Inverse of :func:`result_delta_to_json`: fold one delta into the
    previous snapshot, reconstructing the full snapshot dict (rows
    re-sorted by flat index — the snapshot invariant)."""
    if prev is None:
        return dict(delta)
    cur = dict(prev)
    for k in ("fraction_complete", "front_size", "partial"):
        if k in delta:
            cur[k] = delta[k]
    best = dict(prev.get("best", {}))
    best.update(delta.get("best", {}))
    for f in delta.get("best_del", ()):
        best.pop(f, None)
    cur["best"] = best
    pf = prev.get("front") or {"i": [], "v": []}
    rows = dict(zip(pf["i"], pf["v"]))
    for i in delta.get("front_del", ()):
        rows.pop(i, None)
    add = delta.get("front_add")
    if add:
        rows.update(zip(add["i"], add["v"]))
    idx = sorted(rows)
    cur["front"] = {"i": idx, "v": [rows[i] for i in idx]}
    return cur


# ---------------------------------------------------------------------------
# Host-side exact merges
# ---------------------------------------------------------------------------


def _np_undominated(cand_sg: np.ndarray, wit_sg: np.ndarray) -> np.ndarray:
    """Candidates (signed ``(n, d)``) no witness row strictly dominates —
    the exact vectorized cull behind :func:`_merge_into_front`.  Built
    from per-column 2-D broadcasts (witness-blocked): a (n, w, d) 3-D
    broadcast materializes d× the temporaries and is several times
    slower at these shapes."""
    keep = np.ones(cand_sg.shape[0], bool)
    d = cand_sg.shape[1]
    for lo in range(0, wit_sg.shape[0], 512):
        blk = wit_sg[lo:lo + 512]
        le = blk[:, None, 0] <= cand_sg[None, :, 0]
        lt = blk[:, None, 0] < cand_sg[None, :, 0]
        for c in range(1, d):
            le &= blk[:, None, c] <= cand_sg[None, :, c]
            lt |= blk[:, None, c] < cand_sg[None, :, c]
        keep &= ~(le & lt).any(axis=0)
    return keep


def _merge_into_front(front_v, front_i, cat_v, cat_i, sign):
    """Exactly merge pre-filtered candidates into the running front.

    Equivalent to :func:`repro.core.pareto.merge_fronts` but exploits the
    invariant that ``front`` is already mutually non-dominated: entrants
    are culled against the front, then against each other, then surviving
    entrants evict any front member they dominate — three small
    vectorized passes instead of re-scanning the whole union.  Rows stay
    sorted by flat index, so tie order matches the dense path exactly.
    """
    if cat_v.shape[0] == 0:
        return front_v, front_i
    cat_sg = cat_v * sign
    if front_v.shape[0]:
        front_sg = front_v * sign
        keep_c = _np_undominated(cat_sg, front_sg)
        cat_v, cat_i, cat_sg = cat_v[keep_c], cat_i[keep_c], cat_sg[keep_c]
        if cat_v.shape[0] == 0:
            return front_v, front_i
        keep_c = P.non_dominated_mask(cat_sg)
        cat_v, cat_i, cat_sg = cat_v[keep_c], cat_i[keep_c], cat_sg[keep_c]
        keep_f = _np_undominated(front_sg, cat_sg)
        V = np.concatenate([front_v[keep_f], cat_v])
        I = np.concatenate([front_i[keep_f], cat_i])
    else:
        keep = P.non_dominated_mask(cat_sg)
        V, I = cat_v[keep], cat_i[keep]
    order = np.argsort(I, kind="stable")
    return V[order], I[order]


def _probe(S, axis_vals, shape, n_total, obj_fields, sign, cons, hist_bins,
           hist_ranges):
    """Strided sample pass: seeds the front filter, histogram ranges and
    the per-axis-value validity diagnostics.

    The probe points are ordinary grid points evaluated through the same
    compiled kernel; they only ever *pre-filter* (the exact front is built
    solely from chunk survivors), so correctness never depends on probe
    coverage.  Constraint predicates mask the probe exactly like the
    chunk step, so an infeasible probe point can never cull a feasible
    candidate.  The seed rows are *not* reduced to their own front — a
    dominated evaluated point is still an exact dominance witness, and
    the quantile/prefix-min filter build only gets tighter with more
    rows.
    """
    m = int(min(_PROBE, max(256, n_total // 128), n_total))
    flat = np.unique(np.linspace(0, n_total - 1, m).astype(np.int64))
    fields = obj_fields + tuple(f for f, _, _ in cons
                                if f not in obj_fields)
    out = B.cached_dense_eval("xla", S, shape, fields)(
        tuple(map(jnp.asarray, axis_vals)), jnp.asarray(flat))
    O = np.stack([np.asarray(out[f]) for f in obj_fields], axis=1)
    coords = SW.decode_flat_index(shape, flat)
    feas = np.ones(flat.size, bool)
    with np.errstate(invalid="ignore"):
        for f, op, v in cons:
            feas &= SW.CONSTRAINT_OPS[op](np.asarray(out[f]), v)
    fin = np.isfinite(O).all(axis=1) & feas
    axis_valid = tuple(np.bincount(c[fin], minlength=sz)
                       for c, sz in zip(coords, shape))
    seed = O[fin] * sign
    if seed.shape[0]:
        # The probe runs through the dense jit while chunks run through
        # the step jit; the two lowerings can disagree in the last ulp.
        # Pad the seed rows outward so a probe twin of a front point can
        # never strictly dominate (and wrongly cull) its chunk-evaluated
        # copy — the filter stays conservative, the host merge is exact.
        seed = seed + (1e-9 * np.abs(seed) + 1e-300)

    edges = None
    if hist_bins:
        edges = np.empty((len(obj_fields), hist_bins + 1))
        for oi, f in enumerate(obj_fields):
            if hist_ranges is not None and f in hist_ranges:
                lo, hi = map(float, hist_ranges[f])
            else:
                col = O[:, oi][np.isfinite(O[:, oi])]
                if col.size == 0:
                    lo, hi = 0.0, 1.0
                else:
                    lo, hi = float(col.min()), float(col.max())
                    pad = 0.05 * ((hi - lo) or max(abs(lo), 1.0))
                    lo, hi = lo - pad, hi + pad
            if hi <= lo:
                hi = lo + 1.0
            edges[oi] = np.linspace(lo, hi, hist_bins + 1)
    return seed, edges, axis_valid


def _resume_into(mgr: CheckpointManager, signature: str, state: dict,
                 counters: dict, chunk: int) -> None:
    """Restore the newest valid snapshot of ``mgr`` into ``state``.

    Snapshots are tried newest-first; one whose manifest is unreadable
    (truncated by a foreign writer — the atomic rename means our own
    crashes can only leave ``.tmp`` debris) falls back to the next
    older.  A snapshot recorded under a *different* job signature is a
    hard error: silently merging carry state across specifications
    would corrupt every deliverable, so stale checkpoints must fail
    loudly.
    """
    for step in reversed(mgr.all_steps()):
        try:
            meta = mgr.metadata(step)
        except (OSError, ValueError, KeyError):
            continue
        saved = meta.get("signature") if isinstance(meta, dict) else None
        if saved != signature:
            raise ValueError(
                f"checkpoint directory {mgr.root!r} (step {step}) was "
                f"written by a different sweep job (signature "
                f"{str(saved)[:12]}... != {signature[:12]}...): refusing "
                f"to resume, a stale snapshot must never merge into a "
                f"new sweep.  The signature covers the model stack, "
                f"axes, objectives/tracked fields, constraints, top_k, "
                f"histogram spec, backend, chunk size and scan fusion "
                f"(chunk and scan_chunks auto-derive from the device "
                f"count unless passed explicitly).  Point "
                f"checkpoint_dir at a fresh directory or delete the "
                f"stale checkpoints.")
        items = mgr.restore_items(step)
        state["carry"] = {kk.split("/", 1)[1]: v
                          for kk, v in items.items()
                          if kk.startswith("carry/")}
        state["front_vals"] = np.asarray(items["front_values"],
                                         np.float64)
        state["front_idx"] = np.asarray(items["front_indices"], np.int64)
        state["base"] = int(meta["next_flat"])
        for kk, v in (meta.get("counters") or {}).items():
            if kk in counters:
                counters[kk] = float(v)
        counters["resumed_from_step"] = float(state["base"] // chunk)
        return


# ---------------------------------------------------------------------------
# Plan: the resolved job definition (reusable across runs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class StreamPlan:
    """Resolved execution plan of one streamed sweep.

    Everything that *defines* the job — model stack, axes, tracked
    fields, constraints, chunk/scan geometry, device pool, the
    :class:`~repro.core.backend.ChunkSpec` and its content
    ``signature`` — split out of :func:`stream_grid` so a long-lived
    process (:mod:`repro.core.service`) can build it once per distinct
    job and reuse it across requests.  ``ChunkSpec`` hashes its model
    stack by identity, so re-running with the *same plan object* is
    what makes :func:`repro.core.backend.cached_step` return the
    already-compiled chunk step instead of re-tracing.  Build with
    :func:`plan_stream`, execute with ``stream_grid(plan=...)``
    (runtime knobs — prefetch, checkpointing, retry policy, hooks —
    stay per-call and do not affect the plan or its signature).
    """

    S: object                       # struct-of-arrays model stack
    axis_vals: tuple                # per-axis value arrays (grid order)
    axes: "OrderedDict[str, tuple]"
    shape: tuple
    n_total: int
    kfields: tuple
    objectives: tuple
    maximize: tuple
    fields: tuple                   # objectives + tracked + constrained
    cons: tuple                     # canonical (field, op, bound)
    sign: tuple                     # +1 minimize / -1 maximize per obj
    d: int
    k: int
    chunk: int
    scan: int
    backend: str
    dev_list: tuple
    explicit_devices: bool
    hist_bins: int
    hist_ranges: Optional[Mapping]
    spec: B.ChunkSpec
    #: Content hash of the job (:func:`repro.core.backend.job_signature`)
    #: — the checkpoint/resume key and the service plan-cache key.
    signature: str


def plan_stream(cuts: Optional[Iterable[int]] = None,
                agg_nodes: Sequence[str | TechNode] = ("7nm",),
                sensor_nodes: Sequence[str | TechNode] = ("7nm",),
                weight_mems: Sequence[str] = ("sram",),
                detnet_fps: Sequence[float] = (DETNET_FPS,),
                keynet_fps: Sequence[float] = (KEYNET_FPS,),
                num_cameras: Sequence[float] = (NUM_CAMERAS,),
                mipi_energy_scale: Sequence[float] = (1.0,),
                camera_fps: Sequence[float] = (CAMERA_FPS,),
                detnet: NNWorkload | None = None,
                keynet: NNWorkload | None = None,
                model: A.ModelArrays | None = None,
                models=None,
                scenarios=None,
                chunk_size: int = DEFAULT_CHUNK,
                top_k: int = 4,
                objectives: Sequence[str] = P.DEFAULT_OBJECTIVES,
                maximize: Iterable[str] = (),
                track: Optional[Sequence[str]] = None,
                constraints=None,
                hist_bins: int = 0,
                hist_ranges: Optional[Mapping] = None,
                devices: Optional[Sequence] = None,
                backend: Optional[str] = None,
                scan_chunks: Optional[int] = None) -> StreamPlan:
    """Resolve a :func:`stream_grid` job definition into a reusable
    :class:`StreamPlan` (axes → :class:`~repro.core.backend.ChunkSpec`
    → content signature) without running anything.

    Identical argument semantics to :func:`stream_grid` (which calls
    this when no ``plan=`` is passed), so ``plan_stream(**kw)`` /
    ``stream_grid(plan=plan)`` splits the cheap spec resolution from
    the execution — the split the sweep service uses to key its plan
    LRU by ``plan.signature`` and keep compiled chunk steps hot across
    requests.
    """
    S, axis_vals, axes = SW.build_axes(
        cuts, agg_nodes, sensor_nodes, weight_mems, detnet_fps, keynet_fps,
        num_cameras, mipi_energy_scale, camera_fps, detnet, keynet, model,
        models, scenarios)
    full_shape = tuple(a.size for a in axis_vals)
    n_total = int(np.prod(full_shape))
    kfields = SW.kernel_fields(S)

    objectives = tuple(objectives)
    maximize = tuple(maximize)
    if not objectives:
        raise ValueError("need at least one objective channel")
    if track == "all":
        extra: tuple = kfields
    else:
        extra = tuple(track) if track is not None else ()
    cons = SW.parse_constraints(constraints)
    extra = extra + tuple(f for f, _, _ in cons)
    fields = objectives + tuple(dict.fromkeys(
        f for f in extra if f not in objectives))
    unknown = [o for o in fields if o not in kfields]
    if unknown:
        hint = (" — session channels require scenarios="
                if any(o in SW.SCENARIO_FIELDS for o in unknown) else "")
        raise ValueError(f"unknown objective channels {unknown}; this "
                         f"sweep evaluates {kfields}{hint}")
    stray = [o for o in maximize if o not in objectives]
    if stray:
        raise ValueError(f"maximize entries {stray} not in objectives")
    sign = np.where([o in maximize for o in objectives], -1.0, 1.0)
    d = len(objectives)
    cons_static = tuple((fields.index(f), op) for f, op, _ in cons)

    be = B.get_backend(backend)          # fail fast on unknown backends
    dev_list = list(devices) if devices is not None else jax.local_devices()
    if devices is None and len(dev_list) > 1 and not be.supports_pmap:
        # Auto-derived device lists must not crash a non-pmap backend —
        # fall back to one device; an *explicit* multi-device devices=
        # still raises clearly in backend.build_step.
        dev_list = dev_list[:1]
    n_dev = max(1, len(dev_list))
    k = max(1, min(int(top_k), n_total))
    # Clamp the chunk to the quantized per-device need: a 10⁵-config grid
    # must not pay for a 2.6×-padded 2¹⁸ chunk, and quantizing keeps the
    # compiled-step cache hot across nearby grid sizes.
    chunk = max(1, int(chunk_size), k)
    per_dev = -(-n_total // n_dev)
    chunk = min(chunk, -(-per_dev // _CHUNK_QUANTUM) * _CHUNK_QUANTUM)
    cap = min(_SURVIVOR_CAP, chunk)
    # Scan fusion: fold K consecutive chunks per device dispatch
    # (lax.scan threads the carry), so per-step dispatch overhead is
    # paid once per K chunks.  Auto mode scales K with the raw step
    # count — small grids keep K=1 (nothing to amortize, and the filter
    # refresh cadence stays fine-grained).
    raw_steps = -(-per_dev // chunk)
    if scan_chunks is None:
        scan = max(1, min(_SCAN_MAX, raw_steps // _SCAN_PER))
    else:
        scan = max(1, int(scan_chunks))
    scan = min(scan, raw_steps)
    per_step = chunk * scan * n_dev

    spec = B.ChunkSpec(
        S=S, shape=full_shape, n_total=n_total, chunk=chunk,
        fields=fields, d=d, k=k, sign=tuple(sign),
        cons_static=cons_static, hist_bins=hist_bins,
        survivor_cap=cap,
        small_index=n_total + per_step < 2**31,
        filter_rows=_FILTER_ROWS, filter_bins=_FILTER_BINS)
    signature = B.job_signature(spec, be.name, scan, cons, axis_vals,
                                hist_ranges)
    return StreamPlan(
        S=S, axis_vals=tuple(axis_vals), axes=axes, shape=full_shape,
        n_total=n_total, kfields=kfields, objectives=objectives,
        maximize=maximize, fields=fields, cons=cons, sign=tuple(sign),
        d=d, k=k, chunk=chunk, scan=scan, backend=be.name,
        dev_list=tuple(dev_list), explicit_devices=devices is not None,
        hist_bins=hist_bins, hist_ranges=hist_ranges, spec=spec,
        signature=signature)


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------


def stream_grid(cuts: Optional[Iterable[int]] = None,
                agg_nodes: Sequence[str | TechNode] = ("7nm",),
                sensor_nodes: Sequence[str | TechNode] = ("7nm",),
                weight_mems: Sequence[str] = ("sram",),
                detnet_fps: Sequence[float] = (DETNET_FPS,),
                keynet_fps: Sequence[float] = (KEYNET_FPS,),
                num_cameras: Sequence[float] = (NUM_CAMERAS,),
                mipi_energy_scale: Sequence[float] = (1.0,),
                camera_fps: Sequence[float] = (CAMERA_FPS,),
                detnet: NNWorkload | None = None,
                keynet: NNWorkload | None = None,
                model: A.ModelArrays | None = None,
                models=None,
                scenarios=None,
                chunk_size: int = DEFAULT_CHUNK,
                top_k: int = 4,
                objectives: Sequence[str] = P.DEFAULT_OBJECTIVES,
                maximize: Iterable[str] = (),
                track: Optional[Sequence[str]] = None,
                constraints=None,
                prefetch: int = DEFAULT_PREFETCH,
                hist_bins: int = 0,
                hist_ranges: Optional[Mapping] = None,
                devices: Optional[Sequence] = None,
                backend: Optional[str] = None,
                scan_chunks: Optional[int] = None,
                checkpoint_dir: Optional[str] = None,
                checkpoint_every_s: float = DEFAULT_CHECKPOINT_EVERY_S,
                checkpoint_every_steps: Optional[int] = None,
                checkpoint_keep: int = 3,
                retry_policy: Optional[RetryPolicy] = None,
                fault_injector=None,
                plan: Optional[StreamPlan] = None,
                flat_range: Optional[tuple] = None,
                should_stop=None,
                on_progress=None,
                on_snapshot=None,
                snapshot_every_s: float = 0.5,
                ticket: Optional[str] = None) -> StreamResult:
    """Stream Eqs. 1-11 over an arbitrarily large cartesian grid.

    Same axes (and ``models=`` workload batch) as
    :func:`repro.core.sweep.evaluate_grid`, but the grid is never
    materialized: flat indices are decoded to coordinates on-device in
    ``chunk_size`` pieces (per device) and folded into running
    reductions, so host memory is O(chunk + front) for any grid size.
    Argmin, top-k and Pareto front are *exactly* the dense-path results.

    ``scenarios=`` (a :class:`repro.core.scenario.ScenarioSet` or
    profile name(s)) appends a trailing ``trace`` axis and drives every
    (config × trace) pair through the session simulator inside the same
    chunk contract; the four session channels
    (:data:`repro.core.sweep.SCENARIO_FIELDS` — e.g.
    ``time_to_empty_s``, usually with ``maximize=("time_to_empty_s",)``,
    and ``peak_case_temp_c``) then work as objectives, constraints and
    tracked channels exactly like the static fields.

    ``objectives``/``maximize`` select the channels tracked by top-k and
    the incremental Pareto front.  ``track`` adds further channels to the
    argmin/count/bounds reductions (or ``"all"`` for every kernel field)
    — untracked channels are dead-code-eliminated from the compiled step,
    so track only what you need.  ``constraints`` compiles feasibility
    predicates (:func:`repro.core.sweep.parse_constraints` — e.g.
    ``{"latency": budget}``, ``{"mipi_bytes_per_s": ("<=", link_cap)}``
    or ``("latency <= 1e-3",)``) into the chunk step: infeasible
    configurations are masked before any reduction, matching a dense
    ``SweepResult.constrain`` post-filter exactly; constrained channels
    are tracked automatically.  ``prefetch`` keeps that many chunks in
    flight ahead of the host merges (0 = fully synchronous) so merge
    work overlaps device compute.  ``hist_bins`` adds per-objective
    histograms (ranges from ``hist_ranges`` or a strided probe pass, with
    out-of-range values clamped into the end bins).  ``devices`` shards
    the chunk stream across multiple JAX devices via ``pmap``.

    ``backend`` selects the evaluation backend of the chunk step
    (:func:`repro.core.backend.get_backend`; ``None`` -> ``"xla"``,
    ``"pallas"`` fuses decode + Eq. 1-11 + block reductions into the
    Pallas grid kernel of :mod:`repro.kernels.sweep_grid`).
    ``scan_chunks`` fuses that many consecutive chunk folds into one
    device dispatch via ``lax.scan``, cutting per-chunk dispatch
    overhead on 10^7+-config spaces (``None`` auto-scales with the step
    count; 1 disables).  Both knobs are bitwise result-preserving —
    every backend and every scan depth reproduces the dense-path
    argmin/top-k/front exactly (the parity matrix of
    ``tests/test_backend.py``).

    ``checkpoint_dir`` makes the sweep resumable: the executor
    snapshots its consistent state (merged carry + exact front + next
    flat index) there every ``checkpoint_every_s`` seconds — or every
    ``checkpoint_every_steps`` dispatch steps when given, which is
    deterministic — keeping the ``checkpoint_keep`` newest snapshots,
    and a later call with the *same specification* resumes from the
    newest snapshot with bitwise-identical deliverables (a different
    specification is rejected with :class:`ValueError`).
    ``retry_policy`` (default :class:`repro.runtime.fault_tolerance.
    RetryPolicy`) bounds the recovery machinery: in-place retries of
    transient pre-dispatch faults, pipeline restarts from the last
    snapshot, straggler/timeout accounting.  ``fault_injector`` is a
    test hook called as ``injector(chunk_ordinal, flat_start)`` before
    every dispatch (see :mod:`repro.runtime.fault_injection`).
    Resilience counters land in ``StreamResult.stats``: ``retries``,
    ``restarts``, ``resumed_from_step``, ``checkpoints_written``,
    ``checkpoint_write_s``, ``chunks_reissued``, ``elastic_replans``,
    ``stragglers`` and ``step_timeouts``.

    ``plan`` short-circuits the spec resolution with a prebuilt
    :class:`StreamPlan` (see :func:`plan_stream`) — when given, the
    axis/objective/backend arguments above are ignored in its favor;
    a long-lived process reusing one plan object across calls is what
    keeps the compiled chunk step cached.  ``flat_range=(start, stop)``
    restricts the sweep to one contiguous slice of the flat-index
    space — the unit of work a multi-process worker pool leases
    (:mod:`repro.runtime.workers`): every reduction is exact over
    ``[start, stop)``, the carry keeps the device-count-independent
    serialization form, and :func:`merge_results` folds the per-range
    results of a full tiling back into the bitwise single-process
    answer.  Because the compiled step masks lanes only against the
    *grid* end, ``stop`` must land on a dispatch boundary
    (``(stop - start) % (chunk * scan_chunks * n_devices) == 0``)
    unless it is the grid end itself — pin ``chunk_size`` and
    ``scan_chunks`` explicitly when carving ranges.  ``stats`` gains
    ``range_start``/``range_stop``, and ``fraction_complete`` (and
    progress snapshots) are relative to the range.  ``should_stop`` is a
    zero-argument callable polled before every chunk dispatch (on the
    producer thread in the pipelined path): when it returns truthy the
    executor stops issuing work within one chunk, folds everything
    already dispatched and returns the consistent prefix snapshot as a
    ``partial=True`` result (``stats["fraction_complete"]`` < 1) — and
    still writes a terminal checkpoint when ``checkpoint_dir`` is set,
    so a later call resumes where the stop landed.  ``on_progress`` is
    called after each dispatch with the fraction of the grid issued so
    far (also from the producer thread; keep it cheap and
    thread-safe) — and only after any checkpoint due for that step is
    durably on disk, so every observed fraction is resumable: a kill
    right after a progress event never restarts from before it.
    ``on_snapshot`` is called (from the consumer thread, at most every
    ``snapshot_every_s`` seconds) with a JSON-able consistent progress
    summary over the folded contiguous prefix — ``fraction_complete``,
    running per-objective best and front size (see
    :func:`_progress_snapshot`) — the payload the networked service
    streams to subscribed clients; snapshots obey the same durability
    ordering (a step with a checkpoint due emits its snapshot only
    after the checkpoint is on disk).

    The executor's phases open profiler spans (:mod:`repro.runtime.
    spans`): ``repro.stream.plan`` (the spec resolution when no
    ``plan`` is given), ``repro.stream.grid`` around the rest — with
    ``ticket`` as its metadata when the sweep service is the caller —
    and inside it ``.probe``, ``.dispatch``, ``.fetch``, ``.merge``,
    ``.fallback`` and ``.checkpoint``.  The spans of ``.grid``,
    ``.fetch``, ``.merge``, ``.dispatch``, ``.fallback`` and
    ``.checkpoint`` are the clocks of ``stats``' ``total_s``,
    ``device_wait_s``, ``host_merge_s``, ``dispatch_s``, ``fallback_s``
    and ``checkpoint_write_s``.
    """
    if plan is None:
        with SP.span("repro.stream.plan"):
            plan = plan_stream(
                cuts, agg_nodes, sensor_nodes, weight_mems, detnet_fps,
                keynet_fps, num_cameras, mipi_energy_scale, camera_fps,
                detnet, keynet, model, models, scenarios,
                chunk_size=chunk_size, top_k=top_k, objectives=objectives,
                maximize=maximize, track=track, constraints=constraints,
                hist_bins=hist_bins, hist_ranges=hist_ranges,
                devices=devices, backend=backend, scan_chunks=scan_chunks)
    S = plan.S
    axis_vals = list(plan.axis_vals)
    axes = plan.axes
    full_shape = plan.shape
    n_total = plan.n_total
    kfields = plan.kfields
    objectives = plan.objectives
    maximize = plan.maximize
    fields = plan.fields
    cons = plan.cons
    sign = np.asarray(plan.sign)
    d = plan.d
    k = plan.k
    chunk = plan.chunk
    scan = plan.scan
    spec = plan.spec
    hist_bins = plan.hist_bins
    hist_ranges = plan.hist_ranges
    cap = spec.survivor_cap
    dev_list = list(plan.dev_list)
    n_dev = max(1, len(dev_list))
    per_step = chunk * scan * n_dev
    if flat_range is None:
        start0, stop0 = 0, n_total
    else:
        start0, stop0 = int(flat_range[0]), int(flat_range[1])
        if not 0 <= start0 < stop0 <= n_total:
            raise ValueError(
                f"flat_range {flat_range} outside the grid "
                f"[0, {n_total})")
        if stop0 != n_total and (stop0 - start0) % per_step:
            # The compiled step masks lanes only against the grid end
            # (flat < n_total), so an interior stop must land on a
            # dispatch boundary or the last dispatch would fold lanes
            # belonging to the next range.
            raise ValueError(
                f"flat_range length {stop0 - start0} is not a multiple "
                f"of the dispatch quantum {per_step} (chunk {chunk} x "
                f"scan {scan} x {n_dev} device(s)) and stop != n_total "
                f"({n_total}): pass chunk_size/scan_chunks explicitly "
                f"and carve ranges on dispatch boundaries")
    span = stop0 - start0
    n_steps = math.ceil(span / per_step)
    prefetch = max(0, int(prefetch))

    # The executor's clocks, each the duration of its span (SP.timed).
    clk = {"total_s": 0.0, "device_wait_s": 0.0, "host_merge_s": 0.0,
           "dispatch_s": 0.0, "fallback_s": 0.0}
    policy = retry_policy if retry_policy is not None else RetryPolicy()
    counters = {
        "retries": 0.0, "restarts": 0.0, "resumed_from_step": 0.0,
        "checkpoint_write_s": 0.0, "checkpoints_written": 0.0,
        "chunks_reissued": 0.0, "elastic_replans": 0.0,
        "stragglers": 0.0, "step_timeouts": 0.0,
    }
    def on_devices():
        # An explicit devices= list pins every device op of the run —
        # probe, chunk steps and survivor-overflow fallback — so a
        # reference run on the CPU never evaluates anything elsewhere.
        return (jax.default_device(dev_list[0]) if plan.explicit_devices
                else contextlib.nullcontext())

    with SP.timed(clk, "total_s", "repro.stream.grid",
                  **({} if ticket is None else {"ticket": ticket})) as t0, \
            jax.enable_x64(True), on_devices():
        with SP.span("repro.stream.probe"):
            seed_signed, hist_edges, axis_valid = _probe(
                S, axis_vals, full_shape, n_total, objectives, sign, cons,
                hist_bins, hist_ranges)

        # The consistent snapshot all recovery pivots on: the merged
        # (device-count-independent) host carry, the exact running
        # front, and the next flat-index cursor — every chunk below
        # ``base`` is folded in, nothing above it is.  Restarts,
        # elastic replans and cross-process resumes all rebuild the
        # pipeline from here.  chunk and scan were derived above from
        # the *full* grid geometry (never the remaining work), so a
        # resumed run recreates the identical ChunkSpec and signature.
        state = {"carry": B.init_carry(spec),
                 "front_vals": np.empty((0, d)),
                 "front_idx": np.empty((0,), np.int64),
                 "base": start0}
        mgr = None
        signature = ""
        if checkpoint_dir is not None:
            mgr = CheckpointManager(checkpoint_dir,
                                    keep=max(1, int(checkpoint_keep)))
            # Ranged runs suffix the signature so a lease's checkpoint
            # can never restore into a different range of the same job.
            signature = (plan.signature if flat_range is None else
                         f"{plan.signature}:r{start0}-{stop0}")
            _resume_into(mgr, signature, state, counters, chunk)

        def write_checkpoint():
            with SP.timed(counters, "checkpoint_write_s",
                          "repro.stream.checkpoint"):
                mgr.save(int(state["base"]),
                         {"carry": state["carry"],
                          "front_values": state["front_vals"],
                          "front_indices": state["front_idx"]},
                         metadata={"signature": signature,
                                   "next_flat": int(state["base"]),
                                   "counters": dict(counters),
                                   "format": B.CARRY_VERSION})
            counters["checkpoints_written"] += 1.0

        # Pre-cull the probe seed toward its near-front subset once: the
        # filter build draws quantile bins and spread rows from the rows
        # it is given, and a mostly-dominated cloud drags both toward the
        # data mass instead of the front envelope (culls measurably
        # worse).  Filter-based culling is exact, so this is quality-only.
        if seed_signed.shape[0] > 4 * _FILTER_ROWS:
            f0 = P.build_dominance_filter(seed_signed, d, _FILTER_ROWS,
                                          _FILTER_BINS)
            seed_signed = seed_signed[P.dominance_filter_mask(
                f0, np.ascontiguousarray(seed_signed.T), xp=np)]
        t_first = None
        n_fallback = 0
        detector = StragglerDetector(policy.straggler_factor,
                                     policy.straggler_window)
        dispatched_flat = state["base"]     # dispatch high-water mark
        # Cooperative halt: set when should_stop fires between
        # dispatches; the incarnation then finalizes over exactly the
        # chunks already issued (all of which the consumer folds before
        # the pipeline winds down) instead of the full grid.
        ctl = {"halted": False}
        # Progress-snapshot throttle (consumer-thread clock), shared
        # across pipeline incarnations so restarts don't burst emits.
        snap_t = {"last": time.perf_counter()}

        def drive():
            # One incarnation of the pipeline: rebuild the compiled
            # step, device placement and filter for the *current*
            # device pool, restore carry + front from the snapshot, run
            # every remaining chunk, then advance the snapshot to
            # completion.  Raises on device loss / exhausted retries;
            # the control loop below decides replan vs restart.
            nonlocal t_first, n_fallback, dispatched_flat
            base = state["base"]
            if base >= stop0:       # resumed-from-complete: nothing left
                return
            n_dev = max(1, len(dev_list))
            run = B.cached_step(spec, plan.backend, scan, n_dev,
                                dev_list if n_dev > 1 else None)
            # One batched device_put per pytree — per-leaf jnp.asarray
            # calls cost ~10 ms of pure dispatch per stream on small
            # grids.  With several devices, broadcast state is
            # replicated up front so the pmap path never re-shards an
            # argument per step.
            if n_dev > 1:
                # The pmap layout: a leading device axis holding one
                # replica per device.
                rep = jax.sharding.NamedSharding(
                    jax.sharding.Mesh(np.asarray(dev_list), ("dev",)),
                    jax.sharding.PartitionSpec("dev"))
                put = (lambda t: jax.device_put(jax.tree_util.tree_map(
                    lambda x: np.broadcast_to(x, (n_dev,) + np.shape(x)),
                    t), rep))
            else:
                dev_target = dev_list[0] if plan.explicit_devices else None
                put = (lambda t: jax.device_put(t, dev_target))
            axvals_j = put(tuple(axis_vals))
            per_step = chunk * scan * n_dev
            n_steps = -(-(stop0 - base) // per_step)
            # Snapshot carry -> device: merged state on shard 0, fresh
            # inits on the rest (the merge is associative and exact, so
            # a snapshot restores onto any device count).  np.array
            # copies keep the snapshot's buffers out of donation's
            # reach; the first pmap call shards the host stack, later
            # calls donate the already-sharded buffers.
            merged0 = jax.tree_util.tree_map(np.array, state["carry"])
            if n_dev > 1:
                fresh = B.init_carry(spec)
                carry = jax.tree_util.tree_map(
                    lambda m, f: np.stack([m] + [f] * (n_dev - 1)),
                    merged0, fresh)
            else:
                carry = put(merged0)

            front_vals = state["front_vals"].copy()
            front_idx = state["front_idx"].copy()
            buf_vals: list = []             # pending front candidates
            buf_idx: list = []
            buf_n = 0
            filt_np: dict = {}          # host mirror of the device filter
            aux_extra = {}
            if cons:
                aux_extra["cons"] = put(
                    np.asarray([v for _, _, v in cons], np.float64))
            if hist_bins:
                aux_extra["hist_edges"] = put(hist_edges)
            aux = dict(aux_extra)
            last_ckpt = time.perf_counter()

            def rebuild_filter():
                nonlocal filt_np, aux
                base_sg = np.concatenate([front_vals * sign, seed_signed]) \
                    if seed_signed.size else front_vals * sign
                filt_np = P.build_dominance_filter(base_sg, d, _FILTER_ROWS,
                                                   _FILTER_BINS)
                aux = dict(aux_extra, filter=put(filt_np))

            def merge(final=False):
                # Fold the candidate buffer into the running exact
                # front.  In the pipelined path this runs while the
                # producer thread is inside XLA on the next chunks, so
                # its cost hides under device compute; the filter-based
                # pre-cull keeps the exact dominance passes to a few
                # hundred rows.
                nonlocal front_vals, front_idx, buf_vals, buf_idx, buf_n
                with SP.span("repro.stream.merge"):
                    if buf_n:
                        cat_v = np.concatenate(buf_vals)
                        cat_i = np.concatenate(buf_idx)
                        cat_sg = cat_v * sign
                        base_sg = np.concatenate([front_vals * sign, cat_sg,
                                                  seed_signed])
                        f = P.build_dominance_filter(base_sg, d, _FILTER_ROWS,
                                                     _FILTER_BINS)
                        keep = P.dominance_filter_mask(
                            f, np.ascontiguousarray(cat_sg.T), xp=np)
                        front_vals, front_idx = _merge_into_front(
                            front_vals, front_idx, cat_v[keep], cat_i[keep],
                            sign)
                        buf_vals, buf_idx, buf_n = [], [], 0
                    if not final:
                        rebuild_filter()

            def host_chunk_survivors(dstart, vlen):
                # Survivor-capacity overflow (warmup-only in practice):
                # fetch nothing from the device — re-derive this chunk's
                # survivors exactly through the shared dense evaluator
                # (the same decode + evaluate contract the chunk step
                # runs), with the same constraint mask and (host-mirror)
                # pre-filter.
                flat = np.arange(dstart, dstart + vlen, dtype=np.int64)
                # Full kernel-field evaluation on purpose: this is the
                # *same* cached evaluator (same jaxpr) as
                # sweep.evaluate_grid, so the re-derived survivor values
                # are bitwise the dense path's — a narrower field set
                # lowers differently and can drift in the last ulp.
                out = B.cached_dense_eval("xla", S, full_shape, kfields)(
                    tuple(map(jnp.asarray, axis_vals)), jnp.asarray(flat))
                O = np.stack([np.asarray(out[f]) for f in objectives])
                feas = np.ones(vlen, bool)
                with np.errstate(invalid="ignore"):
                    for f, op, v in cons:
                        feas &= SW.CONSTRAINT_OPS[op](np.asarray(out[f]),
                                                      v)
                Osg = np.where(feas[None, :], O * sign[:, None], np.inf)
                keep = P.dominance_filter_mask(filt_np, Osg, xp=np)
                loc = np.flatnonzero(keep)
                return flat[loc], O[:, loc].T

            n_sub = n_dev * scan        # chunks folded per dispatch

            def maybe_snapshot(covered):
                # Emit a consistent progress snapshot over the folded
                # prefix [start0, covered) if the cadence allows it.
                if (on_snapshot is not None
                        and time.perf_counter() - snap_t["last"]
                        >= snapshot_every_s):
                    # Fold the pending buffer first so the snapshot's
                    # running front covers every survivor of the folded
                    # prefix.
                    merge()
                    snap_t["last"] = time.perf_counter()
                    on_snapshot(_progress_snapshot(
                        covered - start0, span,
                        front_vals, front_idx, objectives, sign))

            def process(item, defer_snap=False):
                # Survivor layout per dispatch: [device,][scan,] cap —
                # both optional leading axes flatten device-major /
                # scan-minor, which is exactly ascending chunk order
                # (device di covers the scan contiguous chunks at
                # start + di*scan*chunk).  ``defer_snap`` suppresses
                # the snapshot for steps with a checkpoint due: the
                # driver re-emits it after the checkpoint is durable,
                # so a watcher can never observe progress that a kill
                # right after the frame would roll back past.
                nonlocal buf_n, t_first, n_fallback
                start, surv = item
                step = (start - base) // per_step
                with SP.timed(clk, "device_wait_s", "repro.stream.fetch",
                              step=step):
                    flat_s, val_s, cnt_s = (np.asarray(x) for x in surv)
                with SP.timed(clk, "host_merge_s", "repro.stream.merge",
                              step=step):
                    flat_s = flat_s.reshape(n_sub, -1)
                    val_s = val_s.reshape(n_sub, -1, d)
                    cnt_s = cnt_s.reshape(n_sub)
                    for j in range(n_sub):
                        dstart = start + chunk * j
                        vlen = min(chunk, n_total - dstart)
                        if vlen <= 0:
                            break
                        cnt = int(cnt_s[j])
                        if cnt > cap:
                            n_fallback += 1
                            with SP.timed(clk, "fallback_s",
                                          "repro.stream.fallback",
                                          chunk=dstart // chunk):
                                fl, vv = host_chunk_survivors(dstart, vlen)
                        else:
                            fl = flat_s[j][:cnt]
                            vv = val_s[j][:cnt]
                        if len(fl):
                            buf_idx.append(np.asarray(fl, np.int64))
                            buf_vals.append(np.asarray(vv, np.float64))
                            buf_n += len(fl)
                    if buf_n >= _MERGE_EVERY:
                        merge()
                    if not defer_snap:
                        maybe_snapshot(min(start + per_step, stop0))
                    if t_first is None:
                        t_first = time.perf_counter() - t0

            def make_starts(si):
                start = base + si * per_step
                if n_dev > 1:
                    return jnp.asarray(
                        start + chunk * scan * np.arange(n_dev),
                        jnp.int64)
                return jnp.int64(start)

            def snapshot_carry(c):
                # Owning host copy, merged to the device-count-
                # independent serialization form (see
                # backend.merge_device_carries).
                host = B.carry_to_host(c)
                return (B.merge_device_carries(host, k) if n_dev > 1
                        else host)

            def dispatch(si, c):
                # Injector hook + bounded in-place retry + dispatch
                # accounting.  A TransientDeviceError fires *before*
                # the step consumed the donated carry, so re-running
                # the dispatch in place is safe; anything raised by
                # run() itself invalidates the carry and propagates to
                # the restart loop instead.
                nonlocal dispatched_flat
                start = base + si * per_step
                dispatched_flat = max(dispatched_flat,
                                      min(start + per_step, stop0))
                tstep = time.perf_counter()
                if fault_injector is not None:
                    backoff = policy.backoff_s
                    for attempt in range(policy.max_retries + 1):
                        try:
                            fault_injector(start // chunk, start)
                            break
                        except TransientDeviceError:
                            counters["retries"] += 1.0
                            if attempt >= policy.max_retries:
                                raise
                            time.sleep(backoff)
                            backoff = min(backoff * 2.0,
                                          policy.backoff_max_s)
                with SP.timed(clk, "dispatch_s", "repro.stream.dispatch",
                              step=si):
                    c, surv = run(c, axvals_j, aux, make_starts(si))
                dur = time.perf_counter() - tstep
                if detector.record(dur):
                    counters["stragglers"] += 1.0
                if (policy.step_timeout_s is not None
                        and dur > policy.step_timeout_s):
                    counters["step_timeouts"] += 1.0
                return c, surv

            def report_progress():
                # Called by the drive loops *after* any checkpoint due
                # for the step has been written, so with a step-cadence
                # checkpoint every observed progress fraction is backed
                # by a durable snapshot — a kill right after a progress
                # event can never resume from before it.
                if on_progress is not None:
                    on_progress(min(1.0, (dispatched_flat - start0)
                                    / span))

            def ckpt_due(si):
                # Snapshot cadence, decided dispatch-side.  The last
                # step never snapshots here — completion writes the
                # terminal checkpoint.
                nonlocal last_ckpt
                if mgr is None or si + 1 >= n_steps:
                    return False
                if checkpoint_every_steps is not None:
                    due = ((si + 1) % max(1, int(checkpoint_every_steps))
                           == 0)
                else:
                    due = (time.perf_counter() - last_ckpt
                           >= checkpoint_every_s)
                if due:
                    last_ckpt = time.perf_counter()
                return due

            def commit_state(si, merged):
                # Fold the pending buffer, then advance the snapshot to
                # "every chunk below base + (si+1)*per_step is folded".
                # FIFO queue ordering guarantees every survivor item
                # <= si was processed before the marker that gets here.
                merge()
                state["carry"] = merged
                state["front_vals"] = front_vals.copy()
                state["front_idx"] = front_idx.copy()
                state["base"] = min(base + (si + 1) * per_step, stop0)

            rebuild_filter()                # front/seed filter
            if prefetch == 0 or n_steps == 1:
                # Fully synchronous reference path (and the single-chunk
                # fast path, where there is nothing to overlap).
                for si in range(n_steps):
                    if should_stop is not None and should_stop():
                        ctl["halted"] = True
                        break
                    carry, surv = dispatch(si, carry)
                    due = ckpt_due(si)
                    process((base + si * per_step, surv),
                            defer_snap=due)
                    if si == 0 and n_steps > 1:
                        merge()
                    if due:
                        commit_state(si, snapshot_carry(carry))
                        write_checkpoint()
                        maybe_snapshot(min(base + (si + 1) * per_step,
                                           stop0))
                    report_progress()
            else:
                # Async double-buffered pipeline: a producer thread
                # drives the chunk chain (XLA releases the GIL while a
                # step executes, so the host merges below genuinely
                # overlap device compute); the bounded queue keeps
                # `prefetch` chunk results in flight.  The producer
                # pauses after dispatching chunk 0 until its survivors
                # have been folded into the filter, so every later
                # chunk pre-filters against a real running front.
                # Checkpoint markers ride the same FIFO queue: the
                # producer snapshots the carry right after step si and
                # enqueues the marker *behind* si's survivors, so by
                # the time the consumer sees it, the host front is
                # exactly consistent with the snapshot carry.
                q: _Queue = _Queue(maxsize=prefetch)
                filter_ready = threading.Event()
                ckpt_done = threading.Event()
                stop = threading.Event()
                box: dict = {}

                def put_or_stop(item):
                    # Never block forever: if the consumer died
                    # (exception in a merge), `stop` is set and the
                    # producer exits instead of leaking a thread wedged
                    # in q.put.
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.05)
                            return True
                        except _Full:
                            continue
                    return False

                def produce():
                    nonlocal carry
                    try:
                        with jax.enable_x64(True), on_devices():
                            for si in range(n_steps):
                                if stop.is_set():
                                    break
                                if (should_stop is not None
                                        and should_stop()):
                                    ctl["halted"] = True
                                    break
                                carry, surv = dispatch(si, carry)
                                due = ckpt_due(si)
                                if not put_or_stop(
                                        ("surv", base + si * per_step,
                                         surv, due)):
                                    break
                                if si == 0:
                                    filter_ready.wait()
                                if due:
                                    # Durability barrier: no later chunk
                                    # dispatches until the snapshot is
                                    # on disk, so a kill at step s can
                                    # never outrun the checkpoint due
                                    # before s.  Costs one pipeline
                                    # stall per checkpoint — nothing at
                                    # the default 30 s cadence.
                                    ckpt_done.clear()
                                    if not put_or_stop(
                                            ("ckpt", si,
                                             snapshot_carry(carry))):
                                        break
                                    while not ckpt_done.wait(0.05):
                                        if stop.is_set():
                                            break
                                report_progress()
                    except BaseException as e:  # pragma: no cover
                        box["err"] = e
                    finally:
                        put_or_stop(None)

                th_prod = threading.Thread(target=produce, daemon=True,
                                           name="stream-producer")
                th_prod.start()
                try:
                    first = True
                    while True:
                        item = q.get()
                        if item is None:
                            break
                        if item[0] == "ckpt":
                            commit_state(item[1], item[2])
                            write_checkpoint()
                            ckpt_done.set()
                            # The deferred snapshot for this step: the
                            # checkpoint is durable, so the progress it
                            # reports can no longer be rolled back.
                            maybe_snapshot(min(base + (item[1] + 1)
                                               * per_step, stop0))
                            continue
                        process((item[1], item[2]),
                                defer_snap=item[3])
                        if first:
                            merge()
                            filter_ready.set()
                            first = False
                finally:
                    # Consumer is done (or raised — including a
                    # KeyboardInterrupt): release the producer from any
                    # blocked put/wait and drain whatever it had in
                    # flight, then collect it — at most one chunk step
                    # runs to completion before it sees `stop`.  The
                    # nested finally keeps the join unconditional: even
                    # if the drain itself is interrupted (a second
                    # Ctrl-C), the producer thread — which holds the
                    # donated device carry — must never outlive this
                    # call.
                    stop.set()
                    filter_ready.set()
                    ckpt_done.set()
                    try:
                        while True:
                            try:
                                q.get_nowait()
                            except _Empty:
                                break
                    finally:
                        th_prod.join()
                if "err" in box:
                    raise box["err"]
            merge(final=True)
            state["carry"] = snapshot_carry(carry)
            state["front_vals"] = front_vals
            state["front_idx"] = front_idx
            # A cooperative halt finalizes at the dispatch high-water
            # mark: every chunk below it was issued *and* folded (the
            # producer enqueues each survivor set before checking the
            # hook again), so the snapshot is the exact contiguous
            # prefix [0, base).
            state["base"] = (min(dispatched_flat, stop0)
                             if ctl["halted"] else stop0)

        def reissue_count():
            # Chunks dispatched past the snapshot when an incarnation
            # died — exactly the ranges the next incarnation re-issues.
            nonlocal dispatched_flat
            n = max(0, -(-(dispatched_flat - state["base"]) // chunk))
            dispatched_flat = state["base"]
            return float(n)

        restarts_left = policy.max_restarts
        while True:
            try:
                drive()
                break
            except DeviceLostError as e:
                counters["chunks_reissued"] += reissue_count()
                if len(dev_list) > 1:
                    # Elastic replan: shrink the worker pool (1-D
                    # data-parallel replan_mesh specialization) and
                    # re-issue only the unfinished chunk ranges on the
                    # survivors.
                    counters["elastic_replans"] += 1.0
                    dev_list = list(drop_worker(dev_list, e.device_index))
                elif restarts_left > 0:
                    # Graceful degradation floor: the last device
                    # "died" — restart it from the snapshot.
                    restarts_left -= 1
                    counters["restarts"] += 1.0
                else:
                    raise
            except _RESTARTABLE:
                # In-place retries exhausted, or the step failed
                # mid-execution (the donated carry is gone either way):
                # restart the pipeline from the last consistent
                # snapshot.
                counters["chunks_reissued"] += reissue_count()
                if restarts_left <= 0:
                    raise
                restarts_left -= 1
                counters["restarts"] += 1.0
                time.sleep(min(
                    policy.backoff_s * (2.0 ** counters["restarts"]),
                    policy.backoff_max_s))
        # Terminal snapshot: resume == done (or, after a cooperative
        # halt, resume == continue from the stop point).
        if mgr is not None and mgr.latest_step() != state["base"]:
            write_checkpoint()
    total_s = clk["total_s"]

    # Deliverables come straight off the committed snapshot — the same
    # arrays a checkpoint would persist, so a resumed run and an
    # uninterrupted run return bitwise-identical results.
    carry = state["carry"]
    front_vals = state["front_vals"]
    front_idx = state["front_idx"]
    partial = int(state["base"]) < stop0
    stats = {
        "n_configs": float(n_total),
        "n_chunks": float(n_steps),
        # Fraction of the (range's) flat-index space folded into this
        # result — 1.0 for a complete sweep; after a cooperative halt
        # (should_stop / deadline) the reductions cover exactly the
        # contiguous prefix [range_start, fraction_complete * span).
        "fraction_complete": ((int(state["base"]) - start0) / span
                              if span else 1.0),
        # The leased flat-index slice this result reduces (the whole
        # grid unless flat_range= was given) — merge_results' tiling
        # contract.
        "range_start": float(start0),
        "range_stop": float(stop0),
        "total_s": total_s,
        "first_chunk_s": t_first if t_first is not None else total_s,
        "configs_per_s": span / total_s if total_s else float("inf"),
        "steady_configs_per_s": (
            (span - min(per_step, span))
            / max(total_s - (t_first or 0.0), 1e-9)
            if n_steps > 1 else span / max(total_s, 1e-9)),
        # Pipeline accounting: host_merge_s is time spent in the exact
        # merges/buffering; device_wait_s is time blocked fetching chunk
        # survivors (≈ un-hidden device compute).  prefetch > 0 shrinks
        # device_wait_s toward the critical path.
        "host_merge_s": clk["host_merge_s"],
        "device_wait_s": clk["device_wait_s"],
        # Dispatch accounting: time spent inside step invocation.  On
        # async accelerator backends this isolates the per-step launch
        # overhead scan fusion amortizes (K chunks per dispatch); XLA
        # CPU dispatch is synchronous, so here it also absorbs blocked
        # device compute — the dispatch *count* (n_chunks) is the
        # backend-independent signal, falling K-fold under scan_chunks.
        # A cold step's first call additionally pays trace + compile.
        "dispatch_s": clk["dispatch_s"],
        "steps_per_s": n_steps / total_s if total_s else float("inf"),
        "scan_chunks": float(scan),
        "prefetch": float(prefetch),
        "fallback_chunks": float(n_fallback),
        # Of host_merge_s: the dense re-derivation of the chunks that
        # overflowed the survivor buffer.
        "fallback_s": clk["fallback_s"],
        # Resilience accounting (see the stream_grid docstring):
        # in-place retries, pipeline restarts, the chunk ordinal a
        # resume started from, checkpoint count/time, chunk ranges
        # re-issued after failures, elastic device-pool shrinks,
        # flagged stragglers and step-deadline overruns.
        **counters,
    }

    # Normalize the top-k table: entries past the feasible count keep the
    # +inf sentinel value — point their indices at n_total too.
    topk_val = carry["topk_val"] * sign[:, None]
    topk_idx = np.where(np.isfinite(carry["topk_val"]), carry["topk_idx"],
                        n_total)

    hist_out = None
    if hist_bins:
        hist_out = {f: (np.asarray(carry["hist"][oi]), hist_edges[oi].copy())
                    for oi, f in enumerate(objectives)}
    visible_axis_valid = (axis_valid[1:] if len(axis_valid) == len(axes) + 1
                          else axis_valid)     # drop hidden model axis
    return StreamResult(
        axes=axes, objectives=objectives, maximize=maximize,
        chunk_size=chunk, n_devices=n_dev,
        min_val={f: float(carry["min_val"][i])
                 for i, f in enumerate(fields)},
        min_idx={f: int(carry["min_idx"][i]) for i, f in enumerate(fields)},
        finite_counts={f: int(carry["finite"][i])
                       for i, f in enumerate(fields)},
        channel_min={f: float(carry["fmin"][i])
                     for i, f in enumerate(fields)},
        channel_max={f: float(carry["fmax"][i])
                     for i, f in enumerate(fields)},
        axis_valid=OrderedDict(zip(axes, visible_axis_valid)),
        topk_val=topk_val,
        topk_idx=topk_idx,
        front_indices=front_idx, front_values=front_vals,
        hist=hist_out, stats=stats, constraints=cons, partial=partial)


#: Moved to the backend layer as the carry serialization contract.
_merge_device_carries = B.merge_device_carries


# ---------------------------------------------------------------------------
# Cross-range folding (the worker pool's merge step)
# ---------------------------------------------------------------------------


def _carry_from_result(res: StreamResult, sign: np.ndarray,
                       n_total: int) -> dict:
    """Reconstruct the serialization-form carry of one
    :class:`StreamResult` — exact, because the result's deliverables
    *are* the carry fields up to the orientation flip (``topk_val`` is
    stored ``carry * sign`` with ``sign`` in ±1, so multiplying by
    ``sign`` again is a bitwise round trip, including the ±inf
    sentinels)."""
    fields = tuple(res.min_val)
    carry = {
        "min_val": np.array([res.min_val[f] for f in fields],
                            np.float64),
        "min_idx": np.array([res.min_idx[f] for f in fields], np.int64),
        "finite": np.array([res.finite_counts[f] for f in fields],
                           np.int64),
        "fmin": np.array([res.channel_min[f] for f in fields],
                         np.float64),
        "fmax": np.array([res.channel_max[f] for f in fields],
                         np.float64),
        "topk_val": np.asarray(res.topk_val, np.float64)
        * sign[:, None],
        "topk_idx": np.where(
            np.isfinite(res.topk_val),
            np.asarray(res.topk_idx, np.int64), n_total),
    }
    if res.hist is not None:
        carry["hist"] = np.stack(
            [np.asarray(res.hist[f][0], np.int64)
             for f in res.objectives])
    return carry


def merge_results(parts: Sequence[StreamResult]) -> StreamResult:
    """Fold per-range :class:`StreamResult` parts (``flat_range=`` runs
    whose ranges tile ``[0, n_configs)``) into one complete result,
    bitwise-identical to a single-process sweep of the whole grid.

    Exactness comes from the same two ingredients the multi-device
    pmap path uses: every carry reduction is associative with the
    dense-path tie rules (:func:`repro.core.backend.
    merge_device_carries` — lexicographic ``(value, index)`` argmin,
    two-key sorted top-k merge, plain sums/min/max), and the exact
    front merge (:func:`_merge_into_front`) over the parts' disjoint
    exact fronts.  Parts may arrive in any order; they are sorted by
    ``range_start``.  Raises :class:`ValueError` on gaps, overlaps,
    incomplete (``partial=True``) parts, or mismatched specs — a torn
    part set must never fold silently.
    """
    if not parts:
        raise ValueError("merge_results needs at least one part")
    parts = sorted(parts, key=lambda r: r.stats.get("range_start", 0.0))
    first = parts[0]
    n_total = first.n_configs
    fields = tuple(first.min_val)
    sign = np.where([o in first.maximize for o in first.objectives],
                    -1.0, 1.0)
    cursor = 0
    for r in parts:
        if (r.axes != first.axes or r.objectives != first.objectives
                or r.maximize != first.maximize
                or tuple(r.min_val) != fields
                or r.constraints != first.constraints):
            raise ValueError("merge_results: parts from different "
                             "sweep specifications")
        start = int(r.stats.get("range_start", 0))
        stop = int(r.stats.get("range_stop", r.n_configs))
        if start != cursor:
            raise ValueError(
                f"merge_results: range gap/overlap at flat index "
                f"{cursor} (next part starts at {start})")
        if r.partial:
            raise ValueError(
                f"merge_results: part [{start}, {stop}) is partial "
                f"({r.stats.get('fraction_complete', 0.0):.1%})")
        cursor = stop
    if cursor != n_total:
        raise ValueError(f"merge_results: ranges cover [0, {cursor}) "
                         f"of {n_total} configs")

    t0 = time.perf_counter()
    k = first.topk_idx.shape[1]
    stacked = B.stack_host_carries(
        [_carry_from_result(r, sign, n_total) for r in parts])
    carry = B.merge_device_carries(stacked, k)

    d = len(first.objectives)
    front_vals = np.empty((0, d))
    front_idx = np.empty((0,), np.int64)
    for r in parts:
        front_vals, front_idx = _merge_into_front(
            front_vals, front_idx,
            np.asarray(r.front_values, np.float64),
            np.asarray(r.front_indices, np.int64), sign)

    hist_out = None
    if first.hist is not None:
        for r in parts[1:]:
            for oi, f in enumerate(first.objectives):
                if not np.array_equal(r.hist[f][1], first.hist[f][1]):
                    raise ValueError(
                        f"merge_results: histogram edges of {f!r} "
                        f"differ across parts")
        hist_out = {f: (np.asarray(carry["hist"][oi]),
                        np.asarray(first.hist[f][1]).copy())
                    for oi, f in enumerate(first.objectives)}

    summed = ("retries", "restarts", "checkpoints_written",
              "checkpoint_write_s", "chunks_reissued", "elastic_replans",
              "stragglers", "step_timeouts", "fallback_chunks",
              "n_chunks", "host_merge_s", "device_wait_s", "dispatch_s")
    total_s = max(float(r.stats.get("total_s", 0.0)) for r in parts)
    stats = {
        "n_configs": float(n_total),
        "fraction_complete": 1.0,
        "range_start": 0.0,
        "range_stop": float(n_total),
        "n_parts": float(len(parts)),
        # Wall-clock of the slowest part: with parts running
        # concurrently (the worker pool) this is the aggregate job
        # duration, so configs_per_s is the *aggregate* throughput.
        "total_s": total_s,
        "configs_per_s": (n_total / total_s if total_s
                          else float("inf")),
        "first_chunk_s": min(float(r.stats.get("first_chunk_s", 0.0))
                             for r in parts),
        "merge_s": 0.0,
        **{kk: float(sum(r.stats.get(kk, 0.0) for r in parts))
           for kk in summed},
    }

    topk_val = carry["topk_val"] * sign[:, None]
    topk_idx = np.where(np.isfinite(carry["topk_val"]),
                        carry["topk_idx"], n_total)
    stats["merge_s"] = time.perf_counter() - t0
    return StreamResult(
        axes=first.axes, objectives=first.objectives,
        maximize=first.maximize, chunk_size=first.chunk_size,
        n_devices=sum(r.n_devices for r in parts),
        min_val={f: float(carry["min_val"][i])
                 for i, f in enumerate(fields)},
        min_idx={f: int(carry["min_idx"][i])
                 for i, f in enumerate(fields)},
        finite_counts={f: int(carry["finite"][i])
                       for i, f in enumerate(fields)},
        channel_min={f: float(carry["fmin"][i])
                     for i, f in enumerate(fields)},
        channel_max={f: float(carry["fmax"][i])
                     for i, f in enumerate(fields)},
        axis_valid=OrderedDict(
            (kk, np.asarray(v).copy())
            for kk, v in first.axis_valid.items()),
        topk_val=topk_val, topk_idx=topk_idx,
        front_indices=front_idx, front_values=front_vals,
        hist=hist_out, stats=stats, constraints=first.constraints,
        partial=False)
