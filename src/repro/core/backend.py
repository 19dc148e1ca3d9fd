"""Unified evaluation-backend layer: one chunk kernel for every engine.

Before this module existed the Eq. 1-11 evaluate-and-reduce logic lived
in three divergent copies — the scalar path of
:mod:`repro.core.partition`, the dense ``sweep.evaluate_grid`` meshgrid
path, and the streaming executor's private ``_build_step``.  All three
engines now run through the single **chunk-evaluation contract** defined
here::

    decode flat indices -> evaluate tracked channels
                        -> fold block reductions into a donated carry

* :class:`EvalBackend` — the backend protocol.  ``build_dense_eval``
  covers the first arrow only (``fn(axvals, flat) -> {field: values}``):
  the dense engine runs the whole grid as *one big chunk* through it,
  and the streaming probe / survivor-overflow fallback reuse it
  chunk-wise.  ``build_chunk_eval`` adds constraint masking, the Pareto
  dominance pre-filter, and the **block-level reductions** (per-block
  min / first-min index / valid count / max, signed block mins for the
  top-k block select, survivor keep mask) that :func:`fold_chunk`
  consumes.
* :func:`fold_chunk` — backend-independent: folds one chunk's block
  partials into the donated running carry (argmin with exact
  first-minimum tie-breaking, feasibility counts, channel bounds, the
  exact per-objective top-k merge, optional histograms) and compacts
  the dominance survivors to an O(survivors) device->host transfer.
  This is the *only* copy of the reduction code — the XLA backend
  traces it behind its inline evaluation, the Pallas backend feeds it
  from the fused ``pallas_call`` of :mod:`repro.kernels.sweep_grid`.
* :func:`build_step` / :func:`cached_step` — assemble ``eval + fold``
  into the compiled chunk step the streaming executor drives, with
  optional **scan fusion** (``scan_chunks > 1`` runs ``lax.scan`` over
  K chunk carries inside one device dispatch, cutting per-step dispatch
  overhead on 10^8-config spaces) and ``pmap`` sharding across devices.
* The **registry** (:func:`register_backend` / :func:`get_backend`) —
  the ``backend=`` knob of ``sweep.evaluate_grid``,
  ``stream.stream_grid`` and ``partition.optimal_partition``.  The
  ``"pallas"`` backend registers lazily on first request from
  :mod:`repro.kernels.sweep_grid`.

Everything here runs under the caller's scoped ``jax.enable_x64`` context;
flat indices are int64 whenever the index space could overflow int32
(see :func:`repro.core.sweep.decode_flat_index`).
"""

from __future__ import annotations

import collections.abc
import dataclasses
import functools
import hashlib
import importlib
from collections import OrderedDict
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import pareto as P
from . import sweep as SW

#: Backend used when the ``backend=`` knob is ``None``.
DEFAULT_BACKEND = "xla"

_REGISTRY: "OrderedDict[str, EvalBackend]" = OrderedDict()

#: Backends that register themselves on first request (import-cost /
#: optional-dependency gating): name -> providing module.
_LAZY = {"pallas": "repro.kernels.sweep_grid"}


class EvalBackend:
    """Protocol of an evaluation backend (see the module docstring).

    Subclasses implement the two builders; ``supports_pmap`` gates the
    multi-device ``pmap`` path of :func:`build_step`, and
    ``supports_scenarios`` gates scenario-wrapped lowerings
    (``scenario.ScenarioStack`` — the session ``lax.scan`` kernel; a
    fused block kernel that re-implements the evaluation, like the
    Pallas grid kernel, must opt out until it lowers the scan too).
    """

    name: str = "?"
    supports_pmap: bool = True
    supports_scenarios: bool = True

    def build_dense_eval(self, S, shape: tuple[int, ...],
                         fields: Sequence[str]) -> Callable:
        """``fn(axvals, flat) -> {field: (n,) array}``: decode flat
        C-order indices into per-axis coordinates, gather the axis
        values, evaluate the requested channels.  ``axvals`` is the
        tuple of per-axis kernel index/value arrays (leading model
        axis included), ``flat`` any int array of grid indices."""
        raise NotImplementedError

    def build_chunk_eval(self, spec: "ChunkSpec") -> Callable:
        """``fn(axvals, aux, start) -> partials``: evaluate the chunk
        ``[start, start + spec.chunk)`` and return the block partials
        of :func:`chunk_partials` for :func:`fold_chunk` to fold."""
        raise NotImplementedError


def register_backend(backend: EvalBackend) -> EvalBackend:
    """Register ``backend`` under ``backend.name`` (last one wins)."""
    _REGISTRY[backend.name] = backend
    return backend


def available_backends() -> tuple[str, ...]:
    """Names accepted by the ``backend=`` knob (registered + lazy)."""
    return tuple(dict.fromkeys((*_REGISTRY, *_LAZY)))


def get_backend(name: str | None = None) -> EvalBackend:
    """Resolve a backend name (``None`` -> :data:`DEFAULT_BACKEND`).

    Lazily imports the providing module for deferred backends (the
    Pallas backend lives in ``repro.kernels.sweep_grid`` and registers
    on import).  Raises :class:`ValueError` naming the available
    backends for unknown names.
    """
    name = name or DEFAULT_BACKEND
    if name not in _REGISTRY and name in _LAZY:
        try:
            importlib.import_module(_LAZY[name])
        except ImportError as e:  # pragma: no cover - env-dependent
            raise ValueError(
                f"evaluation backend {name!r} is unavailable "
                f"({e}); available: {tuple(_REGISTRY)}") from e
    be = _REGISTRY.get(name)
    if be is None:
        raise ValueError(f"unknown evaluation backend {name!r}; "
                         f"available: {available_backends()}")
    return be


def check_scenario_support(be: EvalBackend, S) -> None:
    """Reject a scenario-wrapped lowering on a backend that cannot run
    the session ``lax.scan`` kernel (duck-checked via the
    ``is_scenario`` marker, so plain model stacks cost nothing)."""
    if getattr(S, "is_scenario", False) and not be.supports_scenarios:
        scen = tuple(n for n in available_backends()
                     if get_backend(n).supports_scenarios)
        raise ValueError(
            f"evaluation backend {be.name!r} does not support "
            f"scenario sweeps (the session lax.scan kernel); "
            f"scenario-capable backends: {scen}")


# ---------------------------------------------------------------------------
# The chunk contract
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChunkSpec:
    """Static description of one chunk-evaluation problem.

    This is the compiled-step cache key: everything that shapes the
    traced computation (chunk geometry, tracked channels, constraint
    structure, filter geometry) is here; axis values, constraint bounds
    and the filter *state* are runtime arguments so compiled steps are
    reusable across grids with the same axis sizes and across filter
    refreshes.  ``S`` hashes by identity (frozen, ``eq=False``); keying
    on the object itself keeps it alive so a recycled id can never
    alias a stale compiled step.
    """

    S: object                          # arrays.StackedModelArrays
    shape: tuple[int, ...]             # full axis sizes (incl. model axis)
    n_total: int
    chunk: int
    fields: tuple[str, ...]            # tracked channels; first d objectives
    d: int                             # number of objective channels
    k: int                             # top-k table width
    sign: tuple[float, ...]            # +1 minimize / -1 maximize per obj
    cons_static: tuple[tuple[int, str], ...]   # (field index, op) pairs
    hist_bins: int
    survivor_cap: int
    small_index: bool                  # int32 decode arithmetic is safe
    filter_rows: int = 24              # dominance-filter explicit rows
    filter_bins: int = 256             # ... and prefix-min table bins

    # Block layout of the two-stage reductions: XLA CPU lowers a plain
    # full-axis reduce (and especially lax.top_k) over 2^18 lanes as a
    # scalar loop; reducing (B, W) blocks stage-wise vectorizes, and
    # the exact top-k needs only the k best blocks.
    @property
    def block(self) -> int:            # W — lanes per block
        return min(512, self.chunk)

    @property
    def n_blocks(self) -> int:         # B
        return -(-self.chunk // self.block)

    @property
    def padded(self) -> int:           # CP — lanes incl. block padding
        return self.n_blocks * self.block

    @property
    def nb(self) -> int:               # blocks gathered by the top-k select
        return min(self.k, self.n_blocks)


def decode_gather(shape: Sequence[int], axvals, flat):
    """Decode flat C-order indices and gather the per-axis kernel
    arguments — the one place "flat index -> kernel inputs" is written
    (both backends and the dense engine trace through it)."""
    coords = SW.decode_flat_index(shape, flat)
    return [v[c] for v, c in zip(axvals, coords)]


def chunk_partials(spec: ChunkSpec, F, flat, ingrid, aux) -> dict:
    """Constraint masking + block reductions of one evaluated chunk.

    The backend-independent reference expression: the XLA backend
    traces it directly behind its inline evaluation; the Pallas kernel
    of :mod:`repro.kernels.sweep_grid` computes the same quantities
    per-block inside its ``pallas_call`` (and is parity-tested against
    this).  ``F`` is the ``(n_fields, chunk)`` raw channel matrix,
    ``flat`` the chunk's flat indices, ``ingrid`` the in-grid lane
    mask.  Returns the partials dict :func:`fold_chunk` consumes, all
    lane axes padded to ``spec.padded``.
    """
    d, B, W = spec.d, spec.n_blocks, spec.block
    feas = ingrid
    for ci, (fi, op) in enumerate(spec.cons_static):
        # NaN channel values compare False, so invalid configurations
        # are infeasible under any predicate.
        feas = feas & SW.CONSTRAINT_OPS[op](F[fi], aux["cons"][ci])
    valid = jnp.isfinite(F) & feas[None, :]
    Fm = jnp.where(valid, F, jnp.inf)
    sign = np.asarray(spec.sign)
    if (sign == 1.0).all():
        Fsg = Fm[:d]
    else:
        Fsg = jnp.where(valid[:d], F[:d] * sign[:, None], jnp.inf)
    with jax.named_scope("dominance_filter"):
        keep = P.dominance_filter_mask(aux["filter"], Fsg, xp=jnp)

    lane_pad = spec.padded - spec.chunk

    def pad2(x, fill):
        return (jnp.pad(x, ((0, 0), (0, lane_pad)), constant_values=fill)
                if lane_pad else x)

    def pad1(x, fill):
        return (jnp.pad(x, (0, lane_pad), constant_values=fill)
                if lane_pad else x)

    Fb = pad2(Fm, jnp.inf).reshape(-1, B, W)
    bmin = Fb.min(axis=2)
    flatb = pad1(flat, spec.n_total).reshape(B, W)
    bidx = jnp.where(Fb == bmin[:, :, None], flatb[None], spec.n_total
                     ).min(axis=2)
    return {
        "Fd": pad2(F[:d], jnp.nan),
        "Fsg": pad2(Fsg, jnp.inf),
        "valid": pad2(valid[:d], False),
        "keep": pad1(keep, False),
        "bmin": bmin,
        "bidx": bidx,
        "cnt": pad2(valid.astype(jnp.int32), 0).reshape(-1, B, W
                                                        ).sum(axis=2),
        "bmax": pad2(jnp.where(valid, F, -jnp.inf), -jnp.inf
                     ).reshape(-1, B, W).max(axis=2),
        "sgmin": pad2(Fsg, jnp.inf).reshape(d, B, W).min(axis=2),
    }


def init_carry(spec: ChunkSpec) -> dict:
    """Fresh running-reduction carry (numpy; the executor ships it with
    one batched ``device_put``) — strong dtypes throughout: a weak-typed
    init carry would retrace the step on its second call (outputs come
    back strong-typed)."""
    nf = len(spec.fields)
    carry = {
        "min_val": np.full((nf,), np.inf),
        "min_idx": np.full((nf,), spec.n_total, np.int64),
        "finite": np.zeros((nf,), np.int64),
        "fmin": np.full((nf,), np.inf),
        "fmax": np.full((nf,), -np.inf),
        "topk_val": np.full((spec.d, spec.k), np.inf),
        "topk_idx": np.full((spec.d, spec.k), spec.n_total, np.int64),
    }
    if spec.hist_bins:
        carry["hist"] = np.zeros((spec.d, spec.hist_bins), np.int64)
    return carry


def fold_chunk(spec: ChunkSpec, carry, partials, aux, start):
    """Fold one chunk's block partials into the donated running carry.

    The single copy of the reduction fold shared by every backend:

    * running argmin per channel — lexicographic ``(value, index)`` min
      over the block partials, so ties break toward the lower flat
      index exactly like ``np.nanargmin``'s first-minimum rule;
    * feasibility counts and channel bounds;
    * the fused exact top-k: the k best (value, flat index) pairs of
      the chunk live in the k best blocks ranked by (block min, block
      index) — any element of a lower-ranked block is beaten by >= k
      strictly smaller pairs.  ``lax.top_k`` over the signed block
      mins breaks ties toward the lower block; the gathered k*W
      candidates merge against the running ``(d, k)`` table with an
      exact two-key sort;
    * optional histograms;
    * survivor compaction: a binary search over the keep-count prefix
      sum (an order of magnitude faster than an XLA CPU scatter); the
      count is returned so the host can detect (rare) capacity
      overflow and re-derive that chunk's survivors exactly.
    """
    d, k, W = spec.d, spec.k, spec.block
    n_total = spec.n_total

    lv = partials["bmin"].min(axis=1)
    li = jnp.where(partials["bmin"] == lv[:, None], partials["bidx"],
                   n_total).min(axis=1)
    # isfinite guard: an all-invalid chunk ties at inf == inf and must
    # not swap the sentinel min_idx for an invalid config's index.
    better = (lv < carry["min_val"]) | ((lv == carry["min_val"])
                                        & jnp.isfinite(lv)
                                        & (li < carry["min_idx"]))
    new_carry = {
        "min_val": jnp.where(better, lv, carry["min_val"]),
        "min_idx": jnp.where(better, li, carry["min_idx"]),
        "finite": carry["finite"] + partials["cnt"].sum(axis=1,
                                                        dtype=jnp.int64),
        "fmin": jnp.minimum(carry["fmin"], lv),
        "fmax": jnp.maximum(carry["fmax"], partials["bmax"].max(axis=1)),
    }

    _, bsel = jax.lax.top_k(-partials["sgmin"], spec.nb)       # (d, nb)
    sgb = partials["Fsg"].reshape(d, spec.n_blocks, W)
    gath = jnp.take_along_axis(sgb, bsel[:, :, None], axis=1)
    gpos = (bsel[:, :, None] * W
            + jnp.arange(W, dtype=jnp.int64)[None, None, :])
    cand_v = jnp.concatenate(
        [carry["topk_val"], gath.reshape(d, spec.nb * W)], axis=1)
    cand_i = jnp.concatenate(
        [carry["topk_idx"], start + gpos.reshape(d, spec.nb * W)], axis=1)
    sv, si = jax.lax.sort((cand_v, cand_i), dimension=-1, num_keys=2)
    new_carry["topk_val"] = sv[:, :k]
    new_carry["topk_idx"] = si[:, :k]

    if spec.hist_bins:
        he = aux["hist_edges"]                                 # (d, bins+1)
        hist = carry["hist"]
        for oi in range(d):
            col = jnp.clip(partials["Fd"][oi], he[oi, 0], he[oi, -1])
            b = jnp.clip(
                jnp.searchsorted(he[oi], col, side="right") - 1,
                0, spec.hist_bins - 1)
            hist = hist.at[oi, b].add(
                partials["valid"][oi].astype(hist.dtype))
        new_carry["hist"] = hist

    csum = jnp.cumsum(partials["keep"].astype(jnp.int32))
    pos = jnp.minimum(
        jnp.searchsorted(csum,
                         jnp.arange(1, spec.survivor_cap + 1,
                                    dtype=jnp.int32), side="left"),
        spec.padded - 1)
    surv = (start + pos.astype(jnp.int64), partials["Fd"][:, pos].T,
            csum[-1])
    return new_carry, surv


# ---------------------------------------------------------------------------
# The XLA backend (default)
# ---------------------------------------------------------------------------


class XlaBackend(EvalBackend):
    """Pure-XLA backend: decode + evaluate traced inline so the whole
    chunk step fuses into one compiled computation."""

    name = "xla"
    supports_pmap = True

    def build_dense_eval(self, S, shape, fields):
        kernel = SW.vmapped_kernel(S)
        fields = tuple(fields)

        @jax.jit
        def evalfn(axvals, flat):
            with jax.named_scope("decode_gather"):
                args = decode_gather(shape, axvals, flat)
            with jax.named_scope("eq1_11"):
                out = kernel(*args)
            return {f: out[f] for f in fields}

        return evalfn

    def build_chunk_eval(self, spec: ChunkSpec):
        kernel = SW.vmapped_kernel(spec.S)

        def evalfn(axvals, aux, start):
            with jax.named_scope("decode_gather"):
                flat = start + jnp.arange(spec.chunk, dtype=jnp.int64)
                ingrid = flat < spec.n_total
                # int32 decode arithmetic when the flat index space
                # fits — int64 div/mod is measurably slower on CPU.
                fdec = flat.astype(jnp.int32) if spec.small_index else flat
                args = decode_gather(spec.shape, axvals, fdec)
            with jax.named_scope("eq1_11"):
                out = kernel(*args)
                F = jnp.stack([out[f] for f in spec.fields])
                # Without the barrier XLA fuses the (expensive) kernel
                # body into every reduction that consumes F,
                # re-evaluating it several times per chunk; the barrier
                # forces one materialization.
                F = jax.lax.optimization_barrier(F)
            with jax.named_scope("chunk_partials"):
                return chunk_partials(spec, F, flat, ingrid, aux)

        return evalfn


register_backend(XlaBackend())


# ---------------------------------------------------------------------------
# Step assembly (chunk eval + fold, scan fusion, sharding) and caches
# ---------------------------------------------------------------------------


def build_step(spec: ChunkSpec, backend: str | None = None,
               scan_chunks: int = 1, n_dev: int = 1, devices=None):
    """Compile the chunk step ``(carry, axvals, aux, start) -> (carry,
    survivors)`` for one backend.

    ``scan_chunks > 1`` fuses that many consecutive chunk folds into a
    single device dispatch via ``lax.scan`` (the carry threads through;
    survivor outputs gain a leading K axis) — per-chunk Python/dispatch
    overhead is paid once per K chunks, which matters at 10^7+ configs
    where the step count runs into the hundreds.  With ``n_dev > 1``
    the step is ``pmap``-sharded (one carry per device; every argument
    device-mapped — the executor pre-replicates broadcast state).
    Results are bitwise identical across ``scan_chunks`` values: the
    fold is applied to the same chunks in the same order.
    """
    be = get_backend(backend)
    if n_dev > 1 and not be.supports_pmap:
        raise ValueError(f"backend {be.name!r} does not support the "
                         f"multi-device pmap path; pass devices= with a "
                         f"single device")
    check_scenario_support(be, spec.S)
    evalfn = be.build_chunk_eval(spec)

    def one(carry, axvals, aux, start):
        partials = evalfn(axvals, aux, start)
        with jax.named_scope("fold_chunk"):
            return fold_chunk(spec, carry, partials, aux, start)

    if scan_chunks > 1:
        def step(carry, axvals, aux, start):
            starts = start + spec.chunk * jnp.arange(scan_chunks,
                                                     dtype=jnp.int64)
            return jax.lax.scan(lambda c, s: one(c, axvals, aux, s),
                                carry, starts)
    else:
        step = one

    if n_dev > 1:
        return jax.pmap(step, donate_argnums=(0,),
                        in_axes=(0, 0, 0, 0), devices=devices)
    return jax.jit(step, donate_argnums=(0,))


_STEP_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_STEP_CACHE_MAX = 32
_STEP_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def cached_step(spec: ChunkSpec, backend: str | None = None,
                scan_chunks: int = 1, n_dev: int = 1, devices=None):
    """LRU-cached :func:`build_step` — repeated sweeps over same-shaped
    grids are compile-free.

    The cache keys ``spec`` by the :class:`ChunkSpec` hash, which hashes
    the model stack by *identity*: two processes (or two calls that
    rebuilt their axes from scratch) get different keys even for
    byte-identical jobs.  Long-lived callers that want cross-request
    reuse therefore cache the resolved plan by content signature first
    (:func:`job_signature`, see ``repro.core.service``) and re-submit
    the same spec object.  :func:`step_cache_stats` exposes hit/miss
    counters for such callers' health surfaces.
    """
    key = (spec, backend or DEFAULT_BACKEND, scan_chunks, n_dev,
           tuple(str(dv) for dv in devices or ()))
    fn = _STEP_CACHE.get(key)
    if fn is None:
        _STEP_CACHE_STATS["misses"] += 1
        fn = build_step(spec, backend, scan_chunks, n_dev, devices)
        _STEP_CACHE[key] = fn
        while len(_STEP_CACHE) > _STEP_CACHE_MAX:
            _STEP_CACHE.popitem(last=False)
            _STEP_CACHE_STATS["evictions"] += 1
    else:
        _STEP_CACHE_STATS["hits"] += 1
        _STEP_CACHE.move_to_end(key)
    return fn


def step_cache_stats() -> dict:
    """Snapshot of the compiled-step LRU: ``hits`` / ``misses`` /
    ``evictions`` since process start plus the current ``size`` and
    ``capacity`` — the compile-reuse signal surfaced by the sweep
    service's health endpoint."""
    return dict(_STEP_CACHE_STATS, size=len(_STEP_CACHE),
                capacity=_STEP_CACHE_MAX)


def cached_dense_eval(backend: str | None, S, shape: tuple[int, ...],
                      fields: tuple[str, ...]):
    """LRU-cached :meth:`EvalBackend.build_dense_eval` (keyed by backend
    name, stacked lowering identity, grid shape and field tuple).
    ``None`` normalizes to :data:`DEFAULT_BACKEND` *before* the cache
    key, so the dense engine's default path and the streamer's
    probe/overflow-fallback share one compiled evaluator."""
    return _cached_dense_eval(backend or DEFAULT_BACKEND, S, tuple(shape),
                              tuple(fields))


@functools.lru_cache(maxsize=32)
def _cached_dense_eval(backend: str, S, shape, fields):
    be = get_backend(backend)
    check_scenario_support(be, S)
    return be.build_dense_eval(S, shape, fields)


# ---------------------------------------------------------------------------
# Carry serialization contract (checkpoint/resume, device merging)
# ---------------------------------------------------------------------------

#: Version of the carry layout produced by :func:`init_carry` /
#: :func:`fold_chunk`.  Baked into :func:`job_signature`, so a checkpoint
#: written under an older carry format can never be restored into a newer
#: executor — bump it whenever the carry pytree structure, dtypes or
#: merge semantics change.
CARRY_VERSION = 1


def carry_to_host(carry):
    """Owning host copy of a (possibly device-resident) carry pytree.

    ``np.array`` (not ``np.asarray``): on the CPU backend a zero-copy
    view of the device buffer would be corrupted the moment the next
    step *donates* that buffer, so the snapshot must own its memory.
    """
    return jax.tree_util.tree_map(lambda x: np.array(x), carry)


def merge_device_carries(carry, k: int):
    """Fold per-device reduction carries into one (host side, exact).

    Every carry reduction is associative with the exact dense-path tie
    rules — lexicographic ``(value, index)`` min for the argmin, a
    two-key sorted merge for top-k, plain sums/min/max for counts,
    bounds and histograms — so merging the ``(ndev, ...)`` stacked
    carries is order-independent and bitwise reproducible.  The merged
    tree has the exact structure and dtypes of :func:`init_carry`
    output, which makes it the **serialization form** of a sweep's
    reduction state: device-count independent, so a checkpointed carry
    restores onto any mesh (merged carry on device 0, fresh inits on
    the rest).
    """
    mv, mi = carry["min_val"], carry["min_idx"]     # (ndev, nf)
    order = np.lexsort((mi, mv), axis=0)[0]         # per-field best device
    nf = mv.shape[1]
    merged = {
        "min_val": mv[order, np.arange(nf)],
        "min_idx": mi[order, np.arange(nf)],
        "finite": carry["finite"].sum(axis=0),
        "fmin": carry["fmin"].min(axis=0),
        "fmax": carry["fmax"].max(axis=0),
    }
    tv, ti = carry["topk_val"], carry["topk_idx"]   # (ndev, d, k)
    d = tv.shape[1]
    cat_v = tv.transpose(1, 0, 2).reshape(d, -1)
    cat_i = ti.transpose(1, 0, 2).reshape(d, -1)
    out_v = np.empty((d, k))
    out_i = np.empty((d, k), np.int64)
    for oi in range(d):
        order = np.lexsort((cat_i[oi], cat_v[oi]))[:k]
        out_v[oi], out_i[oi] = cat_v[oi][order], cat_i[oi][order]
    merged["topk_val"], merged["topk_idx"] = out_v, out_i
    if "hist" in carry:
        merged["hist"] = carry["hist"].sum(axis=0)
    return merged


def stack_host_carries(carries: Sequence[dict]) -> dict:
    """Stack N host carries (:func:`init_carry` layout) into the
    ``(n, ...)`` leading-axis form :func:`merge_device_carries` folds.

    This is the bridge the multi-process worker pool uses: each worker
    persists its leased range's merged carry (already in the
    device-count-independent serialization form), and the service
    stacks the per-range carries exactly like per-device shards before
    one associative, bitwise-exact merge.  Histogram-less and
    histogram-carrying carries must not mix — the pytree structures
    differ and ``tree_map`` would fail loudly, which is the right
    outcome for a corrupted part set.
    """
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *carries)


def _hash_update(h, obj) -> None:
    """Recursively fold ``obj`` into hash ``h`` content-wise.

    Covers everything a sweep specification is made of: scalars and
    strings, numpy/JAX arrays (dtype + shape + bytes), dataclasses (the
    stacked model arrays and their nested workload arrays, field by
    field), sequences and mappings.  Type tags and delimiters keep the
    encoding prefix-free, so e.g. ``("ab",)`` and ``("a", "b")`` hash
    differently.
    """
    if obj is None or isinstance(obj, (bool, int, float, str,
                                       np.integer, np.floating)):
        h.update(f"<{type(obj).__name__}:{obj!r}>".encode())
    elif isinstance(obj, bytes):
        h.update(b"<bytes:")
        h.update(obj)
        h.update(b">")
    elif isinstance(obj, np.ndarray):
        h.update(f"<arr:{obj.dtype}:{obj.shape}:".encode())
        h.update(np.ascontiguousarray(obj).tobytes())
        h.update(b">")
    elif dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        h.update(f"<dc:{type(obj).__name__}:".encode())
        for f in dataclasses.fields(obj):
            h.update(f.name.encode())
            h.update(b"=")
            _hash_update(h, getattr(obj, f.name))
        h.update(b">")
    elif isinstance(obj, (tuple, list)):
        h.update(b"<seq:")
        for x in obj:
            _hash_update(h, x)
        h.update(b">")
    elif isinstance(obj, collections.abc.Mapping):
        h.update(b"<map:")
        for kk in sorted(obj, key=str):
            h.update(str(kk).encode())
            h.update(b":")
            _hash_update(h, obj[kk])
        h.update(b">")
    else:
        # device arrays and other array-likes
        _hash_update(h, np.asarray(obj))


#: ChunkSpec fields folded into the job signature.  Deliberately *not*
#: ``small_index`` / ``survivor_cap`` / ``filter_*``: those shape only
#: the traced computation, never the reduction semantics (the dominance
#: filter is a pre-cull; survivor-cap overflow falls back to an exact
#: host re-derivation), so they must not invalidate checkpoints.
_SIGNATURE_SPEC_FIELDS = ("shape", "n_total", "chunk", "fields", "d", "k",
                          "sign", "cons_static", "hist_bins")


def job_signature(spec: ChunkSpec, backend: str | None, scan_chunks: int,
                  cons: Sequence[tuple[str, str, float]],
                  axis_vals: Sequence, hist_ranges=None) -> str:
    """Content hash identifying one resumable sweep job.

    Two runs share a signature iff their checkpoints are
    interchangeable: same model stack (hashed by *content*, down to
    every tech-table entry), same axes and axis values, same tracked
    fields / objectives orientation / top-k width, same constraint
    predicates and bounds, same chunk geometry and scan fusion, same
    backend, same histogram spec, same carry format version.  The
    streaming executor refuses to restore a checkpoint whose recorded
    signature differs — a stale snapshot from a different spec must
    fail loudly, never silently merge.
    """
    h = hashlib.sha256()
    _hash_update(h, ("carry-format", CARRY_VERSION))
    _hash_update(h, ("backend", backend or DEFAULT_BACKEND))
    _hash_update(h, ("scan", int(scan_chunks)))
    for name in _SIGNATURE_SPEC_FIELDS:
        _hash_update(h, (name, getattr(spec, name)))
    _hash_update(h, ("model-stack", spec.S))
    _hash_update(h, ("constraints", tuple(cons)))
    _hash_update(h, ("axes", tuple(np.asarray(a) for a in axis_vals)))
    _hash_update(h, ("hist-ranges", hist_ranges))
    return h.hexdigest()
