"""Gradient-based search over the continuous design knobs.

The Eq. 1-11 kernel of :mod:`repro.core.sweep` is differentiable end to
end with respect to every float knob — DetNet/KeyNet rates, the MIPI
energy scale, the camera frame rate — so instead of densifying a grid
axis until the optimum is resolved, this module drives ``jax.grad``
straight through the analytical model:

* :func:`objective_fn` — a differentiable scalarized objective (a weighted
  sum of kernel output channels, e.g. ``{"avg_power": 1, "latency": 10}``)
  closed over one *discrete* configuration (cut, nodes, weight memory,
  camera count).
* :func:`evaluate` / :func:`gradient` / :func:`evaluate_fields` — scalar
  conveniences that scope ``jax.enable_x64`` for you (the kernel runs in
  float64, same as the grid engine).
* :func:`optimize_knobs` — projected Adam over box-bounded knobs.  Knobs
  are normalized to [0, 1] over their bounds so one learning rate serves
  mixed scales (fps in tens, energy scales near 1); the update reuses the
  :mod:`repro.optim.adamw` machinery with its cosine decay (which anneals
  the terminal oscillation well below grid resolution) and projects back
  into the box after every step.
* :func:`grid_argmin` — the dense-grid cross-check: the same scalarized
  objective minimized by brute force over ``evaluate_grid`` on the same
  bounds.  ``tests/test_optimize.py`` pins the two to within one grid
  step.

Power is monotone in most knobs, so single-objective searches ride the
projection to a bound — the interesting optima are interior points of
*weighted* objectives (e.g. power vs latency over ``camera_fps``, where
faster cameras cost camera power but amortize DetNet latency harder).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..optim import adamw
from . import arrays as A
from . import sweep as S
from .constants import CAMERA_FPS, DETNET_FPS, KEYNET_FPS, NUM_CAMERAS

#: The continuous knobs of the kernel, in its argument order.
KNOBS = ("detnet_fps", "keynet_fps", "mipi_energy_scale", "camera_fps")

_KNOB_DEFAULTS = dict(detnet_fps=DETNET_FPS, keynet_fps=KEYNET_FPS,
                      mipi_energy_scale=1.0, camera_fps=CAMERA_FPS)

_CONFIG_KEYS = frozenset(
    ("cut", "agg_node", "sensor_node", "weight_mem", "num_cameras",
     "model")) | frozenset(KNOBS)


def _weights(objective) -> dict[str, float]:
    """Normalize an objective spec to ``{channel: weight}``."""
    if isinstance(objective, str):
        objective = {objective: 1.0}
    w = {k: float(v) for k, v in objective.items()}
    unknown = sorted(set(w) - set(S.FIELDS))
    if unknown or not w:
        raise ValueError(f"bad objective channels {unknown}; "
                         f"have {S.FIELDS}")
    return w


def _check_knobs(names: Sequence[str]):
    unknown = sorted(set(names) - set(KNOBS))
    if unknown:
        raise ValueError(f"unknown knobs {unknown}; have {KNOBS}")


@dataclasses.dataclass(frozen=True)
class _Resolved:
    """A validated discrete configuration + fixed-knob defaults."""

    M: A.ModelArrays
    cut: int
    agg_i: int
    sen_i: int
    wm_i: int
    num_cameras: float
    base_knobs: dict    # every KNOB bound to its fixed (default) value

    def kernel_kwargs(self, knobs: Mapping) -> tuple:
        kw = {**self.base_knobs, **knobs}
        return (self.cut, self.agg_i, self.sen_i, self.wm_i,
                kw["detnet_fps"], kw["keynet_fps"], self.num_cameras,
                kw["mipi_energy_scale"], kw["camera_fps"])


def _resolve(config: Mapping) -> _Resolved:
    unknown = sorted(set(config) - _CONFIG_KEYS)
    if unknown:
        raise ValueError(f"unknown config keys {unknown}; "
                         f"have {sorted(_CONFIG_KEYS)}")
    if "cut" not in config:
        raise ValueError("a discrete configuration needs cut=<int>")
    model = config.get("model")
    M = model if model is not None else A.model_arrays()
    cut = int(config["cut"])
    if not 0 <= cut < M.n_cuts:
        raise ValueError(f"cut {cut} outside [0, {M.n_cuts - 1}]")
    agg_i = M.node_index(config.get("agg_node", "7nm"))
    sen_i = M.node_index(config.get("sensor_node", "7nm"))
    wm = config.get("weight_mem", "sram")
    if wm not in A.WEIGHT_MEM_KINDS:
        raise ValueError(f"unknown weight_mem {wm!r}; "
                         f"have {A.WEIGHT_MEM_KINDS}")
    wm_i = A.WEIGHT_MEM_KINDS.index(wm)
    ncam = config.get("num_cameras", NUM_CAMERAS)
    if ncam < 1 or ncam % 1:
        raise ValueError("num_cameras must be an integer >= 1")
    # Mirror the scalar path: refuse impossible memory choices eagerly
    # instead of silently optimizing a NaN landscape.
    if cut > 0 and np.isnan(M.wm_e_read[sen_i, wm_i]):
        raise ValueError(f"no {wm.upper()} test vehicle at "
                         f"{M.node_names[sen_i]}")
    fixed = {k: float(config[k]) for k in KNOBS if k in config}
    return _Resolved(M=M, cut=cut, agg_i=agg_i, sen_i=sen_i, wm_i=wm_i,
                     num_cameras=float(ncam),
                     base_knobs={**_KNOB_DEFAULTS, **fixed})


def objective_fn(objective="avg_power", **config) -> Callable:
    """Build a differentiable scalarized objective over the continuous knobs.

    ``objective`` is a kernel channel name or a ``{channel: weight}``
    mapping (see ``sweep.FIELDS``); ``config`` fixes the discrete
    configuration (``cut=`` required; ``agg_node``/``sensor_node``/
    ``weight_mem``/``num_cameras``/``model`` optional) and may pin any
    knob of :data:`KNOBS` to a non-default fixed value.

    Returns ``f(knobs: Mapping[str, Array]) -> Array`` where ``knobs``
    binds any subset of :data:`KNOBS`.  The discrete configuration is
    validated eagerly — an MRAM request on a node with no test vehicle
    raises here, mirroring the scalar path, instead of yielding NaN.

    Call (and differentiate) the result under
    ``jax.enable_x64(True)`` — or use :func:`evaluate`/:func:`gradient`,
    which scope it for you.
    """
    w = _weights(objective)
    r = _resolve(config)
    kernel = S.config_kernel(r.M)

    def f(knobs: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        _check_knobs(knobs)
        out = kernel(*r.kernel_kwargs(knobs))
        return sum(wi * out[k] for k, wi in w.items())

    return f


def evaluate(objective="avg_power", knobs: Mapping[str, float] | None = None,
             **config) -> float:
    """Scalarized objective value at one knob setting (float64)."""
    f = objective_fn(objective, **config)
    with jax.enable_x64(True):
        return float(f({k: jnp.asarray(float(v))
                        for k, v in (knobs or {}).items()}))


def evaluate_fields(knobs: Mapping[str, float] | None = None,
                    **config) -> dict[str, float]:
    """Every kernel channel at one knob setting — like
    ``sweep.evaluate_one`` but resolved through the same config/knob
    plumbing as the optimizer (including custom ``model=`` lowerings)."""
    r = _resolve(config)
    kernel = S.config_kernel(r.M)
    with jax.enable_x64(True):
        out = kernel(*r.kernel_kwargs(
            {k: jnp.asarray(float(v)) for k, v in (knobs or {}).items()}))
        return {k: float(v) for k, v in out.items()}


def gradient(objective="avg_power", knobs: Mapping[str, float] | None = None,
             **config) -> tuple[float, dict[str, float]]:
    """``(value, {knob: d objective / d knob})`` via ``jax.value_and_grad``
    through the Eq. 1-11 kernel at one knob setting (all four knobs, at
    their config-pinned or default values, when ``knobs`` is omitted)."""
    f = objective_fn(objective, **config)
    at = dict(knobs) if knobs is not None else _resolve(config).base_knobs
    with jax.enable_x64(True):
        v, g = jax.value_and_grad(f)(
            {k: jnp.asarray(float(x)) for k, x in at.items()})
    return float(v), {k: float(x) for k, x in g.items()}


@dataclasses.dataclass(frozen=True)
class KnobOptResult:
    """Outcome of one projected-Adam knob search."""

    knobs: dict[str, float]        # optimized knob values (de-normalized)
    objective: float               # scalarized objective at ``knobs``
    weights: dict[str, float]      # the scalarization used
    fields: dict[str, float]       # every kernel channel at the optimum
    trajectory: np.ndarray         # objective value at each iterate
    steps: int


def optimize_knobs(bounds: Mapping[str, tuple[float, float]],
                   objective="avg_power", *,
                   steps: int = 200,
                   lr: float = 0.05,
                   init: Mapping[str, float] | None = None,
                   **config) -> KnobOptResult:
    """Projected-Adam minimization of a scalarized objective over knobs.

    ``bounds`` maps knob name -> ``(lo, hi)`` box constraints and selects
    which knobs are optimized (the rest stay fixed); ``config`` carries the
    discrete configuration of :func:`objective_fn` (``cut=...`` required).
    Optimization runs in [0, 1]-normalized knob space with a cosine-decayed
    Adam (``repro.optim.adamw``), clipping back into the box after every
    step, and returns the best iterate seen (the kernel is cheap enough
    that tracking it is free compared to one compile).

    The search is local/gradient-based: cross-check against
    :func:`grid_argmin` when the objective may be multi-modal.
    """
    if not bounds:
        raise ValueError("bounds must select at least one knob")
    _check_knobs(bounds)
    names = tuple(bounds)
    lo = {n: float(bounds[n][0]) for n in names}
    hi = {n: float(bounds[n][1]) for n in names}
    for n in names:
        if not hi[n] > lo[n]:
            raise ValueError(f"degenerate bounds for {n}: {bounds[n]}")
    w = _weights(objective)
    f = objective_fn(w, **config)

    cfg = adamw.AdamWConfig(lr=lr, warmup_steps=0, total_steps=steps,
                            min_lr_ratio=0.02, weight_decay=0.0)

    with jax.enable_x64(True):
        def denorm(x):
            return {n: lo[n] + x[n] * (hi[n] - lo[n]) for n in names}

        def loss(x):
            return f(denorm(x))

        vg = jax.value_and_grad(loss)

        @jax.jit
        def step(x, st):
            v, g = vg(x)
            x2, st2, _ = adamw.apply(cfg, x, g, st)
            return {n: jnp.clip(x2[n], 0.0, 1.0) for n in names}, st2, v

        if init is None:
            x = {n: jnp.asarray(0.5, jnp.float64) for n in names}
        else:
            x = {n: jnp.clip((jnp.asarray(float(init[n])) - lo[n])
                             / (hi[n] - lo[n]), 0.0, 1.0) for n in names}
        st = adamw.init(cfg, x)
        traj = np.empty(steps + 1, np.float64)
        best_v, best_x = np.inf, x
        for i in range(steps):
            x_before = x
            x, st, v = step(x, st)
            traj[i] = float(v)
            if traj[i] < best_v:
                best_v, best_x = traj[i], x_before
        traj[steps] = float(loss(x))
        if traj[steps] < best_v:
            best_v, best_x = traj[steps], x
        knobs = {n: float(v) for n, v in denorm(best_x).items()}

    return KnobOptResult(knobs=knobs, objective=best_v, weights=w,
                         fields=evaluate_fields(knobs, **config),
                         trajectory=traj, steps=steps)


def grid_argmin(bounds: Mapping[str, tuple[float, float]],
                objective="avg_power", *,
                n: int = 33,
                **config) -> tuple[dict[str, float], float]:
    """Dense-grid brute force of the same scalarized objective.

    Evaluates ``evaluate_grid`` with ``n`` points per bounded knob (other
    knobs fixed as in :func:`objective_fn`) and returns ``(knobs, value)``
    at the grid minimum — the cross-check oracle for
    :func:`optimize_knobs`, accurate to one grid step.
    """
    if not bounds:
        raise ValueError("bounds must select at least one knob")
    _check_knobs(bounds)
    r = _resolve(config)
    axes = {}
    for k in KNOBS:
        if k in bounds:
            axes[k] = tuple(np.linspace(bounds[k][0], bounds[k][1], n))
        else:
            axes[k] = (r.base_knobs[k],)
    res = S.evaluate_grid(
        cuts=(r.cut,),
        agg_nodes=(r.M.node_names[r.agg_i],),
        sensor_nodes=(r.M.node_names[r.sen_i],),
        weight_mems=(A.WEIGHT_MEM_KINDS[r.wm_i],),
        num_cameras=(r.num_cameras,),
        detnet_fps=axes["detnet_fps"], keynet_fps=axes["keynet_fps"],
        mipi_energy_scale=axes["mipi_energy_scale"],
        camera_fps=axes["camera_fps"],
        model=r.M)
    W = scalarize(res, objective)
    flat = int(np.nanargmin(W))
    cfg = res.config_at(flat)
    return ({k: float(cfg[k]) for k in bounds}, float(W.ravel()[flat]))


def scalarize(result: S.SweepResult, objective) -> np.ndarray:
    """Weighted-sum objective over a stored grid — the same scalarization
    as :func:`objective_fn`, evaluated on ``SweepResult`` channels."""
    w = _weights(objective)
    return sum(wi * np.asarray(result.data[k], np.float64)
               for k, wi in w.items())
