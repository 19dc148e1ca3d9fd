"""Session simulator: time-varying traces with battery + thermal state.

Every other engine in the repo evaluates one *static* operating point of
the Eq. 1-11 model.  Real AR/VR sessions duty-cycle: the inference rates
and active camera count follow user activity, dissipated power heats the
case, heat throttles the compute rates, and the battery drains
("Draining our Glass" measures exactly this coupling on Google Glass).
This module adds that session axis without forking the evaluation stack:

* A **scenario trace** is a piecewise-constant schedule of knob
  multipliers: per-:class:`Phase` DetNet/KeyNet rate scales, a camera
  frame-rate scale and an active-camera fraction, each held for
  ``duration_s``.  :data:`PROFILES` names a few user-behavior traces
  (``"steady"``, ``"commute"``, ``"workday"``, ``"gaming"``).
* A :class:`ScenarioSet` bundles traces with a :class:`BatterySpec` and
  :class:`ThermalSpec` and a time resolution; :func:`scenario_stack`
  lowers it against a stacked model lowering into a
  :class:`ScenarioStack` — a drop-in for
  :class:`repro.core.arrays.StackedModelArrays` that the backend layer
  evaluates through the *same* chunk contract
  (:mod:`repro.core.backend`), with **trace as one more batched grid
  axis**.
* The per-configuration kernel runs a ``lax.scan`` over the trace
  steps.  Each step re-evaluates the Eq. 1-11 kernel at the phase's
  scaled knobs (times the current throttle factor), then advances two
  state variables — battery state-of-charge and one lumped-thermal RC
  node — using the *exact* RC step response, so the discretization
  introduces no integration error and the closed-form oracles of
  ``tests/test_scenario.py`` hold to float precision.

Four session channels join the static kernel fields as first-class
sweep objectives/constraints (``sweep.SCENARIO_FIELDS``):

* ``session_energy_j``   — integral of system power over the trace;
* ``time_to_empty_s``    — when the battery crosses empty (exact linear
  interpolation inside the crossing step; if the session ends first,
  the whole-session average drain extrapolates cyclically);
* ``peak_case_temp_c``   — max of the RC node temperature;
* ``throttle_fraction``  — fraction of session time spent throttled.

All four inherit validity from ``avg_power``: invalid grid corners
(MRAM with no test vehicle, padded cuts) are NaN, exactly like the
static channels, so argmin/top-k/Pareto/constraint machinery needs no
special cases.  ``evaluate_grid(scenarios=...)``,
``stream_grid(scenarios=...)`` and ``optimal_partition(scenarios=...)``
all route through here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import arrays as A
from . import sweep as SW
from .constants import (CAMERA_FPS, DEFAULT_BATTERY, DEFAULT_THERMAL,
                        DETNET_FPS, KEYNET_FPS, NUM_CAMERAS, BatterySpec,
                        ThermalSpec)

#: Default number of ``lax.scan`` steps each phase is subdivided into.
#: The RC update is exact per step, so substeps only matter for how
#: often the throttle factor is refreshed against the rising
#: temperature (piecewise-constant-rate approximation of the feedback).
DEFAULT_STEPS_PER_PHASE = 4


# ---------------------------------------------------------------------------
# Trace description
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Phase:
    """One piecewise-constant segment of a scenario trace.

    The scales multiply the swept base knobs, so one trace composes with
    every grid axis: a config with ``detnet_fps=10`` in a phase with
    ``detnet_scale=0.5`` runs DetNet at 5 fps.  ``cameras_active`` is
    the *fraction* of the configured cameras powered during the phase.
    """

    duration_s: float
    detnet_scale: float = 1.0
    keynet_scale: float = 1.0
    camera_fps_scale: float = 1.0
    cameras_active: float = 1.0


@dataclasses.dataclass(frozen=True)
class ScenarioTrace:
    """A named sequence of :class:`Phase` segments."""

    name: str
    phases: tuple[Phase, ...]

    @property
    def duration_s(self) -> float:
        return float(sum(p.duration_s for p in self.phases))


def _idle(duration_s):
    return Phase(duration_s, detnet_scale=0.25, keynet_scale=0.25,
                 camera_fps_scale=0.5, cameras_active=0.5)


#: Named user-behavior traces (Snippet-2-style VR session profiles,
#: "Draining our Glass"-style duty cycles).  All compose with the grid
#: knobs, so e.g. ``num_cameras=8`` under ``"commute"`` still idles at
#: half the cameras during the idle phases.
PROFILES: Mapping[str, ScenarioTrace] = {
    "steady": ScenarioTrace("steady", (Phase(1800.0),)),
    "commute": ScenarioTrace("commute", (
        _idle(420.0),
        Phase(900.0),                                   # navigate, full rate
        Phase(180.0, detnet_scale=1.5, keynet_scale=1.2),   # interaction burst
        _idle(300.0),
    )),
    "workday": ScenarioTrace("workday", (
        _idle(1200.0),
        Phase(240.0),                                   # notification burst
        _idle(1200.0),
        Phase(240.0, detnet_scale=1.25),
        _idle(900.0),
    )),
    "gaming": ScenarioTrace("gaming", (
        Phase(300.0),                                   # lobby
        Phase(1200.0, detnet_scale=1.5, keynet_scale=1.2,
              camera_fps_scale=1.2),                    # match, high rate
        Phase(120.0, detnet_scale=0.5, keynet_scale=0.5,
              camera_fps_scale=0.5, cameras_active=0.5),    # cooldown
    )),
}


@dataclasses.dataclass(frozen=True)
class ScenarioSet:
    """Hashable bundle of traces + device dynamics for one sweep.

    This is what the ``scenarios=`` knob of ``evaluate_grid`` /
    ``stream_grid`` / ``optimal_partition`` lowers to (see
    :func:`as_scenario_set` for the accepted shorthands).  The traces
    become the values of the trailing ``trace`` grid axis, in order.
    """

    traces: tuple[ScenarioTrace, ...]
    battery: BatterySpec = DEFAULT_BATTERY
    thermal: ThermalSpec = DEFAULT_THERMAL
    steps_per_phase: int = DEFAULT_STEPS_PER_PHASE
    throttle: bool = True

    def __post_init__(self):
        if not self.traces:
            raise ValueError("a ScenarioSet needs at least one trace")
        names = [t.name for t in self.traces]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate trace names: {names}")
        if self.steps_per_phase < 1:
            raise ValueError("steps_per_phase must be >= 1")

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(t.name for t in self.traces)

    def only(self, name: str) -> "ScenarioSet":
        """The same set restricted to one named trace (winner rendering)."""
        for t in self.traces:
            if t.name == name:
                return dataclasses.replace(self, traces=(t,))
        raise KeyError(f"unknown trace {name!r}; have {self.names}")


def as_scenario_set(spec) -> ScenarioSet:
    """Lower the ``scenarios=`` knob into a canonical :class:`ScenarioSet`.

    Accepted: a :class:`ScenarioSet` (returned as-is), a profile name
    from :data:`PROFILES` (or ``"all"`` for every profile), a
    :class:`ScenarioTrace`, or an iterable mixing names and traces.
    """
    if isinstance(spec, ScenarioSet):
        return spec
    if isinstance(spec, str):
        spec = tuple(PROFILES) if spec == "all" else (spec,)
    elif isinstance(spec, ScenarioTrace):
        spec = (spec,)
    traces = []
    for t in spec:
        if isinstance(t, ScenarioTrace):
            traces.append(t)
        elif isinstance(t, str):
            if t not in PROFILES:
                raise ValueError(f"unknown scenario profile {t!r}; "
                                 f"have {tuple(PROFILES)}")
            traces.append(PROFILES[t])
        else:
            raise TypeError(f"scenarios entries must be trace names or "
                            f"ScenarioTrace, got {type(t).__name__}")
    return ScenarioSet(traces=tuple(traces))


# ---------------------------------------------------------------------------
# State-update physics (shared by the scan body and the reference loop)
# ---------------------------------------------------------------------------


def throttle_factor(temp_c, thermal: ThermalSpec):
    """Rate multiplier of the throttle law at case temperature ``temp_c``.

    ``clip(1 - gain * max(0, T - onset), floor, 1)`` — exactly 1.0 at or
    below the onset temperature (``max(0, .)`` yields an exact 0.0), so
    an unthrottled session multiplies the rates by exactly 1.0.
    """
    over = jnp.maximum(0.0, temp_c - thermal.throttle_onset_c)
    return jnp.clip(1.0 - thermal.throttle_gain_per_c * over,
                    thermal.throttle_floor, 1.0)


def thermal_step(temp_c, power_w, dt_s, thermal: ThermalSpec):
    """Exact RC step response under constant power for ``dt_s`` seconds:
    ``T' = T_ss + (T - T_ss) * exp(-dt / tau)`` with
    ``T_ss = T_amb + P * R`` and ``tau = R * C``.  Exact integration is
    what makes the closed-form thermal oracle and the re-segmentation
    invariance of ``tests/test_scenario.py`` hold."""
    t_ss = thermal.ambient_c + power_w * thermal.r_th_k_per_w
    decay = jnp.exp(-dt_s / (thermal.r_th_k_per_w * thermal.c_th_j_per_k))
    return t_ss + (temp_c - t_ss) * decay


def effective_drain_w(power_w, battery: BatterySpec):
    """Peukert-corrected drain power ``P * (P / p_ref) ** (peukert - 1)``.
    At ``peukert == 1`` the exponent is exactly 0.0, so the correction
    factor is exactly 1.0 and the drain stays bitwise linear."""
    return power_w * (power_w / battery.p_ref_w) ** (battery.peukert - 1.0)


def _make_step(base_fn, sset: ScenarioSet):
    """The per-step state update ``(carry, cfg, x) -> carry``.

    One function object serves both the ``lax.scan`` body of the batched
    kernel and the jitted python-loop reference of
    :func:`simulate_session` — the scan-vs-loop parity test holds
    because there is literally one copy of this code.

    ``carry = (t, soc, temp, peak, throttled_s, energy, tte)``;
    ``cfg = (model_i, cut, agg_i, sen_i, wm_i, det_fps, key_fps, ncam,
    mipi_scale, cam_fps)``; ``x = (dt, det_scale, key_scale, cam_scale,
    cams_active)`` is one row of the step tables.
    """
    bat, th = sset.battery, sset.thermal

    def step(carry, cfg, x):
        t, soc, temp, peak, throttled_s, energy, tte = carry
        dt, dsc, ksc, csc, act = x
        (model_i, cut, agg_i, sen_i, wm_i, det_fps, key_fps, ncam,
         mipi_scale, cam_fps) = cfg
        thr = throttle_factor(temp, th) if sset.throttle else jnp.float64(1.0)
        out = base_fn(model_i, cut, agg_i, sen_i, wm_i,
                      det_fps * (dsc * thr), key_fps * (ksc * thr),
                      ncam * act, mipi_scale, cam_fps * csc)
        power = out["avg_power"]
        drain = effective_drain_w(power, bat)
        soc_new = soc - drain * dt / bat.capacity_j
        temp_new = thermal_step(temp, power, dt, th)
        # Zero-duration steps (phase-count padding across the traces of
        # one set) are bitwise no-ops on every state variable.
        live = dt > 0.0
        soc_new = jnp.where(live, soc_new, soc)
        temp_new = jnp.where(live, temp_new, temp)
        # Exact in-step linear crossing: at most one crossing per
        # session (soc is non-increasing), so a plain select suffices.
        cross = (soc > 0.0) & (soc_new <= 0.0)
        tte = jnp.where(cross, t + soc * bat.capacity_j / drain, tte)
        return (t + dt, soc_new, temp_new, jnp.maximum(peak, temp_new),
                throttled_s + dt * (thr < 1.0), energy + power * dt, tte)

    return step


def _finalize(carry, static_power, bat: BatterySpec):
    """Map the final scan carry to the four session channels.

    Adding ``static_power * 0.0`` poisons every channel on invalid grid
    corners (NaN propagates; a finite power adds an exact 0.0, and
    ``inf + 0.0 == inf`` keeps the never-empties sentinel intact).
    """
    t_end, soc_end, _, peak, throttled_s, energy, tte = carry
    poison = static_power * 0.0
    drained = bat.soc0 - soc_end
    # No in-session crossing: extrapolate the whole-session average
    # drain cyclically (sessions repeat back-to-back until empty).
    extrap = jnp.where(drained > 0.0, t_end * bat.soc0 / drained, jnp.inf)
    tte = jnp.where(jnp.isfinite(tte), tte, extrap)
    return {
        "session_energy_j": energy + poison,
        "time_to_empty_s": tte + poison,
        "peak_case_temp_c": peak + poison,
        "throttle_fraction": (jnp.where(t_end > 0.0, throttled_s
                                        / jnp.where(t_end > 0.0, t_end, 1.0),
                                        0.0) + poison),
    }


def _init_carry(sset: ScenarioSet):
    f64 = jnp.float64
    th = sset.thermal
    return (f64(0.0), f64(sset.battery.soc0), f64(th.ambient_c),
            f64(th.ambient_c), f64(0.0), f64(0.0), f64(np.inf))


# ---------------------------------------------------------------------------
# Lowering: ScenarioSet -> step tables -> drop-in kernel stack
# ---------------------------------------------------------------------------


def _step_tables(sset: ScenarioSet) -> tuple[np.ndarray, ...]:
    """Lower the trace set to dense ``(n_traces, n_steps)`` step tables
    ``(dt, det_scale, key_scale, cam_scale, cams_active)``.  Each phase
    is split into ``steps_per_phase`` equal substeps; traces with fewer
    phases pad with zero-duration steps (exact no-ops in the scan)."""
    K = sset.steps_per_phase
    n_steps = max(len(t.phases) for t in sset.traces) * K
    tabs = [np.zeros((len(sset.traces), n_steps)) for _ in range(5)]
    for ti in range(5):
        if ti > 0:
            tabs[ti][:] = 1.0       # neutral scales in the padding
    for r, trace in enumerate(sset.traces):
        for p, ph in enumerate(trace.phases):
            cols = slice(p * K, (p + 1) * K)
            tabs[0][r, cols] = ph.duration_s / K
            tabs[1][r, cols] = ph.detnet_scale
            tabs[2][r, cols] = ph.keynet_scale
            tabs[3][r, cols] = ph.camera_fps_scale
            tabs[4][r, cols] = ph.cameras_active
    return tuple(tabs)


@dataclasses.dataclass(frozen=True, eq=False)
class ScenarioStack:
    """A scenario-wrapped model lowering — drop-in for
    :class:`repro.core.arrays.StackedModelArrays` in the backend layer.

    The backend contract only needs two hooks: ``vmapped_kernel()``
    (``sweep.vmapped_kernel`` dispatches here when present) and
    ``fields`` (``sweep.kernel_fields``); everything else — node lookup,
    cut ranges, model names — delegates to the wrapped stack, so
    ``build_axes`` validation and the stream executor run unchanged.
    Hashes by identity (``eq=False``) like the stack it wraps, which
    keeps the compiled-step and dense-eval caches keyed correctly;
    checkpoint signatures hash it by *content* (``backend._hash_update``
    recurses through dataclass fields), so a changed trace or battery
    invalidates resume state exactly like a changed model table.
    """

    S: A.StackedModelArrays
    sset: ScenarioSet
    step_tables: tuple[np.ndarray, ...]

    #: Marker the backend support gate checks (``getattr`` duck-check,
    #: so plain model stacks need no changes).
    is_scenario = True

    @property
    def fields(self) -> tuple[str, ...]:
        return SW.FIELDS + SW.SCENARIO_FIELDS

    @property
    def n_traces(self) -> int:
        return len(self.sset.traces)

    def vmapped_kernel(self):
        return jax.vmap(_make_session_fn(self))

    def __getattr__(self, name):
        if name.startswith("__"):
            raise AttributeError(name)
        return getattr(object.__getattribute__(self, "S"), name)


@functools.lru_cache(maxsize=16)
def scenario_stack(S: A.StackedModelArrays,
                   sset: ScenarioSet) -> ScenarioStack:
    """Lower (and cache) one scenario set against one model stack.

    Cached on ``(S identity, set content)`` — ``stack_model_arrays`` is
    itself cached, so repeated sweeps over the same workloads + traces
    reuse the compiled kernels downstream (``backend.cached_dense_eval``
    and ``cached_step`` key on the stack object's identity).
    """
    return ScenarioStack(S=S, sset=sset, step_tables=_step_tables(sset))


def _make_session_fn(stack: ScenarioStack):
    """Close the per-configuration session kernel over one scenario stack.

    Signature: the ten static-config coordinates of
    ``sweep._make_config_fn`` plus a trailing ``trace_i`` — exactly the
    argument list ``backend.decode_gather`` produces once ``build_axes``
    appends the trace axis.  Emits every static field (evaluated once at
    the base knobs, so a constant trace degenerates bitwise to the
    static kernel) plus the four session channels.
    """
    base_fn = SW._make_config_fn(stack.S)
    step = _make_step(base_fn, stack.sset)
    init = _init_carry(stack.sset)
    tables = stack.step_tables
    j = jnp.asarray

    def session_fn(model_i, cut, agg_i, sen_i, wm_i, det_fps, key_fps, ncam,
                   mipi_scale, cam_fps, trace_i):
        static = base_fn(model_i, cut, agg_i, sen_i, wm_i, det_fps, key_fps,
                         ncam, mipi_scale, cam_fps)
        cfg = (model_i, cut, agg_i, sen_i, wm_i, det_fps, key_fps, ncam,
               mipi_scale, cam_fps)
        xs = tuple(j(tab)[trace_i] for tab in tables)
        carry = jax.lax.scan(
            lambda c, x: (step(c, cfg, x), None), init, xs)[0]
        out = dict(static)
        out.update(_finalize(carry, static["avg_power"],
                             stack.sset.battery))
        return out

    return session_fn


# ---------------------------------------------------------------------------
# Reference python-loop simulator (docs, tests, trajectory rendering)
# ---------------------------------------------------------------------------


def simulate_session(scenarios="steady", trace: str | None = None,
                     cut: int = 0, agg_node="7nm", sensor_node="7nm",
                     sensor_weight_mem: str = "sram",
                     detnet_fps: float = DETNET_FPS,
                     keynet_fps: float = KEYNET_FPS,
                     num_cameras: float = NUM_CAMERAS,
                     mipi_energy_scale: float = 1.0,
                     camera_fps: float = CAMERA_FPS,
                     detnet=None, keynet=None) -> dict:
    """Simulate one configuration through one trace, step by step.

    The reference twin of the batched ``lax.scan`` kernel: a host python
    loop over the *same* jitted step function (:func:`_make_step`), so
    its final state is bitwise the scan path's — pinned by
    ``tests/test_scenario.py``.  Returns per-step trajectory arrays
    (``t_s``, ``soc``, ``temp_c``, ``power_w``, ``throttle``) plus the
    four session channels, for session plots and oracle checks.
    """
    sset = as_scenario_set(scenarios)
    if trace is None:
        trace = sset.traces[0].name
    sset = sset.only(trace)
    with jax.enable_x64(True):
        S = A.stack_model_arrays((A.model_arrays(detnet, keynet),))
        stack = scenario_stack(S, sset)
        base_fn = SW._make_config_fn(S)
        step = jax.jit(_make_step(base_fn, sset))
        wm_i = A.WEIGHT_MEM_KINDS.index(sensor_weight_mem)
        cfg = tuple(map(jnp.asarray, (
            0, int(cut), S.node_index(agg_node), S.node_index(sensor_node),
            wm_i, float(detnet_fps), float(keynet_fps), float(num_cameras),
            float(mipi_energy_scale), float(camera_fps))))
        carry = _init_carry(sset)
        rows = np.stack(stack.step_tables, axis=-1)[0]   # (n_steps, 5)
        traj = {"t_s": [0.0], "soc": [float(carry[1])],
                "temp_c": [float(carry[2])], "energy_j": [0.0],
                "throttle": []}
        for x in rows:
            thr = (float(throttle_factor(carry[2], sset.thermal))
                   if sset.throttle else 1.0)
            carry = step(carry, cfg, tuple(map(jnp.float64, x)))
            traj["t_s"].append(float(carry[0]))
            traj["soc"].append(float(carry[1]))
            traj["temp_c"].append(float(carry[2]))
            traj["energy_j"].append(float(carry[5]))
            traj["throttle"].append(thr)
        out = {k: np.asarray(v) for k, v in traj.items()}
        # Recover per-step power from the energy accumulator differences
        # (NaN across zero-duration padding steps).
        dt = np.diff(out["t_s"])
        with np.errstate(invalid="ignore", divide="ignore"):
            out["power_w"] = np.where(
                dt > 0, np.diff(out["energy_j"]) / np.where(dt > 0, dt, 1.0),
                np.nan)
        static = base_fn(*cfg)
        final = _finalize(carry, static["avg_power"], sset.battery)
        out.update({k: float(v) for k, v in final.items()})
        out["final_carry"] = tuple(float(v) for v in carry)
    return out
