"""Vectorized design-space engine: Eqs. 1-11 as one jit/vmap kernel.

The repo now has **two evaluation paths** over the same analytical model:

* **Scalar path** (:mod:`repro.core.system`, :mod:`repro.core.partition`) —
  builds an explicit, named ``ModuleEnergy`` list for one configuration.
  Use it when you want the full per-module report (the Fig. 5 stacked
  bars, per-sensor groups, labels).
* **Array path** (this module) — consumes the struct-of-arrays lowering
  of :mod:`repro.core.arrays` and evaluates an arbitrary cartesian grid
  over the paper's design knobs in a single ``jax.jit``-compiled,
  ``jax.vmap``-batched device call.  Use it for sweeps: dense sensitivity
  heatmaps, partition × node × memory × rate grids, and as the substrate
  for multi-objective analysis — every configuration evaluates the three
  objective channels (``avg_power``, ``latency``, ``mipi_bytes_per_s``)
  that :mod:`repro.core.pareto` extracts fronts over and
  :mod:`repro.core.optimize` differentiates through.

The two paths are kept numerically interchangeable (``tests/test_sweep.py``
asserts ≤1e-6 relative parity across a sampled grid); the payload plan per
partition cut comes from the shared :func:`repro.core.arrays.mipi_payloads`
so the cut semantics cannot drift.

Grid axes of :func:`evaluate_grid` (cartesian product, in order)::

    cut               partition index over DetNet ++ KeyNet layer list
    agg_node          aggregator tech node        ("7nm" | "16nm" | TechNode)
    sensor_node       on-sensor tech node
    weight_mem        on-sensor weight memory     ("sram" | "mram")
    detnet_fps        DetNet rate (the ROI-reuse knob)
    keynet_fps        KeyNet rate
    num_cameras       camera count
    mipi_energy_scale multiplier on MIPI pJ/B (Eq. 5 sensitivity axis)
    camera_fps        frame delivery rate

Configurations that are physically invalid (MRAM weight memory on a node
with no MRAM test vehicle, with an on-sensor deployment present) evaluate
to NaN rather than raising, so a dense grid can mix valid and invalid
corners.  All arithmetic runs in float64 (scoped ``jax.enable_x64`` — the
global JAX config is left untouched).
"""

from __future__ import annotations

import dataclasses
import functools
import operator
import re
from collections import OrderedDict
from typing import Iterable, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import arrays as A
from .constants import (CAMERA_FPS, DETNET_FPS, KEYNET_FPS, NUM_CAMERAS,
                        TechNode)
from .workloads import NNWorkload

AXIS_NAMES = ("cut", "agg_node", "sensor_node", "weight_mem", "detnet_fps",
              "keynet_fps", "num_cameras", "mipi_energy_scale", "camera_fps")

#: Name of the optional leading axis over stacked workload batches
#: (``models=`` on :func:`evaluate_grid` / ``stream.stream_grid``).
MODEL_AXIS = "model"

#: Name of the optional trailing axis over scenario traces
#: (``scenarios=`` on :func:`evaluate_grid` / ``stream.stream_grid``);
#: its values are the trace names of the scenario set.
SCENARIO_AXIS = "trace"

#: Output fields of the kernel (each becomes one grid-shaped array).
#: ``avg_power`` + the seven power-breakdown groups, plus the three
#: non-power objective channels: ``mipi_bytes_per_s`` (Eq. 5 link traffic),
#: ``sensor_macs_per_s`` and ``latency`` (the generalized per-cut
#: ``repro.core.latency.cut_latency`` model, lowered into the kernel).
FIELDS = ("avg_power", "camera", "utsv", "mipi", "sensor_compute",
          "sensor_memory", "agg_compute", "agg_memory", "mipi_bytes_per_s",
          "sensor_macs_per_s", "latency")

#: Session channels emitted *in addition to* :data:`FIELDS` when a sweep
#: runs with ``scenarios=`` (see :mod:`repro.core.scenario`).  They are
#: first-class objectives/constraints everywhere a static field is;
#: validity (NaN poisoning of invalid grid corners) is inherited from
#: ``avg_power`` exactly.
SCENARIO_FIELDS = ("session_energy_j", "time_to_empty_s",
                   "peak_case_temp_c", "throttle_fraction")


def kernel_fields(S=None) -> tuple[str, ...]:
    """Channels the kernel of a (possibly scenario-wrapped) lowering
    emits: :data:`FIELDS` for a plain model stack, plus
    :data:`SCENARIO_FIELDS` for a ``scenario.ScenarioStack`` (which
    advertises them via its ``fields`` attribute)."""
    return getattr(S, "fields", FIELDS) if S is not None else FIELDS

#: Comparison operators a constraint predicate may use (see
#: :func:`parse_constraints`), mapped to their array-compatible callables
#: — ``operator.le`` etc. dispatch identically on numpy arrays and traced
#: jax values, so the host post-filter and the streaming executor's
#: in-kernel mask evaluate the same expression.
CONSTRAINT_OPS: Mapping[str, callable] = {
    "<=": operator.le, ">=": operator.ge, "<": operator.lt, ">": operator.gt}

_CONSTRAINT_RE = re.compile(
    r"\s*(\w+)\s*(<=|>=|<|>)\s*([-+]?[\d.]+(?:[eE][-+]?\d+)?)\s*")


def parse_constraints(constraints) -> tuple[tuple[str, str, float], ...]:
    """Canonicalize a constraint spec into ``((field, op, bound), ...)``.

    Accepted forms (freely mixable in the iterable variants):

    * a mapping ``{field: bound}`` — upper bounds, i.e. ``field <= bound``
      (the common case: latency budgets, link caps);
    * a mapping ``{field: (op, bound)}`` with ``op`` one of
      :data:`CONSTRAINT_OPS`;
    * an iterable of ``"field <= bound"`` strings or ``(field, op,
      bound)`` tuples.

    Fields must be kernel channels (:data:`FIELDS`, or
    :data:`SCENARIO_FIELDS` on sweeps run with ``scenarios=``).  A
    configuration is
    *feasible* iff every predicate holds; NaN channel values (invalid
    configurations) never satisfy a predicate, so infeasible and invalid
    configurations are excluded identically.
    """
    if not constraints:
        return ()
    items: list[tuple[str, str, float]] = []
    if isinstance(constraints, Mapping):
        for field, spec in constraints.items():
            if isinstance(spec, (tuple, list)):
                if len(spec) != 2:
                    raise ValueError(f"constraint {field!r}: expected "
                                     f"(op, bound), got {spec!r}")
                op, bound = spec
            else:
                op, bound = "<=", spec
            items.append((field, op, bound))
    else:
        for c in constraints:
            if isinstance(c, str):
                m = _CONSTRAINT_RE.fullmatch(c)
                if not m:
                    raise ValueError(
                        f"cannot parse constraint {c!r}; expected "
                        f"'<field> <op> <value>' with op in "
                        f"{tuple(CONSTRAINT_OPS)}")
                items.append((m.group(1), m.group(2), m.group(3)))
            else:
                field, op, bound = c
                items.append((field, op, bound))
    out = []
    for field, op, bound in items:
        if field not in FIELDS + SCENARIO_FIELDS:
            raise ValueError(f"unknown constraint channel {field!r}; "
                             f"kernel channels are {FIELDS} plus the "
                             f"scenario channels {SCENARIO_FIELDS} "
                             f"(which require scenarios=)")
        if op not in CONSTRAINT_OPS:
            raise ValueError(f"unknown constraint op {op!r}; "
                             f"have {tuple(CONSTRAINT_OPS)}")
        out.append((field, op, float(bound)))
    return tuple(out)


def constraint_mask(data: Mapping[str, np.ndarray],
                    constraints) -> np.ndarray:
    """Boolean feasibility mask of a channel dict under a constraint spec
    (the host twin of the streaming executor's in-kernel predicate mask).
    NaN channel values fail every predicate."""
    cons = parse_constraints(constraints)
    mask = np.ones(np.shape(next(iter(data.values()))), bool)
    with np.errstate(invalid="ignore"):
        for field, op, bound in cons:
            mask &= CONSTRAINT_OPS[op](np.asarray(data[field]), bound)
    return mask


# ---------------------------------------------------------------------------
# The per-configuration kernel (vmapped over flat config arrays)
# ---------------------------------------------------------------------------


def _site_power(macs_per_s, w_read_per_s, act_per_s, cycles_per_s, f_clk,
                e_mac, wm_e_read, wm_leak_on, wm_leak_ret, sram_e_read,
                sram_e_write, sram_leak_on, sram_leak_ret, cap_w, cap_a,
                l1_bytes):
    """Eqs. 7-11 for one processor site, per-second accounting.

    Mirrors ``system.Deployment.modules()``: compute (Eq. 7), L2-weight /
    L2-activation / L1 access energy (Eq. 8), and On/Retention leakage for
    the three memory instances (Eqs. 9-11 with a 1 s window).
    """
    p_compute = macs_per_s * e_mac

    act_read = act_per_s / 2
    act_write = act_per_s / 2
    # L1 sees every streamed byte once more (L2 -> L1 -> engine).
    l1_traffic = w_read_per_s + act_read + act_write
    p_l2w = w_read_per_s * wm_e_read
    p_l2a = act_read * sram_e_read + act_write * sram_e_write
    p_l1 = (l1_traffic / 2 * (A.L1_ENERGY_SCALE * sram_e_read)
            + l1_traffic / 2 * (A.L1_ENERGY_SCALE * sram_e_write))

    t_proc = jnp.minimum(1.0, cycles_per_s / f_clk)
    t_idle = jnp.maximum(0.0, 1.0 - t_proc)
    p_leak = (cap_w * (wm_leak_on * t_proc + wm_leak_ret * t_idle)
              + cap_a * (sram_leak_on * t_proc + sram_leak_ret * t_idle)
              + l1_bytes * (sram_leak_on * t_proc + sram_leak_ret * t_idle))
    return p_compute, p_l2w + p_l2a + p_l1 + p_leak


def _make_config_fn(S: A.StackedModelArrays):
    """Close the Eq. 1-11 kernel over a stacked batch of model tables.

    The first argument of the returned function selects the model along
    the stacked (padded) workload axis; for a single-model stack it is a
    constant 0 and the gathers reduce to the plain per-model reads.
    """
    det, key = S.det, S.key
    M = S
    j = jnp.asarray  # constants fold into the jaxpr at trace time

    def config_fn(model_i, cut, agg_i, sen_i, wm_i, det_fps, key_fps, ncam,
                  mipi_scale, cam_fps):
        m = model_i
        n_det = j(det.n_layers)[m]
        n_key = j(key.n_layers)[m]
        n_all = n_det + n_key
        cd = jnp.clip(cut, 0, n_det)          # DetNet layers on-sensor
        ck = jnp.clip(cut - n_det, 0, n_key)  # KeyNet layers on-sensor
        has_sensor = cut > 0
        has_agg = cut < n_all

        # ---- Eq. 3/4: cameras (readout window set by camera-side link) ----
        t_comm_cam = A.FULL_FRAME / jnp.where(has_sensor, A.UTSV_BW,
                                              A.MIPI_BW)
        t_off = jnp.maximum(0.0, 1.0 / cam_fps - A.T_SENSE - t_comm_cam)
        e_cam = (A.CAMERA_SENSE_W * A.T_SENSE + A.CAMERA_READ_W * t_comm_cam
                 + A.CAMERA_IDLE_W * t_off)
        p_camera = e_cam * cam_fps * ncam

        # ---- Eq. 5: uTSV readout link (distributed only) ----
        p_utsv = jnp.where(
            has_sensor, A.FULL_FRAME * A.UTSV_E_PER_BYTE * cam_fps * ncam,
            0.0)

        # ---- Eq. 5: MIPI payload plan for this cut ----
        bps_per_cam = (j(M.pay_cam_rate)[m, cut] * cam_fps
                       + j(M.pay_det_rate)[m, cut] * det_fps
                       + j(M.pay_key_rate)[m, cut] * key_fps)
        p_mipi = bps_per_cam * (A.MIPI_E_PER_BYTE * mipi_scale) * ncam
        mipi_bps = bps_per_cam * ncam

        # ---- on-sensor site (x ncam replicas) ----
        macs_s = (j(det.c_macs)[m, cd] * det_fps
                  + j(key.c_macs)[m, ck] * key_fps)
        w_read_s = (j(det.c_weight_stream)[m, cd] * det_fps
                    + j(key.c_weight_stream)[m, ck] * key_fps)
        act_s = (j(det.c_act_traffic)[m, cd] * det_fps
                 + j(key.c_act_traffic)[m, ck] * key_fps)
        cyc_s = (j(det.c_cycles_sensor)[m, cd] * det_fps
                 + j(key.c_cycles_sensor)[m, ck] * key_fps)
        cap_w_s = j(det.c_weight_bytes)[m, cd] + j(key.c_weight_bytes)[m, ck]
        cap_a_s = (jnp.maximum(j(det.peak_prefix)[m, cd],
                               j(key.peak_prefix)[m, ck])
                   + j(det.input_bytes)[m])
        p_comp_s, p_mem_s = _site_power(
            macs_s, w_read_s, act_s, cyc_s,
            j(M.f_clk)[sen_i], j(M.e_mac)[sen_i],
            j(M.wm_e_read)[sen_i, wm_i], j(M.wm_leak_on)[sen_i, wm_i],
            j(M.wm_leak_ret)[sen_i, wm_i],
            j(M.sram_e_read)[sen_i], j(M.sram_e_write)[sen_i],
            j(M.sram_leak_on)[sen_i], j(M.sram_leak_ret)[sen_i],
            cap_w_s, cap_a_s, A.SENSOR_L1_BYTES)
        p_sensor_compute = jnp.where(has_sensor, p_comp_s * ncam, 0.0)
        p_sensor_memory = jnp.where(has_sensor, p_mem_s * ncam, 0.0)

        # ---- aggregator site (suffix of each network, rate x ncam) ----
        macs_a = ((j(det.c_macs)[m, n_det] - j(det.c_macs)[m, cd])
                  * (det_fps * ncam)
                  + (j(key.c_macs)[m, n_key] - j(key.c_macs)[m, ck])
                  * (key_fps * ncam))
        w_read_a = ((j(det.c_weight_stream)[m, n_det]
                     - j(det.c_weight_stream)[m, cd]) * (det_fps * ncam)
                    + (j(key.c_weight_stream)[m, n_key]
                       - j(key.c_weight_stream)[m, ck]) * (key_fps * ncam))
        act_a = ((j(det.c_act_traffic)[m, n_det]
                  - j(det.c_act_traffic)[m, cd]) * (det_fps * ncam)
                 + (j(key.c_act_traffic)[m, n_key]
                    - j(key.c_act_traffic)[m, ck]) * (key_fps * ncam))
        cyc_a = ((j(det.c_cycles_agg)[m, n_det] - j(det.c_cycles_agg)[m, cd])
                 * (det_fps * ncam)
                 + (j(key.c_cycles_agg)[m, n_key]
                    - j(key.c_cycles_agg)[m, ck]) * (key_fps * ncam))
        cap_w_a = ((j(det.c_weight_bytes)[m, n_det]
                    - j(det.c_weight_bytes)[m, cd])
                   + (j(key.c_weight_bytes)[m, n_key]
                      - j(key.c_weight_bytes)[m, ck]))
        cap_a_a = (jnp.maximum(j(det.peak_suffix)[m, cd],
                               j(key.peak_suffix)[m, ck])
                   + j(M.pay_max)[m, cut] * ncam)
        p_comp_a, p_mem_a = _site_power(
            macs_a, w_read_a, act_a, cyc_a,
            j(M.f_clk)[agg_i], j(M.e_mac)[agg_i],
            # the aggregator's weight memory is always its node SRAM
            j(M.sram_e_read)[agg_i], j(M.sram_leak_on)[agg_i],
            j(M.sram_leak_ret)[agg_i],
            j(M.sram_e_read)[agg_i], j(M.sram_e_write)[agg_i],
            j(M.sram_leak_on)[agg_i], j(M.sram_leak_ret)[agg_i],
            cap_w_a, cap_a_a, A.AGG_L1_BYTES)
        p_agg_compute = jnp.where(has_agg, p_comp_a, 0.0)
        p_agg_memory = jnp.where(has_agg, p_mem_a, 0.0)

        # ---- end-to-end result latency (cut_latency, lowered: Eq. 6/9) ----
        # DetNet work/payloads are amortized by the ROI-reuse ratio; the
        # aggregator serializes the other cameras' suffix work (t_queue).
        det_amort = jnp.minimum(1.0, det_fps / cam_fps)
        t_det_sen = (j(det.c_cycles_sensor)[m, cd] / j(M.f_clk)[sen_i]
                     * det_amort)
        t_det_agg = ((j(det.c_cycles_agg)[m, n_det]
                      - j(det.c_cycles_agg)[m, cd])
                     / j(M.f_clk)[agg_i] * det_amort)
        t_key_sen = j(key.c_cycles_sensor)[m, ck] / j(M.f_clk)[sen_i]
        t_key_agg = ((j(key.c_cycles_agg)[m, n_key]
                      - j(key.c_cycles_agg)[m, ck])
                     / j(M.f_clk)[agg_i])
        t_comm_cut = (j(M.pay_det_rate)[m, cut] * det_amort
                      + j(M.pay_key_rate)[m, cut]) / A.MIPI_BW
        latency = (A.T_SENSE + t_comm_cam + t_det_sen + t_det_agg
                   + t_comm_cut + (ncam - 1.0) * (t_det_agg + t_key_agg)
                   + t_key_sen + t_key_agg)

        # Invalid (node, weight-mem) corners must poison every objective
        # channel — a Pareto front over non-power objectives would otherwise
        # happily select physically impossible configurations.  The power
        # fields inherit NaN from the wm_* tables; the rest get it here.
        invalid = jnp.where(has_sensor,
                            j(M.wm_e_read)[sen_i, wm_i] * 0.0, 0.0)
        # Padded-cut masking: on the stacked workload axis a grid cut index
        # beyond this model's own cut range addresses padding, not a real
        # partition — poison *every* channel (adds an exact 0.0 for the
        # in-range cuts, so single-model grids are bitwise unaffected).
        pad = jnp.where(cut <= n_all, 0.0, jnp.nan)
        invalid = invalid + pad

        total = (p_camera + p_utsv + p_mipi + p_sensor_compute
                 + p_sensor_memory + p_agg_compute + p_agg_memory)
        return {
            "avg_power": total + pad,
            "camera": p_camera + pad,
            "utsv": p_utsv + pad,
            "mipi": p_mipi + pad,
            "sensor_compute": p_sensor_compute + pad,
            "sensor_memory": p_sensor_memory + pad,
            "agg_compute": p_agg_compute + pad,
            "agg_memory": p_agg_memory + pad,
            "mipi_bytes_per_s": mipi_bps + invalid,
            "sensor_macs_per_s": (jnp.where(has_sensor, macs_s * ncam, 0.0)
                                  + invalid),
            "latency": latency + invalid,
        }

    return config_fn


def config_kernel(model: A.ModelArrays | None = None):
    """The unbatched, differentiable Eq. 1-11 kernel for one model.

    Returns the raw per-configuration function ``f(cut, agg_i, sen_i, wm_i,
    detnet_fps, keynet_fps, num_cameras, mipi_energy_scale, camera_fps) ->
    {field: scalar}`` that :func:`evaluate_grid` vmaps.  The integer
    arguments index the model's tables (``ModelArrays.node_index`` /
    ``arrays.WEIGHT_MEM_KINDS``); every float argument is differentiable —
    :mod:`repro.core.optimize` drives ``jax.grad`` through it for the
    continuous-knob search.  (Internally the kernel is the stacked
    multi-model one with the model coordinate pinned to 0.)
    """
    M = model if model is not None else A.model_arrays()
    fn = _make_config_fn(A.stack_model_arrays((M,)))
    return functools.partial(fn, 0)


def vmapped_kernel(S):
    """The un-jitted vmapped kernel (for embedding in a larger jit — the
    backend layer of :mod:`repro.core.backend` wraps it into both the
    dense evaluator and the fused chunk-reduction step).

    The vmapped signature is ``(model_i, cut, agg_i, sen_i, wm_i,
    detnet_fps, keynet_fps, num_cameras, mipi_energy_scale, camera_fps)``
    over equal-length flat arrays — exactly what the shared flat-index
    decode of :func:`repro.core.backend.decode_gather` produces.  A
    scenario-wrapped lowering (``scenario.ScenarioStack``) provides its
    own batched session kernel (one extra trailing ``trace_i``
    coordinate); dispatching on that hook here means every backend and
    engine built on this function runs scenarios unchanged.
    """
    builder = getattr(S, "vmapped_kernel", None)
    if builder is not None:
        return builder()
    return jax.vmap(_make_config_fn(S))


# ---------------------------------------------------------------------------
# Flat-index coordinate decoding (shared with the streaming executor)
# ---------------------------------------------------------------------------


def decode_flat_index(shape: Sequence[int], flat):
    """Mixed-radix decode of C-order flat indices into per-axis indices.

    Pure arithmetic — no coordinate meshes are ever materialized, so the
    cost is O(n_axes) per index regardless of grid size.  ``flat`` may be
    a Python int, a numpy array, or a traced jax array (the backend
    layer runs this decode on-device per chunk); returns one index per
    axis, in axis order.

    Index spaces beyond int32 (> 2^31-config grids) are guarded: a
    narrow integer array input is promoted to int64 before the stride
    arithmetic, so ``flat // stride`` can never overflow.  For traced
    jax inputs the promotion needs the caller's scoped ``jax.enable_x64``
    context (which every engine here runs under) — without it the
    astype would silently stay 32-bit.
    """
    strides = []
    s = 1
    for size in reversed(shape):
        strides.append(s)
        s *= int(size)
    strides.reverse()
    if s > np.iinfo(np.int32).max and hasattr(flat, "dtype"):
        dt = np.dtype(flat.dtype)
        if np.issubdtype(dt, np.integer) and dt.itemsize < 8:
            flat = flat.astype(np.int64)
    return tuple((flat // stride) % size
                 for stride, size in zip(strides, shape))


def config_from_flat(shape: Sequence[int],
                     axes: "OrderedDict[str, tuple]",
                     flat_index: int) -> dict:
    """Axis values of one flat C-order grid index — the single
    ``config_at`` implementation behind both the dense ``SweepResult``
    and the streaming ``StreamResult`` (their flat indices are
    interchangeable by construction)."""
    n = int(np.prod(shape))
    if not 0 <= flat_index < n:
        raise IndexError(f"flat index {flat_index} outside [0, {n})")
    idx = decode_flat_index(shape, int(flat_index))
    return {name: vals[i] for (name, vals), i in zip(axes.items(), idx)}


def _fully_invalid_axis_values(nan_mask: np.ndarray,
                               axes: "OrderedDict[str, tuple]") -> list[str]:
    """``name=value`` notes for axis values whose whole hyperplane is NaN."""
    notes = []
    for ax, (name, vals) in enumerate(axes.items()):
        for i, v in enumerate(vals):
            if np.take(nan_mask, i, axis=ax).all():
                notes.append(f"{name}={v!r}")
    return notes


def invalid_message(field: str, notes: Sequence[str]) -> str:
    """Shared all-invalid error text (dense and streaming paths)."""
    detail = ("; fully-invalid axis values: " + ", ".join(notes)
              if notes else "")
    return (f"every grid configuration is invalid (all-NaN) in channel "
            f"{field!r} — check the weight_mem / sensor_node combinations "
            f"against the available memory test vehicles and the cut range "
            f"of each stacked model{detail}")


# ---------------------------------------------------------------------------
# Grid evaluation
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Dense grid of Eq. 1/2 evaluations.

    ``axes`` maps axis name -> the axis values (in grid order); every array
    in ``data`` has shape ``tuple(len(v) for v in axes.values())``.  Grids
    evaluated with a stacked workload batch carry a leading ``model`` axis
    before the nine knob axes.
    """

    axes: "OrderedDict[str, tuple]"
    data: Mapping[str, np.ndarray]

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(len(v) for v in self.axes.values())

    @property
    def n_configs(self) -> int:
        return int(np.prod(self.shape))

    @property
    def avg_power(self) -> np.ndarray:
        return self.data["avg_power"]

    @property
    def latency(self) -> np.ndarray:
        """End-to-end result latency (s) — ``latency.cut_latency`` lowered."""
        return self.data["latency"]

    @property
    def mipi_bytes_per_s(self) -> np.ndarray:
        """MIPI link traffic (B/s) across all cameras (Eq. 5 payloads)."""
        return self.data["mipi_bytes_per_s"]

    def config_at(self, flat_index: int) -> dict:
        """Axis values of one flat grid index (arithmetic decode — no
        coordinate meshes)."""
        return config_from_flat(self.shape, self.axes, flat_index)

    def argmin(self, field: str = "avg_power") -> dict:
        """Best (lowest-``field``) configuration; NaN entries ignored.

        Raises a :class:`ValueError` naming the fully-invalid axis values
        when *every* grid corner is NaN in ``field`` (e.g. an MRAM-only
        grid on a node with no MRAM test vehicle).
        """
        arr = self.data[field]
        nan = np.isnan(arr)
        if nan.all():
            raise ValueError(invalid_message(
                field, _fully_invalid_axis_values(nan, self.axes)))
        flat = int(np.nanargmin(arr))
        out = self.config_at(flat)
        out[field] = float(self.data[field].ravel()[flat])
        return out

    def top_k(self, field: str = "avg_power", k: int = 4) -> list[dict]:
        """The ``k`` best (lowest-``field``) configurations, best first.

        Ties are broken by flat grid index (matching :meth:`argmin` and
        the streaming executor); NaN entries never appear.  Returns fewer
        than ``k`` entries when the grid has fewer valid configurations.
        """
        vals = self.data[field].ravel().copy()
        nan = np.isnan(vals)
        if nan.all():
            raise ValueError(invalid_message(
                field, _fully_invalid_axis_values(np.isnan(self.data[field]),
                                                  self.axes)))
        vals[nan] = np.inf
        if k * 4 < vals.size and vals.size > 4096:
            # Selection instead of a full stable sort on big grids; ties
            # at the k-th value resolve by flat index via the lexsort,
            # identical to the stable-argsort path below.
            kth = np.partition(vals, k - 1)[k - 1]
            sel = np.flatnonzero(vals <= kth)
            order = sel[np.lexsort((sel, vals[sel]))][:k]
        else:
            order = np.argsort(vals, kind="stable")[:k]
        out = []
        for flat in order:
            if not np.isfinite(vals[flat]):
                break
            cfg = self.config_at(int(flat))
            cfg[field] = float(vals[flat])
            out.append(cfg)
        return out

    def channel_bounds(self, field: str) -> tuple[float, float]:
        """(min, max) of the finite entries of one channel."""
        vals = self.data[field].ravel()
        finite = vals[np.isfinite(vals)]
        if finite.size == 0:
            raise ValueError(invalid_message(
                field, _fully_invalid_axis_values(np.isnan(self.data[field]),
                                                  self.axes)))
        return float(finite.min()), float(finite.max())

    def breakdown_at(self, flat_index: int) -> dict[str, float]:
        return {f: float(self.data[f].ravel()[flat_index])
                for f in self.data}

    def constrain(self, constraints) -> "SweepResult":
        """Dense post-filter twin of ``stream_grid(constraints=...)``.

        Returns a new :class:`SweepResult` with *every* channel NaN
        wherever any predicate fails (see :func:`parse_constraints`), so
        ``argmin``/``top_k``/``channel_bounds`` and
        :func:`repro.core.pareto.pareto_front` all run over the feasible
        set only — exactly what the streaming executor computes when the
        same constraints are compiled into its chunk step.
        """
        cons = parse_constraints(constraints)
        if not cons:
            return self
        mask = constraint_mask(self.data, cons)
        data = {f: np.where(mask, a, np.nan)
                for f, a in self.data.items()}
        return SweepResult(axes=self.axes, data=data)


def _node_axis(S: A.StackedModelArrays,
               nodes: Sequence[str | TechNode]) -> tuple[np.ndarray, tuple]:
    idx = np.asarray([S.node_index(n) for n in nodes], np.int32)
    labels = tuple(n if isinstance(n, str) else n.name for n in nodes)
    return idx, labels


def build_axes(cuts=None, agg_nodes=("7nm",), sensor_nodes=("7nm",),
               weight_mems=("sram",), detnet_fps=(DETNET_FPS,),
               keynet_fps=(KEYNET_FPS,), num_cameras=(NUM_CAMERAS,),
               mipi_energy_scale=(1.0,), camera_fps=(CAMERA_FPS,),
               detnet=None, keynet=None, model=None, models=None,
               scenarios=None):
    """Validate and lower the grid axes (shared by dense and streaming).

    Returns ``(S, axis_arrays, axes)`` where ``S`` is the stacked model
    lowering, ``axis_arrays`` are the per-axis kernel index/value arrays
    *including a leading model axis* (singleton when ``models`` is not
    given), and ``axes`` is the user-facing axis dict — which includes
    ``model`` only when a workload batch was requested, so single-model
    results keep their 9-axis shape.

    ``scenarios`` (a :class:`repro.core.scenario.ScenarioSet`, profile
    name(s), or trace(s) — see ``scenario.as_scenario_set``) wraps the
    lowering into a ``scenario.ScenarioStack`` and appends a trailing
    ``trace`` axis whose user-facing values are the trace names.
    """
    if models is not None:
        if model is not None or detnet is not None or keynet is not None:
            raise ValueError("pass either models= or a single "
                             "detnet/keynet/model, not both")
        S = (models if isinstance(models, A.StackedModelArrays)
             else A.stacked_model_arrays(models))
    elif model is not None:
        S = A.stack_model_arrays((model,))
    else:
        S = A.stack_model_arrays((A.model_arrays(detnet, keynet),))

    model_ax = np.arange(S.n_models, dtype=np.int32)
    if cuts is None:
        cut_ax = np.arange(S.n_cuts_max, dtype=np.int32)
    else:
        cut_ax = np.asarray(list(cuts), np.int32)
        if cut_ax.size and (cut_ax.min() < 0
                            or cut_ax.max() >= S.n_cuts_max):
            raise ValueError(f"cuts outside [0, {S.n_cuts_max - 1}]")
    agg_idx, agg_labels = _node_axis(S, agg_nodes)
    sen_idx, sen_labels = _node_axis(S, sensor_nodes)
    for m in weight_mems:
        if m not in A.WEIGHT_MEM_KINDS:
            raise ValueError(f"unknown weight_mem {m!r}; "
                             f"have {A.WEIGHT_MEM_KINDS}")
    wm_idx = np.asarray([A.WEIGHT_MEM_KINDS.index(m) for m in weight_mems],
                        np.int32)
    f64 = functools.partial(np.asarray, dtype=np.float64)
    float_axes = [f64(list(detnet_fps)), f64(list(keynet_fps)),
                  f64(list(num_cameras)), f64(list(mipi_energy_scale)),
                  f64(list(camera_fps))]
    if float_axes[2].size and (float_axes[2].min() < 1
                               or (float_axes[2] % 1 != 0).any()):
        raise ValueError(  # matches the scalar evaluate_cut semantics
            "num_cameras must be integers >= 1")

    axis_arrays = [model_ax, cut_ax, agg_idx, sen_idx, wm_idx, *float_axes]
    if 0 in (a.size for a in axis_arrays):
        raise ValueError("every grid axis needs at least one value")
    labels = (tuple(int(c) for c in cut_ax), agg_labels, sen_labels,
              tuple(weight_mems), tuple(float_axes[0]), tuple(float_axes[1]),
              tuple(float_axes[2]), tuple(float_axes[3]),
              tuple(float_axes[4]))
    if models is not None:
        axes = OrderedDict(zip((MODEL_AXIS,) + AXIS_NAMES,
                               (S.model_names,) + labels))
    else:
        axes = OrderedDict(zip(AXIS_NAMES, labels))
    if scenarios is not None:
        # Wrap *after* node/cut validation — those ran against the raw
        # stack above; the wrapper delegates every lookup back to it.
        from . import scenario as _scenario  # deferred: scenario imports us
        sset = _scenario.as_scenario_set(scenarios)
        S = _scenario.scenario_stack(S, sset)
        axis_arrays.append(np.arange(len(sset.traces), dtype=np.int32))
        axes[SCENARIO_AXIS] = sset.names
    return S, axis_arrays, axes


def evaluate_grid(cuts: Optional[Iterable[int]] = None,
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
                  backend: Optional[str] = None) -> SweepResult:
    """Evaluate Eqs. 1-11 over the cartesian product of the given axes.

    One compiled device call for the whole grid (post first-call jit
    compile, which is cached per workload batch).  ``cuts=None`` selects
    every legal partition point.  Returns a :class:`SweepResult` whose
    arrays are indexed ``[cut, agg, sensor, wmem, dfps, kfps, ncam,
    mipi_scale, cam_fps]`` — with a leading ``model`` axis when ``models``
    (a workload batch, see :func:`repro.core.arrays.stacked_model_arrays`)
    is given, and a trailing ``trace`` axis when ``scenarios`` (a
    :class:`repro.core.scenario.ScenarioSet` or profile name(s)) is:
    each configuration is then driven through every session trace and
    the four ``SCENARIO_FIELDS`` channels join the output.

    The grid runs as *one big chunk* of the shared evaluation-backend
    contract (:mod:`repro.core.backend`): flat indices are decoded to
    coordinates on-device, so no host coordinate meshes exist.
    ``backend`` selects the evaluation backend (``None`` -> ``"xla"``;
    ``"pallas"`` routes through the fused Pallas grid kernel of
    :mod:`repro.kernels.sweep_grid`).  Output memory is O(grid); for
    spaces that do not fit, use the streaming executor
    :func:`repro.core.stream.stream_grid`.
    """
    from . import backend as _backend   # import cycle: backend uses sweep

    S, axis_arrays, axes = build_axes(
        cuts, agg_nodes, sensor_nodes, weight_mems, detnet_fps, keynet_fps,
        num_cameras, mipi_energy_scale, camera_fps, detnet, keynet, model,
        models, scenarios)
    shape = tuple(len(v) for v in axes.values())
    full_shape = tuple(a.size for a in axis_arrays)
    n = int(np.prod(full_shape))

    with jax.enable_x64(True):
        evalfn = _backend.cached_dense_eval(backend, S, full_shape,
                                            kernel_fields(S))
        out = evalfn(tuple(map(jnp.asarray, axis_arrays)),
                     jnp.arange(n, dtype=jnp.int64))
        data = {k: np.asarray(v).reshape(shape) for k, v in out.items()}
    return SweepResult(axes=axes, data=data)


def scalar_axes(kw: Mapping) -> dict:
    """Map ``partition.evaluate_cut``-style kwargs onto grid axes — the
    one place the kwarg↔axis correspondence is written down (shared by
    :func:`evaluate_one` and ``partition.optimal_partition``).  Scalar
    values become singleton axes; a list/tuple/array value passes through
    as a whole axis, which is how ``optimal_partition`` grows single-knob
    calls into grid (and, past the size threshold, streaming) searches."""
    def ax(name, default):
        v = kw.get(name, default)
        if v is None:
            v = default
        return (tuple(v) if isinstance(v, (list, tuple, np.ndarray))
                else (v,))

    return dict(
        agg_nodes=ax("agg_node", "7nm"),
        sensor_nodes=ax("sensor_node", "7nm"),
        weight_mems=ax("sensor_weight_mem", "sram"),
        detnet_fps=ax("detnet_fps", DETNET_FPS),
        keynet_fps=ax("keynet_fps", KEYNET_FPS),
        num_cameras=ax("num_cameras", NUM_CAMERAS),
        mipi_energy_scale=ax("mipi_energy_scale", 1.0),
        camera_fps=ax("camera_fps", CAMERA_FPS),
        detnet=kw.get("detnet"), keynet=kw.get("keynet"))


def evaluate_one(cut: int, **kw) -> dict[str, float]:
    """Single-configuration convenience wrapper over :func:`evaluate_grid`.

    Scalar keyword arguments match ``partition.evaluate_cut`` (``agg_node``,
    ``sensor_node``, ``sensor_weight_mem``, fps knobs, ...); returns the
    kernel's field dict for that one point.  Sequence-valued kwargs are
    rejected — grid axes belong to :func:`evaluate_grid` (or
    ``partition.optimal_partition``, which accepts them directly).
    """
    seq = sorted(k for k, v in kw.items()
                 if isinstance(v, (list, tuple, np.ndarray)))
    if seq:
        raise ValueError(f"evaluate_one takes scalar knobs only; {seq} "
                         f"are sequences — use evaluate_grid for axes")
    return evaluate_grid(cuts=(cut,), **scalar_axes(kw)).breakdown_at(0)
