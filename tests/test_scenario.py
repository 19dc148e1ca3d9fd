"""Oracle, parity and property tests for the session scenario engine.

The scenario engine (``repro.core.scenario``) advances battery
state-of-charge and a lumped-thermal RC node through piecewise-constant
user-behavior traces, re-evaluating the Eq. 1-11 kernel each step.  Its
correctness contract is pinned here four ways:

* **closed-form oracles** — the exact RC step response and the linear /
  Peukert battery drain admit analytic session solutions for constant
  traces; the engine must match them to <= 1e-6 relative;
* **bitwise parity** — the batched ``lax.scan`` kernel against the
  python-loop reference (``simulate_session``), and the constant-trace
  degeneracy against the plain static ``evaluate_grid``;
* **engine parity** — streaming argmin / top-k / Pareto / constraints
  over the session channels match the dense grid exactly;
* **properties** (hypothesis, guarded) — monotonicity and trace
  re-segmentation invariance, plus deterministic spot-checks of the
  same properties so they run even without hypothesis installed.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax

from repro.core import pareto, partition, scenario as SC, stream, sweep
from repro.core.constants import (DEFAULT_BATTERY, DEFAULT_THERMAL,
                                  BatterySpec, ThermalSpec)

# This file mixes plain tests with hypothesis properties, so a
# module-level importorskip (the test_property.py pattern) would skip
# the oracles too; instead the decorators degrade to pytest skips.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    def given(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*_a, **_k):
        return lambda f: f

    class _StrategyStub:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _StrategyStub()

MAX_EX = 10
RTOL = 1e-6


def _single(duration_s=600.0, **kw):
    """A one-trace ScenarioSet around a single constant full-rate phase."""
    tr = SC.ScenarioTrace("const", (SC.Phase(float(duration_s)),))
    return SC.ScenarioSet(traces=(tr,), throttle=False, **kw)


def _small_grid(sset, **kw):
    kw.setdefault("cuts", (0, 11))
    kw.setdefault("detnet_fps", (5.0, 30.0))
    return sweep.evaluate_grid(scenarios=sset, **kw)


class TestClosedFormOracles:
    """Constant-trace sessions against their analytic solutions."""

    def test_thermal_step_matches_exponential(self):
        """N exact RC substeps compose to the continuous solution."""
        th = DEFAULT_THERMAL
        tau = th.r_th_k_per_w * th.c_th_j_per_k
        P, D = 0.15, 600.0
        with jax.enable_x64(True):
            temp = th.ambient_c
            for _ in range(16):
                temp = float(SC.thermal_step(temp, P, D / 16, th))
        ref = th.ambient_c + P * th.r_th_k_per_w * (1.0 - math.exp(-D / tau))
        assert temp == pytest.approx(ref, rel=1e-12)

    def test_peak_temp_closed_form(self):
        """peak_case_temp_c == amb + P*R*(1 - exp(-D/tau)) to <= 1e-6."""
        D = 600.0
        r = _small_grid(_single(D))
        th = DEFAULT_THERMAL
        tau = th.r_th_k_per_w * th.c_th_j_per_k
        P = r.data["avg_power"][..., 0]
        ref = th.ambient_c + P * th.r_th_k_per_w * (1.0 - np.exp(-D / tau))
        got = r.data["peak_case_temp_c"][..., 0]
        np.testing.assert_allclose(got, ref, rtol=RTOL)

    def test_battery_linear_drain_is_bitwise(self):
        """peukert == 1.0 -> exponent exactly 0.0 -> drain == power."""
        assert DEFAULT_BATTERY.peukert == 1.0
        with jax.enable_x64(True):
            for p in (0.019, 0.37, 2.5):
                assert float(SC.effective_drain_w(p, DEFAULT_BATTERY)) == p

    def test_time_to_empty_linear_oracle_both_regimes(self):
        """tte == soc0 * capacity / P, in-session crossing *and*
        cyclic extrapolation (constant drain makes them coincide)."""
        for capacity_j in (1.0, DEFAULT_BATTERY.capacity_j):
            bat = dataclasses.replace(DEFAULT_BATTERY, name=f"c{capacity_j}",
                                      capacity_j=capacity_j)
            r = _small_grid(_single(600.0, battery=bat))
            P = r.data["avg_power"][..., 0]
            ref = bat.soc0 * capacity_j / P
            got = r.data["time_to_empty_s"][..., 0]
            np.testing.assert_allclose(got, ref, rtol=RTOL)
            # the tiny battery really does empty mid-session (crossing
            # regime), the default one does not (extrapolation regime)
            if capacity_j == 1.0:
                assert (got < 600.0).all()
            else:
                assert (got > 600.0).all()

    def test_time_to_empty_peukert_oracle(self):
        """Nonlinear drain: tte == soc0 * capacity / P**k for p_ref=1."""
        bat = dataclasses.replace(DEFAULT_BATTERY, name="pk", peukert=1.2,
                                  p_ref_w=1.0)
        r = _small_grid(_single(600.0, battery=bat))
        P = r.data["avg_power"][..., 0]
        ref = bat.soc0 * bat.capacity_j / P ** 1.2
        got = r.data["time_to_empty_s"][..., 0]
        np.testing.assert_allclose(got, ref, rtol=RTOL)

    def test_session_energy_oracle(self):
        """session_energy_j == P * D for a constant trace."""
        D = 600.0
        r = _small_grid(_single(D))
        np.testing.assert_allclose(r.data["session_energy_j"][..., 0],
                                   r.data["avg_power"][..., 0] * D,
                                   rtol=RTOL)

    def test_idle_battery_never_empties(self):
        """Zero drain -> time_to_empty_s == +inf (sentinel survives the
        NaN-poisoning arithmetic)."""
        sset = _single(60.0)
        r = sweep.evaluate_grid(cuts=(0,), detnet_fps=(1e-12,),
                                keynet_fps=(1e-12,), camera_fps=(1e-12,),
                                scenarios=sset)
        # power is tiny but nonzero, so check the sentinel via a direct
        # zero-power finalize instead of a grid corner
        import jax.numpy as jnp
        with jax.enable_x64(True):
            carry = SC._init_carry(sset)
            carry = (jnp.float64(60.0),) + carry[1:]
            out = SC._finalize(carry, jnp.float64(0.0), sset.battery)
            assert float(out["time_to_empty_s"]) == np.inf
        assert np.isfinite(r.data["time_to_empty_s"]).all()


class TestScanLoopParity:
    """The batched lax.scan kernel vs the python-loop reference twin."""

    def _check(self, sset, **cfg):
        sim = SC.simulate_session(scenarios=sset, **cfg)
        r = sweep.evaluate_grid(
            cuts=(cfg.get("cut", 0),),
            detnet_fps=(cfg.get("detnet_fps", 10.0),),
            scenarios=sset)
        for f in sweep.SCENARIO_FIELDS:
            assert sim[f] == float(r.data[f].ravel()[0]), f

    def test_bitwise_parity_multiphase(self):
        sset = SC.ScenarioSet(traces=(SC.PROFILES["commute"],))
        self._check(sset, cut=11, detnet_fps=10.0)

    def test_bitwise_parity_with_throttle_active(self):
        """Throttle feedback engaged (onset just above ambient): the
        temperature-dependent rate rescaling must still be bitwise
        between the scan and the loop."""
        th = dataclasses.replace(DEFAULT_THERMAL, throttle_onset_c=25.05,
                                 throttle_gain_per_c=2.0)
        sset = SC.ScenarioSet(traces=(SC.PROFILES["gaming"],), thermal=th)
        sim = SC.simulate_session(scenarios=sset, cut=11, detnet_fps=30.0)
        assert sim["throttle_fraction"] > 0.0     # feedback really engaged
        self._check(sset, cut=11, detnet_fps=30.0)

    def test_trajectory_arrays_consistent(self):
        sim = SC.simulate_session(scenarios="commute", cut=11)
        n = len(sim["t_s"])
        assert len(sim["soc"]) == len(sim["temp_c"]) == n
        assert (np.diff(sim["soc"]) <= 0).all()       # battery only drains
        assert sim["energy_j"][-1] == sim["session_energy_j"]


class TestConstantTraceDegeneracy:
    """A single constant phase with throttling off must reproduce the
    static kernel bitwise — including its NaN validity pattern."""

    KW = dict(sensor_nodes=("7nm", "16nm"), weight_mems=("sram", "mram"),
              detnet_fps=(5.0, 30.0))

    def test_static_channels_bitwise(self):
        r_static = sweep.evaluate_grid(**self.KW)
        r_scen = sweep.evaluate_grid(scenarios=_single(600.0), **self.KW)
        assert tuple(r_scen.axes)[-1] == "trace"
        for f in sweep.FIELDS:
            assert np.array_equal(r_static.data[f],
                                  r_scen.data[f][..., 0],
                                  equal_nan=True), f

    def test_session_channels_inherit_validity(self):
        r_static = sweep.evaluate_grid(**self.KW)
        r_scen = sweep.evaluate_grid(scenarios=_single(600.0), **self.KW)
        nan = np.isnan(r_static.data["avg_power"])
        for f in sweep.SCENARIO_FIELDS:
            assert np.array_equal(np.isnan(r_scen.data[f][..., 0]), nan), f

    def test_unthrottled_session_never_throttles(self):
        r = sweep.evaluate_grid(scenarios=_single(600.0), **self.KW)
        tf = r.data["throttle_fraction"]
        assert (tf[np.isfinite(tf)] == 0.0).all()


class TestStreamParity:
    """Streaming reductions over session channels vs the dense grid."""

    KW = dict(sensor_nodes=("7nm", "16nm"), weight_mems=("sram",),
              detnet_fps=(5.0, 15.0, 30.0))
    OBJ = ("time_to_empty_s", "peak_case_temp_c")

    @pytest.fixture(scope="class")
    def dense(self):
        return sweep.evaluate_grid(scenarios="all", **self.KW)

    @pytest.fixture(scope="class")
    def streamed(self):
        return stream.stream_grid(objectives=self.OBJ,
                                  maximize=("time_to_empty_s",),
                                  scenarios="all", chunk_size=64, top_k=5,
                                  **self.KW)

    def test_argmin_matches_dense_bitwise(self, dense, streamed):
        win = streamed.argmin("peak_case_temp_c")
        assert win["peak_case_temp_c"] == np.nanmin(
            dense.data["peak_case_temp_c"])
        assert win["trace"] in SC.PROFILES

    def test_top_k_maximize_matches_dense(self, dense, streamed):
        tte = dense.data["time_to_empty_s"]
        want = np.sort(tte[np.isfinite(tte)])[::-1][:5]
        got = [p["time_to_empty_s"]
               for p in streamed.top_k("time_to_empty_s")]
        np.testing.assert_array_equal(got, want)

    def test_constrained_stream_matches_dense(self, dense):
        res = stream.stream_grid(
            objectives=self.OBJ, maximize=("time_to_empty_s",),
            constraints={"peak_case_temp_c": ("<=", 40.0)},
            scenarios="all", chunk_size=64, **self.KW)
        tte = dense.data["time_to_empty_s"]
        feas = np.where(dense.data["peak_case_temp_c"] <= 40.0, tte, np.nan)
        best = res.top_k("time_to_empty_s")[0]["time_to_empty_s"]
        assert best == np.nanmax(feas[np.isfinite(feas)])

    def test_pareto_front_matches_dense(self, dense, streamed):
        ref = pareto.pareto_front(dense, objectives=self.OBJ,
                                  maximize=("time_to_empty_s",))
        got = streamed.pareto_front()
        assert ref.objectives == got.objectives == self.OBJ
        ref_pts = {tuple(v) for v in np.asarray(ref.values)}
        got_pts = {tuple(v) for v in np.asarray(got.values)}
        assert got_pts == ref_pts


class TestErrorMessages:
    """The channel-listing / gating error contracts."""

    def test_stream_session_objective_requires_scenarios(self):
        with pytest.raises(ValueError,
                           match="session channels require scenarios="):
            stream.stream_grid(objectives=("time_to_empty_s",),
                               detnet_fps=(5.0,))

    def test_parse_constraints_lists_session_channels(self):
        with pytest.raises(ValueError, match="require scenarios=") as ei:
            sweep.parse_constraints({"bogus": 1.0})
        for f in sweep.SCENARIO_FIELDS:
            assert f in str(ei.value)

    def test_all_nan_session_channel_names_axis_values(self):
        r = sweep.evaluate_grid(cuts=(5, 11), sensor_nodes=("7nm",),
                                weight_mems=("mram",),
                                scenarios=_single(60.0))
        with pytest.raises(ValueError, match="weight_mem='mram'") as ei:
            r.argmin("time_to_empty_s")
        assert "time_to_empty_s" in str(ei.value)

    def test_pallas_backend_rejects_scenarios(self):
        with pytest.raises(ValueError,
                           match="does not support scenario sweeps"):
            sweep.evaluate_grid(cuts=(0,), scenarios=_single(60.0),
                                backend="pallas")

    def test_partition_session_objective_requires_scenarios(self):
        with pytest.raises(ValueError, match="session channel"):
            partition.optimal_partition(objective="time_to_empty_s")

    def test_partition_unknown_objective_lists_session_channels(self):
        with pytest.raises(ValueError, match="time_to_empty_s"):
            partition.optimal_partition(objective="bogus")

    def test_unknown_profile_and_trace(self):
        with pytest.raises(ValueError, match="unknown scenario profile"):
            SC.as_scenario_set("afk")
        with pytest.raises(KeyError, match="unknown trace"):
            SC.as_scenario_set("all").only("afk")

    def test_scenario_set_validation(self):
        with pytest.raises(ValueError, match="at least one trace"):
            SC.ScenarioSet(traces=())
        tr = SC.PROFILES["steady"]
        with pytest.raises(ValueError, match="duplicate"):
            SC.ScenarioSet(traces=(tr, tr))
        with pytest.raises(ValueError, match="steps_per_phase"):
            SC.ScenarioSet(traces=(tr,), steps_per_phase=0)


class TestPartitionScenario:
    """optimal_partition at session level."""

    KW = dict(sensor_node=("7nm", "16nm"), detnet_fps=(5.0, 15.0, 30.0))

    def test_maximize_tte_under_temp_constraint(self):
        p = partition.optimal_partition(
            objective="time_to_empty_s", scenarios="all",
            constraints={"peak_case_temp_c": ("<=", 40.0)}, **self.KW)
        assert p.trace in SC.PROFILES
        assert set(p.session) == set(sweep.SCENARIO_FIELDS)
        assert p.session["peak_case_temp_c"] <= 40.0

    def test_stream_route_matches_dense(self, monkeypatch):
        dense = partition.optimal_partition(
            objective="time_to_empty_s", scenarios="all", **self.KW)
        monkeypatch.setattr(partition, "STREAM_THRESHOLD", 8)
        streamed = partition.optimal_partition(
            objective="time_to_empty_s", scenarios="all", **self.KW)
        assert (streamed.cut, streamed.trace) == (dense.cut, dense.trace)
        assert streamed.session == dense.session

    def test_static_objective_still_minimized(self):
        p = partition.optimal_partition(objective="avg_power",
                                        scenarios="steady")
        assert p.trace == "steady"
        assert p.session is not None
        # plain searches keep the session slots empty
        q = partition.optimal_partition(objective="avg_power")
        assert q.trace is None and q.session is None


def _tte_along(axis_vals, sset=None, **axis_kw):
    """time_to_empty_s as a 1-D array along one opened grid axis."""
    r = sweep.evaluate_grid(cuts=(11,), scenarios=sset or _single(600.0),
                            **axis_kw)
    return np.squeeze(r.data["time_to_empty_s"])


class TestSessionProperties:
    """Monotonicity / invariance — deterministic spot-checks that always
    run, plus hypothesis generalizations when available."""

    def test_tte_monotone_in_power_draw_det(self):
        tte = _tte_along(None, mipi_energy_scale=(0.5, 1.0, 2.0, 4.0))
        assert (np.diff(tte) <= 0).all()

    def test_resegmentation_invariance_det(self):
        ref = _small_grid(_single(256.0))
        split = SC.ScenarioSet(traces=(SC.ScenarioTrace(
            "const", (SC.Phase(128.0), SC.Phase(128.0))),), throttle=False)
        r2 = _small_grid(split)
        for f in sweep.SCENARIO_FIELDS:
            np.testing.assert_allclose(r2.data[f], ref.data[f], rtol=1e-9,
                                       err_msg=f)

    def test_peak_temp_monotone_in_ambient_det(self):
        peaks = []
        for amb in (15.0, 25.0, 35.0):
            th = dataclasses.replace(DEFAULT_THERMAL, ambient_c=amb)
            r = _small_grid(_single(600.0, thermal=th))
            peaks.append(r.data["peak_case_temp_c"])
        assert (peaks[1] > peaks[0]).all() and (peaks[2] > peaks[1]).all()

    @given(lo=st.floats(0.25, 4.0), step=st.floats(0.1, 4.0))
    @settings(max_examples=MAX_EX, deadline=None)
    def test_tte_monotone_in_power_draw(self, lo, step):
        """More MIPI energy per byte -> more power -> no longer runtime."""
        tte = _tte_along(None, mipi_energy_scale=(lo, lo + step))
        assert tte[1] <= tte[0]

    @given(frac=st.sampled_from([0.25, 0.5, 0.75]),
           dur=st.sampled_from([128.0, 256.0, 512.0]))
    @settings(max_examples=MAX_EX, deadline=None)
    def test_resegmentation_invariance(self, frac, dur):
        """Splitting a constant phase at a dyadic point is physically a
        no-op (the RC step is exact); channels agree to 1e-9."""
        ref = _small_grid(_single(dur))
        split = SC.ScenarioSet(traces=(SC.ScenarioTrace(
            "const", (SC.Phase(frac * dur), SC.Phase((1 - frac) * dur))),),
            throttle=False)
        r2 = _small_grid(split)
        for f in sweep.SCENARIO_FIELDS:
            np.testing.assert_allclose(r2.data[f], ref.data[f], rtol=1e-9,
                                       err_msg=f)

    @given(amb=st.floats(0.0, 40.0), delta=st.floats(0.5, 15.0))
    @settings(max_examples=MAX_EX, deadline=None)
    def test_peak_temp_monotone_in_ambient(self, amb, delta):
        lo = _small_grid(_single(600.0, thermal=dataclasses.replace(
            DEFAULT_THERMAL, ambient_c=amb)))
        hi = _small_grid(_single(600.0, thermal=dataclasses.replace(
            DEFAULT_THERMAL, ambient_c=amb + delta)))
        assert (hi.data["peak_case_temp_c"]
                > lo.data["peak_case_temp_c"]).all()
